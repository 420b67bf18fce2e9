"""Seconds per item on JAX's compile path inside the window, from the
program's `CompileWatch` (`obs/profile.py`): the difference of the `stages`
seconds between the snapshot before the window and the one after it, summed
over `stages` (`trace`, `lower`, `backend`, `cache_retrieval`). These are paid
whether anything compiles or not: an eager `pallas_call` is traced, lowered
and looked up in the cache on every call. None where the program's snapshot
holds no `stages`, as before the counter existed."""


def read(run, stages):
    if run.compiles is None or not run.window.items:
        return None
    before, after = (run.compiles[k].get("stages") for k in ("before", "after"))
    if before is None or after is None:
        return None
    seconds = sum(after[s]["seconds"] - before.get(s, {"seconds": 0.0})["seconds"]
                  for s in stages if s in after)
    return seconds / len(run.window.items)
