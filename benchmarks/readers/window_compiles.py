"""Backend compiles inside the window, from the program's `CompileWatch`
(`obs/profile.py`): entries into the backend compiler less those the
persistent cache answered. 0 is the expected reading and a real one."""


def missed(before: dict, after: dict) -> int:
    """Between two `CompileWatch` snapshots: backend compiles that the
    persistent cache did not answer."""
    entered = after["backend"]["compiles"] - before["backend"]["compiles"]
    hits = after["cache_events"].get("cache_hits", 0) - before["cache_events"].get("cache_hits", 0)
    return max(0, entered - hits)


def read(run):
    if run.compiles is None:
        return None
    return float(missed(run.compiles["before"], run.compiles["after"]))
