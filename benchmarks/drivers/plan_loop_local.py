"""`plan-loop-local`: `plan-loop-gpu`'s window for a configuration whose
StatefulSets claim open-local volumes. A plan is run as `simon apply -e
open-local` runs it (`plan_loop_gpu.Driver.one`, `extended_resources` from
the traffic file's parameters), so the report carries the `Node Local
Storage` table. The order the program scheduled the pods in is
`plan_loop_kinds`', a StatefulSet's pods by their ordinals. Beside the
placement, the table at the end of the plan is compared with what the
reference's own choice of VG and device gives on the nodes the answer names
(`storage_diff`, exact): a device row by `used` / `unused`, a VG row by its
`Storage Requests` cell, the reference's bytes written by the report's rule."""

from __future__ import annotations

from typing import Dict, List, Optional, Tuple

from . import plan_loop_gpu, plan_loop_kinds, plan_loop_ref

Table = Dict[Tuple[str, str], str]  # (node, VG or device name) -> the cell compared


def quantity_text(value: float) -> str:
    """A quantity as the report's tables write it (`format_quantity`):
    whole in the largest binary unit it reaches, else at two decimals."""
    if value == 0:
        return "0"
    for suffix, unit in (("Ei", 1 << 60), ("Pi", 1 << 50), ("Ti", 1 << 40), ("Gi", 1 << 30), ("Mi", 1 << 20),
                         ("Ki", 1 << 10)):
        if abs(value) >= unit:
            v = value / unit
            return f"{int(round(v))}{suffix}" if abs(v - round(v)) < 1e-9 else f"{v:.2f}{suffix}"
    return str(int(round(value))) if abs(value - round(value)) < 1e-9 else f"{value:.3f}"


def requests_text(requested: int, capacity: int) -> str:
    """A VG row's `Storage Requests` cell."""
    return f"{quantity_text(requested)}({int(requested / capacity * 100) if capacity else 0}%)"


def local_storage(path: str) -> Table:
    """The `Node Local Storage` table of a report: a VG row's `Storage
    Requests` cell, a device row's `used` or `unused`."""
    out: Table = {}
    section = ""
    with open(path) as f:
        for line in f:
            line = line.rstrip("\n")
            if " | " not in line:
                section = line if line else section
                continue
            cols = [c.strip() for c in line.split("|")]
            if section == "Node Local Storage" and (cols[1] == "VG" or cols[1].startswith("Device(")):
                out[(cols[0], cols[2])] = cols[4]
    return out


def reference_table(ref) -> Table:
    """`local_storage` of the reference's own allocation."""
    return {key: (requests_text(amount, cap) if kind == "VG" else ("used" if amount else "unused"))
            for key, (kind, amount, cap) in ref.storage().items()}


def storage_diff(ref, table: Optional[Table], keep) -> int:
    """Rows of `table` that differ from the reference's own, over the rows
    of either whose node `keep` admits. None (an answer that carries no
    table) reads 0."""
    if table is None:
        return 0
    own = {key: cell for key, cell in reference_table(ref).items() if keep(key[0])}
    table = {key: cell for key, cell in table.items() if keep(key[0])}
    return sum(1 for key in set(own) | set(table) if own.get(key) != table.get(key))


class Driver(plan_loop_gpu.Driver, plan_loop_kinds.Driver):
    def after_window(self, window) -> None:
        """`plan_loop_kinds.Driver.after_window`, and each report's table."""
        plan_loop_kinds.Driver.after_window(self, window)
        for it in window.items:
            rep = it.info.get("report")
            if rep is not None:
                rep["storage"] = local_storage(it.answer)

    def compare(self, window, answer=None) -> List[dict]:
        """`plan_loop_ref.Driver.compare`, and the storage table of every
        plan that answered."""
        checks = plan_loop_ref.Driver.compare(self, window, answer)
        cluster = self.inputs["variants"][self.variant]["cluster"]
        known = {nd.name for nd in cluster.nodes}
        reports = [answer] if answer else [it.info["report"] for it in window.items if it.info.get("report")]
        diff = 0
        for rep in reports:
            # added nodes carry generated names: numbered in the report's order, as `compare` numbers them.
            # One that holds no pod is in no row of the pod table and keeps its name: it is left out
            ren = {n: f"new-{k}" for k, n in enumerate(x for x in rep["node_order"] if x not in known)}
            placed = {w: [ren.get(n, n) for n in nodes] for w, nodes in rep["placed"].items()}
            table = rep.get("storage")
            if table is not None:
                table = {(ren.get(n, n), name): cell for (n, name), cell in table.items()}
            ref = self.ref.follow(cluster.with_new_nodes(rep["added"]), placed)
            diff += storage_diff(ref, table, lambda n: n in known or n in ren.values())
        checks.append({"name": "storage_diff", "value": diff, "limit": self.ctx.limits["storage_diff"]})
        return sorted(checks, key=lambda c: c["name"])
