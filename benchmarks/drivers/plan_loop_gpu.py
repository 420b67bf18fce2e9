"""`plan-loop-gpu`: `plan-loop-ref` for a configuration of bare pods that
share GPUs. Three things differ, and the window is still
`plan_loop.Driver`'s: a plan is run as `simon apply -e gpu` runs it
(`extended_resources` from the traffic file's parameters), so the report
carries the GPU tables; the order the program scheduled the pods in is the
order of the generator's workloads, one bare pod each under its own name
(`plan-loop-ref` reads it from the counter that generated names end in, and
a bare pod's name is its own); and the report's `GPU Node Resource` table,
the memory in use on every device at the end of the plan, is compared with
what the reference's own allocation gives on the nodes the answer names
(`gpu_device_diff`, exact)."""

from __future__ import annotations

import os
import re
import sys
import time
from typing import Dict, List, Tuple

from benchmarks.window import Item

from . import plan_loop, plan_loop_ref

UNITS = {"": 1, "Ki": 1 << 10, "Mi": 1 << 20, "Gi": 1 << 30, "Ti": 1 << 40}


def quantity(text: str) -> int:
    """A cell of the report's tables (`format_quantity`) back to bytes."""
    m = re.fullmatch(r"([0-9.]+)([KMGT]i)?", text)
    if m is None:
        raise ValueError(f"not a quantity: {text!r}")
    return int(round(float(m.group(1)) * UNITS[m.group(2) or ""]))


def gpu_devices(path: str) -> Dict[Tuple[str, int], int]:
    """The `GPU Node Resource` table of a report as (node, device index) ->
    bytes in use. A node's first row is its total (`8 GPUs`); the rows after
    it are its devices, `used/capacity(share%)`."""
    out: Dict[Tuple[str, int], int] = {}
    section = ""
    with open(path) as f:
        for line in f:
            line = line.rstrip("\n")
            if " | " not in line:
                section = line if line else section
                continue
            cols = [c.strip() for c in line.split("|")]
            if section == "GPU Node Resource" and cols[1].isdigit():
                node = cols[0].rsplit(" (", 1)[0]
                out[(node, int(cols[1]))] = quantity(cols[2].split("/", 1)[0])
    return out


class Driver(plan_loop_ref.Driver):
    def one(self, i: int, traced: bool) -> Item:
        """`plan_loop.Driver.one` with the options `-e gpu` sets."""
        from opensim_tpu.obs import trace as tracing
        from opensim_tpu.planner.apply import Applier, Options

        report = os.path.join(self.ctx.scratch, f"report-{i if i >= 0 else 'warm'}.txt")
        opts = Options(simon_config=self.simon_config, output_file=report, report_pods=True,
                       max_new_nodes=self.inputs["max_new_nodes"],
                       extended_resources=list(self.ctx.params["extended_resources"]))
        tr = tracing.start_trace("apply", force=True) if traced else None
        rc = 1
        start = time.monotonic()
        try:
            with tracing.trace_scope(tr):
                rc = Applier(opts).run()
        except Exception as e:  # a plan that raises is a failed plan, not a lost run
            print(f"[bench] plan {i} raised {type(e).__name__}: {e}", file=sys.stderr)
        end = time.monotonic()
        spans = None
        if tr is not None:
            tr.finish(status="ok" if rc == 0 else "error")
            spans = plan_loop.span_tree(tr.root)
            spans["start"], spans["end"] = start, end  # the benchmark's own span round the call
            plan_loop.bracket(spans)
        if i >= 0:
            print(f"[bench] plan {i}: {end - start:.3f}s rc={rc}", file=sys.stderr)
        return Item(start=start, end=end, ok=rc == 0, answer=report, spans=spans)

    def after_window(self, window) -> None:
        """`placed` as workload -> [node]: a bare pod is a workload of one,
        and the stream's order is the order of the cluster's workloads."""
        plan_loop.Driver.after_window(self, window)
        for it in window.items:
            rep = it.info.get("report")
            if rep is not None:
                rep["placed"] = {w: [n for n, k in nodes.items() for _ in range(k)]
                                 for w, nodes in rep["placed"].items()}
                rep["devices"] = gpu_devices(it.answer)

    def questions(self, window) -> List[dict]:
        """Beside the stream, the node count of every scenario the search for
        the least count has to ask (`roofline_gpushare.py`)."""
        from benchmarks import roofline_gpushare

        cluster = self.inputs["variants"][self.variant]["cluster"]
        out = super().questions(window)
        for q, it in zip(out, window.items):
            added = it.info["report"]["added"] if it.info.get("report") else 0
            q["scenario_nodes"] = [len(cluster.nodes) + k for k in
                                   roofline_gpushare.sweep_counts(added, self.inputs["max_new_nodes"])]
        return out

    def compare(self, window, answer=None) -> List[dict]:
        """`plan_loop_ref.Driver.compare`, and the device table of every plan
        that answered."""
        checks = super().compare(window, answer)
        cluster = self.inputs["variants"][self.variant]["cluster"]
        known = {nd.name for nd in cluster.nodes}
        reports = [answer] if answer else [it.info["report"] for it in window.items if it.info.get("report")]
        diff = 0
        for rep in reports:
            # added nodes carry generated names: numbered in the report's order, as `compare` numbers them.
            # One that holds no pod is in no row of the pod table and keeps its name: its devices have to
            # be unused, and are left out
            ren = {n: f"new-{k}" for k, n in enumerate(x for x in rep["node_order"] if x not in known)}
            placed = {w: [ren.get(n, n) for n in nodes] for w, nodes in rep["placed"].items()}
            devices = rep.get("devices")
            if devices is not None:
                devices = {(ren.get(n, n), d): b for (n, d), b in devices.items() if b or n in known or n in ren}
            diff += self.ref.device_diff(cluster.with_new_nodes(rep["added"]), placed, devices)
        checks.append({"name": "gpu_device_diff", "value": diff, "limit": self.ctx.limits["gpu_device_diff"]})
        return sorted(checks, key=lambda c: c["name"])
