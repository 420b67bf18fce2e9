"""`plan-loop-profile`: `plan-loop-ref` under a scheduler profile. Each plan
is `simon apply --default-scheduler-config <file>`: a new `Applier` reads the
YAML directories and the profile's file the generator wrote, and the rest of
the plan is `plan_loop.Driver`'s. The comparison and the control are
`plan_loop_ref.Driver`'s with the configuration's `reference`, whose cluster
carries the profile as plain data. Beside the compile watch the driver reads
the program's own counters (`/metrics` as the recorder renders it), so that a
metric can count the scans that ran under the profile on each engine."""

from __future__ import annotations

import importlib
import os
import sys
import time
from typing import List

from benchmarks import promtext
from benchmarks.window import Item

from . import plan_loop, plan_loop_ref


class Driver(plan_loop_ref.Driver):
    def prepare(self) -> None:
        """`plan_loop.Driver.prepare`, the generator given the configuration's profile."""
        gen = importlib.import_module(f"benchmarks.generators.{self.ctx.config['generator']}")
        self.inputs = gen.generate(self.ctx.sizes, self.ctx.seed, self.ctx.scratch, self.ctx.config["profile"])
        self.simon_config = self.inputs["variants"][self.variant]["simon_config"]

    def counters(self) -> dict:
        from opensim_tpu.obs.metrics import RECORDER

        out = super().counters()
        out["prom"] = promtext.parse("\n".join(RECORDER.render_lines()))
        return out

    def one(self, i: int, traced: bool) -> Item:
        """`plan_loop.Driver.one` with the profile's file."""
        from opensim_tpu.obs import trace as tracing
        from opensim_tpu.planner.apply import Applier, Options

        report = os.path.join(self.ctx.scratch, f"report-{i if i >= 0 else 'warm'}.txt")
        opts = Options(simon_config=self.simon_config, output_file=report, report_pods=True,
                       max_new_nodes=self.inputs["max_new_nodes"],
                       default_scheduler_config=self.inputs["scheduler_config"])
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

    def questions(self, window) -> List[dict]:
        """Beside the stream, the node count of every scenario the search for
        the least count has to ask (`roofline_binpack.py`)."""
        from benchmarks import roofline_gpushare

        cluster = self.inputs["variants"][self.variant]["cluster"]
        out = super().questions(window)
        for q, it in zip(out, window.items):
            added = it.info["report"]["added"] if it.info.get("report") else 0
            q["scenario_nodes"] = [len(cluster.nodes) + k for k in
                                   roofline_gpushare.sweep_counts(added, self.inputs["max_new_nodes"])]
        return out
