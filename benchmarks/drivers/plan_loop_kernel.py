"""`plan-loop-kernel`: `plan-loop` for a cell that measures the megakernel at
a width where a program without it steps down to the XLA scan. That scan is
not what the cell measures, and at such a width one plan on it outlasts a
run: so the warm-up plan runs under a trace that refuses the XLA scan's rungs
(`engine.xla`, `sweep.xla`), and the run fails at the first of them, and
where the warm-up plan's report does not name the megakernel. A traced
plan's spans keep their attributes (`attrs`), which `readers/span_attr_max.py`
reads. The window and the comparison are `plan_loop.Driver`'s."""

from __future__ import annotations

import os
import sys
import time

from benchmarks.window import Item

from . import plan_loop

#: the spans that open where the XLA scan runs a stream or a sweep
XLA_RUNGS = ("engine.xla", "sweep.xla")


class OffTheKernel(RuntimeError):
    """The program left the megakernel for the XLA scan."""


def kernel_only_trace():
    """A trace of one plan that raises `OffTheKernel` where an XLA rung opens."""
    from opensim_tpu.obs import trace as tracing

    class KernelOnly(tracing.TraceContext):
        def span(self, name, attrs=None):
            if name in XLA_RUNGS:
                raise OffTheKernel(f"{name} opened: the program left the megakernel ({attrs or {}})")
            return super().span(name, attrs)

    return KernelOnly("apply")


def with_attrs(tree: dict, span) -> dict:
    """`plan_loop.span_tree(span)` with each span's attributes under `attrs`."""
    tree["attrs"] = dict(span.attrs)
    for sub, child in zip(tree["children"], span.children):
        with_attrs(sub, child)
    return tree


class Driver(plan_loop.Driver):
    def one(self, i: int, traced: bool) -> Item:
        from opensim_tpu.obs import trace as tracing
        from opensim_tpu.planner.apply import Applier, Options

        warm = i < 0
        report = os.path.join(self.ctx.scratch, f"report-{'warm' if warm else i}.txt")
        opts = Options(simon_config=self.simon_config, output_file=report, report_pods=True,
                       max_new_nodes=self.inputs["max_new_nodes"])
        tr = kernel_only_trace() if warm else tracing.start_trace("apply", force=True) if traced else None
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
        if tr is not None and traced:
            spans = with_attrs(plan_loop.span_tree(tr.root), tr.root)
            spans["start"], spans["end"] = start, end  # the benchmark's own span round the call
            plan_loop.bracket(spans)
        if not warm:
            print(f"[bench] plan {i}: {end - start:.3f}s rc={rc}", file=sys.stderr)
        return Item(start=start, end=end, ok=rc == 0, answer=report, spans=spans)

    def warmed(self, window) -> None:
        super().warmed(window)
        engine = plan_loop.parse_report(window.items[0].answer)["engine"]
        if not engine.startswith("megakernel"):
            raise OffTheKernel(f"the warm-up plan ran on {engine!r}, not on the megakernel this cell measures")
