"""`plan-loop`: back-to-back `Applier.run()` on the files on disk.

Each plan of the window is a new `Applier` over the same YAML directories: it
loads and expands them, prepares, schedules (and, on a short cluster, finds
the node count and re-simulates), and writes the report with the pod table to
a file: what a new `simon apply` pays, less the start of the process and of
the TPU runtime, which are in `setup_s`. The planner keeps no cache between
plans. The report is the answer that is compared.
"""

from __future__ import annotations

import importlib
import os
import re
import sys
import time
from typing import Dict, List, Optional

from benchmarks.reference import compare
from benchmarks.reference.kube_reference import Reference
from benchmarks.window import Item, Window

from . import Context, canon_pod_ref, checks_from


def parse_report(path: str) -> dict:
    """What `Applier.run()` wrote (`chip_smoke.py`'s `parse_report`): the
    verdict, the count of added nodes, the engine line, and pods per
    (workload, node) from the Pod Info table, nodes in the table's order."""
    placed: Dict[str, Dict[str, int]] = {}
    node_order: List[str] = []
    added, engine, success, section = 0, "", False, ""
    with open(path) as f:
        for line in f:
            line = line.rstrip("\n")
            if line == "Simulation success!":
                success = True
            m = re.fullmatch(r"\(added (\d+) new node\(s\)\)", line)
            if m:
                added = int(m.group(1))
            if line.startswith("Scheduling engine: "):
                engine = line[len("Scheduling engine: "):]
            if line in ("Node Info", "Pod Info", "App Info"):
                section = line
                continue
            if section == "Pod Info" and " | " in line and not line.startswith("Node "):
                cols = [c.strip() for c in line.split("|")]
                nodes = placed.setdefault(canon_pod_ref(cols[1]), {})
                if cols[0] not in nodes:
                    nodes[cols[0]] = 0
                    if cols[0] not in node_order:
                        node_order.append(cols[0])
                nodes[cols[0]] += 1
    return {"success": success, "added": added, "engine": engine,
            "placed": placed, "node_order": node_order}


def span_tree(span) -> dict:
    """An `obs/trace.py` Span as plain data on the monotonic clock."""
    end = span.end if span.end is not None else span.start
    return {"name": span.name, "start": span.start, "end": end,
            "children": [span_tree(c) for c in span.children]}


def bracket(tree: dict) -> None:
    """The benchmark's own spans over what the program traces nowhere, so that
    an idle gap there has an owner: `bench.load` from the call to the first
    span of the program (YAML load and expansion), `bench.report` from the
    last one to the return (report writing)."""
    kids = tree["children"]
    if not kids:
        return
    first, last = min(c["start"] for c in kids), max(c["end"] for c in kids)
    kids.insert(0, {"name": "bench.load", "start": tree["start"], "end": first, "children": []})
    kids.append({"name": "bench.report", "start": last, "end": tree["end"], "children": []})


class Driver:
    #: the warm-up is one whole plan, `one(-1)`, driven by the harness
    warmup_items = 1

    def __init__(self, ctx: Context) -> None:
        self.ctx = ctx
        self.variant = ctx.params["variant"]
        self.inputs: Optional[dict] = None
        self.reports: List[str] = []

    # -- set-up -------------------------------------------------------------

    def prepare(self) -> None:
        """The inputs, from the seed: files on disk and the same as plain data."""
        gen = importlib.import_module(f"benchmarks.generators.{self.ctx.config['generator']}")
        self.inputs = gen.generate(self.ctx.sizes, self.ctx.seed, self.ctx.scratch)
        self.simon_config = self.inputs["variants"][self.variant]["simon_config"]

    def setup(self) -> None:
        self.prepare()
        if not self.ctx.rehearse:
            # what `simon apply --backend tpu` sets: a megakernel that does
            # not compile is an error, never a silent step down the ladder
            os.environ["OPENSIM_REQUIRE_TPU"] = "1"
        from opensim_tpu.planner.apply import Applier, Options  # noqa: F401  (fail early)

    def warmed(self, window) -> None:
        if window.failed:
            raise RuntimeError("the warm-up plan failed")
        print(f"[bench] warm-up plan {window.elapsed:.3f}s", file=sys.stderr)

    def counters(self) -> dict:
        from opensim_tpu.obs.profile import COMPILES

        return {"compiles": COMPILES.snapshot()}

    # -- the window ---------------------------------------------------------

    def one(self, i: int, traced: bool) -> Item:
        from opensim_tpu.obs import trace as tracing
        from opensim_tpu.planner.apply import Applier, Options

        report = os.path.join(self.ctx.scratch, f"report-{i if i >= 0 else 'warm'}.txt")
        opts = Options(simon_config=self.simon_config, output_file=report, report_pods=True,
                       max_new_nodes=self.inputs["max_new_nodes"])
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
            spans = span_tree(tr.root)
            spans["start"], spans["end"] = start, end  # the benchmark's own span round the call
            bracket(spans)
        if i >= 0:
            print(f"[bench] plan {i}: {end - start:.3f}s rc={rc}", file=sys.stderr)
        return Item(start=start, end=end, ok=rc == 0, answer=report, spans=spans)

    def after_window(self, window) -> None:
        """A plan that did not end in `Simulation success!` has failed."""
        for i, it in enumerate(window.items):
            it.info["report"] = rep = parse_report(it.answer) if os.path.exists(it.answer) else None
            if rep is None or not rep["success"]:
                it.ok = False
            if rep is not None:
                print(f"[bench] plan {i} said: success={rep['success']} added={rep['added']} "
                      f"pods={sum(sum(n.values()) for n in rep['placed'].values())} engine={rep['engine']}",
                      file=sys.stderr)

    def questions(self, window) -> List[dict]:
        cluster = self.inputs["variants"][self.variant]["cluster"]
        pods = sum(w.replicas for w in cluster.workloads)
        out = []
        for it in window.items:
            added = it.info["report"]["added"] if it.info.get("report") else 0
            out.append({"nodes": len(cluster.nodes) + added, "pods": pods, "resident": 0})
        return out

    # -- the comparison -----------------------------------------------------

    def compare(self, window, answer=None) -> List[dict]:
        """Every plan of the window has to give the same answer, and that
        answer is replayed through the reference. `answer` puts another
        answer in the program's place (the control)."""
        precision = self.ctx.config["precision"]
        cluster = self.inputs["variants"][self.variant]["cluster"]
        reports = [it.info.get("report") for it in window.items]
        first = answer or next((r for r in reports if r is not None), None)
        values = {"plans_differing": 0, "plans_unanswered": sum(1 for r in reports if r is None)}
        if first is None:
            values.update(misplaced_pods=0, worst_score_gap=0.0, infeasible_pods=0,
                          unscheduled_diff=0, answer_diff=sum(w.replicas for w in cluster.workloads),
                          added_nodes_diff=0)
            return checks_from(values, self.ctx.limits)
        known = {nd.name for nd in cluster.nodes}

        def canon(rep: dict):
            """New nodes carry generated names: number them in the report's order."""
            ren = {n: f"new-{k}" for k, n in enumerate(x for x in rep["node_order"] if x not in known)}
            return rep["added"], {w: {ren.get(n, n): c for n, c in nodes.items()}
                                  for w, nodes in rep["placed"].items()}

        added, placed = canon(first)
        if answer is None:
            values["plans_differing"] = sum(1 for r in reports if r is not None and canon(r) != (added, placed))
        values.update(compare.replay(cluster.with_new_nodes(added), placed, {}, precision))
        # the count of added nodes is exact: everything schedules with `added`
        # new nodes (the replay above) and something is left over with one fewer
        diff = 0
        if cluster.new_node is not None and added > 0:
            fewer = Reference(cluster.with_new_nodes(added - 1), precision)
            _placed, unscheduled = fewer.free_run(stop_at_unschedulable=True)
            diff = 0 if unscheduled else 1
        elif cluster.new_node is None and added:
            diff = added
        values["added_nodes_diff"] = diff
        return checks_from(values, self.ctx.limits)

    def control(self, precision: str) -> List[dict]:
        """The reference in a lower precision, put in the program's place:
        its own plan (the least count of new nodes with which everything
        schedules, and its placements there), judged like a window's."""
        cluster = self.inputs["variants"][self.variant]["cluster"]
        added = 0
        placed, unscheduled = Reference(cluster, precision).free_run()
        if unscheduled and cluster.new_node is not None:
            added = -(-sum(unscheduled.values()) // cluster.new_node.pods)
            while True:
                grown = cluster.with_new_nodes(added)
                placed, unscheduled = Reference(grown, precision).free_run()
                if not unscheduled:
                    break
                added += 1
            cluster = grown
        counts, _unscheduled = compare.counts_of(placed, unscheduled, cluster)
        answer = {"success": not unscheduled, "added": added, "engine": f"reference in {precision}",
                  "placed": counts, "node_order": [nd.name for nd in cluster.nodes]}
        return self.compare(Window(opened=0.0, closed=0.0, items=[]), answer=answer)

    def close(self) -> None:
        pass
