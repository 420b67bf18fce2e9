"""simon CLI — parity with ``cmd/simon/simon.go``: ``simon {apply, server,
version, gen-doc}`` with the same flags (``cmd/apply/apply.go:27-36``,
``cmd/server/options.go:14``). Log level comes from the ``LogLevel`` env
(``cmd/simon/simon.go:46-66``). Beyond the reference: ``simon lint``
exposes the opensim-lint static analyzer (docs/static-analysis.md)
without make."""

from __future__ import annotations

import argparse
import logging
import os
import sys
from typing import List, Optional

from .. import __version__ as VERSION  # single source of truth
COMMIT_ID = os.environ.get("SIMON_COMMIT_ID", "unknown")

LOG_LEVELS = {
    "panic": logging.CRITICAL,
    "fatal": logging.CRITICAL,
    "error": logging.ERROR,
    "warn": logging.WARNING,
    "warning": logging.WARNING,
    "info": logging.INFO,
    "debug": logging.DEBUG,
    "trace": logging.DEBUG,
}


def build_parser() -> argparse.ArgumentParser:
    parser = argparse.ArgumentParser(
        prog="simon",
        description="Simon: a TPU-native cluster simulator for capacity planning",
    )
    sub = parser.add_subparsers(dest="command")

    backend_parent = argparse.ArgumentParser(add_help=False)
    backend_parent.add_argument(
        "--backend",
        default="auto",
        choices=["auto", "tpu", "cpu", "xla"],
        help=(
            "auto = accelerator if reachable (Pallas fast path on TPU, XLA "
            "scan on CPU); tpu = require the accelerator; cpu = force host "
            "CPU; xla = disable the Pallas engine (pure XLA scan)"
        ),
    )

    apply_p = sub.add_parser(
        "apply", parents=[backend_parent], help="run a capacity-planning simulation",
        description="run a capacity-planning simulation (the reference's `simon apply`)",
    )
    apply_p.add_argument("-f", "--simon-config", required=True, help="path of simon config (Config CR yaml)")
    apply_p.add_argument(
        "-d", "--default-scheduler-config", default="", help="path of kube-scheduler config overrides"
    )
    apply_p.add_argument("-o", "--output-file", default="", help="redirect the report to a file")
    apply_p.add_argument("--use-greed", action="store_true", help="use greed algorithm to sort pods")
    apply_p.add_argument(
        "--enable-preemption", action="store_true",
        help="let unschedulable high-priority pods evict lower-priority ones (beyond-reference)",
    )
    apply_p.add_argument("-i", "--interactive", action="store_true", help="interactive add-node mode")
    apply_p.add_argument(
        "-e",
        "--extended-resources",
        default="",
        help="comma-separated extended resource reports (gpu,open-local)",
    )
    apply_p.add_argument("--max-new-nodes", type=int, default=128, help="upper bound for the node sweep")
    apply_p.add_argument("--report-pods", action="store_true", help="include the per-node Pod Info table")
    apply_p.add_argument(
        "--trace", default="", metavar="FILE",
        help="write a Chrome-trace/Perfetto JSON of the run's span tree "
        "(prepare/encode/engine/decode phases; docs/observability.md)",
    )
    apply_p.add_argument(
        "--tie-break", default="lowest", metavar="lowest|sample[:seed]",
        help="equal-score node selection: deterministic lowest index "
        "(default) or the reference's sampled tie-break, seeded for "
        "reproducible distribution-comparison runs (XLA scan; the Pallas "
        "megakernel stays lowest-index)",
    )
    apply_p.add_argument(
        "--explain", action="store_true",
        help="decision audit (docs/observability.md): append the placement "
        "audit to the report — per-filter reject totals plus a kube-style "
        "'0/N nodes are available' breakdown for every unschedulable pod",
    )

    explain_p = sub.add_parser(
        "explain", parents=[backend_parent],
        help="explain why a pod landed where it did (or why it is unschedulable)",
        description=(
            "run the simulation with the decision audit enabled and print one "
            "pod's full placement explanation: the winning node with its "
            "per-plugin score breakdown and runner-up margin, or the kube-style "
            "'0/N nodes are available' per-filter rejection counts. Without a "
            "pod argument, prints the audit summary and every unschedulable "
            "pod's breakdown"
        ),
    )
    explain_p.add_argument("-f", "--simon-config", required=True, help="path of simon config (Config CR yaml)")
    explain_p.add_argument(
        "-d", "--default-scheduler-config", default="", help="path of kube-scheduler config overrides"
    )
    explain_p.add_argument(
        "pod", nargs="?", default="",
        help="pod to explain, as namespace/name (or bare name when unambiguous)",
    )
    explain_p.add_argument("--use-greed", action="store_true", help="use greed algorithm to sort pods")
    explain_p.add_argument("--json", action="store_true", help="emit the explanation(s) as JSON")

    defrag_p = sub.add_parser(
        "defrag",
        aliases=["drain"],
        parents=[backend_parent],
        help="evaluate node-drain what-ifs (the README's Pods Migration feature, batch-evaluated)",
        description="evaluate node-drain what-ifs (Pods Migration), batch-evaluated as scenarios",
    )
    defrag_p.add_argument("-f", "--simon-config", required=True, help="path of simon config (Config CR yaml)")
    defrag_p.add_argument(
        "--candidates", default="", help="comma-separated node names to evaluate (default: all)"
    )
    defrag_p.add_argument(
        "--json", action="store_true",
        help="emit the drain plan as JSON (the same table rows the text "
        "renderer prints — byte-parity via planner/report.py)",
    )
    defrag_p.add_argument("-o", "--output-file", default="", help="redirect the report to a file")

    campaign_p = sub.add_parser(
        "campaign",
        parents=[backend_parent],
        help="run a cluster-lifecycle campaign (drain waves, reclaim storms, scored what-ifs)",
        description=(
            "execute a declarative lifecycle campaign (docs/campaigns.md): an "
            "ordered list of typed steps — PDB-aware drain waves, spot reclaim "
            "storms, deploys/scales, add-nodes, scale-down safety checks, "
            "defrag plans, journal-sourced event ranges — evaluated against "
            "the spec's cluster (or a live server with --url) with every step "
            "scored by the capacity observatory: placements delta, disruption "
            "budget consumed, utilization/fragmentation/headroom movement, and "
            "a bit-stable step fingerprint"
        ),
    )
    campaign_p.add_argument("spec", help="campaign spec yaml (kind: Campaign)")
    campaign_p.add_argument("--json", action="store_true", help="print the full result JSON instead of tables")
    campaign_p.add_argument(
        "--exec", dest="exec_mode", default="", choices=["", "warm", "cold"],
        help="execution mode override (default OPENSIM_CAMPAIGN_EXEC): warm = "
        "one full prepare + prepcache deltas; cold = per-step full prepare "
        "(the verification mode)",
    )
    campaign_p.add_argument(
        "--url", default="",
        help="POST the campaign's steps to a live server's /api/campaign and "
        "evaluate against its observed cluster (live twin) instead of the "
        "spec's cluster section",
    )
    campaign_p.add_argument("--timeout", type=float, default=600.0, help="--url request timeout seconds")
    campaign_p.add_argument("-o", "--output-file", default="", help="also write the result to a file")

    server_p = sub.add_parser(
        "server", parents=[backend_parent], help="start the simon REST server",
        description="start the simon REST server (deploy-apps / scale-apps / healthz / metrics)",
    )
    server_p.add_argument("--kubeconfig", default="", help="kubeconfig of the real cluster")
    server_p.add_argument("--master", default="", help="apiserver address override")
    server_p.add_argument("--port", type=int, default=8080, help="listen port")
    server_p.add_argument(
        "--watch", default="auto", choices=["auto", "on", "off"],
        help="live-twin mode (docs/live-twin.md): consume the cluster's "
        "watch streams and keep an always-warm incremental snapshot. "
        "auto = watch with graceful fallback to per-TTL polling; on = "
        "require the twin to sync at startup; off = polling only",
    )
    server_p.add_argument(
        "--access-log", action="store_true",
        help="emit one JSON access-log line per request (request id, "
        "endpoint, status, duration) — same as OPENSIM_ACCESS_LOG=1",
    )
    server_p.add_argument(
        "--workers", type=int, default=0,
        help="serve through N worker PROCESSES sharing the port "
        "(docs/serving.md 'Scaling past one process'): a twin-owner "
        "process publishes arena deltas over shared memory and N workers "
        "attach zero-copy and run the full admission/batching ladder "
        "past the GIL. Requires the live twin (--kubeconfig, --watch "
        "auto|on). 0/1 = single process; OPENSIM_WORKERS_FLEET is the "
        "env default",
    )
    server_p.add_argument(
        "--journal", default="",
        help="directory for the crash-safe watch-event journal "
        "(docs/live-twin.md 'Durability & replay'): every accepted twin "
        "event is recorded off the dispatch path, and a restart restores "
        "the twin from the newest checkpoint + suffix replay instead of "
        "a cold relist. Requires the live twin (--kubeconfig, --watch "
        "auto|on)",
    )
    server_p.add_argument(
        "--standby", action="store_true",
        help="run as the HA hot standby (docs/serving.md 'Surviving owner "
        "loss & rolling upgrades'): tail the owner's --journal live onto "
        "a private twin and take over the fleet — fenced by the lease "
        "epoch, at a continuous generation, adopting the surviving "
        "workers — when the owner's lease expires or is handed over. "
        "Requires --journal and the live twin flags; the owner enables "
        "HA with OPENSIM_HA=1",
    )
    server_p.add_argument(
        "--handover", action="store_true",
        help="with --standby: once the journal tail reaches parity, ask "
        "the live owner to drain and hand the fleet over (zero-downtime "
        "rolling upgrade); without it the standby only takes over when "
        "the lease expires",
    )

    loadgen_p = sub.add_parser(
        "loadgen",
        help="drive a live simon server at load and report QPS + latency",
        description=(
            "open/closed-loop load harness for the serving core "
            "(docs/serving.md): drive the live server's /api/deploy-apps at a "
            "target concurrency (closed loop) or arrival rate (open loop) and "
            "report sustained QPS with p50/p99 latency read straight from the "
            "server's simon_request_seconds_bucket histogram, plus batching "
            "and shed statistics. Prints one JSON report"
        ),
    )
    loadgen_p.add_argument("--url", required=True, help="base URL of the live server (http://host:port)")
    loadgen_p.add_argument(
        "--mode", default="closed", choices=["closed", "open"],
        help="closed = each worker waits for its response (sustained-QPS "
        "measurement); open = fire at --qps regardless of completions",
    )
    loadgen_p.add_argument("--concurrency", type=int, default=8, help="closed-loop workers / open-loop in-flight cap")
    loadgen_p.add_argument("--qps", type=float, default=0.0, help="open loop: target arrival rate")
    loadgen_p.add_argument("--duration", type=float, default=10.0, help="measured seconds")
    loadgen_p.add_argument("--replicas", type=int, default=3, help="max replicas per generated deployment")
    loadgen_p.add_argument("--cpu", default="500m", help="per-pod cpu request of the generated workload")
    loadgen_p.add_argument("--mem", default="1Gi", help="per-pod memory request of the generated workload")
    loadgen_p.add_argument("--timeout", type=float, default=60.0, help="per-request client timeout seconds")
    loadgen_p.add_argument("-o", "--output-file", default="", help="also write the JSON report to a file")

    top_p = sub.add_parser(
        "top",
        help="live cluster capacity view (utilization, headroom, fragmentation)",
        description=(
            "render a live capacity view of the cluster a simon server "
            "observes (docs/observability.md 'Watching cluster capacity'): "
            "per-resource utilization/spread/fragmentation, headroom per "
            "registered workload profile, the hottest nodes and pending "
            "pressure — read from GET /api/cluster/report, the same "
            "computation path as the text report tables. One shot by "
            "default; --watch refreshes in place like kubectl top"
        ),
    )
    top_p.add_argument("--url", required=True, help="base URL of the live server (http://host:port)")
    top_p.add_argument("--json", action="store_true", help="print the raw report JSON instead of tables")
    top_p.add_argument(
        "--watch", action="store_true",
        help="refresh the view in place until interrupted (Ctrl-C exits)",
    )
    top_p.add_argument(
        "--interval", type=float, default=2.0,
        help="--watch refresh interval in seconds (default 2)",
    )
    top_p.add_argument(
        "--no-headroom", action="store_true",
        help="skip the headroom probes (cheaper polling; utilization/"
        "fragmentation only)",
    )
    top_p.add_argument(
        "--mem", action="store_true",
        help="add the memory observatory block (process RSS, prep-cache "
        "arena bytes, ring occupancy — docs/observability.md 'Memory & "
        "profiles')",
    )
    top_p.add_argument(
        "-e", "--extended-resources", default="",
        help="comma-separated extended resource sections (gpu,open-local)",
    )
    top_p.add_argument("--timeout", type=float, default=60.0, help="per-request client timeout seconds")

    dash_p = sub.add_parser(
        "dash",
        help="live fleet dashboard (QPS, latency, freshness, lanes, SLO burn)",
        description=(
            "render the fleet's live terminal view (docs/observability.md "
            "'Watching the fleet') from the time-series ring and the SLO "
            "engine of a running server or fleet admin endpoint: fleet QPS "
            "and p50/p99 from merged per-worker histograms, event-to-"
            "servable freshness per pipeline stage, admission lane depths, "
            "takeover markers and multi-window SLO burn rates. Refreshes "
            "in place until interrupted; --once prints one frame"
        ),
    )
    dash_p.add_argument("--url", required=True, help="base URL of the server or fleet admin endpoint (http://host:port)")
    dash_p.add_argument("--once", action="store_true", help="print one frame and exit instead of refreshing")
    dash_p.add_argument("--json", action="store_true", help="print the structured rows as JSON (stable key order)")
    dash_p.add_argument(
        "--interval", type=float, default=2.0,
        help="refresh interval in seconds (default 2)",
    )
    dash_p.add_argument(
        "--range", default="5m", dest="range_spec", metavar="RANGE",
        help="ring query range: bare seconds or <n><s|m|h|d> (default 5m)",
    )
    dash_p.add_argument("--timeout", type=float, default=10.0, help="per-request client timeout seconds")

    mem_p = sub.add_parser(
        "mem",
        help="memory observatory: arena/cache footprint of a live server",
        description=(
            "read GET /api/debug/memory from a live simon server "
            "(docs/observability.md 'Memory & profiles'): process RSS and "
            "watermarks, per-device accelerator memory where available, the "
            "prep cache's host arena bytes attributed per entry (by encoder "
            "field and dtype, with lineage depth and drop-mask density), and "
            "bounded-ring occupancy (flight recorder, capacity timeline, "
            "journal writer queue). Totals count shared delta-entry leaves "
            "once and reconcile exactly with the per-entry unique-bytes sum"
        ),
    )
    mem_p.add_argument("--url", required=True, help="base URL of the live server (http://host:port)")
    mem_p.add_argument("--json", action="store_true", help="print the raw debug JSON instead of tables")
    mem_p.add_argument(
        "--fields", action="store_true",
        help="include the per-entry per-field arena breakdown (verbose)",
    )
    mem_p.add_argument("--timeout", type=float, default=60.0, help="per-request client timeout seconds")

    profile_p = sub.add_parser(
        "profile",
        help="cumulative phase profiles + compile telemetry of a live server",
        description=(
            "read GET /api/debug/profile from a live simon server "
            "(docs/observability.md 'Memory & profiles'): per-span cumulative "
            "latency profiles folded from every recorded request trace "
            "(count, inclusive/exclusive seconds, p50/p99) so 'where do "
            "requests spend their time' is one query instead of N traces, "
            "plus JIT compile telemetry — compiles and seconds per "
            "instrumented boundary with recompile-cause attribution (shape "
            "vs dtype vs static-flag change) and the persistent compile "
            "cache's footprint"
        ),
    )
    profile_p.add_argument("--url", required=True, help="base URL of the live server (http://host:port)")
    profile_p.add_argument("--json", action="store_true", help="print the raw debug JSON instead of tables")
    profile_p.add_argument("--timeout", type=float, default=60.0, help="per-request client timeout seconds")

    replay_p = sub.add_parser(
        "replay",
        help="reconstruct and replay a recorded watch-event journal",
        description=(
            "replay a journal recorded by `simon server --journal` "
            "(docs/live-twin.md 'Durability & replay'): reconstruct the "
            "live twin at any recorded generation and stream the accepted "
            "event history — at N× recorded speed or as fast as possible — "
            "through the same apply path the live dispatch uses, feeding "
            "the capacity observatory as it goes. Prints one JSON summary "
            "line: record counts, final generation, the reconstructed "
            "twin's content fingerprint, event throughput, and the final "
            "capacity sample. --schedule additionally drives the scheduler "
            "against the reconstructed cluster, turning a recorded "
            "production trace into a repeatable scenario"
        ),
    )
    replay_p.add_argument("journal", help="journal directory recorded by `simon server --journal`")
    replay_p.add_argument(
        "--speed", type=float, default=0.0,
        help="pace the stream at N× the recorded inter-event gaps "
        "(0 = as fast as possible, the default; gaps clamp at 30s)",
    )
    replay_p.add_argument(
        "--at-generation", type=int, default=None, metavar="G",
        help="stop once the twin reaches generation G (time-machine view "
        "of any recorded moment; default: the full history)",
    )
    replay_p.add_argument(
        "--capacity", action=argparse.BooleanOptionalAction, default=True,
        help="feed the capacity observatory during replay and include the "
        "final utilization/fragmentation sample in the summary",
    )
    replay_p.add_argument(
        "--schedule", type=int, default=0, metavar="PODS",
        help="after replay, schedule PODS synthetic pods onto the "
        "reconstructed cluster and report placements (proves the replayed "
        "twin is schedulable state, not just a data dump)",
    )
    replay_p.add_argument(
        "--events", action="store_true",
        help="also print one JSON line per replayed record (type, "
        "generation, resource) before the summary — the raw stream view",
    )
    replay_p.add_argument("-o", "--output-file", default="", help="also write the JSON summary to a file")

    lint_p = sub.add_parser(
        "lint",
        help="run the opensim-lint static analyzer (27 OSL rules)",
        description=(
            "repo-specific static analyzer (docs/static-analysis.md): AST "
            "rules, whole-program lock-discipline checks, and the "
            "interprocedural dataflow pack (jit-impurity, tracer-leak, "
            "input-taint). Exit 1 on findings."
        ),
    )
    lint_p.add_argument(
        "lint_paths", nargs="*", metavar="PATH",
        help="files/directories to lint (default: [tool.opensim-lint] "
        "paths in ./pyproject.toml, else opensim_tpu)",
    )
    lint_p.add_argument("--rules", default="", help="comma-separated rule names/codes (default: all)")
    lint_p.add_argument(
        "--format", default="", choices=["", "human", "json", "sarif"],
        help="output format (sarif = SARIF 2.1.0 for CI/editor annotation)",
    )
    lint_p.add_argument("--list-rules", action="store_true", help="list registered rules and exit")
    lint_p.add_argument(
        "--cache", default="", metavar="PATH",
        help="content-hash result cache (unchanged files skip their rules)",
    )
    lint_p.add_argument("--no-cache", action="store_true", help="disable the result cache")
    lint_p.add_argument(
        "--sarif-out", default="", metavar="PATH",
        help="also write SARIF to this path (stable CI artifact)",
    )
    lint_p.add_argument(
        "--corpus", default="", metavar="DIR",
        help="run the detector-awake fixture gate over DIR after linting",
    )
    lint_p.add_argument(
        "--changed", action="store_true",
        help="lint only files with uncommitted git changes (the fast "
        "pre-commit loop)",
    )
    lint_p.add_argument(
        "--jobs", type=int, default=None, metavar="N",
        help="process-pool width for the per-file rule tier (default: "
        "auto; 1 = serial)",
    )

    sub.add_parser("version", help="print version", description="print version and commit id")

    doc_p = sub.add_parser(
        "gen-doc", help="generate markdown docs for the CLI",
        description="generate one markdown doc per subcommand plus an index",
    )
    doc_p.add_argument("--output-dir", default="docs/commandline", help="where to write the docs")
    return parser


def main(argv: Optional[List[str]] = None) -> int:
    # persistent XLA compilation cache: repeated simon invocations with the
    # same shapes skip the (tens of seconds) first-compile cost; opt out /
    # relocate with OPENSIM_JIT_CACHE (utils/jitcache.py)
    from ..utils.jitcache import maybe_enable as _enable_jit_cache

    _enable_jit_cache(default=True)
    # compile telemetry listens from the start, so a run's device line
    # (device_line) counts every compile and persistent-cache hit
    from ..obs.profile import COMPILES  # noqa: F401  (import installs the listeners)

    level = LOG_LEVELS.get(os.environ.get("LogLevel", "info").lower(), logging.INFO)
    logging.basicConfig(level=level, format="%(levelname)s %(message)s")

    parser = build_parser()
    args = parser.parse_args(argv)

    # the platform is whatever JAX selects from JAX_PLATFORMS; only an
    # explicit --backend cpu overrides it in code
    backend = getattr(args, "backend", "auto")
    if backend != "auto":
        _select_backend(backend)

    if args.command == "version":
        print(f"simon version: {VERSION}, commit: {COMMIT_ID}")
        return 0
    if args.command == "apply":
        from ..planner.apply import Applier, Options
        from ..utils import validate

        try:
            # validator rejections render the same one-liner as run errors
            opts = Options(
                simon_config=validate.user_path(args.simon_config, label="--simon-config"),
                default_scheduler_config=validate.user_path(
                    args.default_scheduler_config, label="--default-scheduler-config",
                    allow_empty=True,
                ),
                output_file=validate.user_path(
                    args.output_file, label="--output-file", allow_empty=True
                ),
                use_greed=args.use_greed,
                enable_preemption=args.enable_preemption,
                interactive=args.interactive,
                extended_resources=[r for r in args.extended_resources.split(",") if r],
                report_pods=args.report_pods,
                max_new_nodes=args.max_new_nodes,
                tie_break=args.tie_break,
                explain=args.explain,
            )
            if not args.trace:
                rc = Applier(opts).run()
                logging.getLogger("opensim_tpu").info("apply ran on %s", device_line())
                return rc
            # span-trace the whole apply run and export Chrome-trace JSON
            # (the explicit flag wins over OPENSIM_TRACE=0). The file is
            # written in a finally: a FAILED run's partial trace is exactly
            # the one worth inspecting
            from ..obs import trace as tracing

            tr = tracing.start_trace("apply", force=True)
            rc = 1
            try:
                with tracing.trace_scope(tr):
                    rc = Applier(opts).run()
                return rc
            finally:
                tr.finish(status="ok" if rc == 0 else "error")
                tracing.write_chrome(tr, args.trace)
                logging.getLogger("opensim_tpu").info("apply ran on %s", device_line())
                print(
                    f"trace written to {args.trace} "
                    "(chrome://tracing or ui.perfetto.dev)",
                    file=sys.stderr,
                )
        except (OSError, ValueError) as e:
            print(f"simon apply: {e}", file=sys.stderr)
            return 1
    if args.command in ("defrag", "drain"):
        try:
            return run_defrag(args)
        except (OSError, ValueError) as e:
            print(f"simon defrag: {e}", file=sys.stderr)
            return 1
    if args.command == "campaign":
        try:
            return run_campaign_cmd(args)
        except (OSError, ValueError) as e:
            print(f"simon campaign: {e}", file=sys.stderr)
            return 1
    if args.command == "explain":
        try:
            return run_explain(args)
        except (OSError, ValueError) as e:
            print(f"simon explain: {e}", file=sys.stderr)
            return 1
    if args.command == "server":
        from ..server.rest import serve

        if args.access_log:
            os.environ["OPENSIM_ACCESS_LOG"] = "1"
        try:
            return serve(
                kubeconfig=args.kubeconfig, master=args.master, port=args.port,
                watch=args.watch, journal=args.journal, workers=args.workers,
                standby=args.standby, ha_handover=args.handover,
            )
        except ValueError as e:
            # serve()'s path validators reject control characters
            print(f"simon server: {e}", file=sys.stderr)
            return 1
    if args.command == "replay":
        try:
            return run_replay(args)
        except (OSError, ValueError) as e:
            print(f"simon replay: {e}", file=sys.stderr)
            return 1
    if args.command == "loadgen":
        import json as _json

        from ..server.loadgen import run_loadgen

        try:
            report = run_loadgen(
                args.url.rstrip("/"), mode=args.mode, concurrency=args.concurrency,
                qps=args.qps, duration_s=args.duration, replicas=args.replicas,
                cpu=args.cpu, mem=args.mem, timeout_s=args.timeout,
            )
        except (OSError, ValueError) as e:
            print(f"simon loadgen: {e}", file=sys.stderr)
            return 1
        line = _json.dumps(report, sort_keys=True)
        print(line)
        if args.output_file:
            from ..utils import validate

            try:
                with open(validate.user_path(args.output_file, label="--output-file"), "w") as f:
                    f.write(line + "\n")
            except (OSError, ValueError) as e:
                print(f"simon loadgen: {e}", file=sys.stderr)
                return 1
        return 0
    if args.command == "top":
        try:
            return run_top(args)
        except KeyboardInterrupt:
            return 0
    if args.command == "dash":
        try:
            return run_dash(args)
        except KeyboardInterrupt:
            return 0
    if args.command == "mem":
        return run_mem(args)
    if args.command == "profile":
        return run_profile(args)
    if args.command == "lint":
        # same engine as `python -m opensim_tpu.analysis` / `make lint`:
        # forward the flags so the analyzer stays reachable without make
        from ..analysis.__main__ import main as lint_main

        argv2: List[str] = list(args.lint_paths)
        if args.rules:
            argv2 += ["--rules", args.rules]
        if args.format:
            argv2 += ["--format", args.format]
        if args.list_rules:
            argv2.append("--list-rules")
        if args.cache:
            argv2 += ["--cache", args.cache]
        if args.no_cache:
            argv2.append("--no-cache")
        if args.sarif_out:
            argv2 += ["--sarif-out", args.sarif_out]
        if args.corpus:
            argv2 += ["--corpus", args.corpus]
        if args.changed:
            argv2.append("--changed")
        if args.jobs is not None:
            argv2 += ["--jobs", str(args.jobs)]
        return lint_main(argv2)
    if args.command == "gen-doc":
        try:
            return gen_doc(parser, args.output_dir)
        except (OSError, ValueError) as e:
            print(f"simon gen-doc: {e}", file=sys.stderr)
            return 1
    parser.print_help()
    return 2


def run_top(args) -> int:
    """``simon top``: the capacity observatory's live view — fetch
    ``/api/cluster/report`` and render the same numbers the report tables
    carry (one shot, ``--json``, or a ``--watch`` refresh loop)."""
    import json as _json
    import time as _time
    import urllib.error
    import urllib.parse
    import urllib.request

    from ..obs.capacity import format_top

    params = {}
    if args.no_headroom:
        params["headroom"] = "0"
    if args.mem:
        params["mem"] = "1"
    extended = [e for e in args.extended_resources.split(",") if e]
    if extended:
        params["extended"] = ",".join(extended)
    url = f"{args.url.rstrip('/')}/api/cluster/report"
    if params:
        url += "?" + urllib.parse.urlencode(params)

    def fetch() -> dict:
        with urllib.request.urlopen(url, timeout=args.timeout) as resp:
            return _json.load(resp)

    while True:
        try:
            report = fetch()
        except (urllib.error.URLError, OSError, ValueError) as e:
            if args.watch:
                # a dashboard must survive server restarts and transient
                # blips (watch(1)/kubectl top semantics): report the error
                # in place and keep polling until Ctrl-C
                print(f"\x1b[2J\x1b[Hsimon top: {url}: {e} (retrying)", flush=True)
                _time.sleep(max(0.1, args.interval))
                continue
            print(f"simon top: {url}: {e}", file=sys.stderr)
            return 1
        if args.json:
            rendered = _json.dumps(report, indent=2, sort_keys=True)
        else:
            rendered = format_top(report).rstrip("\n")
        if args.watch:
            # clear + home, like watch(1)/kubectl top: the view refreshes
            # in place instead of scrolling the terminal
            print(f"\x1b[2J\x1b[H{rendered}", flush=True)
            _time.sleep(max(0.1, args.interval))
        else:
            print(rendered)
            return 0


def run_dash(args) -> int:
    """``simon dash``: fetch the ring + SLO surfaces, render via the pure
    row functions in ``cli/dash.py`` (one frame with ``--once``, refresh
    in place otherwise — watch(1) semantics like ``simon top``)."""
    import json as _json
    import time as _time

    from .dash import dash_rows, fetch_dash, format_dash

    while True:
        payload = fetch_dash(args.url, args.range_spec, timeout_s=args.timeout)
        if args.json:
            rendered = _json.dumps(dash_rows(payload), sort_keys=True)
        else:
            rendered = format_dash(payload)
        if args.once:
            print(rendered)
            # both surfaces down = nothing was dashboarded; exit nonzero
            # so smoke harnesses notice
            return 1 if ("timeseries" not in payload and "slo" not in payload) else 0
        print(f"\x1b[2J\x1b[H{rendered}", flush=True)
        _time.sleep(max(0.1, args.interval))


def run_defrag(args) -> int:
    """``simon defrag`` / ``simon drain``: batch-evaluated node-drain
    what-ifs. Text and ``--json`` both serialize the SAME rows
    (``planner/report.drain_plan_rows`` — the byte-parity contract every
    report table follows)."""
    import json as _json

    from ..planner.apply import Applier, Options
    from ..planner.defrag import plan_drains
    from ..planner.report import _table, drain_plan_rows
    from ..utils import validate

    applier = Applier(
        Options(simon_config=validate.user_path(args.simon_config, label="--simon-config"))
    )
    cluster = applier.load_cluster()
    apps = applier.load_apps()

    candidates = [c.strip() for c in args.candidates.split(",") if c.strip()] or None
    if candidates:
        known = {n.metadata.name for n in cluster.nodes}
        unknown = [c for c in candidates if c not in known]
        if unknown:
            print(f"simon defrag: unknown node(s): {', '.join(unknown)}", file=sys.stderr)
            return 1
    result = plan_drains(cluster, apps, candidates=candidates)
    rows = drain_plan_rows(result.plans)
    out = (
        open(validate.user_path(args.output_file, label="--output-file"), "w")
        if args.output_file
        else sys.stdout
    )
    try:
        if args.json:
            print(
                _json.dumps(
                    {
                        "table": {"header": rows[0], "rows": rows[1:]},
                        "drainable": len(result.drainable()),
                        "total": len(result.plans),
                    },
                    sort_keys=True,
                ),
                file=out,
            )
        else:
            print("Drain Plan", file=out)
            _table(rows, out)
            print(f"\n{len(result.drainable())}/{len(result.plans)} node(s) drainable", file=out)
    finally:
        if args.output_file:
            out.close()
    return 0


def run_campaign_cmd(args) -> int:
    """``simon campaign <spec.yaml>``: execute a lifecycle campaign locally
    against the spec's cluster, or — with ``--url`` — POST its steps to a
    live server's ``/api/campaign`` (evaluated against the live twin).
    Text and ``--json`` both serialize the same table rows."""
    import json as _json

    from ..planner import campaign as campaign_mod
    from ..planner.report import render_campaign
    from ..utils import validate

    spec_path = validate.user_path(args.spec, label="spec")
    spec = campaign_mod.load_campaign(spec_path)
    if args.url:
        import urllib.error
        import urllib.request
        import yaml as _yaml

        with open(spec_path) as fh:
            doc = _yaml.safe_load(fh) or {}
        body = _json.dumps(
            {
                "name": spec.name,
                "steps": (doc.get("spec") or {}).get("steps") or [],
                **({"mode": args.exec_mode} if args.exec_mode else {}),
            }
        ).encode()
        req = urllib.request.Request(
            f"{args.url.rstrip('/')}/api/campaign",
            data=body,
            headers={"Content-Type": "application/json"},
        )
        try:
            with urllib.request.urlopen(req, timeout=args.timeout) as resp:
                result = _json.load(resp)
        except urllib.error.HTTPError as e:
            try:
                detail = _json.load(e)
            except ValueError:
                detail = {"error": str(e)}
            print(f"simon campaign: HTTP {e.code}: {detail.get('error', e)}", file=sys.stderr)
            return 1
    else:
        cluster = campaign_mod.load_campaign_cluster(spec)
        result = campaign_mod.run_campaign(
            cluster, spec, mode=args.exec_mode or None
        ).to_dict()
    out = sys.stdout
    if args.json:
        rendered = _json.dumps(result, indent=2, sort_keys=True)
        print(rendered, file=out)
    else:
        render_campaign(result, out)
    if args.output_file:
        with open(validate.user_path(args.output_file, label="--output-file"), "w") as fh:
            fh.write(_json.dumps(result, sort_keys=True) + "\n")
    # a campaign that left evictions blocked or pods unschedulable is a
    # finding, not a failure: exit 0 with the verdict in the report
    return 0


def _fetch_debug(url: str, timeout: float):
    import json as _json
    import urllib.error
    import urllib.request

    try:
        with urllib.request.urlopen(url, timeout=timeout) as resp:
            return _json.load(resp), None
    except (urllib.error.URLError, OSError, ValueError) as e:
        return None, f"{url}: {e}"


def run_mem(args) -> int:
    """``simon mem``: the memory observatory's live view — fetch
    ``GET /api/debug/memory`` and render the footprint tables (or the raw
    JSON with ``--json``)."""
    import json as _json

    from ..obs.footprint import fmt_bytes
    from ..planner.report import _table

    url = f"{args.url.rstrip('/')}/api/debug/memory"
    if not args.fields:
        url += "?fields=0"
    payload, err = _fetch_debug(url, args.timeout)
    if err:
        print(f"simon mem: {err}", file=sys.stderr)
        return 1
    if args.json:
        print(_json.dumps(payload, indent=2, sort_keys=True))
        return 0
    out = sys.stdout
    proc = payload.get("process") or {}
    print(
        f"process: RSS {fmt_bytes(int(proc.get('rss_bytes', 0)))} "
        f"(peak {fmt_bytes(int(proc.get('rss_peak_bytes', 0)))})",
        file=out,
    )
    for dev, stats in sorted((payload.get("devices") or {}).items()):
        print(
            f"device {dev}: {fmt_bytes(int(stats.get('in_use', 0)))} in use "
            f"(peak {fmt_bytes(int(stats.get('peak', 0)))})",
            file=out,
        )
    cache = payload.get("prepcache") or {}
    entries = cache.get("entries") or []
    print(
        f"\nprep cache: {fmt_bytes(int(cache.get('total_bytes', 0)))} across "
        f"{len(entries)} entr{'y' if len(entries) == 1 else 'ies'} "
        f"({fmt_bytes(int(cache.get('shared_bytes', 0)))} shared between "
        f"delta lineages), {cache.get('compactions', 0)} compaction(s)",
        file=out,
    )
    dtypes = cache.get("dtypes") or {}
    if dtypes:
        print(
            "arena bytes by dtype: "
            + ", ".join(f"{k}={fmt_bytes(int(v))}" for k, v in sorted(dtypes.items())),
            file=out,
        )
    if entries:
        rows = [["Entry", "Bytes", "Unique", "Depth", "Pods", "Drop%"]]
        for e in entries:
            rows.append(
                [
                    e.get("key", "")[:40],
                    fmt_bytes(int(e.get("bytes", 0))),
                    fmt_bytes(int(e.get("unique_bytes", 0))),
                    str(e.get("lineage_depth", 0)),
                    str(e.get("pods", 0)),
                    f"{float(e.get('drop_density', 0.0)) * 100:.1f}",
                ]
            )
        print("", file=out)
        _table(rows, out)
    rings = payload.get("rings") or {}
    if rings:
        rows = [["Ring", "Occupancy"]]
        for ring, occ in sorted(rings.items()):
            rows.append([ring, f"{occ.get('entries', 0)}/{occ.get('capacity', 0)}"])
        print("", file=out)
        _table(rows, out)
    return 0


def run_profile(args) -> int:
    """``simon profile``: cumulative per-phase latency profiles + compile
    telemetry from ``GET /api/debug/profile``."""
    import json as _json

    from ..planner.report import _table

    payload, err = _fetch_debug(
        f"{args.url.rstrip('/')}/api/debug/profile", args.timeout
    )
    if err:
        print(f"simon profile: {err}", file=sys.stderr)
        return 1
    if args.json:
        print(_json.dumps(payload, indent=2, sort_keys=True))
        return 0
    out = sys.stdout
    phases = payload.get("phases") or {}
    spans = phases.get("spans") or {}
    print(f"phase profile over {phases.get('traces', 0)} recorded trace(s):", file=out)
    rows = [["Span", "Calls", "Total s", "Exclusive s", "Mean s", "p50 s", "p99 s", "Max s"]]
    for name, d in spans.items():
        rows.append(
            [
                name,
                str(d.get("count", 0)),
                f"{d.get('seconds', 0.0):.3f}",
                f"{d.get('exclusive_seconds', 0.0):.3f}",
                f"{d.get('mean_s', 0.0):.4f}",
                f"{d.get('p50_s', 0.0):.4f}",
                f"{d.get('p99_s', 0.0):.4f}",
                f"{d.get('max_s', 0.0):.4f}",
            ]
        )
    _table(rows, out)
    compiles = payload.get("compiles") or {}
    backend = compiles.get("backend") or {}
    print(
        f"\nbackend compiles: {backend.get('compiles', 0)} "
        f"({backend.get('seconds', 0.0):.2f}s)",
        file=out,
    )
    boundaries = compiles.get("boundaries") or {}
    if boundaries:
        rows = [["Boundary", "Compiles", "Seconds", "Signatures", "Causes"]]
        for name, fn in sorted(boundaries.items()):
            causes = ", ".join(
                f"{c}={n}" for c, n in sorted((fn.get("causes") or {}).items())
            )
            rows.append(
                [
                    name,
                    str(fn.get("compiles", 0)),
                    f"{fn.get('seconds', 0.0):.3f}",
                    str(fn.get("distinct_signatures", 0)),
                    causes,
                ]
            )
        _table(rows, out)
    pc = compiles.get("persistent_cache")
    if pc:
        print(
            f"persistent jit cache: {pc.get('files', 0)} file(s), "
            f"{pc.get('bytes', 0)} bytes at {pc.get('dir', '')}",
            file=out,
        )
    events = compiles.get("cache_events") or {}
    if events:
        print(
            "compilation-cache events: "
            + ", ".join(f"{k}={v}" for k, v in sorted(events.items())),
            file=out,
        )
    pipe = payload.get("pipeline") or {}
    if pipe.get("batches"):
        overlap = pipe.get("prep_overlap_s", 0.0)
        print(
            f"\nadmission pipeline ({'on' if pipe.get('enabled') else 'off'}): "
            f"{pipe.get('batches', 0)} batch(es), "
            f"{pipe.get('overlapped_batches', 0)} overlapped "
            f"({overlap:.3f}s prep under dispatch)",
            file=out,
        )
        stages = pipe.get("stages") or {}
        if stages:
            rows = [["Stage", "Batches", "Total s", "Mean s", "Max s"]]
            for stage in ("prep", "dispatch", "decode"):
                d = stages.get(stage)
                if not d:
                    continue
                count = d.get("count", 0)
                total = d.get("total_s", 0.0)
                rows.append(
                    [
                        stage,
                        str(int(count)),
                        f"{total:.3f}",
                        f"{(total / count if count else 0.0):.4f}",
                        f"{d.get('max_s', 0.0):.4f}",
                    ]
                )
            _table(rows, out)
        lanes = pipe.get("lane_admitted") or {}
        if pipe.get("lanes_enabled") and lanes:
            promo = pipe.get("starvation_promotions", 0)
            print(
                "priority lanes: "
                + ", ".join(
                    f"{lane}={n} admitted" for lane, n in sorted(lanes.items())
                )
                + f", {promo} starvation promotion(s)",
                file=out,
            )
    return 0


def run_replay(args) -> int:
    """``simon replay <journal>`` — the twin time machine (ISSUE 11,
    server/journal.py). Streams the recorded accepted-event history through
    the live apply path, optionally paced, optionally feeding the capacity
    observatory and the scheduler, and prints one JSON summary line."""
    import json as _json
    import time as _time

    from ..server.journal import replay_events

    if not os.path.isdir(args.journal):
        print(f"simon replay: {args.journal}: not a journal directory", file=sys.stderr)
        return 1
    capacity = None
    if args.capacity:
        from ..obs.capacity import CapacityEngine

        capacity = CapacityEngine()
    counts = {"ev": 0, "rb": 0, "ck": 0}
    twin = None
    t0 = _time.time()
    for rec, twin, change in replay_events(
        args.journal, speed=args.speed, at_generation=args.at_generation
    ):
        counts[str(rec.get("t"))] = counts.get(str(rec.get("t")), 0) + 1
        if capacity is not None:
            capacity.on_replay(rec, twin, change)
        if args.events:
            print(_json.dumps({
                "type": rec.get("t"), "generation": rec.get("gen"),
                "resource": rec.get("f", ""), "event": rec.get("k", ""),
            }, sort_keys=True))
    wall_s = _time.time() - t0
    if twin is None:
        print(f"simon replay: {args.journal}: no replayable records", file=sys.stderr)
        return 1
    summary = {
        "journal": args.journal,
        "records": sum(counts.values()),
        "events": counts.get("ev", 0),
        "rebases": counts.get("rb", 0),
        "checkpoints": counts.get("ck", 0),
        "generation": twin.generation,
        "fingerprint": twin.fingerprint(),
        "wall_s": round(wall_s, 3),
        "speed": args.speed,
        "events_per_s": round(counts.get("ev", 0) / wall_s, 1) if wall_s > 0 else 0.0,
    }
    if capacity is not None:
        s = capacity.sample()
        if s is not None:
            summary["capacity"] = {
                "nodes": s.nodes, "pods_bound": s.pods_bound,
                "pods_pending": s.pods_pending,
                "utilization": {k: round(v, 4) for k, v in s.utilization.items()},
                "fragmentation": {k: round(v, 4) for k, v in s.fragmentation.items()},
            }
    if args.schedule > 0:
        # the reconstructed twin is schedulable state, not a data dump:
        # place a synthetic workload onto it through the full engine path
        from ..engine.simulator import AppResource, simulate
        from ..models import ResourceTypes, fixtures as fx

        rt = ResourceTypes()
        rt.deployments.append(
            fx.make_fake_deployment("replay-probe", args.schedule, "100m", "256Mi")
        )
        t1 = _time.time()
        result = simulate(twin.materialize(), [AppResource("replay", rt)])
        summary["schedule"] = {
            "requested": args.schedule,
            "scheduled": args.schedule - len(result.unscheduled_pods),
            "unscheduled": len(result.unscheduled_pods),
            "wall_s": round(_time.time() - t1, 3),
        }
    line = _json.dumps(summary, sort_keys=True)
    print(line)
    if args.output_file:
        from ..utils import validate

        with open(validate.user_path(args.output_file, label="--output-file"), "w") as f:
            f.write(line + "\n")
    return 0


def _render_explanation(e, out) -> None:
    """Human rendering of one PlacementExplanation (``simon explain``)."""
    print(f"pod {e.pod}: {e.status}"
          + (f" on {e.node}" if e.node else "")
          + (" (pre-bound; bypassed the scheduler)" if e.forced else ""),
          file=out)
    from ..engine import reasons as reasons_mod

    if e.message:
        print(f"  {e.message}", file=out)
    for line in reasons_mod.count_lines(e.reasons):
        print(f"  {line}", file=out)
    if e.scores:
        print(f"  per-plugin score breakdown on {e.node}:", file=out)
        width = max(len(k) for k in e.scores)
        for k, v in e.scores.items():
            print(f"    {k:<{width}}  {v:10.4f}", file=out)
        print(f"    {'total':<{width}}  {e.score:10.4f}", file=out)
        if e.runner_up is not None:
            print(f"  margin {e.margin:.4f} over runner-up {e.runner_up}", file=out)


def run_explain(args) -> int:
    """``simon explain``: one simulation with the decision audit on, then
    print the named pod's deep explanation (score breakdown / kube-style
    rejection counts) or, without a pod, the audit summary."""
    import json as _json

    from ..engine import explain as explain_mod
    from ..engine.simulator import simulate
    from ..planner.apply import Applier, Options

    from ..utils import validate

    applier = Applier(
        Options(
            simon_config=validate.user_path(args.simon_config, label="--simon-config"),
            default_scheduler_config=validate.user_path(
                args.default_scheduler_config, label="--default-scheduler-config",
                allow_empty=True,
            ),
            use_greed=bool(args.use_greed),
        )
    )
    cluster = applier.load_cluster()
    apps = applier.load_apps()
    result = simulate(
        cluster, apps, use_greed=args.use_greed,
        sched_config=applier.sched_config, explain=True,
    )
    engine = result.engine
    if engine is None or engine.explain_ctx is None:
        print("simon explain: the simulation produced no decisions (no pods)", file=sys.stderr)
        return 1
    ctx = engine.explain_ctx
    out = sys.stdout
    if args.pod:
        idx = ctx.index_of(args.pod)
        if idx is None:
            known = sorted(
                f"{p.metadata.namespace}/{p.metadata.name}" for p in ctx.prep.ordered
            )
            preview = ", ".join(known[:8]) + (", …" if len(known) > 8 else "")
            print(
                f"simon explain: no pod named {args.pod!r} in the simulated "
                f"stream ({len(known)} pods: {preview})",
                file=sys.stderr,
            )
            return 1
        deep = explain_mod.explain_pod(ctx, idx)
        if args.json:
            print(_json.dumps(deep.to_dict(), indent=2))
        else:
            _render_explanation(deep, out)
        return 0
    # no pod named: summary + every non-scheduled pod's breakdown
    if args.json:
        print(
            _json.dumps(
                {
                    "engine": engine.describe(),
                    "filter_rejects": engine.filter_rejects or {},
                    "unschedulable": [
                        e.to_dict()
                        for e in engine.explanations or []
                        if e.status != "scheduled"
                    ],
                },
                indent=2,
            )
        )
        return 0
    from ..engine import reasons as reasons_mod

    print(f"engine: {engine.describe()}", file=out)
    if engine.filter_rejects:
        print(
            "filter rejects (nodes rejected per filter, all steps): "
            + reasons_mod.format_rejects(engine.filter_rejects),
            file=out,
        )
    bad = [e for e in engine.explanations or [] if e.status != "scheduled"]
    n_ok = len(engine.explanations or []) - len(bad)
    print(f"{n_ok} pod(s) scheduled, {len(bad)} not", file=out)
    for e in bad:
        _render_explanation(e, out)
    return 0


def _select_backend(backend: str) -> None:
    """--backend plumbing (the BASELINE north star's `--backend=tpu` knob):
    auto picks the best engine for the platform (Pallas megakernel on TPU,
    XLA scan on CPU); cpu forces the host platform; xla disables the Pallas
    engine (pure XLA scan); tpu requires the accelerator."""
    import jax

    if backend == "cpu":
        jax.config.update("jax_platforms", "cpu")
    elif backend == "xla":
        os.environ["OPENSIM_DISABLE_FASTPATH"] = "1"
    elif backend == "tpu":
        from ..utils import envknobs

        if envknobs.raw("OPENSIM_FASTPATH") == "interpret":
            print(
                "simon: --backend tpu refuses OPENSIM_FASTPATH=interpret "
                "(the Pallas interpreter is not the chip)", file=sys.stderr,
            )
            raise SystemExit(1)
        if jax.default_backend() != "tpu":
            print(
                "simon: --backend tpu requested but JAX selected "
                f"{jax.default_backend()!r} (JAX_PLATFORMS="
                f"{os.environ.get('JAX_PLATFORMS', '')!r})", file=sys.stderr,
            )
            raise SystemExit(1)
        # a megakernel compile failure must be a hard error under an explicit
        # TPU request, not a silent fallback (engine/simulator.py honors this)
        os.environ["OPENSIM_REQUIRE_TPU"] = "1"


def device_line() -> str:
    """``platform=… device_kind=… devices=N`` of the backend JAX selected,
    plus this process's compile/persistent-cache counters — logged once per
    engine-bearing run so a transcript says where its numbers came from."""
    from ..obs.profile import COMPILES, device_stamp

    dev = device_stamp()
    snap = COMPILES.snapshot()
    events = snap["cache_events"]
    cache = snap["persistent_cache"]
    return (
        f"platform={dev['platform']} device_kind={dev['device_kind']!r} "
        f"devices={dev['device_count']} backend_compiles={snap['backend']['compiles']} "
        f"compile_s={snap['backend']['seconds']:.2f} "
        f"cache_hits={events.get('cache_hits', 0)} "
        f"cache_misses={events.get('cache_misses', 0)} "
        f"cache_dir={cache['dir'] if cache else 'off'}"
    )


def gen_doc(parser: argparse.ArgumentParser, output_dir: str) -> int:
    """Markdown CLI docs — one file per subcommand plus a root index, the
    same tree cobra/doc emits for the reference
    (cmd/doc/generate_markdown.go:33 → docs/commandline/simon_apply.md …)."""
    from ..utils import validate

    output_dir = validate.user_path(output_dir, label="--output-dir")
    os.makedirs(output_dir, exist_ok=True)
    sub_actions = [a for a in parser._actions if isinstance(a, argparse._SubParsersAction)]
    commands = []
    seen_parsers = set()  # aliases map to the same parser: document once
    for action in sub_actions:
        for name, sp in action.choices.items():
            if id(sp) in seen_parsers:
                continue
            seen_parsers.add(id(sp))
            commands.append((name, sp))
    written = []
    with open(os.path.join(output_dir, "simon.md"), "w") as f:
        f.write(f"# simon\n\n{parser.description}\n\n```\n{parser.format_help()}```\n\n")
        f.write("## Commands\n\n")
        for name, sp in commands:
            f.write(f"- [simon {name}](simon_{name.replace('-', '_')}.md) — {sp.description or ''}\n")
    written.append("simon.md")
    for name, sp in commands:
        fname = f"simon_{name.replace('-', '_')}.md"
        with open(os.path.join(output_dir, fname), "w") as f:
            f.write(f"# simon {name}\n\n{sp.description or sp.prog}\n\n")
            f.write(f"```\n{sp.format_help()}```\n\n[simon](simon.md)\n")
        written.append(fname)
    print(f"docs written to {output_dir}: {', '.join(written)}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
