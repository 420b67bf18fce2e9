# parity with the reference's Makefile targets (build/test), TPU edition
.PHONY: test test-quick test-slow bench bench-all bench-serial docs native all lint mypy verify chaos perf-smoke obs-smoke twin-smoke explain-smoke loadgen-smoke capacity-smoke replay-smoke tsan mem-smoke perf-guard campaign-smoke ha-smoke dash-smoke

all: test

test:
	python -m pytest tests/ -q

# opensim-lint: repo-specific static analyzer (docs/static-analysis.md) —
# 27 rules incl. the interprocedural dataflow pack (OSL16xx) and the
# array-contract engine (OSL18xx), result-cached
# by content hash (.lint/cache.json: unchanged files skip their rules), a
# SARIF artifact at a stable path for CI upload, and the detector-awake
# corpus gate (every rule must fire on its fixture, stay quiet on the
# clean twin). `simon lint` is the same engine without make.
lint:
	python -m opensim_tpu.analysis opensim_tpu --cache .lint/cache.json --sarif-out .lint/opensim-lint.sarif --corpus tests/lint_corpus

# strict on the typed core (engine/prepcache, encoding/state, models/quantity);
# skipped with a notice when mypy is not in the image — the CI gate still
# runs the AST signature check below, which needs only the stdlib
mypy:
	@if python -c "import mypy" 2>/dev/null; then \
		python -m mypy opensim_tpu; \
	else \
		echo "mypy not installed: falling back to stdlib signature check"; \
		python -m opensim_tpu.analysis --check-typed-core; \
	fi

# fault-injection suite (docs/resilience.md): every OPENSIM_FAULTS point
# must either recover (retry/fallback, placements identical to an
# uninjected run) or fail closed with a typed error and intact /metrics.
# test_watch.py drives the live twin's watch faults (disconnect/410/lost
# event) against the canned stub apiserver mid-stream (docs/live-twin.md)
chaos:
	python -m pytest tests/test_chaos.py tests/test_resilience.py tests/test_watch.py tests/test_journal.py tests/test_ha.py -q

# perf gate (ISSUE 4, widened by ISSUE 19): small affinity/ports/gpu
# workloads must engage the C++ engine's incremental cache (with per-carry-
# class attribution) AND match the forced-generic path bit-for-bit
perf-smoke:
	python tools/perf_smoke.py

# observability gate (ISSUE 5, docs/observability.md): a live server must
# echo X-Simon-Request-Id, serve the request's span tree from the flight
# recorder, and render phase latency histograms at /metrics
obs-smoke:
	python tools/obs_smoke.py

# live-twin gate (ISSUE 6, docs/live-twin.md): stub apiserver + watch-mode
# server + injected disconnect/410/lost-event storm; the twin must
# reconverge with placements shape-equal to a fresh full relist, drift
# detected, and events carried by delta re-encodes (no full prepare)
twin-smoke:
	python tools/twin_smoke.py

# decision-audit gate (ISSUE 7, docs/observability.md): `simon explain` on
# an unschedulable pod must render a kube-style "0/N nodes are available"
# breakdown whose per-filter counts are identical between the XLA and C++
# generic engines, and the deep per-pod score breakdown must sum to the
# winner's total
explain-smoke:
	python tools/explain_smoke.py

# serving-core gate (ISSUE 8, docs/serving.md): closed-loop loadgen against
# two live stub-backed servers — the admission-queue server must sustain
# more QPS than the single-flight baseline with a non-empty batch-size
# histogram and bounded p99 (the full ≥4x number: bench.py --config serving)
loadgen-smoke:
	python tools/loadgen_smoke.py

# capacity-observatory gate (ISSUE 9, docs/observability.md): an event
# storm against the stub apiserver must move the utilization/headroom
# gauges with full-prepare count == bootstrap only (O(changes) refresh),
# headroom bit-consistent with a fresh simulate probe, and the per-node
# /metrics series capped at OPENSIM_CAPACITY_TOPK
capacity-smoke:
	python tools/capacity_smoke.py

# durability gate (ISSUE 11, docs/live-twin.md "Durability & replay"):
# record a stub storm into a journal, crash with a torn tail, recover —
# fingerprint bit-equal to a fresh relist with ZERO relists and exactly the
# restored lineage's one full prepare — then `simon replay --speed 10` and
# `bench.py --config replay` must reproduce the final twin fingerprint
replay-smoke:
	python tools/replay_smoke.py

# memory-observatory gate (ISSUE 12, docs/observability.md "Memory &
# profiles"): a request storm + twin-delta churn must move the simon_mem_*
# gauges, prep-cache totals must reconcile exactly with the per-entry
# arena attributions, delta lineage/drop density must be visible, and the
# whole scrape must stay exposition-conformant with zero duplicate series
mem-smoke:
	python tools/mem_smoke.py

# perf-regression sentinel (ISSUE 12, BENCH.md "Guarding the trajectory"):
# every committed BENCH_BASELINE.json row must pass its own tolerances AND
# a synthetically slowed copy must fail (detector-awake proof). Run in
# tolerance-only mode under verify so wall-clock on a slow CI box cannot
# flake the build while exact metrics (placement counts, error counts)
# still gate. Fresh-row runs: tools/perf_guard.py --fresh --baseline KEY
perf-guard:
	python tools/perf_guard.py --tolerance-only

# campaign-engine gate (ISSUE 13, docs/campaigns.md): a 3-step lifecycle
# campaign (PDB-aware drain wave + reclaim storm + scale-down check) POSTed
# to /api/campaign on the stub-apiserver twin must run with EXACTLY ONE
# full prepare, move the capacity scores, charge the PDB ledger, keep
# text/JSON table parity, and a small `bench.py --config campaign` row must
# parse with its in-row warm-vs-cold fingerprint gate green
campaign-smoke:
	python tools/campaign_smoke.py

# HA control-plane gate (ISSUE 18, docs/serving.md#surviving-owner-loss):
# loadgen driven straight through an owner SIGKILL — the tailing standby
# takes the fenced lease and adopts the surviving workers with ZERO client
# errors, bit-identical placements, exactly one takeover, and no orphaned
# /dev/shm segment after teardown
ha-smoke:
	python tools/ha_smoke.py

# fleet-observability gate (ISSUE 20, docs/observability.md "Watching
# the fleet"): a live 2-worker fleet under load must serve a non-empty
# time-series ring and a conformant SLO endpoint, render byte-stable
# `simon dash --once --json` rows, expose zero duplicate series at the
# aggregated admin /metrics, stitch the owner's publication span into
# worker request traces, and lose no measurable QPS with OPENSIM_TRACE=0
dash-smoke:
	python tools/dash_smoke.py

# runtime lock-order sanitizer (docs/static-analysis.md#make-tsan): a
# seeded A->B/B->A inversion must be caught (detector self-test), then the
# threaded test modules run under instrumented locks — any observed
# lock-order inversion or non-exempt >OPENSIM_LOCKWATCH_HOLD_MS hold fails;
# skips gracefully when the threaded tests are excluded from the build
tsan:
	python tools/tsan.py

# the CI gate: static analysis + types + tier-1 tests + chaos + perf + obs + twin + explain + loadgen + capacity + replay + lock sanitizer + memory + perf trajectory + campaigns + HA failover + fleet observability
verify: lint mypy test-quick chaos perf-smoke obs-smoke twin-smoke explain-smoke loadgen-smoke capacity-smoke replay-smoke tsan mem-smoke perf-guard campaign-smoke ha-smoke dash-smoke

# inner-loop tier (<90 s): skips the nightly oracle/fuzz/multihost/parity
# matrix suites — run `make test` (both tiers) before shipping
test-quick:
	python -m pytest tests/ -q -m "not slow"

test-slow:
	python -m pytest tests/ -q -m slow

bench:
	python bench.py

bench-all: bench
	python bench.py --config example
	python bench.py --config gpushare
	python bench.py --pods 10000 --nodes 1000
	python bench.py --config affinity --pods 5000 --nodes 500
	python bench.py --config affinity
	python bench.py --config defrag --scenarios 64 --nodes 200 --pods 2000
	python bench.py --config bigu --pods 50000 --nodes 5000
	python bench.py --config forced --pods 50000 --nodes 5000

# measured serial floor on the 5 BASELINE configs (hours at full scale;
# see tools/serial_baseline.py --help for per-config runs)
bench-serial:
	python tools/serial_baseline.py --config all

docs:
	python -m opensim_tpu gen-doc --output-dir docs/commandline
	python -m opensim_tpu.utils.envknobs > docs/env.md

native:
	python -c "from opensim_tpu import native; p = native.ensure_built(); print(p or native.load_error())"
