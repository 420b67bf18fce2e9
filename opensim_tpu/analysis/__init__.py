"""opensim-lint: AST-level correctness analyzer for this repo.

Usage:
    python -m opensim_tpu.analysis [paths...] [--json] [--rules a,b]
    make lint

Rules (short name = suppression id; see docs/static-analysis.md):
    OSL101 jit-boundary       host-side work inside jit-traced code
    OSL201 dtype-drift        encoder arrays off the Go dtype policy
    OSL301 determinism        unordered iteration on ordered streams
    OSL401 cache-mutation     mutation of fingerprinted objects
    OSL501 exception-swallow  broad except without raise/log
    OSL601 unbounded-retry    retry loop without a bound or backoff
    OSL701 deadline-span      Deadline phase boundary without a trace span
    OSL801 unsupervised-watch-loop  `while True` watch/reconnect loop
                              bypassing resilience.retry
    OSL901 reason-literal     inline unschedulable-reason string bypassing
                              the reason-code registry (engine/reasons.py)
    OSL1001 admission-lock-io blocking I/O while holding the admission/
                              dispatch lock (server/admission.py)
    OSL1101 metric-registry   metric-family registration outside
                              obs/metrics.py's FAMILIES registry
    OSL1201 unguarded-shared-state  `# guarded-by:` attribute touched
                              outside its lock's critical sections
    OSL1202 lock-order-inversion    cycle in the whole-program static
                              lock-acquisition graph
    OSL1203 blocking-call-under-lock  OSL1001 generalized to every
                              critical section in the repo
    OSL1204 thread-unsafe-contextvar  ambient Deadline/Trace read in a
                              thread entry without explicit handoff
    OSL1301 journal-discipline  unchecksummed/foreign writes on journal
                              paths (server/journal.py owns the format)
    OSL1401 env-registry      raw os.environ read of an OPENSIM_* knob
                              outside utils/envknobs.py
    OSL1501 campaign-step-registry  campaign step-type dispatch outside
                              planner/campaign.py's STEP_TYPES registry
    OSL1601 jit-impurity      side effect (I/O, clock/RNG, host sync,
                              state write) in a function transitively
                              reachable from a jit-traced region
    OSL1602 tracer-leak       traced value stored into state that
                              outlives the trace
    OSL1603 input-taint       untrusted input (HTTP/CLI/YAML) reaches a
                              filesystem/subprocess sink without a
                              registered @sanitizer validator
    OSL1701 shm-discipline    shared-memory segment create/attach/unlink
                              outside server/fleet.py (the fleet's
                              /dev/shm hygiene owner)
    OSL1801 array-off-policy  array built without a policy dtype reaches
                              a contracted arena field or kernel boundary
    OSL1802 silent-upcast     dtype promotion on a path reaching an arena
                              write or kernel boundary (interprocedural)
    OSL1803 shape-contract    rank/axis-order mismatch vs the declared
                              (dtype, axes) contract
    OSL1804 contract-abi-parity  contract registry / dtypes policy /
                              arena struct fields out of sync

The OSL12xx family is whole-program (symbol table + call graph + lock
graph across all linted files); its runtime counterpart is the lock-order
sanitizer ``analysis/lockwatch.py`` (`make tsan`, ``OPENSIM_LOCKWATCH=1``).
The OSL16xx family runs on the interprocedural dataflow engine
(``analysis/dataflow.py``: per-function CFGs + reaching definitions,
call-graph effect fixpoint, forward taint lattice); see
docs/static-analysis.md. The
OSL18xx family is the array-contract engine (``analysis/arrays.py``): an
abstract interpreter computing a (dtype, rank, symbolic-axis) lattice
over the same CFGs, checked against the contract registry in
``encoding/dtypes.py``.
"""

from .core import (  # noqa: F401
    RULES,
    FileContext,
    Finding,
    ProjectContext,
    Rule,
    lint_paths,
    lint_source,
    register,
    render_human,
    render_json,
    render_sarif,
)

# importing the rule modules registers them
from . import (  # noqa: F401,E402
    rules_admission,
    rules_arrays,
    rules_cache,
    rules_campaign,
    rules_concurrency,
    rules_dataflow,
    rules_determinism,
    rules_dtype,
    rules_env,
    rules_except,
    rules_fleet,
    rules_jit,
    rules_journal,
    rules_metrics,
    rules_obs,
    rules_reasons,
    rules_retry,
    rules_watch,
)
