"""Resilient device dispatch: fault injection, watchdog supervision,
circuit breaking, and checkpointed degradation (docs/resilience.md).

The r05 outage proved the stack's weakest layer is the runtime
boundary: a wedged PJRT client blocks forever inside
``make_c_api_client`` with no Python-level signal, and nothing
in-process could detect, contain, or recover from it. This package is
the containment layer between the checker engines and JAX:

  faults       deterministic fault injector behind the validated
               ``JEPSEN_TPU_FAULTS`` spec — CI drives every
               degradation path on CPU
  supervisor   every device dispatch site runs through
               ``dispatch(site, thunk, backend=...)``: watchdog-
               bounded wait (``DispatchWedged`` instead of a hung
               process), breaker bookkeeping, retries of runtime
               errors (compile errors propagate unchanged); a
               test-pinned near-zero-overhead passthrough when
               nothing is active
  breaker      per-backend circuit breaker (closed -> open on
               consecutive failures, exponential backoff with jitter,
               half-open recovery probing in this process,
               ``probe.check_in_process``)
  recovery     verdict-preserving degradation: whole-key host WGL
               re-checks and FrontierCheckpoint host resumes, each
               tagged with a structured ``resilience`` result note

Import-safe: no JAX anywhere at module scope (the same contract as
envflags and obs — the whole point is surviving a wedged runtime).
"""

from jepsen_tpu.resilience import breaker, faults, recovery, supervisor  # noqa: F401
from jepsen_tpu.resilience.breaker import breaker_for  # noqa: F401
from jepsen_tpu.resilience.faults import (  # noqa: F401
    FaultInjected, FaultSpecError, InjectedCrash, TransientFault,
)
from jepsen_tpu.resilience.supervisor import (  # noqa: F401
    DISPATCH_FAILURES, DeviceUnavailable, DispatchWedged, dispatch,
)


def reset():
    """Test isolation: drop the fault plan and every breaker."""
    faults.reset()
    breaker.reset()
