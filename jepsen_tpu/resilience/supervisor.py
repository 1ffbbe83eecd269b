"""Watchdog-supervised device dispatch — the seam between the engines
and JAX.

Every device dispatch site in the checker (bitdense single/batch,
sparse engine search, sharded tiers, pipeline chunk dispatch,
host->device transfers) runs through :func:`dispatch`. Three jobs:

  1. **Fault injection** (``resilience.faults``): the active
     JEPSEN_TPU_FAULTS plan can wedge, crash, or transiently fail the
     call — deterministically, so CI drives every degradation path on
     CPU.
  2. **Watchdog** (``JEPSEN_TPU_WATCHDOG=<secs>``): the dispatch runs
     on a worker thread with a bounded join. A call past the bound
     raises :class:`DispatchWedged` — a wedged device runtime (it
     blocks in C with no Python-level signal, see jepsen_tpu/probe.py)
     becomes a structured verdict instead of a hung process. A REALLY wedged call cannot be cancelled; its
     daemon thread is abandoned (the documented, bounded cost — the
     breaker stops the pile-up after `threshold` of them).
  3. **Circuit breaker** (``resilience.breaker``): successes and
     failures are recorded per backend; dispatch against an open
     breaker raises :class:`DeviceUnavailable` WITHOUT touching the
     runtime, and the half-open recovery check is one tiny op under a
     watchdog thread (``jepsen_tpu.probe.check_in_process``).

Errors raised while a compiled program executes
(``jax.errors.JaxRuntimeError``, and ``flaky`` faults standing in for
them) are retried up to ``JEPSEN_TPU_DISPATCH_RETRIES`` times while the
breaker stays closed, under a ``resilience.retry`` span. Tracing,
lowering and compile errors propagate unchanged: never retried, never
counted on the breaker, never degraded to the host. Wedges and injected
crashes are NOT retried here — re-dispatching against a wedged
runtime piles up stuck threads, and crash recovery belongs to the
callers' degradation contracts (host fallback / checkpoint resume in
``resilience.recovery``).

The no-op contract: with no fault plan, no watchdog, and every breaker
closed, :func:`dispatch` is a passthrough — two raw env reads, one
set-truthiness check, then the call (test-pinned per-call budget,
same standard as the disabled tracer). The engines therefore route
every dispatch through it unconditionally; the
``concurrency-unsupervised-dispatch`` lint rule enforces that
mechanically.

Import-safe: no JAX at module scope (the same contract as envflags and
obs — a wedged runtime must not turn importing an engine into a hang).
"""

from __future__ import annotations

import logging
import sys
import threading
import time
from typing import Callable, Optional

from jepsen_tpu import envflags
from jepsen_tpu import obs
from jepsen_tpu.resilience import breaker as breaker_mod
from jepsen_tpu.resilience import faults

_log = logging.getLogger(__name__)

_SITES = frozenset(faults.SITES)   # O(1) membership on the fast path

# how long an injected wedge's worker waits before self-releasing even
# if nobody calls release — belt and braces against leaked threads
_WEDGE_SELF_RELEASE_SECS = 60.0
# watchdog bound used for an injected wedge when none is configured:
# the injected hang is fake (it blocks on an Event we control), so a
# short bound keeps fault-matrix tests fast without configuring env
_INJECTED_WEDGE_TIMEOUT = 0.2


class DispatchWedged(RuntimeError):
    """A supervised dispatch exceeded its watchdog bound — a wedged
    device runtime, as a structured verdict."""

    def __init__(self, site: str, timeout: float,
                 backend: Optional[str] = None):
        super().__init__(
            f"device dispatch at site {site!r} exceeded the "
            f"{timeout:.1f}s watchdog bound"
            + (f" (backend {backend!r})" if backend else ""))
        self.site = site
        self.timeout = timeout
        self.backend = backend


class DeviceUnavailable(RuntimeError):
    """Dispatch refused or given up on for a backend — open breaker,
    or a dispatch failure the engines converted into a degradation
    signal. Carries enough structure for result annotations."""

    def __init__(self, site: str, reason: str,
                 backend: Optional[str] = None, cause=None):
        super().__init__(f"device unavailable at site {site!r}: "
                         f"{reason}")
        self.site = site
        self.reason = reason
        self.backend = backend
        self.cause = cause


# the exception classes callers degrade on (host fallback / checkpoint
# resume) rather than treat as programming errors
DISPATCH_FAILURES = (DispatchWedged, faults.InjectedCrash,
                     DeviceUnavailable)


def _resolve_watchdog() -> Optional[float]:
    """JEPSEN_TPU_WATCHDOG seconds; unset or 0 -> None (off)."""
    v = envflags.env_float("JEPSEN_TPU_WATCHDOG", default=None,
                           min_value=0.0, what="watchdog seconds")
    return v if v else None


def _resolve_retries() -> int:
    return envflags.env_int("JEPSEN_TPU_DISPATCH_RETRIES", default=1,
                            min_value=0, what="dispatch retries")


def active(backend: Optional[str] = None) -> bool:
    """Whether the full supervision path is needed. This is the no-op
    fast path's whole cost: three raw env reads + one set check. A set
    JEPSEN_TPU_DISPATCH_RETRIES activates supervision too — an
    operator who configured retries must get retries (and the breaker
    bookkeeping that rides the slow path), not a silent passthrough."""
    return (faults.active()
            or envflags.env_raw("JEPSEN_TPU_WATCHDOG") not in (None, "0")
            or envflags.env_raw("JEPSEN_TPU_DISPATCH_RETRIES") is not None
            or breaker_mod.any_tripped())


def run_watchdogged(thunk: Callable, timeout: float, site: str,
                    backend: Optional[str] = None):
    """Run `thunk` on a daemon worker with a bounded join; past the
    bound, raise DispatchWedged and abandon the worker."""
    box: dict = {}

    def worker():
        try:
            box["value"] = thunk()
        except BaseException:  # noqa: BLE001 — re-raised in the parent
            box["exc"] = sys.exc_info()[1]

    t = threading.Thread(target=worker, daemon=True,
                         name=f"jepsen-dispatch-{site}")
    t.start()
    t.join(timeout)
    if t.is_alive():
        obs.counter("resilience.watchdog_kills").inc()
        _log.warning(
            "device dispatch at site %r exceeded the %.1fs watchdog "
            "bound — abandoning the worker thread (a wedged device "
            "runtime; see docs/resilience.md)", site, timeout)
        # postmortem evidence even with tracing off: the armed flight
        # recorder (JEPSEN_TPU_FLIGHT_RECORDER) dumps its span ring +
        # metric delta; unarmed, this is a single None check
        obs.flight_dump(f"dispatch-wedged-{site}")
        raise DispatchWedged(site, timeout, backend)
    if "exc" in box:
        raise box["exc"]
    return box["value"]


def _injected_wedge(plan_event: threading.Event, site: str,
                    timeout: float, backend: Optional[str]):
    """Simulate a never-returning dispatch: a worker blocks on the
    plan's wedge event, the watchdog times out, then the event is set
    so the worker exits instead of leaking (a real wedge can't be
    released — this seam exists precisely so tests don't need one)."""
    try:
        run_watchdogged(
            lambda: plan_event.wait(timeout + _WEDGE_SELF_RELEASE_SECS),
            timeout, site, backend)
    finally:
        plan_event.set()
    # unreachable unless the event was already set (e.g. a concurrent
    # wedge released first): still honor the wedge contract
    raise DispatchWedged(site, timeout, backend)


def dispatch(site: str, thunk: Callable, backend: Optional[str] = None,
             watchdog: Optional[float] = None,
             retries: Optional[int] = None):
    """Run `thunk` (a zero-arg device-dispatch closure that
    MATERIALIZES its result — async dispatch must surface failures and
    hangs inside the supervised window) through the supervision seam.

    Raises:
      DeviceUnavailable   the backend's breaker is open
      DispatchWedged      watchdog bound exceeded (injected or real)
      InjectedCrash       a `raise` fault fired
      DeviceUnavailable   a runtime error that survived the retry
                          budget (the original rides `cause`)
      (original error)    anything raised before the program ran:
                          tracing, lowering, compiling
    """
    if site not in _SITES:
        raise ValueError(f"unknown dispatch site {site!r} "
                         f"(expected one of {faults.SITES})")
    if watchdog is None and retries is None and not active(backend):
        return thunk()
    _tag_compile_errors()

    wd = watchdog if watchdog is not None else _resolve_watchdog()
    budget = retries if retries is not None else _resolve_retries()
    br = breaker_mod.breaker_for(backend) if backend else None
    attempt = 0
    while True:
        if br is not None:
            allowed, reason = br.allow()
            if not allowed:
                raise DeviceUnavailable(site, reason, backend)
        # attempts after the first run under a retry span, so the
        # retry path is visible in traces of a degraded run
        ctx = (obs.span("resilience.retry", site=site, attempt=attempt)
               if attempt > 0 else _NULL_CTX)
        try:
            with ctx:
                return _one_attempt(site, thunk, backend, wd, br)
        except envflags.EnvFlagError:
            # a malformed JEPSEN_TPU_* value (fault spec, knob) is a
            # CONFIGURATION error, not a dispatch failure: it must
            # fail loudly and untouched — never retried, never
            # recorded on the breaker, never degraded to the host
            # path (a degrade here would silently run zero faults
            # while the operator believes the plan is armed)
            raise
        except (DispatchWedged, faults.InjectedCrash) as err:
            # wedges: re-dispatching a wedged runtime piles up stuck
            # threads. Injected crashes: recovery belongs to the
            # callers' degradation paths, and retrying would hide the
            # very path the fault exists to exercise.
            if br is not None:
                br.record_failure(str(err))
            raise
        except DeviceUnavailable:
            raise
        except Exception as err:  # noqa: BLE001 — judged by _executing
            if not _executing(err):
                # tracing, lowering and compile errors: a bug, or a
                # program this device cannot run. Never retried, never
                # counted on the breaker, never degraded to the host —
                # the check fails loudly with the original error.
                raise
            blocked = br is not None and br.state != breaker_mod.CLOSED
            if attempt >= budget or blocked:
                # ONE breaker failure per failing dispatch CALL, not
                # per attempt: threshold N means "N failed dispatches",
                # and a transient that recovers within its budget never
                # counts at all
                if br is not None:
                    br.record_failure(f"{type(err).__name__}: {err}")
                # budget exhausted (or the breaker tripped mid-retry):
                # surface as DeviceUnavailable so the callers'
                # degradation contract catches it — a persistent
                # runtime error (the dying-chip mode) must degrade to
                # the host path exactly like an injected crash. The
                # original error rides `cause`/`__cause__`.
                raise DeviceUnavailable(
                    site,
                    f"dispatch failed after {attempt + 1} attempt(s): "
                    f"{type(err).__name__}: {err}",
                    backend, cause=err) from err
            attempt += 1
            obs.counter("resilience.retries").inc()


_COMPILE_ERROR = "_jepsen_compile_error"
_compile_hook_lock = threading.Lock()
_compile_hook_installed = False


def _tag_compile_errors() -> None:
    """XLA reports a failed compile with the same JaxRuntimeError type
    as a failed execution. jax's compile step runs registered handlers
    on such errors; the one installed here tags them, and changes
    nothing else. Installed on the first supervised dispatch, so that
    importing this module stays free of JAX."""
    global _compile_hook_installed
    with _compile_hook_lock:
        if _compile_hook_installed:
            return
        from jax._src import compiler

        def tag(err):
            setattr(err, _COMPILE_ERROR, True)
            return None   # propagate the original error

        compiler.register_xla_runtime_error_handler(tag)
        _compile_hook_installed = True


def _executing(err: BaseException) -> bool:
    """Whether `err` was raised while a compiled program executed: the
    only failures dispatch retries, counts on the breaker and turns
    into DeviceUnavailable. A `flaky` fault stands in for one."""
    if isinstance(err, faults.TransientFault):
        return True
    from jax.errors import JaxRuntimeError
    return (isinstance(err, JaxRuntimeError)
            and not getattr(err, _COMPILE_ERROR, False))


class _NullCtx:
    def __enter__(self):
        return self

    def __exit__(self, *a):
        return False


_NULL_CTX = _NullCtx()


def _one_attempt(site: str, thunk: Callable, backend: Optional[str],
                 wd: Optional[float], br):
    """One supervised attempt: fault decision, then the (possibly
    watchdogged) call; success recorded on the breaker."""
    rule = faults.decide(site)
    if rule is not None:
        obs.counter("resilience.faults_injected").inc()
        obs.counter(f"resilience.faults_injected.{site}").inc()
        if rule.kind == "wedge":
            plan = faults.active_plan()
            _injected_wedge(
                plan.wedge_event if plan is not None
                else threading.Event(),
                site, wd or _INJECTED_WEDGE_TIMEOUT, backend)
        elif rule.kind == "raise":
            raise faults.InjectedCrash(site, rule)
        elif rule.kind == "slow":
            # deterministic latency: the dispatch still runs and still
            # answers correctly — it just takes rule.ms longer. The
            # sleep rides INSIDE the watchdogged window, so a watchdog
            # bound below the injected delay fires exactly as it would
            # on a real slow device (a too-slow dispatch IS a wedge).
            delay, inner = rule.ms / 1000.0, thunk
            thunk = lambda: (time.sleep(delay), inner())[1]  # noqa: E731
        else:
            raise faults.TransientFault(site, rule)
    r = (run_watchdogged(thunk, wd, site, backend) if wd
         else thunk())
    if br is not None:
        br.record_success()
    return r
