"""Per-backend circuit breakers for device dispatch.

The r05 wedge cost more than the first hung dispatch: rounds 3-5 kept
re-dispatching against the dead runtime, each attempt paying the full
hang-and-kill cycle, and nothing in-process remembered that the
backend was down. The breaker is that memory:

    closed     dispatch flows; consecutive failures are counted
    open       after `threshold` consecutive failures: dispatch is
               refused outright (DeviceUnavailable at the supervisor)
               until an exponential backoff (base doubling per
               re-open, deterministic jitter, capped) elapses
    half-open  one caller per window runs the recovery probe —
               ``jepsen_tpu.probe.check_in_process``: one tiny op on
               the backend this process holds, under a watchdog
               thread that takes any hang. It never starts a child: a
               chip belongs to one process at a time, and this one
               holds it. A healthy probe closes the breaker; anything
               else re-opens it with a doubled backoff.

Clock, probe, and jitter are injectable (fake-clock lifecycle tests);
defaults come from the validated ``JEPSEN_TPU_BREAKER_*`` flags.
State changes are mirrored to the ``resilience.breaker.<backend>.state``
gauge (0 closed / 1 half-open / 2 open) and the
``resilience.breaker.opens`` counter, so a trace of a degraded run
shows when and why dispatch stopped.

Import-safe: no JAX at module scope — the probe imports it when it
runs.
"""

from __future__ import annotations

import random
import threading
import time
from typing import Callable, Dict, Optional, Tuple

from jepsen_tpu import envflags
from jepsen_tpu import obs

CLOSED, HALF_OPEN, OPEN = "closed", "half-open", "open"
_STATE_GAUGE = {CLOSED: 0, HALF_OPEN: 1, OPEN: 2}

BACKOFF_CAP_SECS = 60.0
JITTER_FRAC = 0.1
PROBE_TIMEOUT_SECS = 30.0


def _default_probe() -> bool:
    """The half-open recovery check, in this process (see the module
    docstring)."""
    from jepsen_tpu import probe
    return probe.check_in_process(PROBE_TIMEOUT_SECS)["verdict"] \
        == "healthy"


def _resolve_threshold() -> int:
    return envflags.env_int("JEPSEN_TPU_BREAKER_THRESHOLD", default=3,
                            min_value=1, what="breaker threshold")


def _resolve_backoff() -> float:
    return envflags.env_float("JEPSEN_TPU_BREAKER_BACKOFF", default=1.0,
                              min_value=0.0, what="breaker backoff")


class CircuitBreaker:
    """One backend's breaker. Thread-safe; all timing through the
    injected clock so the open/half-open/close lifecycle is testable
    without sleeping."""

    def __init__(self, backend: str,
                 threshold: Optional[int] = None,
                 backoff_base: Optional[float] = None,
                 backoff_cap: float = BACKOFF_CAP_SECS,
                 clock: Callable[[], float] = time.monotonic,
                 probe: Optional[Callable[[], bool]] = None,
                 rng: Optional[random.Random] = None,
                 track_global: bool = True):
        self.backend = backend
        # track_global=False keeps this breaker out of the module's
        # _tripped fast-path set: a FLEET breaker watching a PEER
        # replica's health must not push this process's own device
        # dispatches onto the slow supervised path (serve.fleet)
        self._track_global = track_global
        self.threshold = (threshold if threshold is not None
                          else _resolve_threshold())
        self.backoff_base = (backoff_base if backoff_base is not None
                             else _resolve_backoff())
        self.backoff_cap = backoff_cap
        self.clock = clock
        self.probe = probe if probe is not None else _default_probe
        # deterministic jitter: seeded per backend name (crc32, not
        # hash() — str hashing is per-process randomized), not wall
        # clock, so a reproduced run reproduces its backoff schedule
        import zlib
        self.rng = rng if rng is not None else random.Random(
            zlib.crc32(("jepsen-breaker:" + backend).encode()))
        self._lock = threading.Lock()
        self._state = CLOSED
        self._failures = 0
        self._opens = 0
        self._open_until = 0.0
        self._last_reason = ""
        self._gauge()

    # -- introspection

    @property
    def state(self) -> str:
        with self._lock:
            return self._state

    def snapshot(self) -> dict:
        with self._lock:
            return {"backend": self.backend, "state": self._state,
                    "failures": self._failures, "opens": self._opens,
                    "open_until": self._open_until,
                    "reason": self._last_reason}

    def _gauge(self):
        obs.gauge(f"resilience.breaker.{self.backend}.state").set(
            _STATE_GAUGE[self._state])
        # Perfetto counter track: breaker flips render as steps on the
        # trace timeline, aligned with the dispatch spans that caused
        # them (no-op with tracing off)
        obs.counter_sample(f"resilience.breaker.{self.backend}.state",
                           _STATE_GAUGE[self._state])

    # -- transitions

    def _backoff(self) -> float:
        """Exponential in the re-open count, jittered, capped."""
        base = self.backoff_base * (2 ** max(0, self._opens - 1))
        jitter = 1.0 + JITTER_FRAC * self.rng.random()
        return min(base * jitter, self.backoff_cap)

    def _open_locked(self):
        """The one open transition (callers hold the lock): state,
        re-open count, backoff window, counter, gauge. Callers dump
        the flight recorder AFTER releasing the lock — the dump is
        file I/O, and a hung filesystem (plausible on the same sick
        node whose device just failed) must not wedge every dispatch
        blocked on this breaker's lock."""
        self._state = OPEN
        self._opens += 1
        self._open_until = self.clock() + self._backoff()
        obs.counter("resilience.breaker.opens").inc()
        self._gauge()

    def _note(self, tripped: bool) -> None:
        if self._track_global:
            _note_state(self.backend, tripped)

    def record_failure(self, reason: str = ""):
        opened = False
        with self._lock:
            self._failures += 1
            self._last_reason = reason
            if self._state == HALF_OPEN \
                    or (self._state != OPEN
                        and self._failures >= self.threshold):
                # threshold reached — or the probed dispatch itself
                # failed during half-open, which re-opens immediately
                self._open_locked()
                opened = True
            else:
                self._gauge()
            tripped = self._state != CLOSED
        self._note(tripped)
        if opened:
            # an open breaker is exactly the moment a postmortem wants
            # the last spans + metric deltas; a None check when
            # unarmed, and the dump cap bounds a flapping breaker
            obs.flight_dump(f"breaker-open-{self.backend}")

    def record_success(self):
        with self._lock:
            self._failures = 0
            self._opens = 0   # incident over: the next one starts at
            self._state = CLOSED   # the base backoff, not an escalated one
            self._last_reason = ""
            self._gauge()
        self._note(False)

    def allow(self) -> Tuple[bool, str]:
        """Whether a dispatch may proceed now. Closed -> yes. Open ->
        no until the backoff elapses; then ONE caller per window runs
        the recovery probe (half-open): healthy closes the breaker and
        admits the dispatch, anything else re-opens with a doubled
        backoff."""
        with self._lock:
            if self._state == CLOSED:
                return True, ""
            if self._state == HALF_OPEN:
                # another caller's recovery probe is in flight (up to
                # 30s in production): refuse rather than
                # stampede the recovering runtime with N probes
                return False, (
                    f"circuit breaker half-open for backend "
                    f"{self.backend!r}: recovery probe in flight")
            if self.clock() < self._open_until:
                return False, (
                    f"circuit breaker open for backend "
                    f"{self.backend!r} (last failure: "
                    f"{self._last_reason or '?'}; retry in "
                    f"{max(0.0, self._open_until - self.clock()):.1f}s)")
            # backoff elapsed: this caller probes; the state flips to
            # half-open so concurrent callers keep getting refused
            # rather than stampeding the recovering runtime
            self._state = HALF_OPEN
            self._gauge()
            probe = self.probe
        self._note(True)
        try:
            healthy = bool(probe())
        except Exception:  # noqa: BLE001 — a crashed probe is not health
            healthy = False
        with self._lock:
            if healthy:
                self._state = CLOSED
                self._failures = 0
                self._opens = 0   # incident over (record_success's rule):
                self._gauge()     # backoff escalation must not leak into
            else:                 # the NEXT, unrelated incident
                self._open_locked()
        self._note(not healthy)
        if healthy:
            return True, ""
        obs.flight_dump(f"breaker-open-{self.backend}")
        return False, (f"circuit breaker re-opened for backend "
                       f"{self.backend!r}: recovery probe unhealthy")


# ------------------------------------------------------------ registry

_registry_lock = threading.Lock()
_breakers: Dict[str, CircuitBreaker] = {}
# backends currently NOT closed — the supervisor's fast-path check is
# a single truthiness read of this set, so a fully healthy process
# never pays more than that
_tripped: set = set()


def _note_state(backend: str, tripped: bool):
    with _registry_lock:
        if tripped:
            _tripped.add(backend)
        else:
            _tripped.discard(backend)


def breaker_for(backend: str, **kw) -> CircuitBreaker:
    """The process breaker for `backend` (created on first use)."""
    backend = backend or "default"
    with _registry_lock:
        br = _breakers.get(backend)
        if br is None:
            br = _breakers[backend] = CircuitBreaker(backend, **kw)
        return br


def any_tripped() -> bool:
    """Cheap fast-path probe: is any backend's breaker not closed?"""
    return bool(_tripped)


def snapshots() -> list:
    """Every registered breaker's :meth:`CircuitBreaker.snapshot` —
    the /healthz readiness check enumerates these (an empty list means
    no dispatch has needed a breaker yet: healthy)."""
    with _registry_lock:
        brs = list(_breakers.values())
    return [b.snapshot() for b in brs]


def reset():
    """Drop every breaker (test isolation)."""
    with _registry_lock:
        _breakers.clear()
        _tripped.clear()
