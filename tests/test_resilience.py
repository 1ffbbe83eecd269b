"""Resilient device dispatch: fault injection, watchdog supervision,
circuit breaker, and checkpointed degradation (jepsen_tpu.resilience).

Four layers, mirroring docs/resilience.md:

  * the JEPSEN_TPU_FAULTS spec grammar — strict validation (bad specs
    raise, never silently no-op) and deterministic firing;
  * the supervisor — near-zero-overhead passthrough when inactive
    (the disabled-tracer standard), watchdog wedge verdicts, retry
    budget, breaker bookkeeping;
  * the breaker lifecycle on a fake clock — closed/open/half-open,
    exponential jittered backoff, recovery probing;
  * the fault matrix — each injected fault class x the bitdense /
    sparse / sharded / pipeline dispatch paths returns verdicts
    IDENTICAL to the clean run, including a mid-search kill that
    resumes from FrontierCheckpoint, and the breaker demonstrably
    stops re-dispatch after its threshold.

Everything runs CPU-only; injected wedges block on an event the
supervisor releases, so no test waits on a real hang.
"""

import time

import pytest

from jepsen_tpu import envflags, obs, resilience
from jepsen_tpu.histories import corrupt_history, rand_register_history
from jepsen_tpu.resilience import breaker as breaker_mod
from jepsen_tpu.resilience import faults, supervisor as sup


@pytest.fixture(autouse=True)
def _isolate():
    """Every test starts and ends with no fault plan and no breakers."""
    resilience.reset()
    yield
    resilience.reset()


def _cval(name):
    return obs.counter(name).value


# ------------------------------------------------------ fault spec


def test_fault_spec_grammar():
    rs = faults.parse_spec(
        "wedge@dispatch:2, raise@transfer:every=3, flaky@search:n=1,"
        "raise@sharded")
    assert [(r.kind, r.site, r.n, r.every) for r in rs] == [
        ("wedge", "dispatch", 2, None),
        ("raise", "transfer", None, 3),
        ("flaky", "search", 1, None),
        ("raise", "sharded", None, None),
    ]
    # firing semantics: n = first N invocations; every = every K-th
    assert rs[0].fires(1) and rs[0].fires(2) and not rs[0].fires(3)
    assert not rs[1].fires(1) and rs[1].fires(3) and rs[1].fires(6)
    assert rs[3].fires(1) and rs[3].fires(99)


@pytest.mark.parametrize("bad", [
    "nope@dispatch",            # unknown kind
    "wedge@nowhere",            # unknown site
    "wedge dispatch",           # no @
    "wedge@dispatch:zero",      # non-integer count
    "wedge@dispatch:n=0",       # non-positive
    "wedge@dispatch:x=2",       # unknown count key
    "raise@child",              # child seam only implements wedge
])
def test_fault_spec_bad_specs_raise(bad):
    """Bad specs raise, never silently no-op — and the error is an
    EnvFlagError, the namespace's one fail-loud contract."""
    with pytest.raises(faults.FaultSpecError):
        faults.parse_spec(bad)
    assert issubclass(faults.FaultSpecError, envflags.EnvFlagError)


def test_fault_plan_env_and_legacy_wedge(monkeypatch):
    monkeypatch.setenv("JEPSEN_TPU_FAULTS", "raise@search:n=1")
    faults.reset()
    assert faults.decide("dispatch") is None
    assert faults.decide("search").kind == "raise"
    assert faults.decide("search") is None        # n=1 consumed
    # the legacy bench seam maps onto an implicit wedge@child rule
    monkeypatch.delenv("JEPSEN_TPU_FAULTS")
    monkeypatch.setenv("JEPSEN_TPU_TEST_WEDGE", "1")
    faults.reset()
    r = faults.decide("child")
    assert r is not None and r.kind == "wedge"
    assert faults.decide("dispatch") is None
    # a malformed plan raises at the read, not at some later dispatch
    monkeypatch.setenv("JEPSEN_TPU_FAULTS", "bogus")
    faults.reset()
    with pytest.raises(faults.FaultSpecError):
        faults.decide("dispatch")


# ------------------------------------------------------ supervisor


def test_supervisor_noop_overhead_pin():
    """The disabled-supervisor standard (same bar as the disabled
    tracer): a passthrough dispatch costs single-digit microseconds of
    CPU — measured ~4us; pinned with headroom for loaded CI."""
    thunk = lambda: 1  # noqa: E731
    assert sup.dispatch("dispatch", thunk) == 1
    N = 5000
    t0 = time.process_time()
    for _ in range(N):
        sup.dispatch("dispatch", thunk)
    cpu = time.process_time() - t0
    assert cpu / N < 15e-6, f"{cpu / N * 1e9:.0f}ns per no-op dispatch"


def test_supervisor_unknown_site_raises():
    with pytest.raises(ValueError, match="unknown dispatch site"):
        sup.dispatch("warp-core", lambda: 1)


def test_supervisor_malformed_spec_fails_loudly(monkeypatch):
    """A malformed JEPSEN_TPU_FAULTS value is a CONFIGURATION error:
    it propagates untouched through dispatch — never retried, never
    breaker-recorded, never degraded to host (a degrade would silently
    run zero faults while the operator believes the plan is armed)."""
    from jepsen_tpu.models import CASRegister
    from jepsen_tpu.parallel import engine
    monkeypatch.setenv("JEPSEN_TPU_FAULTS", "wedge@gpu")
    faults.reset()
    with pytest.raises(faults.FaultSpecError, match="unknown site"):
        sup.dispatch("dispatch", lambda: 1, backend="fake-m")
    assert breaker_mod.breaker_for("fake-m").snapshot()["failures"] == 0
    # ... including through the full engine path: no silent host-wgl
    h = rand_register_history(n_ops=24, n_processes=3, seed=41)
    with pytest.raises(envflags.EnvFlagError):
        engine.analysis(CASRegister(), h)


def test_supervisor_flaky_retried_then_succeeds(monkeypatch):
    monkeypatch.setenv("JEPSEN_TPU_FAULTS", "flaky@search:n=1")
    faults.reset()
    calls = []
    r0 = _cval("resilience.retries")
    out = sup.dispatch("search", lambda: calls.append(1) or 7,
                       backend="fake-a")
    assert out == 7 and len(calls) == 1
    assert _cval("resilience.retries") == r0 + 1
    # the retry succeeded: the breaker saw failure-then-success, closed
    assert breaker_mod.breaker_for("fake-a").state == breaker_mod.CLOSED


def test_supervisor_flaky_budget_exhausted(monkeypatch):
    """An exhausted retry budget surfaces as DeviceUnavailable (so the
    engines' degradation handlers catch it — a persistent transient OR
    a persistent real device error must degrade, not crash the check),
    with the original failure riding `cause`."""
    monkeypatch.setenv("JEPSEN_TPU_FAULTS", "flaky@search")   # every call
    monkeypatch.setenv("JEPSEN_TPU_DISPATCH_RETRIES", "2")
    faults.reset()
    with pytest.raises(sup.DeviceUnavailable) as ei:
        sup.dispatch("search", lambda: 1)
    assert isinstance(ei.value.cause, faults.TransientFault)
    assert "after 3 attempt(s)" in ei.value.reason


def test_supervisor_real_persistent_error_degrades(monkeypatch):
    """The dying-chip mode: an error raised while the program RUNS
    (jax's JaxRuntimeError) that survives the retry budget reaches
    engine.analysis as DeviceUnavailable and degrades to the host path
    — verdict preserved (docs/resilience.md)."""
    import jax

    from jepsen_tpu.models import CASRegister
    from jepsen_tpu.parallel import bitdense, engine
    m = CASRegister()
    h = rand_register_history(n_ops=24, n_processes=3, seed=31)
    clean = engine.analysis(m, h)

    def chip_died(*a, **k):
        raise jax.errors.JaxRuntimeError("INTERNAL: chip fell off the bus")

    # watchdog env activates the supervision slow path with no faults
    monkeypatch.setenv("JEPSEN_TPU_WATCHDOG", "30")
    monkeypatch.setattr(bitdense, "_check_bitdense", chip_died)
    r = engine.analysis(m, h)
    assert r["valid?"] == clean["valid?"]
    assert r["resilience"]["degraded"] == "host-wgl"
    assert "chip fell off the bus" in r["resilience"]["reason"]


def test_supervisor_lowering_error_propagates_with_watchdog(monkeypatch):
    """A tracing/lowering error (here the compiler's refusal to
    partition a Mosaic kernel) raised inside a supervised dispatch with
    the watchdog armed is raised unchanged: not retried, not counted on
    the breaker, and not degraded to the host by engine.analysis."""
    from jepsen_tpu.models import CASRegister
    from jepsen_tpu.parallel import bitdense, engine

    def refused(*a, **k):
        raise NotImplementedError("Mosaic kernels cannot be "
                                  "automatically partitioned")

    monkeypatch.setenv("JEPSEN_TPU_WATCHDOG", "30")
    monkeypatch.setenv("JEPSEN_TPU_DISPATCH_RETRIES", "3")
    r0 = _cval("resilience.retries")
    with pytest.raises(NotImplementedError, match="Mosaic"):
        sup.dispatch("dispatch", lambda: refused(), backend="fake-l")
    assert _cval("resilience.retries") == r0
    assert breaker_mod.breaker_for("fake-l").snapshot()["failures"] == 0
    # through the engine: the check fails loudly, no host-wgl answer
    monkeypatch.setattr(bitdense, "_check_bitdense", refused)
    h = rand_register_history(n_ops=24, n_processes=3, seed=31)
    with pytest.raises(NotImplementedError, match="Mosaic"):
        engine.analysis(CASRegister(), h)


def test_supervisor_compile_error_propagates(monkeypatch):
    """XLA reports a failed compile with the same JaxRuntimeError type
    as a failed execution; the supervisor tells them apart and raises
    the compile error unchanged (a real one: a custom call with no
    handler on this backend)."""
    import jax
    import numpy as np

    def needs_missing_handler(x):
        return jax.ffi.ffi_call(
            "jepsen_no_such_target",
            jax.ShapeDtypeStruct(x.shape, x.dtype))(x)

    monkeypatch.setenv("JEPSEN_TPU_WATCHDOG", "30")
    r0 = _cval("resilience.retries")
    with pytest.raises(jax.errors.JaxRuntimeError, match="FFI handler"):
        sup.dispatch("dispatch",
                     lambda: jax.jit(needs_missing_handler)(
                         np.ones(3, np.float32)),
                     backend="fake-c")
    assert _cval("resilience.retries") == r0
    assert breaker_mod.breaker_for("fake-c").snapshot()["failures"] == 0


def test_supervisor_crash_not_retried(monkeypatch):
    monkeypatch.setenv("JEPSEN_TPU_FAULTS", "raise@dispatch:n=1")
    faults.reset()
    r0 = _cval("resilience.retries")
    with pytest.raises(faults.InjectedCrash):
        sup.dispatch("dispatch", lambda: 1)
    assert _cval("resilience.retries") == r0
    # n=1 consumed: the next dispatch is clean
    assert sup.dispatch("dispatch", lambda: 5) == 5


def test_supervisor_injected_wedge_is_bounded(monkeypatch):
    """An injected wedge surfaces as DispatchWedged within the bound
    (no real hang, no leaked forever-blocked thread: the wedge worker
    blocks on an event the supervisor releases)."""
    monkeypatch.setenv("JEPSEN_TPU_FAULTS", "wedge@dispatch:n=1")
    faults.reset()
    k0 = _cval("resilience.watchdog_kills")
    t0 = time.monotonic()
    with pytest.raises(sup.DispatchWedged) as ei:
        sup.dispatch("dispatch", lambda: 1, backend="fake-w")
    assert time.monotonic() - t0 < 5.0
    assert ei.value.site == "dispatch"
    assert _cval("resilience.watchdog_kills") == k0 + 1
    # the plan's wedge event was released so the worker exited
    assert faults.active_plan().wedge_event.is_set()


def test_supervisor_watchdog_bounds_a_real_hang():
    """A thunk that outlives the watchdog becomes DispatchWedged — the
    r05 hang-forever signature as a structured verdict."""
    import threading
    release = threading.Event()
    with pytest.raises(sup.DispatchWedged):
        sup.dispatch("search", lambda: release.wait(30), watchdog=0.15)
    release.set()   # let the abandoned worker exit


# --------------------------------------------------------- breaker


def _fake_breaker(threshold=3, healthy=None):
    clk = {"t": 0.0}
    probes = {"n": 0}

    def probe():
        probes["n"] += 1
        return healthy["ok"] if healthy is not None else False

    br = breaker_mod.CircuitBreaker(
        "fake", threshold=threshold, backoff_base=1.0,
        clock=lambda: clk["t"], probe=probe)
    return br, clk, probes


def test_breaker_lifecycle_on_a_fake_clock():
    healthy = {"ok": False}
    br, clk, probes = _fake_breaker(threshold=3, healthy=healthy)
    assert br.state == breaker_mod.CLOSED and br.allow()[0]
    br.record_failure("boom 1")
    br.record_failure("boom 2")
    assert br.state == breaker_mod.CLOSED     # below threshold
    br.record_failure("boom 3")
    assert br.state == breaker_mod.OPEN
    ok, why = br.allow()
    assert not ok and "circuit breaker open" in why and probes["n"] == 0
    # backoff elapses -> half-open -> probe (unhealthy) -> re-open,
    # with the backoff DOUBLED (exponential in the re-open count)
    first_until = br.snapshot()["open_until"]
    assert 1.0 <= first_until <= 1.1 * 1.0 + 1e-9   # base x jitter<=10%
    clk["t"] = first_until + 0.01
    ok, _ = br.allow()
    assert not ok and probes["n"] == 1
    second = br.snapshot()["open_until"] - clk["t"]
    assert 2.0 <= second <= 2.2                      # doubled, jittered
    # healthy probe closes the breaker and admits the dispatch
    clk["t"] = br.snapshot()["open_until"] + 0.01
    healthy["ok"] = True
    ok, _ = br.allow()
    assert ok and probes["n"] == 2
    assert br.state == breaker_mod.CLOSED
    # success resets the failure count entirely
    br.record_failure("late")
    assert br.state == breaker_mod.CLOSED


def test_breaker_half_open_admits_one_prober():
    """While a recovery probe is in flight (HALF_OPEN), concurrent
    callers are refused — one probe per window, no stampede against
    the recovering runtime."""
    results = {}

    def slow_probe():
        # a second allow() issued MID-PROBE must refuse, not probe
        ok2, why2 = br.allow()
        results["mid"] = (ok2, why2)
        return True

    br, clk, _ = _fake_breaker(threshold=1)
    br.probe = slow_probe
    br.record_failure("boom")
    clk["t"] = 100.0                      # backoff elapsed
    ok, _ = br.allow()                    # this caller probes
    assert ok and br.state == breaker_mod.CLOSED
    mid_ok, mid_why = results["mid"]
    assert not mid_ok and "half-open" in mid_why


def test_breaker_success_resets_consecutive_count():
    br, _, _ = _fake_breaker(threshold=2)
    br.record_failure("a")
    br.record_success()
    br.record_failure("b")
    assert br.state == breaker_mod.CLOSED   # never 2 CONSECUTIVE


def test_supervisor_open_breaker_refuses_without_dispatch(monkeypatch):
    """After threshold consecutive failures the supervisor refuses
    dispatch outright: the thunk is NOT called (no re-dispatch against
    a wedged backend — the breaker's whole contract)."""
    monkeypatch.setenv("JEPSEN_TPU_FAULTS", "raise@dispatch")
    monkeypatch.setenv("JEPSEN_TPU_BREAKER_THRESHOLD", "2")
    monkeypatch.setenv("JEPSEN_TPU_BREAKER_BACKOFF", "1000")
    faults.reset()
    for _ in range(2):
        with pytest.raises(faults.InjectedCrash):
            sup.dispatch("dispatch", lambda: 1, backend="fake-b")
    assert breaker_mod.breaker_for("fake-b").state == breaker_mod.OPEN
    ran = []
    i0 = _cval("resilience.faults_injected")
    with pytest.raises(sup.DeviceUnavailable) as ei:
        sup.dispatch("dispatch", lambda: ran.append(1), backend="fake-b")
    assert not ran                                  # never dispatched
    assert _cval("resilience.faults_injected") == i0   # nor injected
    assert "circuit breaker open" in ei.value.reason
    # the state gauge reflects the trip (0 closed / 1 half / 2 open)
    assert obs.gauge("resilience.breaker.fake-b.state").value == 2


def test_breaker_knob_validation(monkeypatch):
    monkeypatch.setenv("JEPSEN_TPU_BREAKER_THRESHOLD", "0")
    with pytest.raises(envflags.EnvFlagError):
        breaker_mod.CircuitBreaker("v")
    monkeypatch.setenv("JEPSEN_TPU_BREAKER_THRESHOLD", "3")
    monkeypatch.setenv("JEPSEN_TPU_BREAKER_BACKOFF", "soon")
    with pytest.raises(envflags.EnvFlagError):
        breaker_mod.CircuitBreaker("v2")
    for bad in ("fast", "inf", "nan"):
        # non-numeric AND non-finite both raise at the read site — a
        # watchdog of inf would otherwise blow up Thread.join at every
        # dispatch, silently degrading everything to host
        monkeypatch.setenv("JEPSEN_TPU_WATCHDOG", bad)
        with pytest.raises(envflags.EnvFlagError):
            sup.dispatch("dispatch", lambda: 1, retries=0)


def test_retries_env_alone_activates_supervision(monkeypatch):
    """An operator who sets ONLY JEPSEN_TPU_DISPATCH_RETRIES gets
    retries (and breaker bookkeeping) — not a silent fast-path
    bypass."""
    import jax

    monkeypatch.setenv("JEPSEN_TPU_DISPATCH_RETRIES", "2")
    calls = []

    def flaky_real():
        calls.append(1)
        if len(calls) < 3:
            raise jax.errors.JaxRuntimeError("UNAVAILABLE: transient")
        return 9

    assert sup.dispatch("dispatch", flaky_real, backend="fake-r") == 9
    assert len(calls) == 3
    assert breaker_mod.breaker_for("fake-r").state == breaker_mod.CLOSED


def test_breaker_backoff_resets_between_incidents():
    """Closing the breaker (recovery) ends the incident: the next trip
    starts at the BASE backoff, not the prior incident's escalation."""
    healthy = {"ok": True}
    br, clk, _ = _fake_breaker(threshold=1, healthy=healthy)
    for _ in range(4):                      # incident 1: 4 re-opens
        br.record_failure("x")
        clk["t"] = br.snapshot()["open_until"] + 0.01
        br.allow()                          # healthy probe -> CLOSED
    assert br.state == breaker_mod.CLOSED
    br.record_failure("incident 2")         # fresh trip
    width = br.snapshot()["open_until"] - clk["t"]
    assert 1.0 <= width <= 1.1 + 1e-9       # base backoff again


# ---------------------------------------------------- fault matrix
#
# Each injected fault class x dispatch path must return verdicts
# identical to the clean run. Histories are small (the engines are
# exercised, not stressed) and shared so jit cache hits keep this
# tier-1 friendly.


@pytest.fixture(scope="module")
def reg_histories():
    hs = [rand_register_history(n_ops=30, n_processes=3, crash_p=0.05,
                                fail_p=0.05, seed=s) for s in range(4)]
    hs[2] = corrupt_history(hs[2], seed=1, n_corruptions=2)
    return hs


@pytest.fixture(scope="module")
def clean_results(reg_histories):
    from jepsen_tpu.models import CASRegister
    from jepsen_tpu.parallel import engine
    return [engine.analysis(CASRegister(), h) for h in reg_histories]


@pytest.mark.parametrize("spec", ["raise@dispatch", "wedge@dispatch:n=2",
                                  "flaky@dispatch:n=1",
                                  "raise@transfer:every=2"])
def test_fault_matrix_bitdense_single(spec, reg_histories, clean_results,
                                      monkeypatch):
    from jepsen_tpu.models import CASRegister
    from jepsen_tpu.parallel import engine
    monkeypatch.setenv("JEPSEN_TPU_FAULTS", spec)
    resilience.reset()
    for h, clean in zip(reg_histories, clean_results):
        r = engine.analysis(CASRegister(), h)
        assert r["valid?"] == clean["valid?"], spec


def test_fault_matrix_sparse_search(monkeypatch):
    """The sparse engine path (site "search"): flaky retries on the
    device (result dict IDENTICAL, no degradation note); a persistent
    crash degrades to host with the verdict preserved."""
    from jepsen_tpu.models import CASRegister
    from jepsen_tpu.parallel import encode, engine
    m = CASRegister()
    hs = [rand_register_history(n_ops=30, n_processes=3, seed=s + 10)
          for s in range(2)]
    hs[1] = corrupt_history(hs[1], seed=2, n_corruptions=2)
    encs = [encode.encode(m, h) for h in hs]
    clean = [engine.check_encoded(e, capacity=64) for e in encs]

    monkeypatch.setenv("JEPSEN_TPU_FAULTS", "flaky@search:n=1")
    resilience.reset()
    assert engine.check_encoded(encs[0], capacity=64) == clean[0]

    # a persistent crash propagates from the raw entry point ...
    monkeypatch.setenv("JEPSEN_TPU_FAULTS", "raise@search")
    resilience.reset()
    with pytest.raises(faults.InjectedCrash):
        engine.check_encoded(encs[0], capacity=64)
    # ... and analysis(), which owns the degradation contract,
    # preserves the verdicts through the host WGL path (dispatch
    # faulted too, so the bitdense router can't dodge the matrix)
    monkeypatch.setenv("JEPSEN_TPU_FAULTS", "raise@search,raise@dispatch")
    resilience.reset()
    for h, c in zip(hs, clean):
        r = engine.analysis(m, h)
        assert r["valid?"] == c["valid?"]
        assert r["resilience"]["degraded"] == "host-wgl"


def test_fault_matrix_sharded(monkeypatch):
    import jax
    import numpy as np
    from jax.sharding import Mesh

    from jepsen_tpu.models import CASRegister
    from jepsen_tpu.parallel import sharded
    m = CASRegister()
    mesh = Mesh(np.array(jax.devices("cpu")[:2]), ("frontier",))
    h = rand_register_history(n_ops=24, n_processes=3, seed=21)
    clean = sharded.analysis(m, h, mesh, capacity=128)
    for spec in ("raise@sharded", "wedge@sharded:n=2",
                 "flaky@sharded:n=1"):
        monkeypatch.setenv("JEPSEN_TPU_FAULTS", spec)
        resilience.reset()
        r = sharded.analysis(m, h, mesh, capacity=128)
        assert r["valid?"] == clean["valid?"], spec
        if spec == "flaky@sharded:n=1":
            assert "resilience" not in r    # retried on device
        if spec == "raise@sharded":
            assert r["resilience"]["degraded"] == "host-wgl"


def test_fault_matrix_pipeline_chunk_degrades_alone(reg_histories,
                                                    monkeypatch):
    """A failed pipeline chunk degrades ONLY its keys to the host path
    (structured reason on each), the rest of the batch keeps device
    results, and verdicts match the clean serial run."""
    from jepsen_tpu.models import CASRegister
    from jepsen_tpu.parallel import engine
    from jepsen_tpu.parallel import pipeline as pipe
    m = CASRegister()
    clean = engine.check_batch(m, reg_histories)
    monkeypatch.setenv("JEPSEN_TPU_FAULTS", "raise@pipeline:n=1")
    resilience.reset()
    d0 = _cval("pipeline.chunks_degraded")
    rs = pipe.check_batch_pipelined(m, reg_histories, chunk_keys=2,
                                    cache=False)
    assert [r["valid?"] for r in rs] == [c["valid?"] for c in clean]
    assert _cval("pipeline.chunks_degraded") == d0 + 1
    degraded = [r for r in rs if "resilience" in r]
    assert 1 <= len(degraded) <= 2          # one chunk's keys only
    assert all(r["resilience"]["degraded"] == "host-wgl"
               for r in degraded)


def _five_families():
    """One clean + one corrupted/contended history per packable model
    family (register, gset, unordered queue, fifo queue, mutex)."""
    from jepsen_tpu.histories import (rand_fifo_history,
                                      rand_gset_history,
                                      rand_queue_history)
    from jepsen_tpu.history import History, invoke_op, ok_op
    from jepsen_tpu.models import (CASRegister, FIFOQueue, GSet, Mutex,
                                   UnorderedQueue)

    def _h(*ops):
        return History.wrap(ops).index()

    reg = [rand_register_history(n_ops=30, n_processes=3, seed=1),
           corrupt_history(rand_register_history(n_ops=30,
                                                 n_processes=3, seed=2),
                           seed=3, n_corruptions=2)]
    gset = [rand_gset_history(n_ops=24, n_processes=3, n_elements=5,
                              seed=s + 70) for s in range(2)]
    uq = [rand_queue_history(n_ops=24, n_processes=3, n_values=3,
                             seed=s + 80) for s in range(2)]
    fifo = [rand_fifo_history(n_ops=24, n_processes=4, n_values=3,
                              seed=s + 90) for s in range(2)]
    mutex = [_h(invoke_op(0, "acquire", None), ok_op(0, "acquire", None),
                invoke_op(0, "release", None), ok_op(0, "release", None)),
             _h(invoke_op(0, "acquire", None), ok_op(0, "acquire", None),
                invoke_op(1, "acquire", None), ok_op(1, "acquire", None))]
    return [(CASRegister(), reg), (GSet(), gset), (UnorderedQueue(), uq),
            (FIFOQueue(), fifo), (Mutex(), mutex)]


def test_fault_matrix_five_families(monkeypatch):
    """Acceptance sweep: every packable model family returns verdicts
    identical to its clean run with a crash injected at every
    supervised dispatch site at once."""
    from jepsen_tpu.parallel import engine
    fams = _five_families()
    clean = {i: [engine.analysis(m, h) for h in hs[:3]]
             for i, (m, hs) in enumerate(fams)}
    monkeypatch.setenv(
        "JEPSEN_TPU_FAULTS",
        "raise@dispatch,raise@search,raise@transfer,raise@sharded,"
        "raise@pipeline")
    resilience.reset()
    for i, (m, hs) in enumerate(fams):
        for h, c in zip(hs[:3], clean[i]):
            r = engine.analysis(m, h)
            assert r["valid?"] == c["valid?"], type(m).__name__
            assert r["resilience"]["degraded"] == "host-wgl"


def test_mid_search_kill_resumes_from_checkpoint(monkeypatch):
    """The degradation contract's hard case: a dispatch killed
    mid-search loses no work — the FrontierCheckpoint taken before the
    failing chunk seeds the recovery (device retry first, then the
    host WGL path), and the verdict matches the clean run."""
    from jepsen_tpu.history import History
    from jepsen_tpu.models import CASRegister
    from jepsen_tpu.parallel import encode, engine
    m = CASRegister()
    ops = []
    for i in range(40):
        ops.append({"process": i % 3, "type": "invoke", "f": "write",
                    "value": i % 5})
        ops.append({"process": i % 3, "type": "ok", "f": "write",
                    "value": i % 5})
    e = encode.encode(m, History.wrap(ops))
    clean = engine.check_encoded_resumable(e, capacity=64,
                                           checkpoint_every=5)
    assert clean["valid?"] is True

    # kill every second chunk dispatch: the outer device retry
    # recovers each one from the checkpoint — no work lost
    monkeypatch.setenv("JEPSEN_TPU_FAULTS", "raise@search:every=2")
    resilience.reset()
    cps = []
    r = engine.check_encoded_resumable(e, capacity=64,
                                       checkpoint_every=5,
                                       checkpoint_cb=cps.append,
                                       model=m)
    assert r["valid?"] is clean["valid?"] is True
    assert r["resilience"]["degraded"] == "device-resume"
    assert r["resilience"]["resumed-from-event"] > 0
    assert cps and cps[-1].event_index == e.n_returns

    # kill EVERY dispatch from chunk 2 on: the host resumes from the
    # checkpoint (device progress kept: resumed-from-event > 0)
    monkeypatch.setenv("JEPSEN_TPU_FAULTS", "raise@search:every=1")
    resilience.reset()
    # consume chunk 1 cleanly so a checkpoint exists... every=1 kills
    # the first chunk too: start from a prior checkpoint instead
    monkeypatch.delenv("JEPSEN_TPU_FAULTS")
    resilience.reset()
    cps = []
    engine.check_encoded_resumable(e, capacity=64, checkpoint_every=5,
                                   checkpoint_cb=cps.append)
    mid = cps[2]
    assert 0 < mid.event_index < e.n_returns
    monkeypatch.setenv("JEPSEN_TPU_FAULTS", "raise@search")
    resilience.reset()
    rk0 = _cval("resilience.recovered_keys")
    r2 = engine.check_encoded_resumable(e, capacity=64,
                                        checkpoint_every=5,
                                        resume=mid, model=m)
    assert r2["valid?"] is True
    assert r2["resilience"]["degraded"] == "host-resume"
    assert r2["resilience"]["resumed-from-event"] == mid.event_index
    assert _cval("resilience.recovered_keys") == rk0 + 1

    # without a model the failure re-raises WITH the checkpoint
    # attached, so the caller can resume later
    resilience.reset()
    with pytest.raises(sup.DISPATCH_FAILURES) as ei:
        engine.check_encoded_resumable(e, capacity=64,
                                       checkpoint_every=5, resume=mid)
    assert ei.value.checkpoint.event_index == mid.event_index


def test_pallas_mesh_error_survives_supervision(monkeypatch):
    """With the watchdog configured, a kernel error on a multi-device
    mesh is still raised as itself: supervision neither wraps it in
    DeviceUnavailable nor lets a caller degrade it."""
    import jax
    import numpy as np
    from jax.sharding import Mesh

    from jepsen_tpu.models import CASRegister
    from jepsen_tpu.parallel import bitdense
    from jepsen_tpu.parallel import encode as enc_mod

    hs = [rand_register_history(n_ops=24, n_processes=3, seed=s + 50)
          for s in range(4)]
    encs = [enc_mod.encode(CASRegister(), h) for h in hs]
    mesh = Mesh(np.array(jax.devices()[:4]), ("keys",))

    def refused(*a, **k):
        raise NotImplementedError("Mosaic kernels cannot be "
                                  "automatically partitioned")

    monkeypatch.setattr(bitdense, "_sharded_batch_fn", refused)
    monkeypatch.setenv("JEPSEN_TPU_WATCHDOG", "30")   # supervision ON
    with pytest.raises(NotImplementedError, match="Mosaic"):
        bitdense.check_batch_bitdense(encs, mesh=mesh)


def test_breaker_stops_redispatch_across_checks(monkeypatch):
    """After the threshold, later checks never touch the device: the
    fault counter stops moving while verdicts stay correct (host
    path), and the fallback is classed breaker-open."""
    from jepsen_tpu.models import CASRegister
    from jepsen_tpu.parallel import engine
    m = CASRegister()
    h = rand_register_history(n_ops=30, n_processes=3, seed=5)
    clean = engine.analysis(m, h)
    monkeypatch.setenv("JEPSEN_TPU_FAULTS", "raise@dispatch,raise@transfer")
    monkeypatch.setenv("JEPSEN_TPU_BREAKER_THRESHOLD", "2")
    monkeypatch.setenv("JEPSEN_TPU_BREAKER_BACKOFF", "1000")
    resilience.reset()
    # each check costs one dispatch failure (crashes are not retried);
    # at threshold 2 the second check trips the breaker
    for _ in range(2):
        r1 = engine.analysis(m, h)
        assert r1["valid?"] == clean["valid?"]
    import jax
    assert breaker_mod.breaker_for(jax.default_backend()).state \
        == breaker_mod.OPEN
    i0 = _cval("resilience.faults_injected")
    r2 = engine.analysis(m, h)       # breaker-refused, no dispatch
    assert r2["valid?"] == clean["valid?"]
    assert _cval("resilience.faults_injected") == i0
    assert r2["resilience"]["degraded"] == "host-wgl"
    assert "circuit breaker open" in r2["resilience"]["reason"]


def test_independent_breaker_aware_fallback(monkeypatch):
    """independent's device fallback is breaker-aware: with the
    backend's breaker open the device batch is never attempted, the
    result carries a structured breaker-open fallback, and the per-key
    path runs host-only (no per-key re-dispatch)."""
    import jax

    from jepsen_tpu import independent
    from jepsen_tpu.checker.linearizable import linearizable
    from jepsen_tpu.models import CASRegister
    from jepsen_tpu.parallel import engine

    h = []
    for k in ("x", "y"):
        h.append({"process": 0, "type": "invoke", "f": "write",
                  "value": independent.KV(k, 1)})
        h.append({"process": 0, "type": "ok", "f": "write",
                  "value": independent.KV(k, 1)})
    from jepsen_tpu.history import History
    h = History.wrap(h)

    monkeypatch.setenv("JEPSEN_TPU_BREAKER_BACKOFF", "1000")
    br = breaker_mod.breaker_for(jax.default_backend())
    for _ in range(br.threshold):
        br.record_failure("simulated r05 wedge")
    assert br.state == breaker_mod.OPEN

    def boom(*a, **k):
        raise AssertionError("device dispatched against an open breaker")

    monkeypatch.setattr(engine, "check_batch", boom)
    monkeypatch.setattr(engine, "analysis", boom)
    c = independent.checker(linearizable(CASRegister(), algorithm="jax"))
    fb0 = _cval("independent.device_fallbacks.breaker-open")
    r = c.check({}, h)
    assert r["valid?"] is True
    assert r["resilience"]["class"] == "breaker-open"
    assert r["resilience"]["no-redispatch"] is True
    assert "circuit breaker open" in r["device-fallback"]
    assert _cval("independent.device_fallbacks.breaker-open") == fb0 + 1
    # per-key results came from the host-forced checker
    assert all(res["analyzer"] in ("packed", "wgl")
               for res in r["results"].values())


# --------------------------------------------------- slow fault kind


def test_fault_slow_spec_grammar():
    rs = faults.parse_spec("slow@search:50, slow@dispatch:ms=10,"
                           "slow@pipeline")
    assert [(r.kind, r.site, r.ms) for r in rs] == [
        ("slow", "search", 50), ("slow", "dispatch", 10),
        ("slow", "pipeline", faults.DEFAULT_SLOW_MS)]
    # slow fires on every invocation (no n/every arg slot)
    assert rs[0].fires(1) and rs[0].fires(99)


@pytest.mark.parametrize("bad", [
    "slow@child:5",        # child seam only implements wedge
    "slow@search:banana",  # non-integer delay
    "slow@search:n=3",     # counts do not apply to slow
    "slow@search:every=2",
    "slow@search:0",       # non-positive delay
    "slow@search:ms=0",
])
def test_fault_slow_bad_specs_raise(bad):
    with pytest.raises(faults.FaultSpecError):
        faults.parse_spec(bad)


def test_fault_slow_injects_latency_preserves_result(monkeypatch):
    """slow@<site> delays the dispatch deterministically and the call
    still runs and answers — the degraded-but-alive device the
    fairness/soak scenarios need (not wedge, not crash)."""
    import time as _time
    monkeypatch.setenv("JEPSEN_TPU_FAULTS", "slow@search:ms=60")
    resilience.reset()
    before = _cval("resilience.faults_injected.search")
    t0 = _time.perf_counter()
    assert sup.dispatch("search", lambda: 42) == 42
    elapsed = _time.perf_counter() - t0
    assert elapsed >= 0.055, elapsed
    assert _cval("resilience.faults_injected.search") == before + 1
    # other sites are untouched (and fast)
    t0 = _time.perf_counter()
    assert sup.dispatch("dispatch", lambda: 7) == 7
    assert _time.perf_counter() - t0 < 0.05


def test_fault_slow_watchdog_below_delay_wedges(monkeypatch):
    """The sleep rides inside the watchdogged window: a watchdog
    bound below the injected delay fires DispatchWedged — a too-slow
    dispatch IS the r05 wedge, by definition."""
    monkeypatch.setenv("JEPSEN_TPU_FAULTS", "slow@search:ms=300")
    resilience.reset()
    with pytest.raises(sup.DispatchWedged):
        sup.dispatch("search", lambda: 42, watchdog=0.05)
    # a bound ABOVE the delay lets the slow dispatch finish
    resilience.reset()
    assert sup.dispatch("search", lambda: 42, watchdog=2.0) == 42


def test_fault_slow_verdict_identical_to_clean(monkeypatch,
                                               reg_histories,
                                               clean_results):
    """A slow device changes latency, never verdicts: the register
    sweep under slow@search matches the clean run exactly."""
    from jepsen_tpu.models import CASRegister
    from jepsen_tpu.parallel import engine as eng
    monkeypatch.setenv("JEPSEN_TPU_FAULTS", "slow@search:ms=1")
    resilience.reset()
    for i, h in enumerate(reg_histories):
        r = eng.analysis(CASRegister(), h)
        ref = clean_results[i]
        assert r["valid?"] == ref["valid?"], i
        assert r.get("op") == ref.get("op"), i
