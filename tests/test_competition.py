"""The competition race (checker/competition.py): first decisive
verdict wins, losers are cooperatively cancelled, and — the point of
having a race at all — a wedged device arm cannot turn a check into a
hang (reference semantics: knossos competition/analysis, raced by
jepsen.checker's default linearizable analyzer, checker.clj:199)."""

import threading
import time

import numpy as np

from jepsen_tpu.checker import competition
from jepsen_tpu.checker.linearizable import linearizable
from jepsen_tpu.history import History, invoke_op, ok_op
from jepsen_tpu.models import CASRegister


def _h(*ops):
    return History.wrap(list(ops)).index()


def _valid_history(n=40):
    from jepsen_tpu.histories import rand_register_history
    return rand_register_history(n_ops=n, n_processes=4, crash_p=0.01,
                                 fail_p=0.05, seed=11)


def _invalid_history():
    return _h(invoke_op(0, "write", 1), ok_op(0, "write", 1),
              invoke_op(1, "read", None), ok_op(1, "read", 7))


def test_race_decisive_winner_and_fields():
    r = competition.analysis(CASRegister(), _valid_history())
    assert r["valid?"] is True
    assert r["analyzer"] in ("jax", "packed", "wgl")
    assert r["competition"]["winner"] == r["analyzer"]


def test_race_invalid_verdict_consistent():
    r = competition.analysis(CASRegister(), _invalid_history())
    assert r["valid?"] is False
    assert r["op"]["value"] == 7, r


def test_stalled_device_arm_still_yields_host_verdict(monkeypatch):
    """A deliberately-wedged jax arm (the wedged-runtime mode: a
    device call that never returns and ignores Python signals) must not
    delay the race beyond the host arms' own runtime."""
    from jepsen_tpu.parallel import engine

    wedge = threading.Event()

    def wedged_analysis(model, history, **kw):
        wedge.wait(300)           # "forever" at test scale
        return {"valid?": "unknown", "error": "wedged"}

    monkeypatch.setattr(engine, "analysis", wedged_analysis)
    t0 = time.monotonic()
    r = competition.analysis(CASRegister(), _valid_history())
    elapsed = time.monotonic() - t0
    wedge.set()                   # unblock the daemon thread
    assert r["valid?"] is True
    assert r["analyzer"] in ("packed", "wgl")
    assert elapsed < 60, elapsed


def test_stalled_device_arm_through_dispatcher(monkeypatch):
    """Same hedge end-to-end through the "competition" algorithm of the
    linearizable checker (the default analyzer)."""
    from jepsen_tpu.parallel import engine

    wedge = threading.Event()

    def wedged_analysis(model, history, **kw):
        wedge.wait(300)
        return {"valid?": "unknown"}

    monkeypatch.setattr(engine, "analysis", wedged_analysis)
    r = linearizable(CASRegister()).check({}, _valid_history())
    wedge.set()
    assert r["valid?"] is True
    assert r["analyzer"] in ("packed", "wgl")
    assert r["competition"]["winner"] == r["analyzer"]


def test_losers_are_cancelled(monkeypatch):
    """When one arm decides, the cancel event must be visible to the
    others (cooperative future-cancel parity)."""
    from jepsen_tpu.checker import wgl

    seen = {}
    real = wgl.analysis

    def spying_wgl(model, history, max_states=50_000_000,
                   deadline=None, cancel=None):
        seen["cancel"] = cancel
        return real(model, history, max_states=max_states,
                    deadline=deadline, cancel=cancel)

    monkeypatch.setattr(wgl, "analysis", spying_wgl)
    r = competition.analysis(CASRegister(), _valid_history())
    assert r["valid?"] is True
    assert isinstance(seen["cancel"], threading.Event)
    # the race sets cancel once the winner is in (and again on return)
    assert seen["cancel"].is_set()


def test_cancelled_host_arm_reports_cancelled_not_timeout():
    """A cancelled host search must say "cancelled" — not masquerade
    as a deadline timeout (the fields feed race diagnostics)."""
    from jepsen_tpu.checker import linear_packed, wgl

    ev = threading.Event()
    ev.set()
    h = _valid_history(200)
    r = linear_packed.analysis(CASRegister(), h, cancel=ev)
    assert r["valid?"] == "unknown"
    assert r.get("error") == "cancelled"
    assert "timeout" not in r
    # wgl polls every 4096 explored states, so it needs a history that
    # actually backtracks (depth-first greedy sails through register
    # histories): a crashy FIFO key explores ~8.4k states (seed 5)
    from jepsen_tpu.histories import rand_fifo_history
    from jepsen_tpu.models import FIFOQueue
    ha = rand_fifo_history(n_ops=40, n_processes=6, n_values=3,
                           crash_p=0.25, seed=5)
    rw = wgl.analysis(FIFOQueue(), ha, cancel=ev)
    assert rw["valid?"] == "unknown"
    assert rw.get("error") == "cancelled"
    assert "timeout" not in rw
    # linear polls per return event — the arm raced for unpackable
    # models must carry the same contract
    from jepsen_tpu.checker import linear
    rl = linear.analysis(FIFOQueue(), ha, cancel=ev)
    assert rl["valid?"] == "unknown"
    assert rl.get("error") == "cancelled"
    assert "timeout" not in rl


def test_all_arms_indecisive_reports_unknown(monkeypatch):
    """When every arm is indecisive (crash/unknown), the race must
    return an honest "unknown" carrying the per-arm results."""
    from jepsen_tpu.parallel import engine
    from jepsen_tpu.checker import linear_packed, wgl

    monkeypatch.setattr(engine, "analysis",
                        lambda *a, **k: {"valid?": "unknown", "error": "x"})
    monkeypatch.setattr(linear_packed, "analysis",
                        lambda *a, **k: (_ for _ in ()).throw(
                            RuntimeError("packed crashed")))
    monkeypatch.setattr(wgl, "analysis",
                        lambda *a, **k: {"valid?": "unknown",
                                         "timeout": True})
    r = competition.analysis(CASRegister(), _valid_history())
    assert r["valid?"] == "unknown"
    assert r["competition"]["winner"] is None
    per_arm = r["competition"]["results"]
    assert set(per_arm) == {"jax", "packed", "wgl"}
    assert "packed crashed" in per_arm["packed"]["error"]


def test_race_timeout_returns_indecisive(monkeypatch):
    """With every arm stalled, `timeout` bounds the race."""
    from jepsen_tpu.parallel import engine
    from jepsen_tpu.checker import linear_packed, wgl

    wedge = threading.Event()

    def stall(*a, **k):
        wedge.wait(300)
        return {"valid?": "unknown"}

    monkeypatch.setattr(engine, "analysis", stall)
    monkeypatch.setattr(linear_packed, "analysis", stall)
    monkeypatch.setattr(wgl, "analysis", stall)
    t0 = time.monotonic()
    r = competition.analysis(CASRegister(), _valid_history(), timeout=1.0)
    elapsed = time.monotonic() - t0
    wedge.set()
    assert r["valid?"] == "unknown"
    assert "still running" in r["error"]
    assert elapsed < 30, elapsed


def test_unpackable_model_races_linear_vs_wgl():
    """Unpackable models fall back to the reference's exact race:
    linear vs wgl."""
    from jepsen_tpu.models import Model

    class Opaque(Model):
        """A register the packer doesn't know."""
        def __init__(self, v=None):
            self.v = v

        def step(self, op):
            if op.f == "write":
                return Opaque(op.value)
            if op.f == "read":
                if op.value is not None and op.value != self.v:
                    from jepsen_tpu.models import inconsistent
                    return inconsistent(f"read {op.value} != {self.v}")
                return self
            return self

        def __eq__(self, o):
            return isinstance(o, Opaque) and self.v == o.v

        def __hash__(self):
            return hash(("Opaque", self.v))

    r = linearizable(Opaque()).check({}, _h(
        invoke_op(0, "write", 2), ok_op(0, "write", 2),
        invoke_op(1, "read", None), ok_op(1, "read", 2)))
    assert r["valid?"] is True
    assert r["analyzer"] in ("linear", "wgl")
    assert r["competition"]["arms"] == ["linear", "wgl"]

def test_engine_probe_timeout_is_bounded(monkeypatch):
    """jax.devices() wedged in backend start-up must not hang
    the availability probe — it times out and reports unavailable."""
    import jax
    import importlib
    lz = importlib.import_module("jepsen_tpu.checker.linearizable")

    monkeypatch.setattr(lz, "_engine_probe_result", None)
    monkeypatch.setattr(lz, "_engine_probe", {})
    wedge = threading.Event()

    def hanging_devices(*a, **k):
        wedge.wait(300)
        return jax_real_devices()

    jax_real_devices = jax.devices
    monkeypatch.setattr(jax, "devices", hanging_devices)
    t0 = time.monotonic()
    ok = lz._engine_available(timeout=1.0)
    elapsed = time.monotonic() - t0
    assert ok is False
    assert elapsed < 30, elapsed
    # still unanswered: later calls peek at the SAME probe thread (no
    # new thread, no fresh full wait) and stay unavailable
    t0 = time.monotonic()
    assert lz._engine_available(timeout=300.0) is False
    assert time.monotonic() - t0 < 30
    assert len(lz._engine_probe) == 2          # one probe, reused
    # the probe finally answers (slow, not wedged): availability
    # RECOVERS — only actual answers are cached
    wedge.set()
    lz._engine_probe["thread"].join(30)
    assert lz._engine_available(timeout=1.0) is True
    assert lz._engine_probe_result is True


def test_unavailable_engine_races_host_arms_only(monkeypatch):
    """With the device runtime unavailable, packable models race
    packed vs wgl — no device thread is spawned to wedge."""
    import importlib
    lz = importlib.import_module("jepsen_tpu.checker.linearizable")

    monkeypatch.setattr(lz, "_engine_probe_result", False)
    r = lz.linearizable(CASRegister()).check({}, _valid_history())
    assert r["valid?"] is True
    assert r["competition"]["arms"] == ["packed", "wgl"]
    assert r["analyzer"] in ("packed", "wgl")

def test_no_timeout_race_bounded_once_hosts_report(monkeypatch):
    """Without an overall timeout, a wedged device arm plus indecisive
    host arms must not hang the race: once every host arm has reported,
    the wait for the device arm is bounded by DEVICE_ARM_GRACE_SECS."""
    from jepsen_tpu.parallel import engine
    from jepsen_tpu.checker import linear_packed, wgl

    monkeypatch.setattr(competition, "DEVICE_ARM_GRACE_SECS", 1.0)
    wedge = threading.Event()

    def wedged(*a, **k):
        wedge.wait(300)
        return {"valid?": "unknown"}

    monkeypatch.setattr(engine, "analysis", wedged)
    monkeypatch.setattr(linear_packed, "analysis",
                        lambda *a, **k: {"valid?": "unknown",
                                         "error": "config budget"})
    monkeypatch.setattr(wgl, "analysis",
                        lambda *a, **k: {"valid?": "unknown",
                                         "error": "state budget"})
    t0 = time.monotonic()
    r = competition.analysis(CASRegister(), _valid_history())
    elapsed = time.monotonic() - t0
    wedge.set()
    assert r["valid?"] == "unknown"
    assert "'jax'" in r["error"] and "still running" in r["error"]
    assert elapsed < 30, elapsed


def test_mid_process_wedge_skips_device_arm_recoverably(monkeypatch):
    """A device arm orphaned by an earlier race and silent since
    (the runtime wedged AFTER the availability probe cached healthy) must flip
    later competition checks to host arms only — no new wedged thread
    per check — and the suspicion must CLEAR when the arm finally
    reports (a slow-but-healthy device is not a wedge)."""
    import importlib
    lz = importlib.import_module("jepsen_tpu.checker.linearizable")

    monkeypatch.setattr(lz, "_engine_probe_result", True)
    # simulate: a device arm its race gave up on long ago, still silent
    ghost = threading.Thread(target=lambda: None)
    monkeypatch.setitem(competition._orphaned, ghost,
                        time.monotonic() - 1000.0)
    assert competition.device_engine_suspect() is True
    r = lz.linearizable(CASRegister()).check({}, _valid_history())
    assert r["valid?"] is True
    assert r["competition"]["arms"] == ["packed", "wgl"]
    # the arm finally reports (run_arm's finally pops it): suspicion
    # clears and the device arm rejoins the race
    with competition._device_arms_lock:
        competition._orphaned.pop(ghost, None)
    assert competition.device_engine_suspect() is False
    r2 = lz.linearizable(CASRegister()).check({}, _valid_history())
    assert r2["competition"]["arms"] == ["jax", "packed", "wgl"]


def test_orphaned_device_arm_registered_on_giveup(monkeypatch):
    """A race that stops waiting on its device arm must register the
    orphan that feeds the wedge detection."""
    from jepsen_tpu.parallel import engine

    wedge = threading.Event()

    def wedged(*a, **k):
        wedge.wait(300)
        return {"valid?": "unknown"}

    monkeypatch.setattr(engine, "analysis", wedged)
    with competition._device_arms_lock:
        before = set(competition._orphaned)
    r = competition.analysis(CASRegister(), _valid_history())
    assert r["valid?"] is True          # a host arm decided
    with competition._device_arms_lock:
        # set-difference, not a count: orphans left by OTHER tests'
        # races may be popped concurrently as their arms unwedge
        new = set(competition._orphaned) - before
    assert len(new) == 1, new
    wedge.set()                         # let the arm report and clean up


def test_decisive_verdict_posted_just_before_expiry_wins(monkeypatch):
    """On timeout expiry the race must drain already-posted results:
    a decisive verdict enqueued moments before the deadline beats
    "unknown"."""
    from jepsen_tpu.parallel import engine
    from jepsen_tpu.checker import linear_packed, wgl

    wedge = threading.Event()

    def stall(*a, **k):
        wedge.wait(300)
        return {"valid?": "unknown"}

    monkeypatch.setattr(engine, "analysis", stall)
    monkeypatch.setattr(linear_packed, "analysis", stall)

    def slow_decisive(*a, **k):
        time.sleep(0.7)
        return {"valid?": True}

    monkeypatch.setattr(wgl, "analysis", slow_decisive)
    r = competition.analysis(CASRegister(), _valid_history(), timeout=1.0)
    wedge.set()
    assert r["valid?"] is True
    assert r["analyzer"] == "wgl"
