"""TPU engine tests: encoding, fixtures, differential vs host oracles,
batch/vmap, and the 8-virtual-device mesh path (conftest forces
JAX_PLATFORMS=cpu with xla_force_host_platform_device_count=8)."""

import numpy as np
import pytest

from jepsen_tpu.checker import linear, wgl
from jepsen_tpu.histories import corrupt_history, rand_register_history
from jepsen_tpu.history import History, invoke_op, ok_op, fail_op, info_op
from jepsen_tpu.models import CASRegister, Mutex, Register, UnorderedQueue
from jepsen_tpu.parallel import encode as enc_mod
from jepsen_tpu.parallel import engine


def _h(*ops):
    return History.wrap(ops).index()


# ------------------------------------------------------------- encoding


def test_encode_basic():
    h = _h(
        invoke_op(0, "write", 1),
        invoke_op(1, "read", None),
        ok_op(0, "write", 1),
        ok_op(1, "read", 1),
    )
    e = enc_mod.encode(CASRegister(), h)
    assert e.n_returns == 2
    assert e.n_calls == 2
    assert e.n_slots == 2
    # first return: both calls open -> both slots occupied
    assert e.slot_occ[0].sum() == 2
    # second return: only the read's slot occupied
    assert e.slot_occ[1].sum() == 1
    assert e.step_name == "register"


def test_encode_crashed_call_holds_slot():
    h = _h(
        invoke_op(0, "write", 1),
        info_op(0, "write", 1),
        invoke_op(1, "write", 2),
        ok_op(1, "write", 2),
    )
    e = enc_mod.encode(Register(), h)
    assert e.n_returns == 1
    assert e.n_slots == 2   # crashed write keeps slot 0
    assert e.slot_occ[0].sum() == 2


def test_encode_unpackable_model():
    from jepsen_tpu.models import Model

    class Weird(Model):  # no pack_spec arm: host-only
        def step(self, op):
            return self

    with pytest.raises(enc_mod.EncodeError):
        enc_mod.encode(Weird(), _h())


# ------------------------------------------------------------- fixtures


FIXTURES = [
    # (model, history ops, expected valid?)
    (Register(), [
        invoke_op(0, "write", 1), ok_op(0, "write", 1),
        invoke_op(0, "read", None), ok_op(0, "read", 1)], True),
    (Register(), [
        invoke_op(0, "write", 1), ok_op(0, "write", 1),
        invoke_op(0, "read", None), ok_op(0, "read", 2)], False),
    (Register(), [
        invoke_op(0, "write", 1), ok_op(0, "write", 1),
        invoke_op(1, "write", 2), invoke_op(2, "read", None),
        ok_op(2, "read", 2), ok_op(1, "write", 2)], True),
    (Register(), [
        invoke_op(0, "write", 1), ok_op(0, "write", 1),
        invoke_op(1, "write", 2), info_op(1, "write", 2),
        invoke_op(2, "read", None), ok_op(2, "read", 2)], True),
    (Register(), [
        invoke_op(0, "write", 2), info_op(0, "write", 2),
        invoke_op(1, "write", 3), ok_op(1, "write", 3),
        invoke_op(2, "read", None), ok_op(2, "read", 3),
        invoke_op(2, "read", None), ok_op(2, "read", 2)], True),
    (Register(), [
        invoke_op(0, "write", 1), ok_op(0, "write", 1),
        invoke_op(1, "write", 2), fail_op(1, "write", 2),
        invoke_op(2, "read", None), ok_op(2, "read", 2)], False),
    (CASRegister(), [
        invoke_op(0, "write", 0), ok_op(0, "write", 0),
        invoke_op(1, "cas", [0, 1]), invoke_op(2, "cas", [1, 2]),
        ok_op(1, "cas", [0, 1]), ok_op(2, "cas", [1, 2]),
        invoke_op(0, "read", None), ok_op(0, "read", 2)], True),
    (CASRegister(), [
        invoke_op(0, "write", 0), ok_op(0, "write", 0),
        invoke_op(1, "cas", [5, 1]), ok_op(1, "cas", [5, 1])], False),
    (Mutex(), [
        invoke_op(0, "acquire", None), info_op(0, "acquire", None),
        invoke_op(1, "release", None), ok_op(1, "release", None)], True),
    (Mutex(), [
        invoke_op(0, "acquire", None), ok_op(0, "acquire", None),
        invoke_op(1, "acquire", None), ok_op(1, "acquire", None)], False),
]


@pytest.mark.parametrize("model,ops,expect", FIXTURES)
def test_engine_fixtures(model, ops, expect):
    r = engine.analysis(model, _h(*ops))
    assert r["valid?"] is expect, r


def test_engine_counterexample_op():
    h = _h(
        invoke_op(0, "write", 1), ok_op(0, "write", 1),
        invoke_op(0, "read", None), ok_op(0, "read", 2),
    )
    r = engine.analysis(Register(), h)
    assert r["valid?"] is False
    assert r["op"]["f"] == "read" and r["op"]["value"] == 2
    # host re-search attaches a final path
    assert "final-paths" in r


def test_engine_empty():
    assert engine.analysis(Register(), _h())["valid?"] is True


def _long_invalid_history(n_ops):
    """A long valid cas-register history with an impossible read
    appended at the end — the failure is in the last few events."""
    from jepsen_tpu.histories import (rand_register_history,
                                      with_impossible_read)
    h = rand_register_history(n_ops=n_ops, n_processes=4, crash_p=0.0,
                              fail_p=0.0, n_values=4, seed=7)
    return with_impossible_read(h, value="never-written", process=97)


@pytest.mark.slow
def test_counterexample_extraction_long_history():
    """Past the 500-call whole-prefix limit the engine seeds a host
    window re-search from a device frontier checkpoint — a failing
    10k-op history still yields final-paths (the reference always
    produces them, checker.clj:203-213)."""
    from jepsen_tpu.models import CASRegister
    h = _long_invalid_history(10_000)
    r = engine.analysis(CASRegister(), h)
    assert r["valid?"] is False
    assert r["op"]["value"] == "never-written"
    assert r["final-paths"]
    # the windowed (device-seeded) path ran, not the whole-prefix one
    assert r["final-paths-window"][1] == r["fail-event"]
    for path in r["final-paths"]:
        assert path, "empty path"


@pytest.mark.slow
def test_counterexample_window_cause_precedes_window():
    """Adversarial placement for the windowed re-search: value 7 is
    written at the very start of the history, overwritten two calls
    later, and never written again; 700 valid ops follow; the final read
    returns 7. The *cause* of the failure (the overwrite) sits ~700
    return-events before the re-search window, so sound paths depend
    entirely on the device-seeded frontier carrying the correct states
    across the long prefix (engine.extract_final_paths; reference
    semantics checker.clj:203-213)."""
    from jepsen_tpu.models import CASRegister

    body = rand_register_history(n_ops=700, n_processes=4, crash_p=0.0,
                                 fail_p=0.0, n_values=5, seed=11)
    ops = [{"process": 90, "type": "invoke", "f": "write", "value": 7},
           {"process": 90, "type": "ok", "f": "write", "value": 7},
           {"process": 90, "type": "invoke", "f": "write", "value": 0},
           {"process": 90, "type": "ok", "f": "write", "value": 0}]
    ops += [dict(o) for o in body]
    ops += [{"process": 91, "type": "invoke", "f": "read", "value": None},
            {"process": 91, "type": "ok", "f": "read", "value": 7}]
    for i, o in enumerate(ops):
        o["index"], o["time"] = i, i
    h = _h(*ops)

    r = engine.analysis(CASRegister(), h)
    assert r["valid?"] is False
    assert r["op"]["f"] == "read" and r["op"]["value"] == 7
    assert r["final-paths"], r.get("final-paths-note")
    # the windowed path ran, and the window starts long after the cause
    start_ev, end_ev = r["final-paths-window"]
    # the overwrite of 7 is at return-event ~1; the window starts
    # hundreds of return events later (cas ops that legally failed are
    # dropped by encode, so returns < calls)
    assert start_ev > 400 and end_ev == r["fail-event"]

    # soundness: every path op is a genuine call from the history (no
    # fabricated linearizations), and no path linearizes a write of 7 —
    # i.e. the seeds really carried "register != 7" across the prefix
    invokes = {o["index"]: o for o in h if o["type"] == "invoke"}
    for path in r["final-paths"]:
        for step in path:
            op = step["op"]
            src = invokes[op["index"]]
            assert src["f"] == op["f"]
            assert not (op["f"] == "write" and op["value"] == 7)


def test_window_calls_drops_past_and_linearized():
    from jepsen_tpu.history import Call
    cs = [
        Call(0, 0, "write", 1, None, 0, 1, False),    # before window
        Call(1, 1, "write", 2, None, 2, 10, False),   # spans boundary
        Call(2, 2, "read", None, 2, 5, 9, False),     # in window
        Call(3, 3, "write", 3, None, 6, 20, False),   # completes past fail
    ]
    out = engine._window_calls(cs, boundary=4, fail_idx=12,
                               linearized=frozenset([1]))
    ids = [(c.process, c.crashed) for c in out]
    # call 0 dropped (past), call 1 dropped (linearized), call 3 clamped
    assert ids == [(2, False), (3, True)]
    assert out[0].index == 0 and out[1].index == 1  # renumbered


# ----------------------------------------------------------- differential


def test_differential_vs_host():
    for seed in range(20):
        h = rand_register_history(
            n_ops=60, n_processes=5, n_values=4,
            crash_p=0.06, fail_p=0.06, seed=seed + 1000,
        )
        expect = wgl.analysis(CASRegister(), h)["valid?"]
        got = engine.analysis(CASRegister(), h)
        assert got["valid?"] is expect, f"seed {seed}: {got}"

        bad = corrupt_history(h, seed=seed, n_corruptions=1)
        e1 = wgl.analysis(CASRegister(), bad)["valid?"]
        e2 = linear.analysis(CASRegister(), bad)["valid?"]
        e3 = engine.analysis(CASRegister(), bad)["valid?"]
        assert e1 == e2 == e3, f"seed {seed}: wgl={e1} linear={e2} jax={e3}"


def test_differential_gset_vs_host():
    """Device gset (bitmask state) vs host WGL on random + corrupted
    histories, covering both the bitdense (<= 7 elements) and sparse
    (> 7 elements) dispatch tiers."""
    from jepsen_tpu.histories import rand_gset_history
    from jepsen_tpu.models import GSet
    for seed in range(12):
        n_el = 5 if seed % 2 == 0 else 12  # bitdense / sparse tiers
        h = rand_gset_history(n_ops=40, n_processes=4, n_elements=n_el,
                              crash_p=0.06, seed=seed + 7000)
        expect = wgl.analysis(GSet(), h)["valid?"]
        got = engine.analysis(GSet(), h)
        assert got["valid?"] is expect, f"seed {seed}: {got}"
        assert "fallback" not in got, got

        # corrupt one ok read to include a never-added element
        ops = [dict(o) for o in h]
        for o in ops:
            if o.get("type") == "ok" and o.get("f") == "read":
                o["value"] = list(o["value"]) + [999]
                break
        bad = _h(*ops)
        e1 = wgl.analysis(GSet(), bad)["valid?"]
        e2 = engine.analysis(GSet(), bad)["valid?"]
        assert e1 == e2, f"seed {seed}: wgl={e1} jax={e2}"


def test_differential_uqueue_vs_host():
    """Device unordered-queue (packed count lanes) vs host WGL, random +
    corrupted, bitdense (4 bits) and sparse (9+ bits) tiers."""
    from jepsen_tpu.histories import rand_queue_history
    from jepsen_tpu.models import UnorderedQueue
    for seed in range(12):
        n_vals = 2 if seed % 2 == 0 else 4
        h = rand_queue_history(n_ops=40, n_processes=4, n_values=n_vals,
                               crash_p=0.06, seed=seed + 8000)
        expect = wgl.analysis(UnorderedQueue(), h)["valid?"]
        got = engine.analysis(UnorderedQueue(), h)
        assert got["valid?"] is expect, f"seed {seed}: {got}"
        assert "fallback" not in got, got

        # corrupt one ok dequeue to a never-enqueued value
        ops = [dict(o) for o in h]
        for o in ops:
            if o.get("type") == "ok" and o.get("f") == "dequeue":
                o["value"] = 777
                break
        else:
            continue
        bad = _h(*ops)
        e1 = wgl.analysis(UnorderedQueue(), bad)["valid?"]
        e2 = engine.analysis(UnorderedQueue(), bad)["valid?"]
        assert e1 == e2, f"seed {seed}: wgl={e1} jax={e2}"
        assert e1 is False  # dequeue of a never-enqueued value


def test_differential_fifo_vs_host():
    """Device strict-FIFO queue (value-code lanes, head at low bits) vs
    host WGL, random + corrupted histories."""
    from jepsen_tpu.histories import rand_fifo_history
    from jepsen_tpu.models import FIFOQueue
    for seed in range(12):
        n_vals = 2 if seed % 2 == 0 else 4
        h = rand_fifo_history(n_ops=36, n_processes=4, n_values=n_vals,
                              crash_p=0.06, seed=seed + 9100)
        expect = wgl.analysis(FIFOQueue(), h)["valid?"]
        got = engine.analysis(FIFOQueue(), h)
        assert got["valid?"] is expect, f"seed {seed}: {got}"
        assert "fallback" not in got, got

        # corrupt one ok dequeue to a never-enqueued value
        ops = [dict(o) for o in h]
        for o in ops:
            if o.get("type") == "ok" and o.get("f") == "dequeue":
                o["value"] = 777
                break
        else:
            continue
        bad = _h(*ops)
        e1 = wgl.analysis(FIFOQueue(), bad)["valid?"]
        e2 = engine.analysis(FIFOQueue(), bad)["valid?"]
        assert e1 == e2 is False, f"seed {seed}: wgl={e1} jax={e2}"


def test_fifo_order_sensitivity():
    """The FIFO device tier must reject out-of-order dequeues the
    unordered queue would accept — sequential enqueue a,b then
    dequeue b is FIFO-invalid; concurrent enqueues go either way."""
    from jepsen_tpu.models import FIFOQueue, UnorderedQueue
    seq = _h(invoke_op(0, "enqueue", "a"), ok_op(0, "enqueue", "a"),
             invoke_op(0, "enqueue", "b"), ok_op(0, "enqueue", "b"),
             invoke_op(0, "dequeue", None), ok_op(0, "dequeue", "b"))
    assert engine.analysis(UnorderedQueue(), seq)["valid?"] is True
    r = engine.analysis(FIFOQueue(), seq)
    assert r["valid?"] is False and "fallback" not in r
    assert r["op"]["f"] == "dequeue" and r["op"]["value"] == "b"

    conc = _h(invoke_op(0, "enqueue", "a"), invoke_op(1, "enqueue", "b"),
              ok_op(0, "enqueue", "a"), ok_op(1, "enqueue", "b"),
              invoke_op(2, "dequeue", None), ok_op(2, "dequeue", "b"))
    assert engine.analysis(FIFOQueue(), conc)["valid?"] is True

    # crashed dequeue pops ANY head (host value=None semantics): a
    # crashed dequeue can explain the missing "a"
    crashed = _h(invoke_op(0, "enqueue", "a"), ok_op(0, "enqueue", "a"),
                 invoke_op(0, "enqueue", "b"), ok_op(0, "enqueue", "b"),
                 invoke_op(1, "dequeue", None), info_op(1, "dequeue", None),
                 invoke_op(2, "dequeue", None), ok_op(2, "dequeue", "b"))
    assert engine.analysis(FIFOQueue(), crashed)["valid?"] is True
    assert wgl.analysis(FIFOQueue(), crashed)["valid?"] is True

    # initial items (FIFOQueue.of equivalent): head is the first item
    pre = FIFOQueue(("x", "y"))
    assert engine.analysis(pre, _h(
        invoke_op(0, "dequeue", None), ok_op(0, "dequeue", "x")))["valid?"] \
        is True
    assert engine.analysis(pre, _h(
        invoke_op(0, "dequeue", None), ok_op(0, "dequeue", "y")))["valid?"] \
        is False


def test_crashed_dequeue_invoke_value_is_ignored():
    """A crashed dequeue's result is unknown regardless of its invoke
    value (wgl._StepOp sets value=None): the device tiers must pop
    any head / stay unconstrained, not constrain on the invoke value
    (that was a KeyError for unlaned values and a false violation for
    laned ones)."""
    from jepsen_tpu.models import FIFOQueue, UnorderedQueue
    # unlaned invoke value 5: must not KeyError
    h = _h(invoke_op(0, "enqueue", 1), ok_op(0, "enqueue", 1),
           invoke_op(1, "dequeue", 5), info_op(1, "dequeue", 5))
    for model in (FIFOQueue(), UnorderedQueue()):
        r = engine.analysis(model, h)
        assert r["valid?"] is True and "fallback" not in r, (model, r)
    # laned invoke value: crashed deq(5) must be able to pop head 1
    h2 = _h(invoke_op(0, "enqueue", 1), ok_op(0, "enqueue", 1),
            invoke_op(0, "enqueue", 5), ok_op(0, "enqueue", 5),
            invoke_op(1, "dequeue", 5), info_op(1, "dequeue", 5),
            invoke_op(2, "dequeue", None), ok_op(2, "dequeue", 5))
    assert wgl.analysis(FIFOQueue(), h2)["valid?"] is True
    assert engine.analysis(FIFOQueue(), h2)["valid?"] is True


def test_none_is_an_ordinary_element():
    """The host models append/add literal None; the device tiers must
    agree (a None-valued ok enqueue/add encoded as a wildcard identity
    would report a false linearizability violation)."""
    from jepsen_tpu.models import FIFOQueue, GSet
    h = _h(invoke_op(0, "enqueue", None), ok_op(0, "enqueue", None),
           invoke_op(0, "dequeue", None), ok_op(0, "dequeue", None))
    assert wgl.analysis(FIFOQueue(), h)["valid?"] is True
    r = engine.analysis(FIFOQueue(), h)
    assert r["valid?"] is True and "fallback" not in r, r

    g = _h(invoke_op(0, "add", None), ok_op(0, "add", None),
           invoke_op(1, "read", None), ok_op(1, "read", [None]))
    assert wgl.analysis(GSet(), g)["valid?"] is True
    rg = engine.analysis(GSet(), g)
    assert rg["valid?"] is True and "fallback" not in rg, rg
    # and the read must CONSTRAIN: an empty read after the add completes
    g2 = _h(invoke_op(0, "add", None), ok_op(0, "add", None),
            invoke_op(1, "read", None), ok_op(1, "read", []))
    assert wgl.analysis(GSet(), g2)["valid?"] is False
    assert engine.analysis(GSet(), g2)["valid?"] is False


def test_fifo_depth_budget_falls_back_to_host():
    """> 31 bits of lane space (here 16 pending x 2-bit codes) must go
    to the host engine, loudly tagged."""
    from jepsen_tpu.models import FIFOQueue
    ops = []
    for i in range(16):
        ops.append(invoke_op(0, "enqueue", i % 3))
        ops.append(ok_op(0, "enqueue", i % 3))
    r = engine.analysis(FIFOQueue(), _h(*ops))
    assert r["valid?"] is True
    assert "fallback" in r and "fifo" in r["fallback"]


def test_crashed_wildcard_dequeues_pruned():
    """25 crashed dequeues (unknown results) pack to wildcards and are
    pruned at encode — without this each would double the mask space
    forever and overflow every capacity tier."""
    from jepsen_tpu.models import UnorderedQueue
    ops = []
    for p in range(25):
        ops.append(invoke_op(p, "dequeue", None))
        ops.append(info_op(p, "dequeue", None))
    ops += [invoke_op(30, "enqueue", "a"), ok_op(30, "enqueue", "a"),
            invoke_op(30, "dequeue", None), ok_op(30, "dequeue", "a")]
    e = enc_mod.encode(UnorderedQueue(), _h(*ops))
    assert e.n_calls == 2      # the crashed wildcards are gone
    assert e.n_slots <= 2
    r = engine.analysis(UnorderedQueue(), _h(*ops))
    assert r["valid?"] is True and "fallback" not in r


def test_gset_read_constrains_completed_adds():
    """Sequential add a; add b; read [a] is invalid (the read missed a
    completed add); an add CONCURRENT with the read goes either way."""
    from jepsen_tpu.models import GSet
    seq = _h(invoke_op(0, "add", "a"), ok_op(0, "add", "a"),
             invoke_op(0, "add", "b"), ok_op(0, "add", "b"),
             invoke_op(1, "read", None), ok_op(1, "read", ["a"]))
    assert wgl.analysis(GSet(), seq)["valid?"] is False
    assert engine.analysis(GSet(), seq)["valid?"] is False

    conc = _h(invoke_op(0, "add", "a"), ok_op(0, "add", "a"),
              invoke_op(1, "read", None),
              invoke_op(0, "add", "b"), ok_op(0, "add", "b"),
              ok_op(1, "read", ["a"]))
    assert wgl.analysis(GSet(), conc)["valid?"] is True
    assert engine.analysis(GSet(), conc)["valid?"] is True


def test_uqueue_multiset_counting():
    """Two enqueues of the same value supply exactly two dequeues."""
    from jepsen_tpu.models import UnorderedQueue
    ops = [invoke_op(0, "enqueue", "a"), ok_op(0, "enqueue", "a"),
           invoke_op(0, "enqueue", "a"), ok_op(0, "enqueue", "a"),
           invoke_op(1, "dequeue", None), ok_op(1, "dequeue", "a"),
           invoke_op(1, "dequeue", None), ok_op(1, "dequeue", "a")]
    assert engine.analysis(UnorderedQueue(), _h(*ops))["valid?"] is True
    ops += [invoke_op(1, "dequeue", None), ok_op(1, "dequeue", "a")]
    r = engine.analysis(UnorderedQueue(), _h(*ops))
    assert r["valid?"] is False
    assert wgl.analysis(UnorderedQueue(), _h(*ops))["valid?"] is False


def test_uqueue_counterexample_reports_observed_value():
    from jepsen_tpu.models import UnorderedQueue
    r = engine.analysis(UnorderedQueue(), _h(
        invoke_op(0, "enqueue", "a"), ok_op(0, "enqueue", "a"),
        invoke_op(0, "dequeue", None), ok_op(0, "dequeue", "a"),
        invoke_op(0, "dequeue", None), ok_op(0, "dequeue", "a")))
    assert r["valid?"] is False
    # the op is completion-valued: the impossible second dequeue of "a",
    # not the invocation's value=None
    assert r["op"]["f"] == "dequeue" and r["op"]["value"] == "a"


def test_gset_device_fixtures():
    from jepsen_tpu.models import GSet
    # exact-read semantics: read must observe the full set
    r = engine.analysis(GSet(), _h(
        invoke_op(0, "add", "x"), ok_op(0, "add", "x"),
        invoke_op(0, "read", None), ok_op(0, "read", ["x"])))
    assert r["valid?"] is True
    r = engine.analysis(GSet(), _h(
        invoke_op(0, "add", "x"), ok_op(0, "add", "x"),
        invoke_op(0, "read", None), ok_op(0, "read", [])))
    assert r["valid?"] is False
    # concurrent add may or may not be visible
    r = engine.analysis(GSet(), _h(
        invoke_op(0, "add", "x"), ok_op(0, "add", "x"),
        invoke_op(1, "add", "y"), invoke_op(2, "read", None),
        ok_op(2, "read", ["x", "y"]), ok_op(1, "add", "y")))
    assert r["valid?"] is True
    # > 31 distinct elements: loud host fallback, same verdict
    big = []
    for i in range(33):
        big += [invoke_op(0, "add", i), ok_op(0, "add", i)]
    r = engine.analysis(GSet(), _h(*big))
    assert r["valid?"] is True and "fallback" in r


def test_uqueue_device_fixtures():
    from jepsen_tpu.models import UnorderedQueue
    # unordered: dequeue order need not match enqueue order
    r = engine.analysis(UnorderedQueue(), _h(
        invoke_op(0, "enqueue", "a"), ok_op(0, "enqueue", "a"),
        invoke_op(0, "enqueue", "b"), ok_op(0, "enqueue", "b"),
        invoke_op(0, "dequeue", None), ok_op(0, "dequeue", "b"),
        invoke_op(0, "dequeue", None), ok_op(0, "dequeue", "a")))
    assert r["valid?"] is True
    # dequeue of a value enqueued only once, twice: invalid
    r = engine.analysis(UnorderedQueue(), _h(
        invoke_op(0, "enqueue", "a"), ok_op(0, "enqueue", "a"),
        invoke_op(0, "dequeue", None), ok_op(0, "dequeue", "a"),
        invoke_op(0, "dequeue", None), ok_op(0, "dequeue", "a")))
    assert r["valid?"] is False
    # concurrent enqueue may satisfy a concurrent dequeue
    r = engine.analysis(UnorderedQueue(), _h(
        invoke_op(0, "enqueue", "a"), invoke_op(1, "dequeue", None),
        ok_op(1, "dequeue", "a"), ok_op(0, "enqueue", "a")))
    assert r["valid?"] is True
    # crashed enqueue may supply a later dequeue
    r = engine.analysis(UnorderedQueue(), _h(
        invoke_op(0, "enqueue", "a"), info_op(0, "enqueue", "a"),
        invoke_op(1, "dequeue", None), ok_op(1, "dequeue", "a")))
    assert r["valid?"] is True
    # initial pending elements count (UnorderedQueue.of)
    r = engine.analysis(UnorderedQueue.of("x"), _h(
        invoke_op(0, "dequeue", None), ok_op(0, "dequeue", "x")))
    assert r["valid?"] is True


# ------------------------------------------------------------- batching


def test_check_batch():
    hs = [rand_register_history(n_ops=30, n_processes=3, crash_p=0.05,
                                seed=s) for s in range(8)]
    bad = corrupt_history(hs[3], seed=3, n_corruptions=2)
    expected = [wgl.analysis(CASRegister(), h)["valid?"] for h in hs[:3]] + \
               [wgl.analysis(CASRegister(), bad)["valid?"]] + \
               [wgl.analysis(CASRegister(), h)["valid?"] for h in hs[4:]]
    batch = hs[:3] + [bad] + hs[4:]
    rs = engine.check_batch(CASRegister(), batch)
    assert [r["valid?"] for r in rs] == expected


def test_check_batch_sharded_mesh():
    import jax
    from jax.sharding import Mesh
    devs = np.array(jax.devices())
    assert devs.size == 8, "conftest should provide 8 virtual CPU devices"
    mesh = Mesh(devs, ("keys",))
    hs = [rand_register_history(n_ops=24, n_processes=3, crash_p=0.0,
                                seed=100 + s) for s in range(8)]
    rs = engine.check_batch(CASRegister(), hs, mesh=mesh)
    assert all(r["valid?"] is True for r in rs)


def _concurrent_writes_history(m, base_process=0):
    """m concurrent writes of m distinct values (all invoked, then all
    acked): the frontier peaks at ~m * 2^(m-1) configs during the first
    closure — an adversarial single key whose cost is tunable by m."""
    ops = []
    for p in range(m):
        ops.append(invoke_op(base_process + p, "write", 1000 + p))
    for p in range(m):
        ops.append(ok_op(base_process + p, "write", 1000 + p))
    return _h(*ops)


def test_check_batch_per_key_capacity_retry():
    """One hot key among cheap ones in the sparse batch path: only the
    hot key re-runs at doubled capacity; the cheap keys' results record
    the base tier, proving they were not re-padded and re-searched at
    the hot key's capacity."""
    cheap = [rand_register_history(n_ops=20, n_processes=3, crash_p=0.0,
                                   seed=300 + s) for s in range(16)]
    hot = _concurrent_writes_history(7)       # needs ~450 configs -> 512
    doomed = _concurrent_writes_history(26)   # blows past any tier
    pre = [enc_mod.encode(CASRegister(), h)
           for h in cheap + [hot, doomed]]
    rs = engine._check_batch_sparse(CASRegister(), pre, capacity=128,
                                    max_capacity=2048)
    for r in rs[:16]:
        assert r["valid?"] is True
        assert r["capacity"] == 128, r   # never re-run at a higher tier
    assert rs[16]["valid?"] is True
    assert rs[16]["capacity"] == 512, rs[16]  # bucketed retry found 512
    assert rs[17]["valid?"] == "unknown"
    assert "overflow" in rs[17]["error"]


def test_adversarial_register_history_oracle():
    """The bench's adversarial shape (histories.adversarial_register_
    history) must be valid-by-construction under both engines, ride the
    bit-packed device path, and genuinely hold its k crashed writes
    open (slot window = k + sequential slot)."""
    from jepsen_tpu.histories import adversarial_register_history
    from jepsen_tpu.parallel import bitdense
    h = adversarial_register_history(n_ops=80, k_crashed=6, seed=3)
    e = enc_mod.encode(CASRegister(), h)
    assert e.n_slots == 7
    assert bitdense.fits_bitdense(bitdense.n_states(e), e.n_slots)
    assert wgl.analysis(CASRegister(), h)["valid?"] is True
    r = engine.analysis(CASRegister(), h)
    assert r["valid?"] is True and r.get("engine") == "bitdense"


def test_check_batch_c_tier_bucketing():
    """A wide key must not drag narrow keys into its padded mask-space:
    check_batch buckets by slot-window tier, so the narrow keys still
    ride the bit-packed engine while the 26-slot key lands in its own
    (sparse) bucket. Results per key are unchanged."""
    narrow = [rand_register_history(n_ops=30, n_processes=3, crash_p=0.02,
                                    seed=400 + s) for s in range(6)]
    bad = corrupt_history(narrow[2], seed=9, n_corruptions=2)
    doomed = _concurrent_writes_history(26)
    batch = narrow[:2] + [bad] + narrow[3:] + [doomed]
    rs = engine.check_batch(CASRegister(), batch, capacity=128,
                            max_capacity=2048)
    oracle = [wgl.analysis(CASRegister(), h)["valid?"] for h in batch[:-1]]
    assert [r["valid?"] for r in rs[:-1]] == oracle
    for r in rs[:-1]:
        assert r.get("engine") == "bitdense", r  # narrow bucket stayed fast
    assert rs[-1]["valid?"] == "unknown"         # wide bucket overflowed


def test_check_batch_exact_bucketing_matches_tier():
    """bucket="exact" (one program per distinct slot count — the
    opt-in strategy tools/perf_ab.py's bucketed line measures) must be
    verdict- and localization-identical to the default tiers on a
    mixed-C batch with an invalid key; a bogus strategy name raises."""
    batch = [rand_register_history(n_ops=40, n_processes=3 + (s % 4),
                                   crash_p=0.04, seed=500 + s)
             for s in range(8)]
    batch[5] = corrupt_history(batch[5], seed=3, n_corruptions=2)
    rs_tier = engine.check_batch(CASRegister(), batch, capacity=128,
                                 max_capacity=4096)
    rs_exact = engine.check_batch(CASRegister(), batch, capacity=128,
                                  max_capacity=4096, bucket="exact")
    strip = lambda rs: [{k: v for k, v in r.items()  # noqa: E731
                         if k != "closure"} for r in rs]
    assert strip(rs_tier) == strip(rs_exact)
    assert rs_exact[5]["valid?"] is False
    with pytest.raises(ValueError, match="bucket"):
        engine.check_batch(CASRegister(), [], bucket="bogus")
    # the env lever resolves the None default (and bad values raise
    # even on an empty batch)
    import os
    import unittest.mock as mock
    with mock.patch.dict(os.environ, {"JEPSEN_TPU_BUCKET": "exact"}):
        rs_env = engine.check_batch(CASRegister(), batch[:2],
                                    capacity=128, max_capacity=4096)
    assert strip(rs_env) == strip(rs_tier[:2])
    with mock.patch.dict(os.environ, {"JEPSEN_TPU_BUCKET": "bogus"}), \
            pytest.raises(ValueError, match="bucket"):
        engine.check_batch(CASRegister(), [])

    # the encoded-entry half (public for encode/device-split callers)
    # preserves input order and matches the full path
    pre = [enc_mod.encode(CASRegister(), h) for h in batch]
    rs_enc = engine.check_batch_encoded(CASRegister(), pre,
                                        capacity=128,
                                        max_capacity=4096,
                                        bucket="exact")
    assert strip(rs_enc) == strip(rs_tier)
    assert engine.check_batch_encoded(CASRegister(), []) == []


def test_dispatcher_jax_route():
    from jepsen_tpu.checker import linearizable
    h = _h(
        invoke_op(0, "write", 1), ok_op(0, "write", 1),
        invoke_op(0, "read", None), ok_op(0, "read", 1),
    )
    r = linearizable(Register(), algorithm="jax").check({}, h)
    assert r["valid?"] is True and r["analyzer"] == "jax"
    # competition now RACES jax/packed/wgl — first decisive wins
    r = linearizable(Register(), algorithm="competition").check({}, h)
    assert r["valid?"] is True
    assert r["analyzer"] in ("jax", "packed", "wgl")
    assert r["competition"]["winner"] == r["analyzer"]
    assert r["competition"]["arms"] == ["jax", "packed", "wgl"]
    # packed: the int-config host engine behind the same boundary
    r = linearizable(Register(), algorithm="packed").check({}, h)
    assert r["valid?"] is True and r["analyzer"] == "packed"
    bad = _h(invoke_op(0, "write", 1), ok_op(0, "write", 1),
             invoke_op(0, "read", None), ok_op(0, "read", 2))
    r = linearizable(Register(), algorithm="packed").check({}, bad)
    assert r["valid?"] is False and r["op"]["value"] == 2

    # packed on an unpackable model falls back to wgl, tagged
    from jepsen_tpu.models import Model

    class Weird(Model):
        def step(self, op):
            return self

    r = linearizable(Weird(), algorithm="packed").check({}, _h())
    assert r["valid?"] is True and r["analyzer"] == "wgl"


def test_batch_overflow_escalates_to_wider_tiers():
    """A key too wide for the batch program must escalate — first the
    single-key sparse engine at a higher ceiling, then the mesh-sharded
    engine — instead of returning "unknown" (the dp -> sp long-history
    escalation, SURVEY.md §5.7). State-rich FIFO keys route through the
    sparse path (S far past bitdense's cap); measured frontiers: the
    mid key peaks ~512 configs (single tier's 4x ceiling decides it),
    the giant ~1.3k (only the sharded tier's aggregate reaches it)."""
    import jax
    from jax.sharding import Mesh

    from jepsen_tpu.histories import rand_fifo_history
    from jepsen_tpu.models import FIFOQueue

    cheap = rand_fifo_history(n_ops=40, n_processes=6, n_values=3,
                              crash_p=0.15, seed=5)    # peak ~86
    mid = rand_fifo_history(n_ops=40, n_processes=6, n_values=3,
                            crash_p=0.15, seed=1)      # peak ~512
    giant = rand_fifo_history(n_ops=40, n_processes=6, n_values=3,
                              crash_p=0.25, seed=2)    # peak ~1.3k

    rs = engine.check_batch(FIFOQueue(), [cheap, mid],
                            capacity=64, max_capacity=128)
    assert rs[0]["valid?"] is True and "escalated" not in rs[0]
    assert rs[1]["valid?"] is True, rs[1]
    assert rs[1].get("escalated") == "single", rs[1]

    mesh = Mesh(np.array(jax.devices()[:8]), ("keys",))
    rs = engine.check_batch(FIFOQueue(), [cheap, giant],
                            capacity=64, max_capacity=128, mesh=mesh)
    assert rs[0]["valid?"] is True
    assert rs[1]["valid?"] is True, rs[1]
    assert rs[1].get("escalated") == "sharded", rs[1]

    # without a mesh the giant is honestly unknown, with the error tagged
    rs = engine.check_batch(FIFOQueue(), [giant],
                            capacity=64, max_capacity=128)
    assert rs[0]["valid?"] == "unknown"
    assert "error" in rs[0]


def test_escalation_crash_is_loud(monkeypatch, caplog):
    """A broken sharded escalation tier must warn loudly and tag the
    result — never silently degrade a key to "unknown" (the same rule
    independent.py enforces for its device fallback)."""
    import logging

    import jax
    from jax.sharding import Mesh

    from jepsen_tpu.histories import rand_fifo_history
    from jepsen_tpu.models import FIFOQueue
    from jepsen_tpu.parallel import sharded

    def boom(*a, **k):
        raise RuntimeError("sharded tier exploded")

    monkeypatch.setattr(sharded, "check_encoded_sharded", boom)
    giant = rand_fifo_history(n_ops=40, n_processes=6, n_values=3,
                              crash_p=0.25, seed=2)
    mesh = Mesh(np.array(jax.devices()[:8]), ("keys",))
    with caplog.at_level(logging.WARNING,
                         logger="jepsen_tpu.parallel.engine"):
        rs = engine.check_batch(FIFOQueue(), [giant],
                                capacity=64, max_capacity=128, mesh=mesh)
    assert rs[0]["valid?"] == "unknown"
    assert "sharded tier exploded" in rs[0].get("escalation-error", "")
    assert "escalation tiers exhausted" in rs[0]["error"]
    assert any("sharded escalation tier crashed" in r.message
               for r in caplog.records)

def test_escalation_single_tier_pinned_to_callers_mesh(monkeypatch):
    """The single-key escalation tier must run on the caller's mesh,
    never on the default backend — the batch and sharded paths keep
    that invariant (the default backend can be a wedged TPU runtime
    while we deliberately run on a CPU mesh), and a batch-overflow key
    previously broke it right in the middle of the hardened path."""
    import jax
    from jax.sharding import Mesh

    from jepsen_tpu.histories import rand_fifo_history
    from jepsen_tpu.models import FIFOQueue

    seen = {}
    real = engine.check_encoded

    def spy(e, capacity=1024, max_capacity=1 << 20, device=None, **kw):
        seen["device"] = device
        return real(e, capacity=capacity, max_capacity=max_capacity,
                    device=device, **kw)

    monkeypatch.setattr(engine, "check_encoded", spy)
    mid = rand_fifo_history(n_ops=40, n_processes=6, n_values=3,
                            crash_p=0.15, seed=1)     # peak ~512
    mesh = Mesh(np.array(jax.devices()[4:8]), ("keys",))
    rs = engine.check_batch(FIFOQueue(), [mid],
                            capacity=64, max_capacity=128, mesh=mesh)
    assert rs[0]["valid?"] is True
    assert rs[0].get("escalated") == "single", rs[0]
    assert seen["device"] == np.asarray(mesh.devices).flat[0]


def test_check_encoded_explicit_device_placement():
    """check_encoded(device=...) places every input on that device and
    reaches the same verdict as the default-backend path."""
    import jax

    from jepsen_tpu.histories import rand_fifo_history
    from jepsen_tpu.models import FIFOQueue

    h = rand_fifo_history(n_ops=30, n_processes=4, n_values=3,
                          crash_p=0.05, seed=3)
    e = enc_mod.encode(FIFOQueue(), h)
    dev = jax.devices()[5]
    xs = engine._xs_from_encoded(e, dev)
    for name, a in xs.items():
        assert a.devices() == {dev}, (name, a.devices())
    r_pinned = engine.check_encoded(e, device=dev)
    r_default = engine.check_encoded(e)
    assert r_pinned["valid?"] == r_default["valid?"]
    # the resumable arm keeps the same invariant: chunks and carries
    # placed on the given device, same verdict
    r_res = engine.check_encoded_resumable(e, checkpoint_every=8,
                                           device=dev)
    assert r_res["valid?"] == r_default["valid?"]
    cp = engine.FrontierCheckpoint(
        0, 64, e.step_name, engine.history_digest(e),
        np.zeros(64, np.int32), np.zeros(64, np.uint32),
        np.zeros(64, np.uint32), np.arange(64) < 1, True, -1, 1, 0)
    for a in cp.carry(dev):
        assert a.devices() == {dev}, a.devices()

def test_device_false_invalid_escalates_to_host_recheck(monkeypatch):
    """A fabricated device-invalid on a genuinely valid key must END in
    the correct verdict: the host prefix re-search contradicts the
    device, the bounded full-host recheck decides valid, and the device
    verdict is overridden (tagged engine-disagreement) instead of
    shipping "invalid, no paths"."""
    from jepsen_tpu.histories import rand_register_history
    from jepsen_tpu.models import CASRegister
    from jepsen_tpu.parallel import bitdense

    model = CASRegister()
    h = rand_register_history(n_ops=60, n_processes=4, crash_p=0.01,
                              fail_p=0.05, seed=21)
    e = enc_mod.encode(model, h)
    fake = {"valid?": False, "engine": "bitdense",
            "fail-event": e.n_returns - 1}
    fake.update(enc_mod.fail_op_fields(e, e.n_returns - 1))
    monkeypatch.setattr(bitdense, "check_encoded_bitdense",
                        lambda *a, **k: dict(fake))
    r = engine.analysis(model, h)
    assert r["valid?"] is True, r
    assert "engine-disagreement" in r, r
    assert "overridden" in r["engine-disagreement"]
    # the device's stale counterexample fields must not survive on a
    # valid verdict
    assert "op" not in r and "fail-event" not in r, r


def test_device_false_invalid_long_history_window_branch():
    """Same escalation through the >500-call window/seed machinery: a
    fabricated fail event on a valid key means SOME frontier seed
    linearizes its window through the "failure" — that contradiction
    must escalate to the recheck, not ship near-miss paths from the
    dead-end seeds. max_seeds covers the whole frontier here so the
    surviving lineage is guaranteed to be sampled (at the default 8 the
    outcome would depend on frontier row order)."""
    from jepsen_tpu.histories import rand_register_history
    from jepsen_tpu.models import CASRegister

    model = CASRegister()
    h = rand_register_history(n_ops=700, n_processes=4, crash_p=0.005,
                              fail_p=0.03, seed=9)
    e = enc_mod.encode(model, h)
    assert e.n_calls > 500
    r = engine.extract_final_paths(model, e, e.n_returns - 1,
                                   max_seeds=1024)
    assert r.get("valid?") is True, r
    assert "engine-disagreement" in r


def test_indecisive_recheck_keeps_device_verdict(monkeypatch):
    """When the bounded recheck cannot decide (budget exhausted), the
    device verdict stands, tagged — never silently flipped."""
    from jepsen_tpu.histories import rand_register_history
    from jepsen_tpu.models import CASRegister

    model = CASRegister()
    h = rand_register_history(n_ops=60, n_processes=4, crash_p=0.01,
                              fail_p=0.05, seed=21)
    e = enc_mod.encode(model, h)
    monkeypatch.setattr(engine, "DISAGREEMENT_RECHECK_MAX_STATES", 1)
    r = engine.extract_final_paths(model, e, e.n_returns - 1)
    assert "valid?" not in r           # verdict untouched
    assert "recheck indecisive" in r.get("final-paths-note", ""), r

def _assert_snapshots_match_naive_oracle(e):
    """Every column of every row of `e`'s snapshot tables against a
    naive per-return reconstruction (the straightforward O(R*C)
    formulation): replay the events, snapshot before each return."""
    import heapq as hq
    packed = [e.spec.encode_call(c.f, c.value, c.result, c.crashed)
              for c in e.calls]
    events = []
    for c in e.calls:
        events.append((c.invoke_index, 0, c.index))
        if not c.crashed:
            events.append((c.complete_index, 1, c.index))
    events.sort()
    free, n_slots, slot_of, occupant = [], 0, {}, {}
    r = 0
    for _, kind, cid in events:
        if kind == 0:
            s = hq.heappop(free) if free else n_slots
            if s == n_slots:
                n_slots += 1
            slot_of[cid] = s
            occupant[s] = cid
        else:
            for s in range(e.slot_f.shape[1]):
                if s in occupant:
                    pk = packed[occupant[s]]
                    assert e.slot_occ[r, s], (r, s)
                    assert e.slot_f[r, s] == pk[0]
                    assert e.slot_a0[r, s] == pk[1]
                    assert e.slot_a1[r, s] == pk[2]
                    assert e.slot_wild[r, s] == pk[3]
                else:
                    assert not e.slot_occ[r, s], (r, s)
                    assert e.slot_f[r, s] == -1
                    assert e.slot_a0[r, s] == -1
                    assert e.slot_a1[r, s] == -1
                    assert not e.slot_wild[r, s]
            assert e.ev_slot[r] == slot_of[cid]
            assert e.ret_call[r] == cid
            r += 1
            s = slot_of[cid]
            del occupant[s]
            hq.heappush(free, s)
    assert r == e.n_returns
    assert n_slots == e.n_slots
    for a in (e.slot_f, e.slot_a0, e.slot_a1, e.slot_wild, e.slot_occ):
        assert a.shape == (e.n_returns, e.slot_f.shape[1])
        assert a.flags["C_CONTIGUOUS"]
    assert e.slot_f.dtype == e.slot_a0.dtype == np.int32
    assert e.slot_wild.dtype == e.slot_occ.dtype == bool


def test_encode_snapshot_interval_fill_matches_naive_oracle():
    """encode()'s snapshot tables vs a naive per-return reconstruction:
    every column of every row must match, including slot reuse after
    returns and crashed calls holding slots to the end."""
    from jepsen_tpu.histories import (adversarial_register_history,
                                      rand_fifo_history,
                                      rand_register_history)
    from jepsen_tpu.models import CASRegister, FIFOQueue

    cases = [(CASRegister(), rand_register_history(
                 n_ops=150, n_processes=8, n_values=4, crash_p=0.05,
                 fail_p=0.08, seed=s)) for s in range(4)]
    cases += [(CASRegister(), adversarial_register_history(
                  n_ops=80, k_crashed=9, seed=1))]
    cases += [(FIFOQueue(), rand_fifo_history(
                  n_ops=40, n_processes=5, n_values=3, crash_p=0.1,
                  seed=2))]
    for model, h in cases:
        _assert_snapshots_match_naive_oracle(enc_mod.encode(model, h))


def _mutex_history(seed, n=80):
    import random
    from jepsen_tpu.history import op
    rng = random.Random(seed)
    ops = []
    for _ in range(n):
        p, f = rng.randrange(4), rng.choice(["acquire", "release"])
        ops.append(invoke_op(p, f, None))
        ops.append(op(rng.choice(["ok", "ok", "fail", "info"]), p, f, None))
    return _h(*ops)


def _snapshot_case(name):
    from jepsen_tpu.histories import (adversarial_register_history,
                                      rand_fifo_history, rand_gset_history,
                                      rand_queue_history)
    from jepsen_tpu.models import FIFOQueue, GSet
    if name == "invoked-after-last-return":
        # p1's and p2's writes start after the last return and never
        # complete, so they sit in no snapshot row
        return CASRegister(), _h(
            invoke_op(0, "write", 1), ok_op(0, "write", 1),
            invoke_op(1, "write", 2), invoke_op(2, "write", 3),
            info_op(1, "write", 2))
    if name == "slot-retaken-between-adjacent-rows":
        # p0 frees slot 0 at row 0; p2 takes it before row 1
        return CASRegister(), _h(
            invoke_op(0, "write", 1), invoke_op(1, "write", 2),
            ok_op(0, "write", 1), invoke_op(2, "write", 3),
            ok_op(1, "write", 2), ok_op(2, "write", 3))
    if name == "adversarial-2000-k12":
        return CASRegister(), adversarial_register_history(
            n_ops=2000, k_crashed=12, seed=3)
    if name == "register":
        return Register(), rand_register_history(
            n_ops=150, n_processes=6, n_values=3, cas=False,
            crash_p=0.05, fail_p=0.05, seed=5)
    if name == "cas-register":
        return CASRegister(), rand_register_history(
            n_ops=150, n_processes=10, n_values=5, crash_p=0.02,
            fail_p=0.05, busy=0.8, seed=6)
    if name == "mutex":
        return Mutex(), _mutex_history(7)
    if name == "gset":
        return GSet(), rand_gset_history(
            n_ops=150, n_processes=6, n_elements=12, crash_p=0.05, seed=8)
    if name == "unordered-queue":
        return UnorderedQueue(), rand_queue_history(
            n_ops=150, n_processes=6, n_values=4, crash_p=0.05, seed=9)
    if name == "fifo-queue":
        return FIFOQueue(), rand_fifo_history(
            n_ops=40, n_processes=5, n_values=3, crash_p=0.1, seed=10)
    raise ValueError(name)


@pytest.mark.parametrize("pad", [None, 40])
@pytest.mark.parametrize("name", [
    "invoked-after-last-return", "slot-retaken-between-adjacent-rows",
    "adversarial-2000-k12", "register", "cas-register", "mutex", "gset",
    "unordered-queue", "fifo-queue"])
def test_encode_snapshot_fill_cases_match_naive_oracle(name, pad):
    """The same oracle over the owner table's edge cases — a call
    invoked after the last return, a slot freed and re-taken between
    two adjacent return rows, a long crashed-writes key, one history of
    each model family — at the natural width and padded wider than
    n_slots (columns past n_slots stay empty)."""
    model, h = _snapshot_case(name)
    e = enc_mod.encode(model, h, pad_slots=pad)
    if pad is not None:
        assert e.slot_f.shape[1] == max(pad, e.n_slots) > e.n_slots
    _assert_snapshots_match_naive_oracle(e)
    prep = enc_mod.prepare_encode(model, h)
    assert engine.history_digest(enc_mod.finish_encode(prep, pad)) == \
        engine.history_digest(e)
    if name == "invoked-after-last-return":
        assert e.n_calls == 3 and e.n_returns == 1
        assert e.slot_occ.sum() == 1
    if name == "slot-retaken-between-adjacent-rows":
        w3 = e.spec.encode_call("write", 3, None, False)
        assert list(e.ev_slot) == [0, 1, 0]
        assert e.slot_occ[1, 0] and e.slot_f[1, 0] == w3[0]
        assert e.slot_a0[1, 0] == w3[1]
    if name == "adversarial-2000-k12":
        assert e.n_slots == 13


# engine.history_digest of encode() on rand_register_history keys at the
# benchmark's shape (10 processes, 120 ops, 5 values), pinned: the
# encode's arrays must not move
_BENCH_SHAPE_DIGESTS = {
    2500: "00bc6f4f63d9aa74", 2501: "416ab46f117479ad",
    2502: "3eb5fcc364ac903d", 2503: "6b60a9d1df12676d",
    2504: "57d2c7824cdc95e1", 2505: "8cba1bfc6736cb6b",
    2506: "2fc21283dd2c7b10", 2507: "4adb882da7cf1506"}


@pytest.mark.parametrize("seed", sorted(_BENCH_SHAPE_DIGESTS))
def test_encode_digest_pinned_at_benchmark_shape(seed):
    h = rand_register_history(n_ops=120, n_processes=10, n_values=5,
                              crash_p=0.005, fail_p=0.05, busy=0.8,
                              seed=seed)
    e = enc_mod.encode(CASRegister(), h)
    assert engine.history_digest(e) == _BENCH_SHAPE_DIGESTS[seed]


@pytest.mark.parametrize("shape", [(60, 4), (300, 8), "crashy"])
def test_bitdense_frontier_rows_equal_the_sparse_frontier(shape):
    """Counterexample extraction seeds its window search from the
    bitdense reachable-set tensor when the history fits that engine
    (one scan, no capacity escalation). The rows must be exactly the
    sparse engine's live frontier at every cut."""
    from jepsen_tpu.histories import (adversarial_register_history,
                                      with_impossible_read)
    from jepsen_tpu.parallel import bitdense
    if shape == "crashy":
        h = rand_register_history(n_ops=200, n_processes=6, n_values=3,
                                  crash_p=0.05, seed=3)
    else:
        n, k = shape
        h = with_impossible_read(adversarial_register_history(
            n_ops=n, k_crashed=k, seed=7))
    e = enc_mod.encode(CASRegister(), h)
    for ev in (5, e.n_returns // 2, e.n_returns - 3):
        rows = bitdense.frontier_rows(e, ev)
        assert len(rows) == len(set(rows))
        assert set(rows) == set(engine._frontier_at(e, ev)), ev
