"""Per-key independence tests (reference:
jepsen/test/jepsen/independent_test.clj + generator_test.clj:386-454)."""

import pytest

import jepsen_tpu.generator as gen
from jepsen_tpu import independent
from jepsen_tpu.checker import linearizable
from jepsen_tpu.checker.core import FnChecker
from jepsen_tpu.generator.testing import default_context, perfect, quick
from jepsen_tpu.history import History, invoke_op, ok_op
from jepsen_tpu.independent import KV
from jepsen_tpu.models import CASRegister


def test_ktuple():
    t = KV("x", 5)
    assert t.key == "x" and t.value == 5
    assert tuple(t) == ("x", 5)
    assert independent.is_tuple(t)
    assert not independent.is_tuple(("x", 5))


def test_sequential_generator():
    g = independent.sequential_generator(
        [0, 1], lambda k: gen.limit(2, lambda: {"f": "read", "value": None}))
    h = quick(g)
    vals = [o["value"] for o in h]
    assert vals == [KV(0, None), KV(0, None), KV(1, None), KV(1, None)]


def test_concurrent_generator_covers_keys_in_order():
    ctx = default_context(4)  # 4 workers -> 2 groups of 2
    g = independent.concurrent_generator(
        2, ["a", "b", "c", "d"],
        lambda k: gen.limit(3, lambda: {"f": "w", "value": 1}))
    h = perfect(g, ctx)
    keys = [o["value"].key for o in h]
    assert len(h) == 12  # 4 keys x 3 ops
    # first two keys are worked concurrently by distinct groups
    first_half = set(keys[:6])
    assert first_half == {"a", "b"}
    # threads stay within their group per key
    by_key = {}
    for o in h:
        by_key.setdefault(o["value"].key, set()).add(o["process"] % 4)
    for k, procs in by_key.items():
        assert procs <= {0, 1} or procs <= {2, 3}, (k, procs)


def test_history_keys_and_subhistory():
    h = History.wrap([
        invoke_op(0, "write", KV("x", 1)),
        invoke_op("nemesis", "kill", None),
        ok_op(0, "write", KV("x", 1)),
        invoke_op(1, "read", KV("y", None)),
        ok_op(1, "read", KV("y", 7)),
    ])
    assert independent.history_keys(h) == ["x", "y"]
    hx = independent.subhistory("x", h)
    assert [o.get("f") for o in hx] == ["write", "kill", "write"]
    assert hx[0]["value"] == 1  # unwrapped
    hy = independent.subhistory("y", h)
    assert [o.get("value") for o in hy if o["f"] == "read"] == [None, 7]


def test_kv_history_reinterprets_vectors():
    h = History.wrap([invoke_op(0, "w", [3, 9]), ok_op(0, "w", [3, 9])])
    h2 = independent.kv_history(h)
    assert independent.history_keys(h2) == [3]


def _keyed_register_history():
    """Two keys: x linearizable, y not (read 5 never written)."""
    ops = [
        invoke_op(0, "write", KV("x", 1)), ok_op(0, "write", KV("x", 1)),
        invoke_op(0, "read", KV("x", None)), ok_op(0, "read", KV("x", 1)),
        invoke_op(1, "write", KV("y", 2)), ok_op(1, "write", KV("y", 2)),
        invoke_op(1, "read", KV("y", None)), ok_op(1, "read", KV("y", 5)),
    ]
    return History.wrap(ops).index()


def test_independent_checker_host():
    c = independent.checker(linearizable(CASRegister(), algorithm="wgl"))
    r = c.check({}, _keyed_register_history())
    assert r["valid?"] is False
    assert r["results"]["x"]["valid?"] is True
    assert r["results"]["y"]["valid?"] is False
    assert r["failures"] == ["y"]


def test_independent_checker_device_batch():
    c = independent.checker(linearizable(CASRegister(), algorithm="jax"))
    r = c.check({}, _keyed_register_history())
    assert r["valid?"] is False
    assert r["failures"] == ["y"]
    assert r["results"]["x"]["analyzer"] == "jax"


def test_independent_checker_device_batch_with_mesh():
    """test["mesh"] shards the per-key batch over the device mesh (the
    dp axis) and arms the sharded-escalation path for overflow keys."""
    import jax
    import numpy as np
    from jax.sharding import Mesh

    mesh = Mesh(np.array(jax.devices()[:8]), ("keys",))
    c = independent.checker(linearizable(CASRegister(), algorithm="jax"))
    r = c.check({"mesh": mesh}, _keyed_register_history())
    assert r["valid?"] is False
    assert r["failures"] == ["y"]
    assert r["results"]["x"]["analyzer"] == "jax"

    # a Mesh on the test map must be stripped before serialization
    from jepsen_tpu.store import serializable_test
    assert "mesh" not in serializable_test({"mesh": mesh, "name": "t"})


def test_device_batch_dispatch_failure_is_loud(monkeypatch, caplog):
    """A device that fails while it runs (here: a watchdog wedge) must
    not silently degrade to the host checker: the result carries a
    device-fallback tag and a warning is logged (the host still
    produces correct per-key results)."""
    import logging

    from jepsen_tpu.parallel import engine
    from jepsen_tpu.resilience import supervisor as sup

    def wedged(*a, **k):
        raise sup.DispatchWedged("dispatch", 1.0, "cpu")

    monkeypatch.setattr(engine, "check_batch", wedged)
    c = independent.checker(linearizable(CASRegister(), algorithm="jax"))
    with caplog.at_level(logging.WARNING, logger="jepsen_tpu.independent"):
        r = c.check({}, _keyed_register_history())
    assert r["valid?"] is False          # host path still checked keys
    assert r["failures"] == ["y"]
    assert "watchdog bound" in r["device-fallback"]
    assert r["resilience"]["class"] == "wedged"
    assert any("FAILED" in rec.message for rec in caplog.records)


def test_device_batch_error_propagates(monkeypatch):
    """Any other device-batch error (a kernel the compiler refuses, a
    bug) is raised, not answered from the host."""
    from jepsen_tpu.parallel import engine

    def boom(*a, **k):
        raise NotImplementedError("Mosaic kernels cannot be "
                                  "automatically partitioned")

    monkeypatch.setattr(engine, "check_batch", boom)
    c = independent.checker(linearizable(CASRegister(), algorithm="jax"))
    with pytest.raises(NotImplementedError, match="Mosaic"):
        c.check({}, _keyed_register_history())


def test_device_batch_not_applicable_is_quiet():
    """A host-only checker never gets the fallback tag — 'not
    applicable' is not a failure."""
    c = independent.checker(linearizable(CASRegister(), algorithm="wgl"))
    r = c.check({}, _keyed_register_history())
    assert r["valid?"] is False
    assert "device-fallback" not in r


def test_independent_checker_plain_fn():
    seen = []

    def f(test, history, opts):
        seen.append(opts.get("history-key"))
        return {"valid?": True, "n": len(history)}

    c = independent.checker(FnChecker(f))
    r = c.check({}, _keyed_register_history())
    assert r["valid?"] is True
    assert sorted(seen) == ["x", "y"]


# ------------------------------------------------- span tree of a check


@pytest.fixture
def traced():
    from jepsen_tpu import obs
    tr = obs.configure(True)
    yield tr
    obs.configure(False)
    obs.reset()


def _tree(spans):
    """{name: [parent name, ...]} over the host spans (the collector's
    python.gc spans, on their own track, left out)."""
    by_id = {s.sid: s for s in spans}
    out = {}
    for s in spans:
        if s.track is None:
            p = by_id.get(s.parent)
            out.setdefault(s.name, []).append(p.name if p else None)
    return out


def test_keyed_device_check_span_tree(traced):
    c = independent.checker(linearizable(CASRegister(), algorithm="jax"))
    c.check({}, _keyed_register_history())
    traced.drain()              # the first check compiles
    r = c.check({}, _keyed_register_history())
    assert r["failures"] == ["y"]
    tree = _tree(traced.drain())
    assert tree["independent.check"] == [None]
    assert tree["independent.split"] == ["independent.check"]
    assert tree["independent.assemble"] == ["independent.check"]
    assert tree["independent.device_batch"] == ["independent.check"]
    assert tree["transfer.place"] == ["bitdense.pad_place"]
    assert tree["bitdense.fetch"] == ["bitdense.finalize"]
    assert tree["bitdense.issue"] == ["engine.check_batch"]
    assert tree["bitdense.results"] == ["engine.check_batch"]


def test_host_per_key_check_roots_each_key_in_linearizable_check(traced):
    c = independent.checker(linearizable(CASRegister(), algorithm="wgl"))
    c.check({}, _keyed_register_history())
    tree = _tree(traced.drain())
    assert tree["independent.key"] == ["independent.check"] * 2
    assert tree["linearizable.check"] == ["independent.key"] * 2
    assert tree["independent.assemble"] == ["independent.check"]


def test_spans_read_by_the_benchmark_keep_their_count_and_children(
        traced):
    """The benchmark reads engine.encode_batch by its self time and
    bitdense.pad_place / .finalize by their total: once each per keyed
    check, and the encode span gains no child that would take time out
    of it."""
    c = independent.checker(linearizable(CASRegister(), algorithm="jax"))
    c.check({}, _keyed_register_history())
    traced.drain()
    for _ in range(3):
        c.check({}, _keyed_register_history())
    spans = traced.drain()
    names = [s.name for s in spans]
    for name in ("engine.encode_batch", "bitdense.pad_place",
                 "bitdense.finalize", "independent.device_batch"):
        assert names.count(name) == 3, name
    encode = {s.sid for s in spans if s.name.startswith("engine.encode")}
    assert not [s.name for s in spans if s.parent in encode]
