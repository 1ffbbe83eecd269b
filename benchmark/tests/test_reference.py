"""The plain reference on hand-made histories, and against the
program's host engine on generated ones."""

import pytest

import generators
import named
from conftest import ROOT

reference = named.load(ROOT, "references", "cas-register")


def op(typ, p, f, v, **kw):
    return {"type": typ, "process": p, "f": f, "value": v, **kw}


def test_sequential_valid_and_invalid():
    ok = [op("invoke", 0, "write", 1), op("ok", 0, "write", 1),
          op("invoke", 0, "read", None), op("ok", 0, "read", 1)]
    assert reference.check(ok) == {"valid?": True}
    bad = ok + [op("invoke", 1, "read", None), op("ok", 1, "read", 2)]
    r = reference.check(bad)
    assert r == {"valid?": False, "fail-event": 2,
                 "op": {"process": 1, "f": "read", "value": 2, "index": 4}}


def test_concurrent_write_may_go_either_side_of_a_read():
    h = [op("invoke", 0, "write", 1), op("invoke", 1, "read", None),
         op("ok", 1, "read", 1), op("ok", 0, "write", 1)]
    assert reference.check(h)["valid?"] is True


def test_crashed_write_may_take_effect_later():
    h = [op("invoke", 0, "write", 7), op("info", 0, "write", 7),
         op("invoke", 1, "write", 1), op("ok", 1, "write", 1),
         op("invoke", 1, "read", None), op("ok", 1, "read", 7)]
    assert reference.check(h)["valid?"] is True
    # the control breaks the crash rule and calls the same history invalid
    r = reference.check(h, crashed_dropped=True)
    assert r["valid?"] is False and r["op"]["value"] == 7


def test_failed_ops_never_happened():
    h = [op("invoke", 0, "write", 3), op("fail", 0, "write", 3),
         op("invoke", 1, "read", None), op("ok", 1, "read", 3)]
    assert reference.check(h)["valid?"] is False


def test_cas():
    h = [op("invoke", 0, "write", 1), op("ok", 0, "write", 1),
         op("invoke", 0, "cas", [1, 2]), op("ok", 0, "cas", [1, 2]),
         op("invoke", 0, "cas", [1, 3]), op("ok", 0, "cas", [1, 3])]
    r = reference.check(h)
    assert r["valid?"] is False and r["fail-event"] == 2
    assert r["op"] == {"process": 0, "f": "cas", "value": [1, 3],
                       "index": 4}


@pytest.mark.parametrize("seed", range(6))
def test_agrees_with_the_programs_host_engine(seed):
    from jepsen_tpu.checker import linear_packed
    from jepsen_tpu.history import History
    from jepsen_tpu.models import CASRegister
    ops = generators.rand_register_history(
        n_ops=60, n_processes=6, crash_p=0.05, seed=seed, readers=3)
    for h in (ops, generators.corrupt_history(ops, seed=seed)):
        want = linear_packed.analysis(CASRegister(),
                                      History.wrap(h).index())
        got = reference.check(h)
        assert got["valid?"] == want["valid?"]
        if got["valid?"] is False:
            assert got["fail-event"] == want["fail-event"]
            assert got["op"]["index"] == want["op"]["index"]
