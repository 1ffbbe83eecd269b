import numpy as np
import pytest

from jepsen_tpu.history import (
    Columns, History, Op, calls, invoke_op, ok_op, fail_op, info_op,
)


def _h(*ops):
    return History.wrap(ops).index()


def test_op_attr_access():
    o = Op(type="invoke", process=0, f="read", value=None)
    assert o.type == "invoke"
    assert o.f == "read"
    assert o.value is None
    assert o.is_invoke
    o.value = 3
    assert o["value"] == 3


def test_index_and_pairs():
    h = _h(
        invoke_op(0, "write", 1),
        invoke_op(1, "read", None),
        ok_op(0, "write", 1),
        ok_op(1, "read", 1),
    )
    h.pairs()
    assert h[0]["pair-index"] == 2
    assert h[2]["pair-index"] == 0
    assert h[1]["pair-index"] == 3


def test_complete_fills_read_values():
    h = _h(
        invoke_op(0, "read", None),
        ok_op(0, "read", 7),
    ).complete()
    assert h[0]["value"] == 7


def test_calls_pairing():
    h = _h(
        invoke_op(0, "write", 1),
        invoke_op(1, "read", None),
        ok_op(0, "write", 1),
        info_op(1, "read", None),       # crashed
        invoke_op(2, "cas", [1, 2]),
        fail_op(2, "cas", [1, 2]),      # failed: dropped
    )
    cs = calls(h)
    assert len(cs) == 2
    w, r = cs
    assert w.f == "write" and not w.crashed and w.complete_index == 2
    assert r.f == "read" and r.crashed and r.complete_index == len(h)


def test_edn_round_trip():
    h = _h(
        invoke_op(0, "write", 1, time=10),
        ok_op(0, "write", 1, time=20),
        info_op("nemesis", "start", None, time=30),
    )
    text = h.to_edn()
    h2 = History.from_edn(text)
    assert len(h2) == 3
    assert h2[0]["type"] == "invoke"
    assert h2[0]["process"] == 0
    assert h2[2]["process"] == "nemesis"


def test_columns():
    h = _h(
        invoke_op(0, "write", 5, time=1),
        ok_op(0, "write", 5, time=2),
        invoke_op("nemesis", "start", None, time=3),
    )
    c = Columns.from_history(h)
    assert len(c) == 3
    assert c.process[2] == -2
    assert c.type[0] == 0 and c.type[1] == 1
    assert c.f_table.value(c.f[0]) == "write"
    assert c.value_table.value(c.value[0]) == 5
    assert c.value[2] == -1
    assert c.index.dtype == np.int64


def test_calls_keep_failed():
    h = _h(
        invoke_op(0, "write", 1),
        fail_op(0, "write", 1),
    )
    assert calls(h) == []
    kept = calls(h, drop_failed=False)
    assert len(kept) == 1 and kept[0].complete_index == 1


def _calls_per_op_oracle(history, drop_failed=True):
    """The per-op pairing rule, one dict of open invokes by process:
    the straightforward formulation `calls` must reproduce."""
    from jepsen_tpu.history import Call
    n = len(history)
    open_by_process, out, failed = {}, [], set()
    for i, o in enumerate(history):
        p = o.get("process")
        if not isinstance(p, int):
            continue
        if o.is_invoke:
            c = Call(index=-1, process=p, f=o.get("f"),
                     value=o.get("value"), result=None, invoke_index=i,
                     complete_index=n, crashed=True)
            open_by_process[p] = c
            out.append(c)
            continue
        c = open_by_process.pop(p, None)
        if c is None:
            continue
        if o.is_ok:
            c.result, c.complete_index, c.crashed = o.get("value"), i, False
        elif o.is_fail:
            c.complete_index, c.crashed = i, False
            failed.add(id(c))
    if drop_failed:
        out = [c for c in out if id(c) not in failed]
    for j, c in enumerate(out):
        c.index = j
    return out


def _messy_history(seed, n=300):
    """Seeded client traffic with :fail and :info completions, nemesis
    ops, a non-int process, stray completions, and processes that invoke
    again without completing."""
    import random
    rng = random.Random(seed)
    ops, busy = [], {}
    for _ in range(n):
        r = rng.random()
        if r < 0.05:
            ops.append(Op(type=rng.choice(["invoke", "info"]),
                          process="nemesis", f="start", value=None))
            continue
        if r < 0.08:
            ops.append(Op(type=rng.choice(["invoke", "ok"]),
                          process=rng.choice(["p9", 2.0, None]),
                          f="read", value=None))
            continue
        p = rng.randrange(6)
        if p in busy and rng.random() < 0.85:
            f, v = busy.pop(p)
            t = rng.choices(["ok", "fail", "info"], [6, 2, 1])[0]
            ops.append(Op(type=t, process=p, f=f,
                          value=rng.randrange(4) if f == "read" else v))
        elif rng.random() < 0.05:
            ops.append(Op(type="ok", process=p, f="read", value=1))
        else:  # a fresh invoke, even if one is still open
            f = rng.choice(["read", "write", "cas"])
            v = (None if f == "read" else rng.randrange(4) if f == "write"
                 else [rng.randrange(4), rng.randrange(4)])
            busy[p] = (f, v)
            ops.append(Op(type="invoke", process=p, f=f, value=v))
    return History.wrap(ops).index()


@pytest.mark.parametrize("drop_failed", [True, False])
@pytest.mark.parametrize("seed", [0, 1, 2, 3])
def test_calls_matches_per_op_pairing_oracle(seed, drop_failed):
    h = _messy_history(seed)
    got = calls(h, drop_failed=drop_failed)
    want = _calls_per_op_oracle(h, drop_failed=drop_failed)
    assert [vars(c) for c in got] == [vars(c) for c in want]
    for c in got:  # plain Python ints, as the oracle builds them
        assert type(c.index) is int and type(c.invoke_index) is int
        assert type(c.complete_index) is int and type(c.crashed) is bool
    kinds = {(c.crashed, c.result is None) for c in got}
    assert (True, True) in kinds and (False, False) in kinds


def test_calls_edge_histories():
    assert calls(History()) == []
    assert calls(_h(info_op("nemesis", "start", None))) == []
    h = _h(invoke_op(0, "write", 1), invoke_op(0, "write", 2),
           ok_op(0, "write", 2), ok_op(0, "write", 3))
    got = calls(h)
    assert [vars(c) for c in got] == [vars(c) for c in
                                      _calls_per_op_oracle(h)]
    assert got[0].crashed and got[0].complete_index == 4
    assert not got[1].crashed and got[1].complete_index == 2


# ---------------------------------------------------- npz sidecar

def test_npz_roundtrip_exact_plain():
    """Typical checker history: reconstructs fully from columns (zero
    override lines) and round-trips exactly."""
    import numpy as np
    from jepsen_tpu.histories import rand_register_history

    h = rand_register_history(n_ops=300, n_processes=5, crash_p=0.02,
                              fail_p=0.05, seed=4)
    import tempfile, os
    with tempfile.TemporaryDirectory() as d:
        p = h.save_npz(os.path.join(d, "history"))
        assert p.endswith(".npz")
        z = np.load(p, allow_pickle=False)
        # only ops the columns can't express (here: crashed ops with an
        # "error" key) may need an override line; the bulk must be
        # purely columnar
        n_err = sum(1 for o in h if set(o) - {
            "index", "time", "process", "type", "f", "value"})
        assert len(z["override_idx"]) == n_err < len(h) // 10
        h2 = History.load_npz(p)
    assert len(h2) == len(h)
    assert [dict(a) for a in h2] == [dict(b) for b in h]


def test_npz_roundtrip_exact_weird_ops():
    """Ops the columns cannot express — extra keys, non-int non-nemesis
    process, unknown type, tuple values — ride as EDN overrides and
    still round-trip exactly."""
    from jepsen_tpu.history import NEMESIS

    h = History.wrap([
        {"index": 0, "time": 3, "process": 0, "type": "invoke",
         "f": "write", "value": 3},
        {"index": 1, "time": 4, "process": 0, "type": "ok",
         "f": "write", "value": 3, "node": "n1", "error": ["timed-out"]},
        {"index": 2, "process": NEMESIS, "type": "info",
         "f": "start-partition", "value": ["n1", "n2"]},
        {"index": 3, "process": 1, "type": "invoke", "f": "cas",
         "value": [1, 2]},
        {"index": 4, "process": 1, "type": "fail", "f": "cas",
         "value": [1, 2]},
        {"index": 5, "process": 2, "type": "invoke", "f": "read"},
    ])
    import tempfile, os
    with tempfile.TemporaryDirectory() as d:
        p = h.save_npz(os.path.join(d, "history.npz"))
        h2 = History.load_npz(p)
    assert [dict(a) for a in h2] == [dict(b) for b in h]


def test_store_writes_and_prefers_npz(tmp_path, monkeypatch):
    """save_1 writes the sidecar next to history.edn; load_run prefers
    it (the EDN is not even parsed), falling back loudly when the
    sidecar is corrupt."""
    from jepsen_tpu import store as store_mod
    from jepsen_tpu.histories import rand_register_history
    import os

    h = rand_register_history(n_ops=50, n_processes=3, crash_p=0.01,
                              fail_p=0.05, seed=8)
    st = store_mod.Store("npz-test", base_dir=str(tmp_path))
    st.save_1({"name": "npz-test"}, h)
    assert os.path.exists(st.path("history.npz"))

    # poison the EDN: a parse would now blow up, proving npz is used
    # (bump the sidecar's mtime past the rewrite so it is not treated
    # as stale)
    with open(st.path("history.edn"), "w") as fh:
        fh.write("{:broken")
    os.utime(st.path("history.npz"))
    run = store_mod.load_run(st.dir)
    assert [dict(a) for a in run["history"]] == [dict(b) for b in h]

    # a history.edn rewritten AFTER the sidecar (hand-corrected replay)
    # must win: the stale sidecar is skipped, loudly
    import time as _t
    h_fixed = rand_register_history(n_ops=20, n_processes=3,
                                    crash_p=0.0, fail_p=0.0, seed=99)
    _t.sleep(0.02)
    h_fixed.save(st.path("history.edn"))
    run = store_mod.load_run(st.dir)
    assert [dict(a) for a in run["history"]] == [dict(b) for b in h_fixed]

    # corrupt sidecar: loud fallback to EDN (restore it first)
    h.save(st.path("history.edn"))
    with open(st.path("history.npz"), "wb") as fh:
        fh.write(b"not-an-npz")
    run = store_mod.load_run(st.dir)
    assert [dict(a) for a in run["history"]] == [dict(b) for b in h]
