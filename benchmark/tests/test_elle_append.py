"""The list-append deployment (`elle-append.16k-txns`) on the CPU: the
cell end to end at a tiny size, the plain reference on hand-made
histories, the program against the reference on seeded pools, the
control, the readers of the new spans and counters, and the pool's
shapes at full size."""

import time

import pytest

import compare
import control
import harness
import named
import traffic
from conftest import ROOT

CELL = "elle-append.16k-txns"
reference = named.load(ROOT, "references", "elle-append")
entry = named.load(ROOT, "entries", "elle_append")


def shrink(cfg, mix):
    """1,200 transactions a history."""
    cfg["txns"] = 1200
    return cfg, mix


@pytest.fixture(autouse=True)
def device_engine(monkeypatch):
    """The device SCC engine at the test sizes too (1,200 transactions
    pad to the 2,048 bucket), so a run's path is the cell's."""
    from jepsen_tpu import elle
    monkeypatch.setattr(elle, "_DEVICE_SCC_MIN_NODES", 0)
    return monkeypatch


def run(trace, seed=2**40 + 3):
    return harness.run_cell(ROOT, CELL, seed, 1.0, trace,
                            time.perf_counter(), require_accelerator=False,
                            shrink=shrink)


@pytest.mark.parametrize("trace", [False, True])
def test_cell_runs_end_to_end(trace):
    out = run(trace)
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] >= 1
    assert out["closures"] == ["scc:device"]
    assert out["compared"]["off_device_keys"]["value"] == 0
    spec = harness.load_spec(ROOT)
    want = {m["name"] for m in harness.cell_metrics(spec, CELL, trace)}
    # the CPU has no device plane: the device-trace metrics that find
    # nothing there are left out
    missing = {"scc_closure_roofline_share"} if trace else set()
    assert set(out["metrics"]) == want - missing
    if trace:
        assert out["metrics"]["compiles_in_window"]["value"] == 0
        assert out["metrics"]["elle_graph_share"]["value"] > 0
        assert out["metrics"]["elle_scc_device_share"]["value"] > 0


# ------------------------------------------------ hand-made histories


def txn(p, mops, done=None, typ="ok"):
    """An invocation and its completion; reads are None in the
    invocation and `done`'s values in the completion."""
    inv = [[f, k, None if f == "r" else v] for f, k, v in mops]
    return [{"type": "invoke", "process": p, "f": "txn", "value": inv},
            {"type": typ, "process": p, "f": "txn",
             "value": mops if typ == "ok" else inv}]


def concurrent(a, b):
    """Two calls whose intervals overlap: a invoked, b invoked, a
    completes, b completes."""
    return [a[0], b[0], a[1], b[1]]


HISTORIES = {
    "G0": (txn(0, [["append", "x", 1], ["append", "y", 2]])
           + txn(1, [["append", "y", 1], ["append", "x", 2]])
           + txn(2, [["r", "x", [1, 2]], ["r", "y", [1, 2]]]),
           {"G0": 1}),
    "G1a": (txn(0, [["append", "x", 1]], typ="fail")
            + txn(1, [["r", "x", [1]]]), {"G1a": 1}),
    # the read of 1 also misses T0's 2: T1 -rw-> T0 -wr-> T1
    "G1b": (txn(0, [["append", "x", 1], ["append", "x", 2]])
            + txn(1, [["r", "x", [1]]]), {"G1b": 1, "G-single": 1}),
    "G1c": (concurrent(txn(0, [["append", "x", 1], ["r", "y", [1]]]),
                       txn(1, [["append", "y", 1], ["r", "x", [1]]])),
            {"G1c": 1}),
    # a read invoked after an append completed misses it: rw then rt
    "G-single": (txn(0, [["append", "x", 1]]) + txn(1, [["r", "x", []]]),
                 {"G-single": 1}),
    "G2": (concurrent(txn(0, [["r", "x", []], ["append", "y", 1]]),
                      txn(1, [["append", "x", 1], ["r", "y", []]])),
           {"G2": 1}),
    "internal": (txn(0, [["append", "x", 1], ["r", "x", [5, 9]]]),
                 {"internal": 1}),
    "incompatible-order": (txn(0, [["r", "x", [1, 2]]])
                           + txn(1, [["r", "x", [2, 1]]]),
                           {"incompatible-order": 1}),
    "info-read": (txn(0, [["append", "x", 1]], typ="info")
                  + txn(1, [["r", "x", [1]]]), {}),
    "valid": (txn(0, [["append", "x", 1], ["r", "x", [1]]])
              + txn(1, [["append", "x", 2], ["r", "x", [1, 2]]]), {}),
}


def ops_of(h):
    return [dict(o, time=i) for i, o in enumerate(h)]


@pytest.mark.parametrize("name", sorted(HISTORIES))
def test_reference_answers_hand_made_histories(name):
    h, counts = HISTORIES[name]
    a = reference.check(ops_of(h))
    assert a == {"valid?": not counts, "anomaly-types": sorted(counts),
                 "counts": counts, "cycles_hold": True}


@pytest.mark.parametrize("engine", ["device", "host"])
@pytest.mark.parametrize("name", sorted(HISTORIES))
def test_program_agrees_on_hand_made_histories(name, engine, device_engine):
    from jepsen_tpu import elle
    if engine == "host":
        device_engine.setattr(elle, "_DEVICE_SCC_MIN_NODES", 10 ** 9)
    cfg = traffic.load(ROOT, "configs", "elle-append")
    e = [(None, ops_of(HISTORIES[name][0]))]
    r = entry.check(entry.checker(cfg), entry.wrap(e))
    assert entry.answers(r, e) == compare.reference_answers(reference, e)


def test_the_control_drops_crashed_appends():
    h, _ = HISTORIES["info-read"]
    assert reference.check(ops_of(h), crashed_dropped=True)["counts"] == {
        "G1a": 1}


def test_a_cycle_that_does_not_hold_is_caught():
    def holds(r, e):
        return entry.answers(r, e)["keys"][None]["cycles_hold"]

    cfg = traffic.load(ROOT, "configs", "elle-append")
    e = [(None, ops_of(HISTORIES["G0"][0]))]
    r = entry.check(entry.checker(cfg), entry.wrap(e))
    assert holds(r, e) is True
    r["anomalies"]["G0"][0]["cycle"] = [0, 2, 0]     # T2 precedes nothing
    assert holds(r, e) is False
    e = [(None, ops_of(HISTORIES["G-single"][0]))]
    r = entry.check(entry.checker(cfg), entry.wrap(e))
    assert holds(r, e) is True
    r["anomalies"]["G0"] = r["anomalies"].pop("G-single")   # has an rw edge
    assert holds(r, e) is False


# ------------------------------------------------------ seeded pools


@pytest.mark.parametrize("seed", [5, 2**33 + 1, 2**41 + 7])
def test_program_agrees_with_the_reference_on_seeded_pools(seed):
    cfg, mix = shrink(traffic.load(ROOT, "configs", "elle-append"),
                      traffic.load(ROOT, "traffic", "16k-txns"))
    pool = traffic.pool(ROOT, cfg, mix, seed)
    built = entry.checker(cfg)
    refs = [compare.reference_answers(reference, e) for e in pool]
    got = [(i, entry.answers(entry.check(built, entry.wrap(e)), e))
           for i, e in enumerate(pool)]
    assert compare.correct(compare.compare(got, refs))
    kinds = [tuple(r["keys"][0]["anomaly-types"]) for r in refs]
    assert kinds.count(("G-single",)) == 1 and kinds.count(()) == 3


@pytest.mark.parametrize("seed", [11, 2**32 + 7, 2**40 + 1])
def test_control_is_not_correct(seed):
    numbers = control.control_numbers(ROOT, CELL, seed, shrink=shrink)
    assert numbers["keys_wrong"]["value"] > 0
    assert numbers["checks_wrong"]["value"] > 0


def test_a_host_engine_is_off_the_device(device_engine):
    from jepsen_tpu import elle
    device_engine.setattr(elle, "_DEVICE_SCC_MIN_NODES", 10 ** 9)
    out = harness.run_cell(ROOT, CELL, 7, 0.5, False, time.perf_counter(),
                           require_accelerator=False, shrink=shrink)
    assert out["correct"] is False
    assert out["compared"]["off_device_keys"]["value"] > 0


def test_full_size_pool_is_one_node_bucket():
    from jepsen_tpu import elle

    cfg = traffic.load(ROOT, "configs", "elle-append")
    mix = traffic.load(ROOT, "traffic", "16k-txns")
    pool = traffic.pool(ROOT, cfg, mix, 2**35 + 9)
    assert [traffic.n_invocations(e) for e in pool] == [16384] * 4
    oks = [sum(o["type"] == "ok" for o in e[0][1]) for e in pool]
    assert {elle.node_bucket(n) for n in oks} == {16384}


# ----------------------------------------------------------- readers


class FakeRun(harness.Run):
    def __init__(self, traced, device, spans):
        super().__init__()
        self.traced, self.device, self.spans = traced, device, spans


def span(sid, name, parent, t0, t1):
    return {"name": name, "id": sid, "parent": parent, "t0": t0, "t1": t1}


# two checks in a 10 s window: scan + graph 2 s and 1.5 s, device 1 s
# and 0.7 s, cycles 0.1 s each
SPANS = [span(1, "elle.check", None, 0.0, 4.0),
         span(2, "elle.scan", 1, 0.0, 0.5), span(3, "elle.graph", 1, 0.5, 2.0),
         span(4, "elle.sccs", 1, 2.0, 3.8),
         span(5, "elle.sccs.place", 4, 2.0, 2.5),
         span(6, "elle.sccs.device", 4, 2.5, 3.5),
         span(7, "elle.sccs.fetch", 4, 3.5, 3.6),
         span(8, "elle.cycles", 1, 3.8, 3.9),
         span(9, "elle.check", None, 5.0, 8.0),
         span(10, "elle.scan", 9, 5.0, 5.5), span(11, "elle.graph", 9, 5.5, 6.5),
         span(12, "elle.sccs", 9, 6.5, 7.8),
         span(13, "elle.sccs.device", 12, 7.0, 7.7),
         span(14, "elle.cycles", 9, 7.8, 7.9)]


def read(name, run, **kw):
    return harness.load_reader(ROOT, name)(run, **kw)


def test_span_share_readers():
    run = FakeRun((0.0, 10.0), [("op", 2.6, 3.4)], SPANS)
    assert read("elle_graph_share", run) == pytest.approx(0.35)
    assert read("elle_scc_device_share", run) == pytest.approx(0.17)
    assert read("elle_cycles_share", run) == pytest.approx(0.02)
    parent = FakeRun((0.0, 10.0), [], [])       # a program with no spans
    for name in ("elle_graph_share", "elle_scc_device_share",
                 "elle_cycles_share", "scc_closure_roofline_share"):
        assert read(name, parent) is None


def test_roofline_reader():
    roof = named.load(ROOT, "metrics", "scc_closure_roofline_share")
    n = 16384
    counts = {"elle.scc.padded_nodes": 3 * n, "elle.scc.closure_calls": 3}
    kind = "TPU v5 lite"
    least = roof.closure_flops(n) / roof.PEAK_FLOPS[kind]
    assert roof.closure_flops(n) == 14 * 2 * n ** 3
    # device time that covers only the counted flops, in each span
    dev = [("%fusion dot", 2.5, 2.5 + least), ("%fusion dot", 7.0, 7.0 + least),
           ("%outside", 0.0, 2.0)]
    run = FakeRun((0.0, 10.0), dev, SPANS)
    assert read("scc_closure_roofline_share", run, counts=counts,
                kind=kind) == pytest.approx(100.0)
    # slower closures read less, and never more than 100
    slow = [(a, s, s + 1.1 * (e - s)) if a != "%outside" else (a, s, e)
            for a, s, e in dev]
    run = FakeRun((0.0, 10.0), slow, SPANS)
    assert read("scc_closure_roofline_share", run, counts=counts,
                kind=kind) == pytest.approx(100.0 / 1.1)
    # a span that sticks out of the traced window is not counted
    run = FakeRun((0.0, 7.5), dev, SPANS)
    assert read("scc_closure_roofline_share", run, counts=counts,
                kind=kind) == pytest.approx(100.0)
    with pytest.raises(KeyError):
        read("scc_closure_roofline_share", FakeRun((0.0, 10.0), dev, SPANS),
             counts=counts, kind="cpu")
    assert read("scc_closure_roofline_share", run, counts={},
                kind=kind) is None
