"""Elle-equivalent cycle checker tests: graph machinery, list-append,
rw-register, and the workload wrappers. Fixture style follows the
reference's checker tests (hand-written histories asserted against exact
anomaly classifications)."""

from __future__ import annotations

import pytest

from jepsen_tpu import elle
from jepsen_tpu.elle import Graph, RW, WR, WW, list_append, rw_register
from jepsen_tpu.elle import txn as txn_mod
from jepsen_tpu.generator import fixed_rand
from jepsen_tpu.history import History, Op
from jepsen_tpu.workloads import cycle as cycle_wl


def H(ops):
    h = History()
    for i, o in enumerate(ops):
        op = Op(o)
        op["index"] = i
        h.append(op)
    return h


def txn_pair(process, mops_invoke, mops_ok, final="ok"):
    return [
        {"type": "invoke", "process": process, "f": "txn",
         "value": mops_invoke},
        {"type": final, "process": process, "f": "txn", "value": mops_ok},
    ]


# ----------------------------------------------------------- Graph/SCC


class TestSCC:
    def g3cycle(self):
        g = Graph()
        g.add(0, 1, WW)
        g.add(1, 2, WW)
        g.add(2, 0, WW)
        g.add(2, 3, WW)  # dangling tail, not in the SCC
        return g

    def test_tarjan(self):
        sccs = elle.tarjan_sccs(self.g3cycle())
        assert sorted(map(sorted, sccs)) == [[0, 1, 2]]

    def test_device_matches_tarjan(self):
        sccs = elle.device_sccs(self.g3cycle())
        assert sorted(map(sorted, sccs)) == [[0, 1, 2]]

    def test_device_random_graphs_match(self):
        import random

        r = random.Random(7)
        for _ in range(5):
            g = Graph()
            n = 30
            for _e in range(60):
                g.add(r.randrange(n), r.randrange(n), WW)
            a = sorted(map(sorted, elle.tarjan_sccs(g)))
            b = sorted(map(sorted, elle.device_sccs(g)))
            assert a == b

    def test_g_single_search(self):
        g = Graph()
        g.add(0, 1, RW)
        g.add(1, 0, WW)
        cyc = elle.find_cycle_with_one(g, [0, 1], RW, {WW, WR})
        assert cyc is not None and cyc[0] == cyc[-1]

    def test_cycle_classification_priority(self):
        g = Graph()
        g.add(0, 1, WW)
        g.add(1, 0, WW)
        found = elle.cycle_anomalies(g, by_id={0: {}, 1: {}})
        assert list(found) == ["G0"]


# --------------------------------------------------------- list-append


class TestListAppend:
    def test_valid_history(self):
        h = H([*txn_pair(0, [["append", "x", 1], ["r", "x", None]],
                         [["append", "x", 1], ["r", "x", [1]]]),
               *txn_pair(1, [["append", "x", 2], ["r", "x", None]],
                         [["append", "x", 2], ["r", "x", [1, 2]]])])
        r = list_append.check(None, h)
        assert r["valid?"] is True

    def test_g0_write_cycle(self):
        # T0: x<-1 then y<-2;  T1: y<-1 then x<-2 — ww cycle
        h = H([*txn_pair(0, [["append", "x", 1], ["append", "y", 2]],
                         [["append", "x", 1], ["append", "y", 2]]),
               *txn_pair(1, [["append", "y", 1], ["append", "x", 2]],
                         [["append", "y", 1], ["append", "x", 2]]),
               *txn_pair(2, [["r", "x", None], ["r", "y", None]],
                         [["r", "x", [1, 2]], ["r", "y", [1, 2]]])])
        r = list_append.check({"anomalies": ["G0"]}, h)
        assert r["valid?"] is False
        assert "G0" in r["anomaly-types"]

    def test_g1a_aborted_read(self):
        h = H([*txn_pair(0, [["append", "x", 1]], [["append", "x", 1]],
                         final="fail"),
               *txn_pair(1, [["r", "x", None]], [["r", "x", [1]]])])
        r = list_append.check({"anomalies": ["G1"]}, h)
        assert r["valid?"] is False
        assert "G1a" in r["anomaly-types"]

    def test_g1b_intermediate_read(self):
        h = H([*txn_pair(0, [["append", "x", 1], ["append", "x", 2]],
                         [["append", "x", 1], ["append", "x", 2]]),
               *txn_pair(1, [["r", "x", None]], [["r", "x", [1]]])])
        r = list_append.check({"anomalies": ["G1"]}, h)
        assert r["valid?"] is False
        assert "G1b" in r["anomaly-types"]

    def test_g_single(self):
        # T1 reads x=[] then T2 appends x<-1 and reads y=[]; T1 appends y<-1.
        # T1 -rw-> T2 (T2 overwrote T1's read of x),
        # T2 -rw-> T1 (T1 overwrote T2's read of y): cycle w/ rw edges.
        h = H([*txn_pair(0, [["r", "x", None], ["append", "y", 1]],
                         [["r", "x", []], ["append", "y", 1]]),
               *txn_pair(1, [["append", "x", 1], ["r", "y", None]],
                         [["append", "x", 1], ["r", "y", []]]),
               *txn_pair(2, [["r", "x", None], ["r", "y", None]],
                         [["r", "x", [1]], ["r", "y", [1]]])])
        r = list_append.check({"anomalies": ["G2"]}, h)
        assert r["valid?"] is False
        assert any(a in r["anomaly-types"] for a in ("G-single", "G2"))

    def test_internal(self):
        h = H([*txn_pair(0, [["append", "x", 1], ["r", "x", None]],
                         [["append", "x", 1], ["r", "x", [5, 9]]])])
        r = list_append.check(None, h)
        assert r["valid?"] is False
        assert "internal" in r["anomaly-types"]

    def test_incompatible_order(self):
        h = H([*txn_pair(0, [["r", "x", None]], [["r", "x", [1, 2]]]),
               *txn_pair(1, [["r", "x", None]], [["r", "x", [2, 1]]])])
        r = list_append.check(None, h)
        assert r["valid?"] is False
        assert "incompatible-order" in r["anomaly-types"]

    def test_cycle_has_explanation_steps(self):
        h = H([*txn_pair(0, [["append", "x", 1], ["append", "y", 2]],
                         [["append", "x", 1], ["append", "y", 2]]),
               *txn_pair(1, [["append", "y", 1], ["append", "x", 2]],
                         [["append", "y", 1], ["append", "x", 2]]),
               *txn_pair(2, [["r", "x", None], ["r", "y", None]],
                         [["r", "x", [1, 2]], ["r", "y", [1, 2]]])])
        r = list_append.check({"anomalies": ["G0"]}, h)
        case = r["anomalies"]["G0"][0]
        assert len(case["steps"]) == len(case["cycle"]) - 1
        assert "--[ww]-->" in case["steps"][0]


# --------------------------------------------------------- rw-register


class TestRwRegister:
    def test_valid(self):
        h = H([*txn_pair(0, [["w", "x", 1]], [["w", "x", 1]]),
               *txn_pair(1, [["r", "x", None]], [["r", "x", 1]])])
        r = rw_register.check(None, h)
        assert r["valid?"] is True

    def test_g1a(self):
        h = H([*txn_pair(0, [["w", "x", 1]], [["w", "x", 1]], final="fail"),
               *txn_pair(1, [["r", "x", None]], [["r", "x", 1]])])
        r = rw_register.check(None, h)
        assert r["valid?"] is False
        assert "G1a" in r["anomaly-types"]

    def test_g1b(self):
        h = H([*txn_pair(0, [["w", "x", 1], ["w", "x", 2]],
                         [["w", "x", 1], ["w", "x", 2]]),
               *txn_pair(1, [["r", "x", None]], [["r", "x", 1]])])
        r = rw_register.check(None, h)
        assert r["valid?"] is False
        assert "G1b" in r["anomaly-types"]

    def test_internal(self):
        h = H([*txn_pair(0, [["w", "x", 1], ["r", "x", None]],
                         [["w", "x", 1], ["r", "x", 2]])])
        r = rw_register.check(None, h)
        assert r["valid?"] is False
        assert "internal" in r["anomaly-types"]

    def test_g1c_with_wfr(self):
        # T0 writes x=1, reads y=1; T1 writes y=1, reads x=1:
        # wr cycle (circular information flow)
        h = H([*txn_pair(0, [["w", "x", 1], ["r", "y", None]],
                         [["w", "x", 1], ["r", "y", 1]]),
               *txn_pair(1, [["w", "y", 1], ["r", "x", None]],
                         [["w", "y", 1], ["r", "x", 1]])])
        r = rw_register.check({"anomalies": ["G1"]}, h)
        assert r["valid?"] is False
        assert "G1c" in r["anomaly-types"]

    def test_linearizable_keys_ww(self):
        # sequential non-overlapping writes 1 then 2; a txn that read 1
        # *after* 2 was written has an rw edge forward and a wr edge back:
        # stale read -> G-single under linearizable-keys
        h = H([
            {"type": "invoke", "process": 0, "f": "txn",
             "value": [["w", "x", 1]]},
            {"type": "ok", "process": 0, "f": "txn",
             "value": [["w", "x", 1]]},
            {"type": "invoke", "process": 1, "f": "txn",
             "value": [["w", "x", 2]]},
            {"type": "ok", "process": 1, "f": "txn",
             "value": [["w", "x", 2]]},
            {"type": "invoke", "process": 2, "f": "txn",
             "value": [["r", "x", None]]},
            {"type": "ok", "process": 2, "f": "txn",
             "value": [["r", "x", 1]]},
        ])
        r = rw_register.check({"linearizable-keys": True,
                               "additional-graphs": ["realtime"]}, h)
        assert r["valid?"] is False


# ----------------------------------------------------------- generators


class TestTxnGen:
    def test_append_txns_shape(self):
        with fixed_rand(7):
            stream = txn_mod.append_txns({"key-count": 3,
                                          "min-txn-length": 1,
                                          "max-txn-length": 4})
            txns = [next(stream) for _ in range(50)]
        for t in txns:
            assert 1 <= len(t) <= 4
            for f, k, v in t:
                assert f in ("r", "append")
                assert (v is None) == (f == "r")

    def test_max_writes_per_key_rotates_keys(self):
        with fixed_rand(3):
            stream = txn_mod.wr_txns({"key-count": 2,
                                      "max-writes-per-key": 4})
            writes = {}
            for _ in range(200):
                for f, k, v in next(stream):
                    if f == "w":
                        writes.setdefault(k, []).append(v)
        assert len(writes) > 2  # keys rotated
        for vs in writes.values():
            assert len(vs) <= 4
            assert vs == sorted(vs)  # fresh increasing values per key

    def test_workload_generator_emits_txn_ops(self):
        wl = cycle_wl.append({"key-count": 2})
        with fixed_rand(1):
            op = wl["generator"]()
        assert op["f"] == "txn"
        assert isinstance(op["value"], list)


# --------------------------------------------------- end-to-end wrapper


class TestWorkloadCheckers:
    def test_append_checker_via_protocol(self):
        h = H([*txn_pair(0, [["append", "x", 1]], [["append", "x", 1]]),
               *txn_pair(1, [["r", "x", None]], [["r", "x", [1]]])])
        r = cycle_wl.append().get("checker").check({}, h)
        assert r["valid?"] is True

    def test_wr_checker_via_protocol(self):
        h = H([*txn_pair(0, [["w", "x", 1]], [["w", "x", 1]])])
        r = cycle_wl.wr().get("checker").check({}, h)
        assert r["valid?"] is True

    def test_generic_cycle_checker(self):
        def analyzer(history):
            g = Graph()
            g.add(0, 1, WW)
            g.add(1, 0, WW)
            return g, None, {0: {}, 1: {}}

        r = cycle_wl.checker(analyzer).check({}, H([]))
        assert r["valid?"] is False
        assert "G0" in r["anomaly-types"]


# ------------------------------------------------- device SCC closure


def _random_graph(n, seed, edges_per_node=1.6):
    import random

    r = random.Random(seed)
    g = Graph()
    for i in range(n):
        g.add_node(i)
    for _ in range(int(n * edges_per_node)):
        g.add(r.randrange(n), r.randrange(n), WW)
    return g


def _parts(comps):
    return sorted(sorted(c) for c in comps)


class TestDeviceSCC:
    @pytest.mark.parametrize("n", [1023, 1024, 1025])
    def test_bucketed_closure_matches_tarjan_at_a_bucket_edge(self, n):
        g = _random_graph(n, seed=n)
        assert elle.node_bucket(n) == (1024 if n <= 1024 else 2048)
        want = _parts(elle.tarjan_sccs(g))
        assert want  # the seeds give cycles to find
        assert _parts(elle.device_sccs(g)) == want

    def test_a_ring_needs_every_squaring(self, monkeypatch):
        # node i reaches i - 1 only over 1,499 edges: 11 squarings of
        # the 2,048 bucket close it, 10 (paths up to 1,024) do not
        n = 1500
        g = Graph()
        for i in range(n):
            g.add(i, (i + 1) % n, WW)
        assert elle.closure_steps(elle.node_bucket(n)) == 11
        assert _parts(elle.device_sccs(g)) == [list(range(n))]
        steps = elle.closure_steps
        monkeypatch.setattr(elle, "closure_steps", lambda n: steps(n) - 1)
        assert _parts(elle.device_sccs(g)) != [list(range(n))]

    def test_padding_adds_no_component(self):
        # an acyclic graph padded from 1,000 to 1,024 nodes has none,
        # and ids that are not 0..n-1 come back as themselves
        g = Graph()
        for i in range(1000):
            g.add(5000 + i, 5000 + (i + 1) % 1000 if i % 100 else 4000, WW)
        assert elle.node_bucket(len(g)) == 1024
        assert elle.device_sccs(g) == elle.tarjan_sccs(g) == []
        g.add(5999, 5000, WW)
        g.add(5000, 5999, WW)
        assert _parts(elle.device_sccs(g)) == _parts(elle.tarjan_sccs(g))

    @pytest.mark.parametrize("offset", [0, 7_000])
    def test_edge_arrays_pad_to_a_bucket_with_dropped_positions(self, offset):
        base = _random_graph(300, seed=3)
        g = Graph()
        for a, bs in reversed(list(base.out.items())):
            g.add_node(a + offset)
            for b in bs:
                g.add(a + offset, b + offset, WW)
        ids, src, dst = elle.edge_arrays(g)
        e = sum(len(bs) for bs in g.out.values())
        assert ids == sorted(g.out)
        assert len(src) == len(dst) == elle.edge_bucket(e) == 1024
        assert (src[e:] == elle.node_bucket(300)).all()
        assert (dst[e:] == elle.node_bucket(300)).all()
        assert {(ids[s], ids[d]) for s, d in zip(src[:e], dst[:e])} == {
            (a, b) for a, bs in g.out.items() for b in bs}

    def test_sccs_names_its_engine(self, monkeypatch):
        g = _random_graph(40, seed=1)
        comps, engine = elle.sccs(g)
        assert engine == "host" and comps
        monkeypatch.setattr(elle, "_DEVICE_SCC_MIN_NODES", 0)
        dev, engine = elle.sccs(g)
        assert engine == "device" and _parts(dev) == _parts(comps)

    def test_over_the_ceiling_the_host_answers_and_says_so(self, caplog):
        n = elle.DEVICE_SCC_MAX_NODES + 1
        g = Graph()
        for i in range(n):
            g.add_node(i)
        g.add(7, 8, WW)
        g.add(8, 7, WW)
        with caplog.at_level("WARNING", logger="jepsen_tpu.elle"):
            comps, engine = elle.sccs(g)
        assert engine == "host" and _parts(comps) == [[7, 8]]
        assert "ceiling" in caplog.text
        with pytest.raises(ValueError, match="ceiling"):
            elle.device_sccs(g)


# ---------------------------- list-append through both SCC engines


def _store_history(seed, n_txns=400, fault=None):
    """A list-append history from a seeded strict-serializable store:
    5 processes, each txn applied atomically inside its interval;
    `fault` "stale" makes one read lag, "info" makes a crashed append
    apply and be read."""
    import random

    r = random.Random(seed)
    with fixed_rand(seed):
        stream = txn_mod.append_txns({"key-count": 2})
        mops = [next(stream) for _ in range(n_txns)]
    free = [0.0] * 5
    txns = []
    for i, m in enumerate(mops):
        p = min(range(5), key=lambda q: free[q])
        inv = free[p] + r.random() * 0.1
        comp = inv + 0.05 + r.random()
        typ = "info" if i % 97 == 50 else "fail" if i % 53 == 7 else "ok"
        at = r.uniform(inv, comp) if typ == "ok" or (
            typ == "info" and fault == "info") else None
        txns.append([p, inv, comp, typ, at, m, None])
        free[p] = comp
    state, log = {}, {}
    for t in sorted((t for t in txns if t[4] is not None),
                    key=lambda t: t[4]):
        out = []
        for f, k, v in t[5]:
            if f == "append":
                state.setdefault(k, []).append(v)
                log.setdefault(k, []).append((t[4], v))
                out.append([f, k, v])
            else:
                out.append([f, k, list(state.get(k, []))])
        t[6] = out
    if fault == "stale":
        for t in txns[n_txns // 2:]:
            if t[3] != "ok" or t[5][0][0] != "r":
                continue
            k = t[5][0][1]
            old = [v for at, v in log.get(k, []) if at <= t[1] - 3.0]
            if old != t[6][0][2]:
                t[6][0] = ["r", k, old]
                break
    ops = []
    for when, done, t in sorted([(t[1], 0, i) for i, t in enumerate(txns)]
                                + [(t[2], 1, i)
                                   for i, t in enumerate(txns)]):
        p, _, _, typ, _, m, reads = txns[t]
        ops.append({"type": typ if done else "invoke", "process": p,
                    "f": "txn",
                    "value": reads if done and typ == "ok" else
                    [[f, k, None if f == "r" else v] for f, k, v in m]})
    return H(ops)


def _answer(r):
    return (r["valid?"], r["anomaly-types"],
            {t: len(cs) for t, cs in r["anomalies"].items()})


def _engine(monkeypatch, engine):
    """Steers `elle.sccs` to one engine at any graph size."""
    monkeypatch.setattr(elle, "_DEVICE_SCC_MIN_NODES",
                        0 if engine == "device" else 10 ** 9)


class TestListAppendEngines:
    @pytest.mark.parametrize("fault,seed", [(None, 1), (None, 2),
                                            ("stale", 3), ("stale", 4),
                                            ("info", 5)])
    def test_device_and_host_engines_agree(self, fault, seed, monkeypatch):
        h = _store_history(seed, fault=fault)
        opts = {"anomalies": ["G1", "G2"], "additional-graphs": ["realtime"]}
        _engine(monkeypatch, "device")
        dev = list_append.check(opts, h)
        _engine(monkeypatch, "host")
        host = list_append.check(opts, h)
        assert (dev["scc"], host["scc"]) == ("device", "host")
        assert _answer(dev) == _answer(host)
        if fault == "stale":
            assert dev["anomaly-types"] == ["G-single"]
        else:
            assert dev["valid?"] is True

    def test_result_names_the_engine(self, monkeypatch):
        h = _store_history(6, n_txns=60)
        assert list_append.check(None, h)["scc"] == "host"
        _engine(monkeypatch, "device")
        assert cycle_wl.AppendChecker().check({}, h)["scc"] == "device"

    def test_over_the_ceiling_the_result_names_the_host(self, monkeypatch,
                                                       caplog):
        h = _store_history(7, n_txns=60)
        monkeypatch.setattr(elle, "_DEVICE_SCC_MIN_NODES", 4)
        monkeypatch.setattr(elle, "DEVICE_SCC_MAX_NODES", 16)
        with caplog.at_level("WARNING", logger="jepsen_tpu.elle"):
            r = list_append.check(None, h)
        assert r["scc"] == "host" and "ceiling" in caplog.text

    def test_spans_and_counters(self, monkeypatch):
        from jepsen_tpu import obs

        h = _store_history(8, n_txns=200, fault="stale")
        _engine(monkeypatch, "device")
        before = obs.registry().snapshot()
        obs.configure(on=True)
        try:
            list_append.check({"additional-graphs": ["realtime"]}, h)
            spans = obs.tracer().drain()
        finally:
            obs.reset()
        by = {s.name: s for s in spans}
        root = by["elle.check"]
        for name in ("elle.scan", "elle.graph", "elle.sccs", "elle.cycles"):
            assert by[name].parent == root.sid
        for name in ("elle.sccs.place", "elle.sccs.device",
                     "elle.sccs.fetch"):
            assert by[name].parent == by["elle.sccs"].sid
        moved = obs.registry().delta(before)
        n_pad = elle.node_bucket(len(txn_mod.ok_txns(h)))
        assert moved["elle.scc.closure_calls"]["value"] == 1
        assert moved["elle.scc.padded_nodes"]["value"] == n_pad
        assert moved["elle.scc.closure_flops"]["value"] == (
            elle.closure_steps(n_pad) * 2 * n_pad ** 3)
        assert moved["elle.nodes"]["value"] == len(txn_mod.ok_txns(h))
        assert moved["elle.edges"]["value"] > 0


# ------------------------------- the array graph against the dict builder


def _dict_graph(oks, additional=()):
    """The dict-of-sets builder the array graph replaced, kept as the
    oracle: ww/wr/rw loops over the appender map, the bisect realtime
    loop, and the process graph merged in."""
    import bisect

    g = Graph()
    for o in oks:
        g.add_node(o["_id"])
    appender = {}
    for o in oks:
        for f, k, v in o.get("value") or []:
            if f == "append":
                appender.setdefault(k, {})[v] = o["_id"]
    orders, _ = list_append._key_orders(oks)
    for k, order in orders.items():
        writer = appender.get(k, {})
        for v1, v2 in zip(order, order[1:]):
            a, b = writer.get(v1), writer.get(v2)
            if a is not None and b is not None:
                g.add(a, b, WW)
    for o in oks:
        for f, k, rv in o.get("value") or []:
            if f != "r" or rv is None:
                continue
            got = list(rv)
            if got:
                w = appender.get(k, {}).get(got[-1])
                if w is not None and w != o["_id"]:
                    g.add(w, o["_id"], WR)
            got_set = set(got)
            for v, w2 in appender.get(k, {}).items():
                if v not in got_set and w2 != o["_id"]:
                    g.add(o["_id"], w2, RW)
    if "realtime" in additional:
        starts = sorted(oks, key=lambda o: o["_invoke_index"])
        invs = [t["_invoke_index"] for t in starts]
        suffix_min = [0] * (len(starts) + 1)
        suffix_min[len(starts)] = float("inf")
        for i in range(len(starts) - 1, -1, -1):
            suffix_min[i] = min(starts[i]["_complete_index"],
                                suffix_min[i + 1])
        for t1 in oks:
            i = bisect.bisect_right(invs, t1["_complete_index"])
            if i >= len(starts):
                continue
            for j in range(i, len(starts)):
                if invs[j] > suffix_min[i]:
                    break
                g.add(t1["_id"], starts[j]["_id"], elle.RT)
    if "process" in additional:
        g.merge(elle.process_graph(oks))
    return g


def _typed(g):
    """A graph's edges as (src, dst, types), either form."""
    if isinstance(g, elle.ArrayGraph):
        return {(a, b, elle._TYPES_OF[m]) for a, b, m in zip(
            g.src.tolist(), g.dst.tolist(), g.types.tolist())}
    return {(a, b, frozenset(ts)) for a, bs in g.out.items()
            for b, ts in bs.items()}


def _incompatible_history():
    # x is read as [1, 2] and as [2, 1]: no order, so its two reads
    # take the set test; y's order is consistent
    return H([*txn_pair(0, [["append", "x", 1], ["append", "y", 1]],
                        [["append", "x", 1], ["append", "y", 1]]),
              *txn_pair(1, [["append", "x", 2], ["r", "y", None]],
                        [["append", "x", 2], ["r", "y", [1]]]),
              *txn_pair(2, [["r", "x", None], ["append", "x", 3]],
                        [["r", "x", [1, 2]], ["append", "x", 3]]),
              *txn_pair(3, [["r", "x", None], ["r", "y", None]],
                        [["r", "x", [2, 1]], ["r", "y", []]])])


def _own_read_history():
    # T0 appends x and reads its own append back: no wr self-loop
    return H([*txn_pair(0, [["append", "x", 1], ["r", "x", None]],
                        [["append", "x", 1], ["r", "x", [1]]]),
              *txn_pair(1, [["r", "x", None], ["append", "x", 2]],
                        [["r", "x", [1]], ["append", "x", 2]]),
              *txn_pair(2, [["r", "x", None]], [["r", "x", [1, 2]]])])


def _duplicate_append_history():
    # two txns append (x, 1): the later one is its appender
    return H([*txn_pair(0, [["append", "x", 1]], [["append", "x", 1]]),
              *txn_pair(1, [["r", "x", None]], [["r", "x", []]]),
              *txn_pair(2, [["append", "x", 1], ["append", "y", 5]],
                        [["append", "x", 1], ["append", "y", 5]]),
              *txn_pair(3, [["r", "x", None], ["r", "y", None]],
                        [["r", "x", [1]], ["r", "y", [5]]])])


class TestArrayGraph:
    @pytest.mark.parametrize("case,additional,set_reads", [
        ("store", ("realtime",), 0),
        ("stale", ("realtime",), 0),
        ("info", ("realtime",), 0),
        ("store", ("realtime", "process"), 0),
        ("incompatible", ("realtime",), 2),
        ("own-read", ("realtime",), 0),
        ("duplicate", (), 0),
    ])
    def test_same_typed_edges_as_the_dict_builder(self, case, additional,
                                                  set_reads):
        from jepsen_tpu import obs

        h = {"store": lambda: _store_history(1),
             "stale": lambda: _store_history(3, fault="stale"),
             "info": lambda: _store_history(5, fault="info"),
             "incompatible": _incompatible_history,
             "own-read": _own_read_history,
             "duplicate": _duplicate_append_history}[case]()
        oks = txn_mod.ok_txns(h)
        before = obs.registry().snapshot()
        g, incompat = list_append.graph(oks, additional)
        moved = obs.registry().delta(before)
        want = _dict_graph(oks, additional)
        assert len(g) == len(oks) == len(want)
        assert g.edge_count() == want.edge_count() > 0
        assert _typed(g) == _typed(want)
        assert bool(incompat) == (case == "incompatible")
        assert moved.get("elle.graph.set_path_reads",
                         {"value": 0})["value"] == set_reads
        if "realtime" in additional:
            assert elle.realtime_graph(oks).out == \
                _dict_graph(oks, ("realtime",)).restrict({elle.RT}).out
        if case == "duplicate":
            assert (0, 1, frozenset({WR})) not in _typed(g)
            assert (2, 3, frozenset({WR})) in _typed(g)

    def test_dict_forms(self):
        h = _store_history(3, fault="stale")
        oks = txn_mod.ok_txns(h)
        g, _ = list_append.graph(oks, ("realtime",))
        want = _dict_graph(oks, ("realtime",))
        assert g.to_graph().out == want.out
        comps = elle.tarjan_sccs(want)
        members = {v for c in comps for v in c}
        assert members
        sub = g.subgraph(members)
        assert set(sub.out) == members
        assert sub.out == {a: {b: ts for b, ts in want.out[a].items()
                               if b in members} for a in members}
        # the same classes; which cycle a search finds may differ
        assert {t: len(cs) for t, cs in elle.classify(sub, comps).items()} \
            == {t: len(cs) for t, cs in elle.classify(want, comps).items()}
        ids, src, dst = elle.edge_arrays(g)
        ids2, src2, dst2 = elle.edge_arrays(g.to_graph())
        assert ids == ids2 == list(range(len(oks)))
        assert (src == src2).all() and (dst == dst2).all()

    def test_an_empty_graph(self):
        g, incompat = list_append.graph([], ("realtime",))
        assert (len(g), g.edge_count(), incompat) == (0, 0, [])
        assert g.to_graph().out == {} and g.subgraph([]).out == {}
