"""List-append transactional checker (elle.list-append capability;
call surface jepsen/src/jepsen/tests/cycle/append.clj:11-27).

Transactions append unique values to per-key lists and read whole lists.
Because reads expose the full list, the version order per key is directly
observable: every read is a prefix of the key's final append order, so
incompatible reads are themselves an anomaly ("incompatible-order"), and
ww/wr/rw edges fall out of the longest observed order.

Checked anomalies: internal, G1a (aborted read), G1b (intermediate read),
dirty-update, incompatible-order, and the cycle family G0/G1c/G-single/G2
(classification machinery in jepsen_tpu.elle).
"""

from __future__ import annotations

from typing import Dict, List, Optional, Set, Tuple

from jepsen_tpu import elle, obs
from jepsen_tpu.elle import (ArrayGraph, PROC, RT, RW, WR, WW,
                              txn as txn_mod)

DEFAULT_ANOMALIES = ["G1", "G2"]

#: anomaly aliases -> concrete anomalies (wr.clj:47-48: G2 implies
#: G-single and G1c; G1 implies G1a, G1b, G1c; G1c implies G0)
_EXPANSION = {
    "G1": {"G1a", "G1b", "G1c", "G0"},
    "G1c": {"G1c", "G0"},
    "G2": {"G2", "G-single", "G1c", "G0"},
    "G-single": {"G-single", "G1c", "G0"},
}


def expand_anomalies(names) -> Set[str]:
    out: Set[str] = set()
    for n in names:
        n = str(n).lstrip(":")
        out |= _EXPANSION.get(n, {n})
    return out | {"internal", "incompatible-order", "dirty-update"}


# ----------------------------------------------------------- single-txn


def internal_cases(oks: List[dict]) -> List[dict]:
    """Reads inconsistent with the txn's own prior reads/appends
    (elle `internal`). Expected list state per key is tracked through the
    txn; a read must equal expectation when known, or end with the txn's
    own prior appends when the prefix is unknown."""
    bad = []
    for o in oks:
        # key -> (known_prefix_or_None, [own appends since])
        state: Dict = {}
        for f, k, v in o.get("value") or []:
            if f == "append":
                known, own = state.get(k, (None, []))
                state[k] = (known, own + [v])
            else:  # read
                got = list(v) if v is not None else []
                if k in state:
                    known, own = state[k]
                    if known is not None:
                        expected = known + own
                        if got != expected:
                            bad.append({"op": dict(o), "mop": [f, k, v],
                                        "expected": expected})
                            continue
                    elif own and got[-len(own):] != own:
                        bad.append({"op": dict(o), "mop": [f, k, v],
                                    "expected": ["...", *own]})
                        continue
                state[k] = (got, [])
    return bad


# -------------------------------------------------------- version orders


class IncompatibleOrder(Exception):
    def __init__(self, key, readings):
        super().__init__(f"incompatible reads of key {key}")
        self.case = {"key": key, "values": readings}


def _key_orders(oks: List[dict]) -> Tuple[Dict, List[dict]]:
    """key -> append order [v1 v2 ...], from reads (longest read wins;
    all reads must be prefixes of it) extended with appends whose position
    is known: the longest-read order, then any appends by the reading txns
    immediately after their observed prefix. Returns (orders, error-cases)."""
    longest: Dict = {}
    reads_by_key: Dict[int, List[list]] = {}
    for o in oks:
        for f, k, v in o.get("value") or []:
            if f == "r" and v is not None:
                got = list(v)
                reads_by_key.setdefault(k, []).append(got)
                if len(got) > len(longest.get(k, [])):
                    longest[k] = got
    errors = []
    orders: Dict = {}
    for k, lead in longest.items():
        ok = True
        for r in reads_by_key[k]:
            if lead[:len(r)] != r:
                errors.append({"key": k, "values": [lead, r]})
                ok = False
                break
        if ok:
            orders[k] = lead
    return orders, errors


# ------------------------------------------------------- graph building


def graph(oks: List[dict], additional=()) -> Tuple[ArrayGraph, List[dict]]:
    """The ww/wr/rw dependency graph over the txns' `_id`s, with the
    `additional` graphs' edges ("realtime", "process") merged in.
    Returns (graph, incompatible-order cases).

    ww links consecutive appends of a key's version order; wr the
    appender of a read's last value to the reader. rw: the observed list
    is a prefix of the key's final append order, so every committed
    append NOT in it happened after the read: reader --rw--> its
    appender. That covers appends whose exact position is unknown (two
    txns that both read [] and appended: mutual rw, no later read
    needed). Of two appends of one (k, v), the later txn is the
    appender."""
    import numpy as np

    appender: Dict = {}                   # k -> v -> txn, the last wins
    r_t, r_k, r_v = [], [], []            # the reads that returned a list
    for o in oks:
        t = o["_id"]
        for f, k, v in o.get("value") or []:
            if f == "append":
                w = appender.get(k)
                if w is None:
                    w = appender[k] = {}
                w[v] = t
            elif f == "r" and v is not None:
                r_t.append(t)
                r_k.append(k)
                r_v.append(v)
    orders, incompat = _key_orders(oks)
    bad_keys = {c["key"] for c in incompat}

    # ww: consecutive appends in the version order
    ww_src, ww_dst = [], []
    for k, order in orders.items():
        writer = appender.get(k, {})
        ws = [writer.get(v) for v in order]
        for a, b in zip(ws, ws[1:]):
            if a is not None and b is not None:
                ww_src.append(a)
                ww_dst.append(b)
    # wr: the appender of each read's last value
    wr_src, wr_dst = [], []
    for t, k, v in zip(r_t, r_k, r_v):
        if len(v):
            w = appender.get(k, {}).get(v[-1])
            if w is not None:
                wr_src.append(w)
                wr_dst.append(t)

    # rw: an append is in a read of k iff its first position in k's
    # order is below the read's length (an append absent from the order
    # sits at `absent`), so a read's targets are one suffix of k's
    # appends sorted by (key, position). A key with no consistent order
    # takes the set test, read by read.
    absent = max(map(len, orders.values()), default=0) + 1
    span = absent + 1
    key_base: Dict = {}
    a_sort, a_txn = [], []
    for k, writer in appender.items():
        base = key_base[k] = len(key_base) * span
        first: Dict = {}
        for i, v in enumerate(orders.get(k, ())):
            first.setdefault(v, i)
        for v, w in writer.items():
            a_sort.append(base + first.get(v, absent))
            a_txn.append(w)
    a_sort = np.array(a_sort, np.int64)
    by_pos = np.argsort(a_sort, kind="stable")
    a_sort, a_txn = a_sort[by_pos], np.array(a_txn, np.int64)[by_pos]
    r_txn, r_base, r_len = [], [], []
    set_src, set_dst = [], []
    set_reads = 0
    for t, k, v in zip(r_t, r_k, r_v):
        if k in bad_keys:
            set_reads += 1
            got = set(v)
            for x, w in appender.get(k, {}).items():
                if x not in got:
                    set_src.append(t)
                    set_dst.append(w)
        elif k in key_base:
            r_txn.append(t)
            r_base.append(key_base[k])
            r_len.append(len(v))
    obs.counter("elle.graph.set_path_reads").inc(set_reads)
    r_base = np.array(r_base, np.int64)
    lo = np.searchsorted(a_sort, r_base + np.array(r_len, np.int64))
    hi = np.searchsorted(a_sort, r_base + span)
    rows, cols = elle.expand_ranges(lo, hi)

    parts = [(ww_src, ww_dst, WW), (wr_src, wr_dst, WR),
             (np.array(r_txn, np.int64)[rows], a_txn[cols], RW),
             (set_src, set_dst, RW)]
    if "realtime" in additional:
        parts.append((*elle.realtime_edges(oks), RT))
    if "process" in additional:
        pg = elle.process_graph(oks)
        parts.append(([a for a, bs in pg.out.items() for _ in bs],
                      [b for bs in pg.out.values() for b in bs], PROC))
    return ArrayGraph.from_edges(len(oks), parts), incompat


# ---------------------------------------------------------------- check


def check(opts: Optional[Dict], history) -> Dict:
    """elle.list-append/check equivalent. opts: anomalies (default
    [G1 G2]), additional-graphs ("realtime"/"process"). The result's
    "scc" names the engine that found the SCCs (`elle.sccs`)."""
    o = opts or {}
    with obs.span("elle.check"):
        return _check(o, history)


def _check(o: Dict, history) -> Dict:
    wanted = expand_anomalies(o.get("anomalies", DEFAULT_ANOMALIES))
    anomalies: Dict[str, list] = {}
    with obs.span("elle.scan"):
        oks = txn_mod.ok_txns(history)
        by_id = {t["_id"]: t for t in oks}
        if "internal" in wanted:
            cases = internal_cases(oks)
            if cases:
                anomalies["internal"] = cases
        _read_anomalies(history, oks, wanted, anomalies)

    with obs.span("elle.graph"):
        g, incompat = graph(oks, o.get("additional-graphs") or ())
        if incompat:
            anomalies["incompatible-order"] = incompat

    comps, engine = elle.sccs(g)
    with obs.span("elle.cycles", sccs=len(comps)):
        members = g.subgraph(v for comp in comps for v in comp)
        cyc = elle.classify(members, comps, by_id=by_id)
    for name, cases in cyc.items():
        if name in wanted:
            anomalies[name] = cases

    return {
        "valid?": not anomalies,
        "anomaly-types": sorted(anomalies),
        "anomalies": anomalies,
        "scc": engine,
    }


def _read_anomalies(history, oks: List[dict], wanted: Set[str],
                    anomalies: Dict[str, list]) -> None:
    """G1a (a read of a failed append) and G1b (a read ending in
    another txn's non-final append) cases, into `anomalies`."""
    failed = txn_mod.failed_writes(history, "append")
    inter = txn_mod.intermediate_writes(oks, "append")
    for t in oks:
        for f, k, v in t.get("value") or []:
            if f != "r" or v is None:
                continue
            for x in v:
                if "G1a" in wanted and x in failed.get(k, ()):
                    anomalies.setdefault("G1a", []).append(
                        {"op": dict(t), "mop": [f, k, list(v)], "value": x})
                src = inter.get(k, {}).get(x)
                # an intermediate read shows a txn's non-final append of k
                # as the *last* element — the final append is missing
                if ("G1b" in wanted and src is not None
                        and src["_id"] != t["_id"] and list(v)[-1] == x):
                    anomalies.setdefault("G1b", []).append(
                        {"op": dict(t), "mop": [f, k, list(v)], "value": x})


def gen(opts: Optional[Dict] = None):
    """Generator of append/read txns (tests/cycle/append.clj:24-27)."""
    return txn_mod.txn_generator(opts, "append")
