"""Transactional-anomaly cycle checker — the elle 0.1.2 capability rebuilt
TPU-first (reference call surface: jepsen/src/jepsen/tests/cycle.clj,
tests/cycle/append.clj, tests/cycle/wr.clj; anomaly classification documented at
tests/cycle/wr.clj:31-45).

Transactions become nodes in a dependency graph with typed edges:

  ww  write-write   T1's write of version v precedes T2's write of v'
  wr  write-read    T2 read the version T1 wrote
  rw  anti-dep      T1 read a version that T2 overwrote
  rt  realtime      T1 completed before T2 was invoked
  p   process       T1 preceded T2 on the same process

Anomalies are cycles in restricted subgraphs (Adya's classification):

  G0        cycle of only ww edges
  G1c       cycle of ww+wr edges (at least one wr)
  G-single  cycle with exactly one rw edge
  G2        cycle with one or more rw edges

Strongly connected components are found two ways: an iterative Tarjan on
the host for small graphs, and — the TPU path — boolean transitive
closure by repeated squaring of the adjacency matrix on the MXU
(`jnp.dot` over bfloat16 lifts reachability onto the systolic array;
SCC = R & R.T), which turns the irregular graph walk into dense matmuls
for histories with thousands of transactions.
"""

from __future__ import annotations

from collections import deque
from typing import Callable, Dict, List, Optional, Sequence, Set, Tuple

# Edge types, in explanation-priority order.
WW, WR, RW, RT, PROC = "ww", "wr", "rw", "rt", "process"

# Device SCC pays off once the adjacency matrix is big enough to fill the
# MXU; below this we stay on host.
_DEVICE_SCC_MIN_NODES = 1024


class Graph:
    """Directed multigraph over txn ids with typed edges."""

    def __init__(self):
        # a -> b -> set of edge types
        self.out: Dict[int, Dict[int, Set[str]]] = {}

    def add(self, a: int, b: int, typ: str) -> None:
        if a == b:
            return
        self.out.setdefault(a, {}).setdefault(b, set()).add(typ)
        self.out.setdefault(b, {})

    def add_node(self, a: int) -> None:
        self.out.setdefault(a, {})

    def nodes(self) -> List[int]:
        return list(self.out)

    def edge_types(self, a: int, b: int) -> Set[str]:
        return self.out.get(a, {}).get(b, set())

    def merge(self, other: "Graph") -> "Graph":
        for a, bs in other.out.items():
            self.add_node(a)
            for b, ts in bs.items():
                for t in ts:
                    self.add(a, b, t)
        return self

    def restrict(self, types: Set[str], nodes: Optional[Set[int]] = None) -> "Graph":
        g = Graph()
        for a, bs in self.out.items():
            if nodes is not None and a not in nodes:
                continue
            g.add_node(a)
            for b, ts in bs.items():
                if nodes is not None and b not in nodes:
                    continue
                keep = ts & types
                for t in keep:
                    g.add(a, b, t)
        return g

    def __len__(self):
        return len(self.out)


# ------------------------------------------------------------------- SCC


def tarjan_sccs(g: Graph) -> List[List[int]]:
    """Iterative Tarjan; returns SCCs with >1 node (self-loops excluded
    by construction — Graph.add drops a==b)."""
    index: Dict[int, int] = {}
    low: Dict[int, int] = {}
    on_stack: Set[int] = set()
    stack: List[int] = []
    sccs: List[List[int]] = []
    counter = [0]

    for root in g.nodes():
        if root in index:
            continue
        work: List[Tuple[int, int]] = [(root, 0)]
        while work:
            v, pi = work.pop()
            if pi == 0:
                index[v] = low[v] = counter[0]
                counter[0] += 1
                stack.append(v)
                on_stack.add(v)
            recurse = False
            succs = list(g.out.get(v, {}))
            for i in range(pi, len(succs)):
                w = succs[i]
                if w not in index:
                    work.append((v, i + 1))
                    work.append((w, 0))
                    recurse = True
                    break
                if w in on_stack:
                    low[v] = min(low[v], index[w])
            if recurse:
                continue
            if low[v] == index[v]:
                comp = []
                while True:
                    w = stack.pop()
                    on_stack.discard(w)
                    comp.append(w)
                    if w == v:
                        break
                if len(comp) > 1:
                    sccs.append(comp)
            if work:
                parent = work[-1][0]
                low[parent] = min(low[parent], low[v])
    return sccs


_scc_closure_jit = None  # memoized jit wrapper (see _get_scc_closure)


def _get_scc_closure():
    """The jitted transitive-closure program, built ONCE and memoized
    in a module global. jax stays a lazy import (this module must be
    usable with no backend), but the wrapper must not be re-created per
    device_sccs call — a fresh jax.jit each call would never reuse the
    compile cache (found by `jepsen-tpu lint`, recompile-closure-
    capture); the memo makes the jit effectively module-level, so the
    suppression below records intent, not a hazard."""
    global _scc_closure_jit
    if _scc_closure_jit is not None:
        return _scc_closure_jit
    import jax
    import jax.numpy as jnp
    from jax import lax

    def closure(adj, steps: int):
        r = jnp.minimum(adj + jnp.eye(adj.shape[0], dtype=adj.dtype), 1.0)

        def body(_, r):
            rr = jnp.dot(r.astype(jnp.bfloat16), r.astype(jnp.bfloat16),
                         preferred_element_type=jnp.float32)
            return jnp.minimum(rr, 1.0).astype(adj.dtype)

        r = lax.fori_loop(0, steps, body, r)
        return jnp.logical_and(r > 0, r.T > 0)

    # jepsen-lint: disable=recompile-closure-capture
    _scc_closure_jit = jax.jit(closure, static_argnums=1)
    return _scc_closure_jit


def device_sccs(g: Graph) -> List[List[int]]:
    """SCCs via MXU transitive closure: R := A | I, square ceil(log2 n)
    times (boolean matmul = bfloat16 dot > 0), SCC membership = R & R.T.
    One XLA program; the graph walk becomes dense systolic-array work."""
    import math

    import numpy as np

    ids = sorted(g.nodes())
    n = len(ids)
    if n == 0:
        return []
    pos = {v: i for i, v in enumerate(ids)}
    a = np.zeros((n, n), dtype=np.float32)
    for u, bs in g.out.items():
        for v in bs:
            a[pos[u], pos[v]] = 1.0
    # static trip count computed host-side (it was np math inside the
    # traced closure before — legal but a purity-rule exception for no
    # gain)
    steps = max(1, math.ceil(math.log2(max(2, n))))
    s = np.asarray(_get_scc_closure()(a, steps))
    seen: Set[int] = set()
    sccs: List[List[int]] = []
    for i in range(n):
        if i in seen:
            continue
        members = np.nonzero(s[i])[0]
        comp = [ids[j] for j in members]
        seen.update(int(j) for j in members)
        if len(comp) > 1:
            sccs.append(comp)
    return sccs


def sccs(g: Graph) -> List[List[int]]:
    if len(g) >= _DEVICE_SCC_MIN_NODES:
        return device_sccs(g)
    return tarjan_sccs(g)


# --------------------------------------------------------------- cycles


def _bfs_path(g: Graph, src: int, dst: int,
              types: Optional[Set[str]] = None) -> Optional[List[int]]:
    """Shortest path src..dst (inclusive) using only edges of `types`
    (None = any). src == dst finds the shortest cycle through src."""
    parent: Dict[int, int] = {}
    q = deque([src])
    seen = {src} if src != dst else set()
    while q:
        v = q.popleft()
        for w, ts in g.out.get(v, {}).items():
            if types is not None and not (ts & types):
                continue
            if w == dst:
                path = [w, v]
                while path[-1] != src:
                    path.append(parent[path[-1]])
                path.reverse()
                return path
            if w not in seen:
                seen.add(w)
                parent[w] = v
                q.append(w)
    return None


def find_cycle(g: Graph, scc: Sequence[int],
               types: Optional[Set[str]] = None) -> Optional[List[int]]:
    """A cycle [v0, v1, ..., v0] inside scc using only `types` edges."""
    members = set(scc)
    sub = g.restrict(types if types is not None else {WW, WR, RW, RT, PROC},
                     members)
    for v in scc:
        p = _bfs_path(sub, v, v)
        if p:
            return p
    return None


def find_cycle_with_one(g: Graph, scc: Sequence[int], one: str,
                        rest: Set[str]) -> Optional[List[int]]:
    """A cycle containing exactly one edge of type `one`, all others drawn
    from `rest` — the G-single search (one rw edge, back via ww/wr)."""
    members = set(scc)
    sub_rest = g.restrict(rest, members)
    for a in scc:
        for b, ts in g.out.get(a, {}).items():
            if b not in members or one not in ts:
                continue
            back = _bfs_path(sub_rest, b, a)
            if back is not None:
                return [a] + back
    return None


# ---------------------------------------------------------- explanation


def explain_cycle(cycle: List[int], g: Graph,
                  explainer: Callable[[int, int, Set[str]], str]) -> List[str]:
    out = []
    for a, b in zip(cycle, cycle[1:]):
        out.append(explainer(a, b, g.edge_types(a, b)))
    return out


def _default_explainer(by_id: Dict[int, dict]) -> Callable:
    def show(i: int) -> dict:
        return {k: v for k, v in by_id.get(i, {}).items()
                if not str(k).startswith("_")}

    def explain(a: int, b: int, types: Set[str]) -> str:
        t = next((x for x in (WW, WR, RW, RT, PROC) if x in types), "?")
        return f"T{a} {show(a)} --[{t}]--> T{b} {show(b)}"
    return explain


# --------------------------------------------------------------- check


#: anomaly -> (edge types allowed, required type, "exactly-one" type)
_CYCLE_SPECS = [
    ("G0", {WW, RT, PROC}, None, None),
    ("G1c", {WW, WR, RT, PROC}, WR, None),
    ("G-single", {WW, WR, RT, PROC}, None, RW),
    ("G2", {WW, WR, RW, RT, PROC}, RW, None),
]


def cycle_anomalies(g: Graph, explainer: Optional[Callable] = None,
                    by_id: Optional[Dict[int, dict]] = None) -> Dict[str, list]:
    """Classify every SCC into the most severe anomaly classes it exhibits.
    Returns anomaly-name -> list of {"cycle": [...ids...], "steps": [...]}."""
    if explainer is None:
        explainer = _default_explainer(by_id or {})
    found: Dict[str, list] = {}
    for comp in sccs(g):
        for name, types, required, exactly_one in _CYCLE_SPECS:
            if exactly_one is not None:
                cyc = find_cycle_with_one(g, comp, exactly_one,
                                          types - {exactly_one})
            else:
                cyc = find_cycle(g, comp, types)
                if cyc is not None and required is not None:
                    if not any(required in g.edge_types(a, b)
                               for a, b in zip(cyc, cyc[1:])):
                        cyc = None
            if cyc is not None:
                found.setdefault(name, []).append({
                    "cycle": cyc,
                    "steps": explain_cycle(cyc, g, explainer),
                })
                break  # most severe classification for this SCC wins
    return found


def check(analyzer: Callable, history) -> Dict:
    """elle.core/check equivalent (tests/cycle.clj:9-16): `analyzer` maps a
    history to (graph, explainer, by_id); cycles become anomalies."""
    g, explainer, by_id = analyzer(history)
    anomalies = cycle_anomalies(g, explainer, by_id)
    return {
        "valid?": not anomalies,
        "anomaly-types": sorted(anomalies),
        "anomalies": anomalies,
    }


# ------------------------------------------------- generic graph builders


def realtime_graph(oks: List[dict]) -> Graph:
    """rt edges: T1's completion precedes T2's invocation. Uses the
    reduced form: edge only from each txn to the txns invoked after it and
    before any later completion (transitively implied edges dropped)."""
    import bisect

    g = Graph()
    for t in oks:
        g.add_node(t["_id"])
    # oks carry "_invoke_index"/"_complete_index"/"_id" annotations.
    starts = sorted(oks, key=lambda o: o["_invoke_index"])
    invs = [t["_invoke_index"] for t in starts]
    # suffix_min[i] = min complete index among starts[i:]
    suffix_min = [0] * (len(starts) + 1)
    suffix_min[len(starts)] = float("inf")
    for i in range(len(starts) - 1, -1, -1):
        suffix_min[i] = min(starts[i]["_complete_index"], suffix_min[i + 1])
    for t1 in oks:
        i = bisect.bisect_right(invs, t1["_complete_index"])
        if i >= len(starts):
            continue
        horizon = suffix_min[i]
        for j in range(i, len(starts)):
            if invs[j] > horizon:
                break
            g.add(t1["_id"], starts[j]["_id"], RT)
    return g


def process_graph(oks: List[dict]) -> Graph:
    g = Graph()
    by_proc: Dict = {}
    for o in sorted(oks, key=lambda o: o["_invoke_index"]):
        by_proc.setdefault(o.get("process"), []).append(o)
    for chain in by_proc.values():
        for t1, t2 in zip(chain, chain[1:]):
            g.add(t1["_id"], t2["_id"], PROC)
    return g
