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
for histories with thousands of transactions. A big graph is built as
typed int arrays (`ArrayGraph`), and the dict form is made only for
the code that walks it: an SCC's members, or the host's Tarjan. The
device path pads the node and edge counts to buckets (one compiled
program per bucket), scatters int32 edge arrays into the adjacency on
the device and fetches one component label per node; above
`DEVICE_SCC_MAX_NODES` it refuses, and `sccs` answers on the host and
names that engine.
"""

from __future__ import annotations

import itertools
import logging
import math
from collections import deque
from typing import Callable, Dict, List, Optional, Sequence, Set, Tuple, Union

from jepsen_tpu import obs

log = logging.getLogger(__name__)

# Edge types, in explanation-priority order.
WW, WR, RW, RT, PROC = "ww", "wr", "rw", "rt", "process"

# Device SCC pays off once the adjacency matrix is big enough to fill the
# MXU; below this we stay on host.
_DEVICE_SCC_MIN_NODES = 1024

# The most nodes the dense closure takes. It holds two bfloat16 n x n
# matrices (XLA fuses the squaring's float32 accumulation into its
# output): 12.25 GiB at 57,344 nodes, of a TPU v5e's 15.75 GiB; at
# 65,536 the compiler refuses it for HBM.
DEVICE_SCC_MAX_NODES = 57344

#: the engines `sccs` names: the dense closure on the device, Tarjan on
#: the host
DEVICE, HOST = "device", "host"


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

    def edge_count(self) -> int:
        """Distinct (a, b) pairs with an edge."""
        return sum(len(bs) for bs in self.out.values())

    def __len__(self):
        return len(self.out)


#: an edge type's bit in `ArrayGraph.types`
TYPE_BITS = {WW: 1, WR: 2, RW: 4, RT: 8, PROC: 16}
#: bit mask -> the edge types it holds
_TYPES_OF = [frozenset(t for t, bit in TYPE_BITS.items() if mask & bit)
             for mask in range(1 << len(TYPE_BITS))]


class ArrayGraph:
    """Typed edges over the nodes 0..n-1 as int arrays: the distinct
    (src, dst) pairs, self-loops dropped as `Graph.add` drops them, and
    `types`, each pair's bit mask over `TYPE_BITS`. It is what a big
    graph is built as; `subgraph` and `to_graph` give the dict form to
    the code that walks one."""

    def __init__(self, n: int, src, dst, types):
        self.n, self.src, self.dst, self.types = n, src, dst, types

    @classmethod
    def from_edges(cls, n: int, parts) -> "ArrayGraph":
        """From `parts`, an iterable of (src, dst, type): two int
        sequences of one length and the edge type they all have. A pair
        given more than once keeps every type it was given."""
        import numpy as np

        parts = list(parts)
        src = np.concatenate([np.asarray(s, np.int64) for s, _, _ in parts])
        dst = np.concatenate([np.asarray(d, np.int64) for _, d, _ in parts])
        bits = np.concatenate([np.full(len(s), TYPE_BITS[t], np.uint8)
                               for s, _, t in parts])
        keep = src != dst
        pair = src[keep] * n + dst[keep]
        order = np.argsort(pair, kind="stable")
        pair, bits = pair[order], bits[keep][order]
        starts = np.flatnonzero(np.diff(pair, prepend=-1))
        pair = pair[starts]
        return cls(n, pair // n, pair % n,
                   np.bitwise_or.reduceat(bits, starts))

    def __len__(self):
        return self.n

    def edge_count(self) -> int:
        """Distinct (src, dst) pairs with an edge."""
        return len(self.src)

    def subgraph(self, nodes) -> Graph:
        """The dict form induced on `nodes`: each of them, and every
        edge between two of them with its types."""
        import numpy as np

        members = np.zeros(self.n, bool)
        members[np.fromiter(nodes, np.int64)] = True
        return self._dict_form(np.flatnonzero(members),
                               members[self.src] & members[self.dst])

    def to_graph(self) -> Graph:
        """The whole dict form: every node and every edge."""
        import numpy as np

        return self._dict_form(range(self.n), np.ones(len(self.src), bool))

    def _dict_form(self, nodes, sel) -> Graph:
        g = Graph()
        out = g.out
        for a in nodes:
            out[int(a)] = {}
        for a, b, m in zip(self.src[sel].tolist(), self.dst[sel].tolist(),
                           self.types[sel].tolist()):
            out[a][b] = set(_TYPES_OF[m])
        return g


def expand_ranges(lo, hi):
    """(rows, cols): for each i, the positions lo[i]..hi[i]-1 in cols,
    with i repeated as often in rows: the ranges laid end to end."""
    import numpy as np

    counts = np.maximum(np.asarray(hi) - np.asarray(lo), 0)
    rows = np.repeat(np.arange(len(counts)), counts)
    starts = np.cumsum(counts) - counts
    cols = np.arange(int(counts.sum())) - starts[rows] + np.asarray(lo)[rows]
    return rows, cols


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
    """The jitted closure program, built ONCE and memoized in a module
    global. jax stays a lazy import (this module must be usable with no
    backend), but the wrapper must not be re-created per device_sccs
    call — a fresh jax.jit each call would never reuse the compile cache
    (found by `jepsen-tpu lint`, recompile-closure-capture); the memo
    makes the jit effectively module-level, so the suppression below
    records intent, not a hazard.

    `labels(src, dst, n, steps)`: the [n, n] adjacency scattered from
    the int32 edge arrays (an index of n or more is padding and is
    dropped), R := A | I squared `steps` times (boolean matmul =
    bfloat16 dot > 0, float32 accumulation), and each node's label: the
    smallest j with R[i, j] & R[j, i]."""
    global _scc_closure_jit
    if _scc_closure_jit is not None:
        return _scc_closure_jit
    import jax
    import jax.numpy as jnp
    from jax import lax

    def labels(src, dst, n: int, steps: int):
        diag = jnp.arange(n)
        r = jnp.zeros((n, n), jnp.bfloat16)
        r = r.at[src, dst].set(1, mode="drop").at[diag, diag].set(1)

        def body(_, r):
            rr = jnp.dot(r, r, preferred_element_type=jnp.float32)
            return jnp.minimum(rr, 1.0).astype(jnp.bfloat16)

        r = lax.fori_loop(0, steps, body, r)
        both = jnp.logical_and(r > 0, r.T > 0)
        return jnp.argmax(both, axis=1).astype(jnp.int32)

    # jepsen-lint: disable=recompile-closure-capture
    _scc_closure_jit = jax.jit(labels, static_argnums=(2, 3))
    return _scc_closure_jit


def node_bucket(n: int) -> int:
    """The padded node count the closure runs at: the next power of two
    (at least 128) up to 1,024, then the next multiple of 1,024."""
    if n <= 1024:
        return max(128, 1 << max(0, n - 1).bit_length())
    return -(-n // 1024) * 1024


def edge_bucket(e: int) -> int:
    """The padded edge count: the next power of two, at least 1,024."""
    return max(1024, 1 << max(0, e - 1).bit_length())


def closure_steps(n_pad: int) -> int:
    """Squarings that close paths of every length in an n_pad-node
    graph: ceil(log2 n_pad)."""
    return max(1, math.ceil(math.log2(max(2, n_pad))))


def closure_flops(n_pad: int) -> int:
    """Multiply-adds of one closure, counted as 2 flops each."""
    return closure_steps(n_pad) * 2 * n_pad ** 3


def edge_arrays(g: Union[Graph, ArrayGraph]):
    """(ids, src, dst): the graph's node ids in sorted order and its
    edges as int32 positions into them, padded to `edge_bucket` with
    the out-of-range position `node_bucket(len(ids))`. An `ArrayGraph`'s
    ids are its positions already."""
    import numpy as np

    if isinstance(g, ArrayGraph):
        ids, heads, tails = list(range(g.n)), g.src, g.dst
    else:
        ids = np.array(sorted(g.out), dtype=np.int64)
        fan = np.fromiter(map(len, g.out.values()), np.int64,
                          count=len(g.out))
        heads = np.repeat(np.fromiter(g.out, np.int64, count=len(g.out)),
                          fan)
        tails = np.fromiter(itertools.chain.from_iterable(g.out.values()),
                            np.int64, count=int(fan.sum()))
        heads, tails = np.searchsorted(ids, heads), np.searchsorted(ids, tails)
        ids = ids.tolist()
    n_pad = node_bucket(len(ids))
    e = len(heads)
    src = np.full(edge_bucket(e), n_pad, dtype=np.int32)
    dst = np.full(edge_bucket(e), n_pad, dtype=np.int32)
    src[:e] = heads
    dst[:e] = tails
    return ids, src, dst


def device_sccs(g: Union[Graph, ArrayGraph]) -> List[List[int]]:
    """SCCs (more than one node) via the MXU transitive closure: the
    edge arrays go to the device, the adjacency is built there at the
    padded node count, closed by ceil(log2 n_pad) squarings, and only
    the n int32 component labels come back. Padding nodes have no
    edges, so each is its own label and adds no component. Raises
    ValueError above `DEVICE_SCC_MAX_NODES`."""
    import jax
    import numpy as np

    n = len(g)
    if n > DEVICE_SCC_MAX_NODES:
        raise ValueError(
            f"{n} nodes: over the device closure's ceiling of "
            f"{DEVICE_SCC_MAX_NODES}")
    if n == 0:
        return []
    with obs.span("elle.sccs.place"):
        ids, src, dst = edge_arrays(g)
        src, dst = jax.device_put(src), jax.device_put(dst)
    n_pad = node_bucket(n)
    steps = closure_steps(n_pad)
    with obs.span("elle.sccs.device", n=n_pad, edges=int(src.shape[0])):
        lab = _get_scc_closure()(src, dst, n_pad, steps)
        lab.block_until_ready()
    with obs.span("elle.sccs.fetch"):
        lab = np.asarray(lab)[:n]
    obs.counter("elle.scc.padded_nodes").inc(n_pad)
    obs.counter("elle.scc.closure_calls").inc()
    obs.counter("elle.scc.closure_flops").inc(closure_flops(n_pad))
    order = np.argsort(lab, kind="stable")
    cuts = np.flatnonzero(np.diff(lab[order])) + 1
    return [[ids[j] for j in comp.tolist()]
            for comp in np.split(order, cuts) if len(comp) > 1]


def sccs(g: Union[Graph, ArrayGraph]) -> Tuple[List[List[int]], str]:
    """(SCCs with more than one node, the engine that found them): the
    device closure from `_DEVICE_SCC_MIN_NODES` nodes up to
    `DEVICE_SCC_MAX_NODES`, Tarjan on the host otherwise (over an
    `ArrayGraph`'s dict form). Over the ceiling it logs why the host
    answers."""
    n = len(g)
    obs.counter("elle.nodes").inc(n)
    obs.counter("elle.edges").inc(g.edge_count())
    engine = DEVICE if n >= _DEVICE_SCC_MIN_NODES else HOST
    if engine == DEVICE and n > DEVICE_SCC_MAX_NODES:
        log.warning("elle: %d nodes are over the device closure's ceiling "
                    "of %d; SCCs found on the host", n, DEVICE_SCC_MAX_NODES)
        engine = HOST
    with obs.span("elle.sccs", engine=engine, nodes=n):
        if engine == DEVICE:
            comps = device_sccs(g)
        else:
            comps = tarjan_sccs(g.to_graph() if isinstance(g, ArrayGraph)
                                else g)
    return comps, engine


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
    return classify(g, sccs(g)[0], explainer, by_id)


def classify(g: Graph, comps: List[List[int]],
             explainer: Optional[Callable] = None,
             by_id: Optional[Dict[int, dict]] = None) -> Dict[str, list]:
    """`cycle_anomalies` over SCCs already found: one case per SCC, in
    the most severe class it has a cycle of."""
    if explainer is None:
        explainer = _default_explainer(by_id or {})
    found: Dict[str, list] = {}
    for comp in comps:
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


def realtime_edges(oks: List[dict]):
    """rt edges as (src, dst) int arrays of `_id`s: T1's completion
    precedes T2's invocation. Uses the reduced form: edge only from each
    txn to the txns invoked after it and before any later completion
    (transitively implied edges dropped). oks carry "_invoke_index",
    "_complete_index" and "_id" annotations."""
    import numpy as np

    ids = np.array([t["_id"] for t in oks], np.int64)
    inv = np.array([t["_invoke_index"] for t in oks], np.int64)
    comp = np.array([t["_complete_index"] for t in oks], np.int64)
    order = np.argsort(inv, kind="stable")
    invs = inv[order]
    # suffix_min[i] = min complete index among the txns invoked i-th on
    suffix_min = np.minimum.accumulate(comp[order][::-1])[::-1]
    lo = np.searchsorted(invs, comp, side="right")
    has = lo < len(oks)
    hi = np.zeros_like(lo)
    hi[has] = np.searchsorted(invs, suffix_min[lo[has]], side="right")
    rows, cols = expand_ranges(lo, hi)
    return ids[rows], ids[order][cols]


def realtime_graph(oks: List[dict]) -> Graph:
    """`realtime_edges` as a `Graph` over every txn."""
    g = Graph()
    for t in oks:
        g.add_node(t["_id"])
    for a, b in zip(*(e.tolist() for e in realtime_edges(oks))):
        g.add(a, b, RT)
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
