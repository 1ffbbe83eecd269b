"""Plain reference for Jepsen list-append histories (elle's
list-append under strict serializability).

Independent of the program: it imports nothing of it and reads the
generator's plain op dicts. It answers, for one history, what the
checker's result must say: the verdict, the set of anomaly types, and
how many cases of each type. `cycles_hold` checks the cycles a result
reports against this module's own edges.

Definitions (elle's list-append, as the configuration states them):

- a transaction is its invocation paired with the next completion of
  the same process (an :ok completion with none is its own
  invocation); only :ok ones are nodes, numbered in the order they
  complete. A :fail transaction never happened: a read of one of
  its appends is G1a. An :info one may or may not have happened: its
  appends are nobody's, and no edge leads to or from it;
- a read of a key returns the key's whole list, so every read must be a
  prefix of the longest read of that key; a key where two reads
  disagree is one `incompatible-order` case, and it gives no ww edges;
- ww: the appends of two consecutive values of the longest read;
- wr: the append of a read's last value, to the reader;
- rw: a read of k to every :ok append of k the read does not show: the
  read is a prefix of the key's final order, so each such append came
  after it (this covers appends past the longest read too);
- rt (realtime): T1 completed before T2 was invoked. Here as a reduced
  graph with the same reachability (a completed transaction links to
  each later invocation until a transaction that follows it completes),
  the one departure from the definition; `cycles_hold` reads the
  definition itself;
- internal: a read that disagrees with the transaction's own earlier
  read and appends of the key; G1b: a read whose last value is another
  transaction's non-final append of that key;
- each strongly connected component of ww + wr + rw + rt (found by
  this module's own Tarjan) is one case, in the most severe class it
  has a cycle of: G0 (ww, rt), G1c (ww, wr, rt), G-single (one rw edge,
  the rest ww, wr, rt), else G2.

`crashed_dropped=True` is the control: every :info transaction is
treated as failed, so a read of a crashed append becomes G1a.
"""

from __future__ import annotations

WW, WR, RW, RT = "ww", "wr", "rw", "rt"
CLASSES = (("G0", {WW, RT}), ("G1c", {WW, WR, RT}))
REST = {WW, WR, RT}


class Txns:
    """The :ok transactions of a history, and the appends that failed."""

    def __init__(self, ops: list, crashed_dropped: bool = False):
        self.mops, self.inv, self.comp = [], [], []
        self.failed: dict = {}           # key -> values of failed appends
        start: dict = {}
        for pos, o in enumerate(ops):
            if o["f"] != "txn":
                continue
            p = o["process"]
            if o["type"] == "invoke":
                start[p] = (pos, o)
                continue
            at, inv = start.pop(p, (pos, None))
            if o["type"] == "ok":
                self.mops.append(o["value"])
                self.inv.append(at)
                self.comp.append(pos)
            elif inv is not None and (o["type"] == "fail"
                                      or crashed_dropped):
                for f, k, v in inv["value"]:
                    if f == "append":
                        self.failed.setdefault(k, set()).add(v)

    def reads(self):
        """(txn, key, list) for every read that returned a list."""
        for t, mops in enumerate(self.mops):
            for f, k, v in mops:
                if f == "r" and v is not None:
                    yield t, k, list(v)


def internal(txns: Txns) -> int:
    n = 0
    for mops in txns.mops:
        seen: dict = {}                  # key -> (list read or None, own)
        for f, k, v in mops:
            if f == "append":
                known, own = seen.get(k, (None, []))
                seen[k] = (known, own + [v])
                continue
            got = list(v or [])
            if k in seen:
                known, own = seen[k]
                if (got != known + own if known is not None
                        else own and got[-len(own):] != own):
                    n += 1
                    continue
            seen[k] = (got, [])
    return n


def intermediate(txns: Txns) -> dict:
    """key -> value -> txn, for each append that is not its txn's last
    append of that key."""
    out: dict = {}
    for t, mops in enumerate(txns.mops):
        apps = [(k, v) for f, k, v in mops if f == "append"]
        for i, (k, v) in enumerate(apps):
            if any(k2 == k for k2, _ in apps[i + 1:]):
                out.setdefault(k, {})[v] = t
    return out


class Graph:
    """The dependency edges: (a, b) -> set of types, and successors."""

    def __init__(self, n: int):
        self.n = n
        self.types: dict = {}
        self.succ = [set() for _ in range(n)]

    def add(self, a: int, b: int, typ: str) -> None:
        if a != b:
            self.types.setdefault((a, b), set()).add(typ)
            self.succ[a].add(b)


def dependencies(txns: Txns):
    """(graph, incompatible keys) for one history."""
    g = Graph(len(txns.mops))
    appender: dict = {}
    for t, mops in enumerate(txns.mops):
        for f, k, v in mops:
            if f == "append":
                appender.setdefault(k, {})[v] = t
    longest: dict = {}
    by_key: dict = {}
    for t, k, v in txns.reads():
        by_key.setdefault(k, []).append(v)
        if len(v) > len(longest.get(k, [])):
            longest[k] = v
    bad = 0
    for k, order in longest.items():
        if any(order[:len(r)] != r for r in by_key[k]):
            bad += 1
            continue
        writers = appender.get(k, {})
        for v1, v2 in zip(order, order[1:]):
            if v1 in writers and v2 in writers:
                g.add(writers[v1], writers[v2], WW)
    for t, k, v in txns.reads():
        writers = appender.get(k, {})
        if v and v[-1] in writers:
            g.add(writers[v[-1]], t, WR)
        shown = set(v)
        for x, w in writers.items():
            if x not in shown:
                g.add(t, w, RW)
    realtime(txns, g)
    return g, bad


def realtime(txns: Txns, g: Graph) -> None:
    """Reduced rt edges: at each invocation, an edge from every
    transaction on the frontier (completed, and no transaction it
    precedes has completed yet); a completion takes its predecessors
    off the frontier and joins it."""
    events = sorted([(p, 0, t) for t, p in enumerate(txns.inv)]
                    + [(p, 1, t) for t, p in enumerate(txns.comp)])
    frontier: set = set()
    before: dict = {}
    for _, done, t in events:
        if done:
            frontier -= before.pop(t)
            frontier.add(t)
        else:
            before[t] = set(frontier)
            for a in frontier:
                g.add(a, t, RT)


def tarjan(n: int, succ) -> list:
    """Strongly connected components of more than one node."""
    index = [-1] * n
    low = [0] * n
    on = [False] * n
    stack: list = []
    out = []
    counter = 0
    for root in range(n):
        if index[root] >= 0:
            continue
        work = [(root, iter(sorted(succ[root])))]
        index[root] = low[root] = counter
        counter += 1
        stack.append(root)
        on[root] = True
        while work:
            v, it = work[-1]
            w = next(it, None)
            if w is not None:
                if index[w] < 0:
                    index[w] = low[w] = counter
                    counter += 1
                    stack.append(w)
                    on[w] = True
                    work.append((w, iter(sorted(succ[w]))))
                elif on[w]:
                    low[v] = min(low[v], index[w])
                continue
            work.pop()
            if work:
                u = work[-1][0]
                low[u] = min(low[u], low[v])
            if low[v] == index[v]:
                comp = []
                while True:
                    w = stack.pop()
                    on[w] = False
                    comp.append(w)
                    if w == v:
                        break
                if len(comp) > 1:
                    out.append(comp)
    return out


def _reach(g: Graph, comp: set, types: set, src: int) -> set:
    """Nodes of `comp` that `src` reaches over edges of `types`."""
    seen = {src}
    todo = [src]
    while todo:
        a = todo.pop()
        for b in g.succ[a]:
            if b in comp and b not in seen and g.types[(a, b)] & types:
                seen.add(b)
                todo.append(b)
    return seen


def _cyclic(g: Graph, comp: set, types: set) -> bool:
    return any(a in _reach(g, comp, types, b) for a in comp
               for b in g.succ[a] if b in comp and g.types[(a, b)] & types)


def classify(g: Graph, comp: list) -> str:
    c = set(comp)
    for name, types in CLASSES:
        if _cyclic(g, c, types):
            return name
    for a in comp:
        for b in g.succ[a]:
            if (b in c and RW in g.types[(a, b)]
                    and a in _reach(g, c, REST, b)):
                return "G-single"
    return "G2"


def check(ops: list, crashed_dropped: bool = False) -> dict:
    """{"valid?", "anomaly-types", "counts", "cycles_hold"} for one
    history."""
    txns = Txns(ops, crashed_dropped)
    counts: dict = {}

    def found(name, n=1):
        if n:
            counts[name] = counts.get(name, 0) + n

    found("internal", internal(txns))
    inter = intermediate(txns)
    for t, k, v in txns.reads():
        found("G1a", sum(1 for x in v if x in txns.failed.get(k, ())))
        if v and inter.get(k, {}).get(v[-1], t) != t:
            found("G1b")
    g, bad = dependencies(txns)
    found("incompatible-order", bad)
    for comp in tarjan(g.n, g.succ):
        found(classify(g, comp))
    return {"valid?": not counts, "anomaly-types": sorted(counts),
            "counts": counts, "cycles_hold": True}


class Edges:
    """What `cycles_hold` reads: ww, wr, rw edges of one history, and
    its transactions' intervals for rt."""

    def __init__(self, ops: list):
        self.txns = Txns(ops)
        g, _ = dependencies(self.txns)
        self.types = {e: ts - {RT} for e, ts in g.types.items()}

    def of(self, a: int, b: int) -> set:
        ts = set(self.types.get((a, b), ()))
        if self.txns.comp[a] < self.txns.inv[b]:
            ts.add(RT)
        return ts


def cycle_holds(edges: Edges, name: str, cycle: list) -> bool:
    """Whether `cycle` ([T0, T1, ..., T0], :ok transaction numbers) is a
    cycle of this history whose edges meet class `name`."""
    n = len(edges.txns.mops)
    if (len(cycle) < 3 or cycle[0] != cycle[-1]
            or not all(isinstance(t, int) and 0 <= t < n for t in cycle)):
        return False
    steps = [edges.of(a, b) for a, b in zip(cycle, cycle[1:])]
    if not all(steps):
        return False
    if name == "G0":
        return all(s & {WW, RT} for s in steps)
    if name == "G1c":
        return all(s & REST for s in steps) and any(WR in s for s in steps)
    if name == "G-single":
        return any(RW in s and all(o & REST for j, o in enumerate(steps)
                                   if j != i)
                   for i, s in enumerate(steps))
    if name == "G2":
        return any(RW in s for s in steps)
    return False
