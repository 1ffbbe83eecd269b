"""Plain linearizability reference for cas-register histories.

Independent of the program: it imports nothing of it and reads the
generators' plain op dicts. It answers, for one key's history, what the
checker's result must say: whether the history is linearizable, and for
an invalid one the first completion at which no linearization of the
history so far exists (`fail-event`, counted over the ok completions of
the calls that take part) and that call (`op`).

Semantics (knossos's cas-register and crash rules, which the
configurations state):

- a register starting at nil; `write v` sets it; `cas [old new]` sets
  `new` where it holds `old` and is impossible elsewhere; `read v`
  must see `v`, and a read of nil sees anything;
- a `fail` completion means the call never happened: it is dropped;
- an `info` completion, or none, means the call may or may not have
  happened, at any point after its invocation: it stays open to the end
  of the history; a crashed read constrains nothing and is dropped;
- a call may take effect between its invocation and its completion.

The search is Wing and Gong's, with Lowe's memo of visited
configurations: depth first, taking at each step a call that no
unlinearized completed call precedes in real time. Completed calls are
tried in completion order before crashed ones, so a valid history is
mostly walked straight through. The fail event is found by bisection:
a prefix ending at completion r is linearizable exactly when the
search's frontier is still alive after r, and that property only falls
as r grows.

`crashed_dropped=True` is the control: the same search with the crash
rule broken, every crashed call treated as one that never happened.
"""

from __future__ import annotations

import json

OBSERVED_FS = ("read",)


class Call:
    __slots__ = ("process", "f", "value", "result", "inv", "ret")

    def __init__(self, process, f, value, inv):
        self.process, self.f, self.value = process, f, value
        self.result = None
        self.inv = inv
        self.ret = None          # position of the ok completion; None: crashed


def calls(ops: list, crashed_dropped: bool = False) -> list:
    """Pair invocations with completions by process. Failed calls and
    crashed reads are dropped (and, for the control, every crashed
    call)."""
    open_: dict = {}
    out: list = []
    failed: set = set()
    for i, o in enumerate(ops):
        p = o["process"]
        if o["type"] == "invoke":
            c = Call(p, o["f"], o.get("value"), i)
            open_[p] = c
            out.append(c)
            continue
        c = open_.pop(p, None)
        if c is None:
            continue
        if o["type"] == "ok":
            c.result, c.ret = o.get("value"), i
        elif o["type"] == "fail":
            failed.add(id(c))
    return [c for c in out if id(c) not in failed
            and not (c.ret is None and (c.f == "read" or crashed_dropped))]


def step(state, c: Call):
    """The register's next state after `c`, or the marker `_NO`."""
    if c.f == "write":
        return c.value
    if c.f == "cas":
        old, new = c.value
        return new if state == old else _NO
    if c.f == "read":
        v = c.result
        return state if v is None or v == state else _NO
    raise ValueError(f"unknown op {c.f!r}")


_NO = object()


def _linearizable(cs: list, horizon: int) -> bool:
    """Whether the calls invoked before position `horizon` can be
    linearized with every call that completed before it included.
    Calls completing at or after `horizon` are open, like crashed
    ones: they may be taken or not."""
    req = sorted((c for c in cs if c.ret is not None and c.ret < horizon),
                 key=lambda c: c.ret)
    opt = sorted((c for c in cs if c.inv < horizon
                  and (c.ret is None or c.ret >= horizon)),
                 key=lambda c: c.inv)
    n = len(req)
    # window[j]: ranks m >= j of required calls invoked before req[j]
    # completed — the required calls that may go next while req[j] is
    # the earliest completion not yet linearized
    by_inv = sorted(range(n), key=lambda m: req[m].inv)
    window: list = []
    live: set = set()
    k = 0
    for j in range(n):
        bound = req[j].ret
        while k < n and req[by_inv[k]].inv < bound:
            live.add(by_inv[k])
            k += 1
        live.discard(j - 1)
        window.append(tuple(sorted(m for m in live if m >= j)))

    def successors(j, ahead, taken, state):
        bound = req[j].ret if j < n else None
        for m in window[j] if j < n else ():
            if m in ahead:
                continue
            s = step(state, req[m])
            if s is _NO:
                continue
            if m == j:
                j2, a2 = j + 1, set(ahead)
                while j2 in a2:
                    a2.discard(j2)
                    j2 += 1
                yield (j2, frozenset(a2), taken, s)
            else:
                yield (j, ahead | {m}, taken, s)
        for o, c in enumerate(opt):
            if o in taken or (bound is not None and c.inv >= bound):
                continue
            s = step(state, c)
            if s is not _NO:
                yield (j, ahead, taken | {o}, s)

    start = (0, frozenset(), frozenset(), None)
    seen = {start}
    stack = [successors(*start)]
    if n == 0:
        return True
    while stack:
        nxt = next(stack[-1], None)
        if nxt is None:
            stack.pop()
            continue
        if nxt[0] == n:
            return True
        if nxt not in seen:
            seen.add(nxt)
            stack.append(successors(*nxt))
    return False


def canon(v):
    """A value in the form both sides are compared in."""
    return json.loads(json.dumps(v))


def check(ops: list, crashed_dropped: bool = False) -> dict:
    """{"valid?": bool, and for an invalid history "op" and
    "fail-event"} for one key's ops."""
    cs = calls(ops, crashed_dropped)
    if _linearizable(cs, len(ops) + 1):
        return {"valid?": True}
    rets = sorted((c for c in cs if c.ret is not None), key=lambda c: c.ret)
    lo, hi = 0, len(rets) - 1          # the first failing completion
    while lo < hi:
        mid = (lo + hi) // 2
        if _linearizable(cs, rets[mid].ret + 1):
            lo = mid + 1
        else:
            hi = mid
    c = rets[lo]
    value = c.result if c.f in OBSERVED_FS else c.value
    return {"valid?": False, "fail-event": lo,
            "op": {"process": c.process, "f": c.f, "value": canon(value),
                   "index": c.inv}}
