"""History generators of the benchmark: the yardstick's own copy.

Copied from the program's synthetic corpora (`rand_register_history`,
`adversarial_register_history` and `corrupt_history`), so that a later
change to the program cannot move the traffic it is measured on.
`tests/test_generators.py` pins each copy to the program's original at
the cells' parameters, while the two still agree.

Histories here are plain lists of op dicts (`type`, `process`, `f`,
`value`, `time`, `index`, and `error` on crashes): the harness wraps
them into the program's own history type, and the reference reads them
as they are. `n_ops` counts invocations; every invocation gets exactly
one completion row, so a history has `2 * n_ops` rows.

One addition, off by default: `readers` reserves the first `readers`
client threads for reads and gives the others writes and
compare-and-sets half and half, as the Tendermint cas-register
workload does (`gen.reserve n r (gen.mix [w cas])`). With `readers=0`
the draws are the original's, draw for draw.
"""

from __future__ import annotations

import random


def indexed(ops: list) -> list:
    """`ops` with each op's `index` set to its position."""
    for i, o in enumerate(ops):
        o["index"] = i
    return ops


def _simulate(n_ops, n_processes, busy, crash_p, seed,
              choose_op, complete_op, crash_op):
    """A true model state evolves; each op's effect is applied at its
    completion instant, inside its [invoke, complete] window, so the
    history is linearizable by construction. A crashed (:info) op's
    effect is applied or not by `crash_op`, and its process is retired
    for a fresh one, which keeps the crashed thread's role. `busy`
    biases toward opening new calls before completing pending ones.
    `choose_op(rng, thread)` picks (f, invoke value)."""
    rng = random.Random(seed)
    ops: list = []
    pending: dict = {}      # process -> (f, invoke value)
    free = list(range(n_processes))
    thread = {p: p for p in free}
    next_process = n_processes
    started = 0
    t = 0

    def emit(typ, process, f, val, **kw):
        nonlocal t
        t += rng.randint(1, 1000)
        ops.append({"type": typ, "process": process, "f": f,
                    "value": val, "time": t, **kw})

    while started < n_ops or pending:
        can_start = started < n_ops and free
        if can_start and (not pending or rng.random() < busy):
            p = free.pop(rng.randrange(len(free)))
            f, v = choose_op(rng, thread[p])
            emit("invoke", p, f, v)
            pending[p] = (f, v)
            started += 1
        else:
            p = rng.choice(list(pending))
            f, v = pending.pop(p)
            if rng.random() < crash_p:
                crash_op(rng, f, v)
                emit("info", p, f, v, error="indeterminate")
                thread[next_process] = thread[p]
                free.append(next_process)
                next_process += 1
            else:
                typ, out = complete_op(rng, f, v)
                emit(typ, p, f, out)
                free.append(p)
    return indexed(ops)


def _apply(value, f, v):
    if f == "write":
        return v, True
    if f == "cas":
        old, new = v
        if value == old:
            return new, True
        return value, False
    return value, True


def rand_register_history(n_ops=100, n_processes=5, n_values=5, cas=True,
                          crash_p=0.05, fail_p=0.05, busy=0.5,
                          seed=45100, readers=0) -> list:
    """A random cas-register history, linearizable by construction.
    Failed ops never apply; a crashed op applies with probability 1/2."""
    state = {"value": None}

    def choose(rng, thread):
        if readers:
            if thread < readers:
                return "read", None
            if cas and rng.random() < 0.5:
                return "cas", [rng.randrange(n_values),
                               rng.randrange(n_values)]
            return "write", rng.randrange(n_values)
        r = rng.random()
        if cas and r < 0.3:
            return "cas", [rng.randrange(n_values), rng.randrange(n_values)]
        if r < 0.6:
            return "write", rng.randrange(n_values)
        return "read", None

    def complete(rng, f, v):
        if f != "read" and rng.random() < fail_p:
            return "fail", v
        value = state["value"]
        if f == "read":
            return "ok", value
        new_value, ok = _apply(value, f, v)
        state["value"] = new_value
        return ("ok", v) if ok else ("fail", v)

    def crash(rng, f, v):
        if rng.random() < 0.5:
            state["value"] = _apply(state["value"], f, v)[0]

    return _simulate(n_ops, n_processes, busy, crash_p, seed,
                     choose, complete, crash)


def adversarial_register_history(n_ops=1000, k_crashed=12, n_values=5,
                                 seed=45100) -> list:
    """k concurrent crashed writes of distinct values (1000 + i), opened
    at the start and never completed, then a sequential write/read tail
    on process 0. Every crashed write stays open to the end, so a
    search carries about 2^k configurations through every later event.
    Reads return the last completed write."""
    rng = random.Random(seed)
    ops: list = []
    t = 0

    def emit(typ, process, f, val):
        nonlocal t
        t += 1
        ops.append({"type": typ, "process": process, "f": f,
                    "value": val, "time": t})

    for i in range(k_crashed):
        emit("invoke", 500 + i, "write", 1000 + i)
    state = None
    for j in range(max(0, n_ops - k_crashed)):
        if j % 2 == 0:
            v = rng.randrange(n_values)
            emit("invoke", 0, "write", v)
            emit("ok", 0, "write", v)
            state = v
        else:
            emit("invoke", 0, "read", None)
            emit("ok", 0, "read", state)
    return indexed(ops)


def corrupt_history(ops: list, seed=0, n_corruptions=1) -> list:
    """A copy of `ops` with `n_corruptions` ok reads claiming a value
    no write produced (the read value plus 1000)."""
    rng = random.Random(seed)
    out = [dict(o) for o in ops]
    targets = [i for i, o in enumerate(out)
               if o.get("type") == "ok" and o.get("f") in ("read", "dequeue")
               and o.get("value") is not None]
    for i in rng.sample(targets, min(n_corruptions, len(targets))):
        out[i]["value"] = (out[i]["value"] or 0) + 1000
    return indexed(out)
