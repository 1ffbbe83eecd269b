"""`list_append`: one Jepsen list-append history (jepsen.tests.cycle.append)
from a seeded strict-serializable store.

Transactions follow elle's `wr-txns` defaults as the configuration sets
them: 1-2 micro-ops, each a read (`read_share`) or an append to one of
`key_count` active keys; a key retires after `max_writes_per_key`
appends, its values 1, 2, ... in invocation order. `processes` threads
work in a closed loop, one call open each; a call takes an exponential
time (mean 1, at least 0.05) and its thread waits an exponential 0.1
before the next. Each call completes :fail (`fail_share`, never
applied), :info (`info_share`, applied with probability `info_applies`;
its thread's process is replaced by process + `processes`) or :ok.
Every applied transaction takes effect atomically at a uniform point
inside its invoke-complete interval, and its reads see the store at
that point, its own earlier appends included.

Two faults, each in one history of `every` (by its position in the
pool):

- `stale_read`: one read of a key returns the key's list as of `lag`
  (drawn from the range) mean transaction durations before its
  invocation, where an append that completed before the invocation is
  then missing: a G-single through realtime edges;
- `info_read`: one crashed (:info) append is applied and a later read
  sees it (valid only because a crashed call may have committed).
"""

from __future__ import annotations

import heapq
import random

NS_PER_UNIT = 33_670_000     # 1 mean duration + 0.1 wait over 10 threads: 270 txn/s


def txn_stream(cfg: dict, rng: random.Random) -> list:
    """`cfg["txns"]` micro-op lists, [[f, k, v]], in invocation order."""
    active = list(range(cfg["key_count"]))
    next_key = cfg["key_count"]
    writes: dict = {}
    out = []
    for _ in range(cfg["txns"]):
        mops = []
        for _ in range(rng.randint(cfg["min_txn_length"],
                                   cfg["max_txn_length"])):
            k = active[rng.randrange(len(active))]
            if rng.random() < cfg["read_share"]:
                mops.append(["r", k, None])
                continue
            v = writes.get(k, 0) + 1
            if v > cfg["max_writes_per_key"]:
                active[active.index(k)] = k = next_key
                next_key += 1
                v = 1
            writes[k] = v
            mops.append(["append", k, v])
        out.append(mops)
    return out


class Txn:
    __slots__ = ("mops", "process", "inv", "comp", "outcome", "at", "reads")

    def __init__(self, mops, process, inv, comp, outcome):
        self.mops, self.process = mops, process
        self.inv, self.comp, self.outcome = inv, comp, outcome
        self.at = None           # apply time; None: never applied
        self.reads = None        # the ok completion's micro-ops


def schedule(cfg: dict, rng: random.Random) -> list:
    """Every transaction with its process, interval and outcome."""
    n_proc = cfg["processes"]
    free = [(rng.random() * 0.1, p) for p in range(n_proc)]
    heapq.heapify(free)
    txns = []
    for mops in txn_stream(cfg, rng):
        t, p = heapq.heappop(free)
        inv = t + rng.expovariate(10.0)
        comp = inv + max(0.05, rng.expovariate(1.0))
        u = rng.random()
        outcome = ("fail" if u < cfg["fail_share"] else
                   "info" if u < cfg["fail_share"] + cfg["info_share"]
                   else "ok")
        txn = Txn(mops, p, inv, comp, outcome)
        if outcome == "ok" or (outcome == "info"
                               and rng.random() < cfg["info_applies"]):
            txn.at = rng.uniform(inv, comp)
        txns.append(txn)
        heapq.heappush(free, (comp, p + n_proc if outcome == "info" else p))
    return txns


def apply_all(txns: list) -> dict:
    """Runs the store: fills each ok txn's reads; returns key ->
    [(apply time, value)] in apply order."""
    state: dict = {}
    log: dict = {}
    for t in sorted((t for t in txns if t.at is not None),
                    key=lambda t: t.at):
        done = []
        for f, k, v in t.mops:
            if f == "append":
                state.setdefault(k, []).append(v)
                log.setdefault(k, []).append((t.at, v, t))
                done.append([f, k, v])
            else:
                done.append([f, k, list(state.get(k, []))])
        t.reads = done
    return log


def stale_read(txns: list, log: dict, lag: float, rng: random.Random):
    """Makes one ok read return its key's list as of `lag` units before
    its invocation, where an ok append that completed before the
    invocation is then missing."""
    cands = [t for t in txns[len(txns) // 4:] if t.outcome == "ok"]
    rng.shuffle(cands)
    for t in cands:
        for i, (f, k, _) in enumerate(t.mops):
            if f != "r" or any(g == "append" and kk == k
                               for g, kk, _ in t.mops[:i]):
                continue
            then = t.inv - lag
            if not any(then < at and w.outcome == "ok" and w.comp < t.inv
                       for at, _, w in log.get(k, ())):
                continue
            t.reads[i] = ["r", k, [v for at, v, _ in log[k] if at <= then]]
            return
    raise ValueError("no read can be made stale")


def info_read(txns: list, rng: random.Random) -> None:
    """Applies one crashed append (where it has not applied already)
    that a later ok read of its key will see."""
    reads: dict = {}
    for t in txns:
        if t.outcome == "ok":
            for f, k, _ in t.mops:
                if f == "r":
                    reads.setdefault(k, []).append(t.at)
    cands = [t for t in txns if t.outcome == "info"
             and any(f == "append" for f, _, _ in t.mops)]
    rng.shuffle(cands)
    for t in cands:
        at = rng.uniform(t.inv, t.comp) if t.at is None else t.at
        if any(f == "append" and any(r > at for r in reads.get(k, ()))
               for f, k, _ in t.mops):
            t.at = at
            return
    raise ValueError("no crashed append can be read")


def history(txns: list) -> list:
    """The op dicts, invocations and completions in time order."""
    events = []
    for i, t in enumerate(txns):
        events.append((t.inv, 0, i))
        events.append((t.comp, 1, i))
    events.sort()
    ops = []
    for when, done, i in events:
        t = txns[i]
        op = {"type": "invoke", "process": t.process, "f": "txn",
              "value": [[f, k, None if f == "r" else v]
                        for f, k, v in t.mops],
              "time": int(when * NS_PER_UNIT)}
        if done:
            op["type"] = t.outcome
            if t.outcome == "ok":
                op["value"] = t.reads
        ops.append(op)
    return ops


def entry(cfg: dict, traffic_mix: dict, seed: int, position: int):
    """One history: [(None, ops)]."""
    rng = random.Random(seed)
    txns = schedule(cfg, rng)
    faults = traffic_mix.get("faults", {})
    on = {f for f, spec in faults.items()
          if position % spec["every"] == spec["at"]}
    if "info_read" in on:
        info_read(txns, rng)
    log = apply_all(txns)
    if "stale_read" in on:
        mean = sum(t.comp - t.inv for t in txns) / len(txns)
        stale_read(txns, log, mean * rng.uniform(*faults["stale_read"]["lag"]),
                   rng)
    return [(None, history(txns))]
