"""The general traffic generator: a pool of histories from a seed.

A cell names a configuration (`configs/<name>.json`: the deployment's
shapes, which generator makes its histories, which checker entry reads
them) and a traffic mix (`traffic/<name>.json`: how many keys a history
holds, how many distinct histories the pool holds, which of them carry
a fault). This module reads both and returns the pool. Two generators
exist:

- `register`: a jepsen.independent history of `keys` cas-register keys,
  one key after the next as the Tendermint workload's single group of
  `processes_per_key` threads works them. Each key's process ids are
  offset by `PROCESS_STRIDE * key`, so no process id repeats across
  keys. Every key is the generator's draw as it comes: nothing is
  redrawn or filtered, so each key's open-call window (the most calls
  open at once, which sets the width of its search) follows the
  source's own distribution. Two faults, each in one history of
  `every` (by its position in the pool), on one key drawn from the
  seed:
  `corrupt` makes one ok read claim a value no write produced (the
  history is invalid); `indeterminate` appends a write of a fresh value
  that completes `:info`, then a read by another process that sees that
  value (valid only because a crashed write may have taken effect).
- `crashed_writes`: one key with `crashed_writes` writes open forever,
  then a sequential tail. `observed_reads` of the tail's last reads see
  crashed writes 0, 1, ... instead of the last completed write (valid
  for the same reason).

Where the program pads a batch to its widest and longest key and
compiles one program per padded shape, histories drawn afresh from
each seed would change both the work and the programs from seed to
seed. A mix that fixes `structure_seed` therefore draws its histories'
shapes once, from that seed, and the run's seed only reorders them:
the pool's order, the keys' order, and the names of the values.
"""

from __future__ import annotations

import json
import os
import random

import generators

PROCESS_STRIDE = 1000


def load(root: str, kind: str, name: str) -> dict:
    """`<root>/benchmark/<kind>/<name>.json`."""
    path = os.path.join(root, "benchmark", kind, name + ".json")
    with open(path) as f:
        return json.load(f)


def _sub_seeds(seed: int, n: int) -> list:
    rng = random.Random(seed)
    return [rng.getrandbits(31) for _ in range(n)]


def _register_key(cfg: dict, seed: int) -> list:
    return generators.rand_register_history(
        n_ops=cfg["ops_per_key"], n_processes=cfg["processes_per_key"],
        n_values=cfg["values"], crash_p=cfg["crash_p"],
        fail_p=cfg["fail_p"], busy=cfg["busy"], seed=seed,
        readers=cfg["readers"])


def _indeterminate(ops: list, value, process: int) -> list:
    """Append a write of `value` that crashes, then a read seeing it."""
    t = ops[-1]["time"] if ops else 0
    tail = [{"type": "invoke", "process": process, "f": "write",
             "value": value, "time": t + 1},
            {"type": "info", "process": process, "f": "write",
             "value": value, "time": t + 2, "error": "indeterminate"},
            {"type": "invoke", "process": process + 1, "f": "read",
             "value": None, "time": t + 3},
            {"type": "ok", "process": process + 1, "f": "read",
             "value": value, "time": t + 4}]
    return generators.indexed([dict(o) for o in ops] + tail)


def _offset(ops: list, key: int) -> list:
    out = []
    for o in ops:
        o = dict(o)
        o["process"] += PROCESS_STRIDE * key
        out.append(o)
    return out


def register_entry(cfg: dict, traffic: dict, seed: int, position: int):
    """One keyed history: [(key, ops)], every key's processes from 0."""
    n_keys = traffic["keys"]
    seeds = _sub_seeds(seed, n_keys + 2)
    keys = [(k, _register_key(cfg, seeds[k])) for k in range(n_keys)]
    pick = random.Random(seeds[n_keys])
    for fault, spec in traffic.get("faults", {}).items():
        if position % spec["every"] != spec["at"]:
            continue
        k = pick.randrange(n_keys)
        ops = keys[k][1]
        if fault == "corrupt":
            ops = generators.corrupt_history(ops, seed=seeds[n_keys + 1])
        elif fault == "indeterminate":
            top = max(o["process"] for o in ops) + 1
            ops = _indeterminate(ops, cfg["values"], top)
        else:
            raise ValueError(f"unknown fault {fault!r}")
        keys[k] = (k, ops)
    return keys


def crashed_writes_entry(cfg: dict, traffic: dict, seed: int, position: int):
    ops = generators.adversarial_register_history(
        n_ops=cfg["ops"], k_crashed=cfg["crashed_writes"],
        n_values=cfg["values"], seed=seed)
    reads = [i for i, o in enumerate(ops)
             if o["type"] == "ok" and o["f"] == "read"]
    m = traffic.get("observed_reads", 0)
    for i, pos in enumerate(reads[len(reads) - m:] if m else []):
        ops[pos]["value"] = 1000 + i
    return [(None, ops)]


GENERATORS = {"register": register_entry,
              "crashed_writes": crashed_writes_entry}


def relabel(ops: list, perm: list) -> list:
    """`ops` with every register value v < len(perm) renamed perm[v],
    and a value 1000 + v that no write produced renamed 1000 + perm[v]:
    a bijection, so every verdict, fail event and shape stays."""
    n = len(perm)

    def f(x):
        if isinstance(x, list):
            return [f(y) for y in x]
        if isinstance(x, int) and 0 <= x < n:
            return perm[x]
        if isinstance(x, int) and 1000 <= x < 1000 + n:
            return 1000 + perm[x - 1000]
        return x

    return [dict(o, value=f(o.get("value"))) for o in ops]


def _reorder(entries: list, n_values: int, seed: int) -> list:
    """The same histories in another order: the pool rotated, each
    history's keys shuffled, each key's values renamed."""
    rng = random.Random(seed)
    turn = rng.randrange(len(entries))
    out = []
    for entry in entries[turn:] + entries[:turn]:
        keys = [ops for _, ops in entry]
        rng.shuffle(keys)
        out.append([(k, relabel(ops, rng.sample(range(n_values), n_values)))
                    for k, ops in enumerate(keys)])
    return out


def pool(cfg: dict, traffic: dict, seed: int) -> list:
    """`traffic["pool"]` distinct histories, each a list of (key, ops);
    key is None for a single-key history. Where the mix fixes a
    `structure_seed`, the histories' shapes come from it, and `seed`
    reorders them (`_reorder`): every seed then runs the same programs
    on the same amount of work. Otherwise `seed` makes them."""
    make = GENERATORS[cfg["generator"]]
    base = traffic.get("structure_seed")
    seeds = _sub_seeds(seed if base is None else base, traffic["pool"])
    entries = [make(cfg, traffic, s, i) for i, s in enumerate(seeds)]
    if base is not None:
        entries = _reorder(entries, cfg["values"], seed)
    return [[(k, ops if k is None else _offset(ops, k)) for k, ops in e]
            for e in entries]


def n_invocations(entry: list) -> int:
    return sum(1 for _, ops in entry for o in ops if o["type"] == "invoke")
