"""The general traffic generator: a pool of histories from a seed.

A cell names a configuration (`configs/<name>.json`: the deployment's
shapes, which generator makes its histories, which checker entry reads
them, which reference answers them) and a traffic mix
(`traffic/<name>.json`: how many keys a history holds, how many
distinct histories the pool holds, which of them carry a fault). This
module reads both and returns the pool; the configuration's `generator`
(`gens/<name>.py`, found by name) makes each entry of it. In a keyed
entry each key's process ids are offset by `PROCESS_STRIDE * key`, so
no process id repeats across keys.

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

import named

PROCESS_STRIDE = 1000


def load(root: str, kind: str, name: str) -> dict:
    """`<root>/benchmark/<kind>/<name>.json`."""
    path = os.path.join(root, "benchmark", kind, name + ".json")
    with open(path) as f:
        return json.load(f)


def sub_seeds(seed: int, n: int) -> list:
    rng = random.Random(seed)
    return [rng.getrandbits(31) for _ in range(n)]


def _offset(ops: list, key: int) -> list:
    out = []
    for o in ops:
        o = dict(o)
        o["process"] += PROCESS_STRIDE * key
        out.append(o)
    return out


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


def pool(root: str, cfg: dict, traffic: dict, seed: int) -> list:
    """`traffic["pool"]` distinct histories, each a list of (key, ops);
    key is None for a single-key history. Where the mix fixes a
    `structure_seed`, the histories' shapes come from it, and `seed`
    reorders them (`_reorder`): every seed then runs the same programs
    on the same amount of work. Otherwise `seed` makes them."""
    make = named.load(root, "gens", cfg["generator"]).entry
    base = traffic.get("structure_seed")
    seeds = sub_seeds(seed if base is None else base, traffic["pool"])
    entries = [make(cfg, traffic, s, i) for i, s in enumerate(seeds)]
    if base is not None:
        entries = _reorder(entries, cfg.get("values", 0), seed)
    return [[(k, ops if k is None else _offset(ops, k)) for k, ops in e]
            for e in entries]


def n_invocations(entry: list) -> int:
    return sum(1 for _, ops in entry for o in ops if o["type"] == "invoke")
