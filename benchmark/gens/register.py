"""`register`: a jepsen.independent history of `keys` cas-register
keys, one key after the next as the Tendermint workload's single group
of `processes_per_key` threads works them. Every key is the
generator's draw as it comes: nothing is redrawn or filtered, so each
key's open-call window (the most calls open at once, which sets the
width of its search) follows the source's own distribution. Two
faults, each in one history of `every` (by its position in the pool),
on one key drawn from the seed:

- `corrupt` makes one ok read claim a value no write produced (the
  history is invalid);
- `indeterminate` appends a write of a fresh value that completes
  `:info`, then a read by another process that sees that value (valid
  only because a crashed write may have taken effect).
"""

from __future__ import annotations

import random

import generators
import traffic


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


def entry(cfg: dict, traffic_mix: dict, seed: int, position: int):
    """One keyed history: [(key, ops)], every key's processes from 0."""
    n_keys = traffic_mix["keys"]
    seeds = traffic.sub_seeds(seed, n_keys + 2)
    keys = [(k, _register_key(cfg, seeds[k])) for k in range(n_keys)]
    pick = random.Random(seeds[n_keys])
    for fault, spec in traffic_mix.get("faults", {}).items():
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
