"""`crashed_writes`: one key with `crashed_writes` writes open forever,
then a sequential tail. `observed_reads` of the tail's last reads see
crashed writes 0, 1, ... instead of the last completed write (valid
only because a crashed write may have taken effect)."""

from __future__ import annotations

import generators


def entry(cfg: dict, traffic_mix: dict, seed: int, position: int):
    ops = generators.adversarial_register_history(
        n_ops=cfg["ops"], k_crashed=cfg["crashed_writes"],
        n_values=cfg["values"], seed=seed)
    reads = [i for i, o in enumerate(ops)
             if o["type"] == "ok" and o["f"] == "read"]
    m = traffic_mix.get("observed_reads", 0)
    for i, pos in enumerate(reads[len(reads) - m:] if m else []):
        ops[pos]["value"] = 1000 + i
    return [(None, ops)]
