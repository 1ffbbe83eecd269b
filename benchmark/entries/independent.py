"""`independent`: `independent.checker(linearizable(<model>,
algorithm="jax"))`, the checker the Tendermint suite builds, one check
a call; the history's values are `KV(key, value)` tuples."""

from __future__ import annotations

import program

path_notes = program.path_notes


def checker(cfg: dict):
    from jepsen_tpu import independent
    return independent.checker(program.linearizable(cfg))


def check(built, history) -> dict:
    return built.check({}, history, {})


def wrap(entry: list):
    """The program's history for one pool entry ([(key, ops)])."""
    from jepsen_tpu.history import History
    from jepsen_tpu.independent import KV
    ops = []
    for k, key_ops in entry:
        for o in key_ops:
            o = dict(o)
            o["value"] = KV(k, o.get("value"))
            ops.append(o)
    return History.wrap(ops).index()


def answers(result: dict, entry: list) -> dict:
    """{"valid?", "failures", "keys": {key: answer}} of one result."""
    return {"valid?": result.get("valid?"),
            "failures": list(result.get("failures") or []),
            "keys": {k: program.key_answer(r)
                     for k, r in (result.get("results") or {}).items()}}
