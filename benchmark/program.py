"""The boundary to the system under test.

Builds the checker a configuration names, wraps the generators' plain
histories into the program's history type, and reads back from each
result the answers that are compared with the reference. Nothing else
of the benchmark imports the program.

Entries:
- `independent`: `independent.checker(linearizable(CASRegister(),
  algorithm="jax"))`, the checker the Tendermint suite builds; the
  history's values are `KV(key, value)` tuples;
- `single`: `linearizable(CASRegister(), algorithm="jax")` on one key.
"""

from __future__ import annotations

import reference


def checker(entry: str):
    from jepsen_tpu import independent
    from jepsen_tpu.checker import linearizable
    from jepsen_tpu.models import CASRegister
    lin = linearizable(CASRegister(), algorithm="jax")
    if entry == "independent":
        return independent.checker(lin)
    if entry == "single":
        return lin
    raise ValueError(f"unknown checker entry {entry!r}")


def history(entry: list):
    """The program's history for one pool entry ([(key, ops)])."""
    from jepsen_tpu.history import History
    from jepsen_tpu.independent import KV
    if len(entry) == 1 and entry[0][0] is None:
        return History.wrap(dict(o) for o in entry[0][1]).index()
    ops = []
    for k, key_ops in entry:
        for o in key_ops:
            o = dict(o)
            o["value"] = KV(k, o.get("value"))
            ops.append(o)
    return History.wrap(ops).index()


def _key_answer(r: dict) -> dict:
    a = {"valid?": r.get("valid?")}
    if a["valid?"] is False:
        op = r.get("op") or {}
        a["fail-event"] = r.get("fail-event")
        a["op"] = {k: reference.canon(op.get(k))
                   for k in ("process", "f", "value", "index")}
    return a


def answers(result: dict, entry: list) -> dict:
    """{"valid?", "failures", "keys": {key: answer}} of one result."""
    if entry[0][0] is None:
        a = _key_answer(result)
        return {"valid?": a["valid?"], "failures": None, "keys": {None: a}}
    return {"valid?": result.get("valid?"),
            "failures": list(result.get("failures") or []),
            "keys": {k: _key_answer(r)
                     for k, r in (result.get("results") or {}).items()}}


FALLBACK_NOTES = ("device-fallback", "resilience", "fallback")


def path_notes(result: dict) -> dict:
    """What path the program took: the closure kinds its keys report,
    and how many keys the device did not answer. A device engine names
    its closure in each key's result and a host engine names none; a
    fallback to the host leaves a note on the key, or on the whole
    check, where every key then counts."""
    rs = ([result] if "results" not in result
          else list(result["results"].values()))
    closures = sorted({str(r.get("closure")) for r in rs
                       if r.get("closure") is not None})
    if "results" in result and any(k in result for k in FALLBACK_NOTES):
        off = len(rs)
    else:
        off = sum(1 for r in rs if r.get("closure") is None
                  or any(k in r for k in FALLBACK_NOTES))
    return {"closures": closures, "off_device": off}
