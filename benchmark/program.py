"""What the checker entries (`entries/<name>.py`) share at the
boundary to the system under test: the model a configuration names,
and the reading of a result back into the answers that are compared
with the reference. Only the entries import the program.
"""

from __future__ import annotations

import json

# a configuration's `model` -> the program's model class, with its
# defaults (the names `jepsen serve --checker --model` takes)
MODELS = {"register": "Register", "cas-register": "CASRegister",
          "mutex": "Mutex", "gset": "GSet", "fifo": "FIFOQueue",
          "uqueue": "UnorderedQueue"}

FALLBACK_NOTES = ("device-fallback", "resilience", "fallback")


def model(cfg: dict):
    from jepsen_tpu import models
    return getattr(models, MODELS[cfg["model"]])()


def linearizable(cfg: dict):
    """`linearizable(<model>, algorithm="jax")`, the checker a Jepsen
    suite builds."""
    from jepsen_tpu.checker import linearizable as lin
    return lin(model(cfg), algorithm="jax")


def canon(v):
    """A value in the form both sides are compared in."""
    return json.loads(json.dumps(v))


def key_answer(r: dict) -> dict:
    a = {"valid?": r.get("valid?")}
    if a["valid?"] is False:
        op = r.get("op") or {}
        a["fail-event"] = r.get("fail-event")
        a["op"] = {k: canon(op.get(k))
                   for k in ("process", "f", "value", "index")}
    return a


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
