"""The comparison that decides `correct`.

Every check the window completed is compared with the reference's
answer for the same pool entry. The reference is the configuration's
`model`, found by name (`references/<model>.py`). Two numbers count
disagreements, and each limit is 0: the comparison is exact.

- `keys_wrong`: keys, over all checks, whose answer differs from the
  reference's: the verdict, and for an invalid key its failing op
  (process, f, value, index) and fail event; a key the result lacks or
  adds, and every key of a check that raised, counts too;
- `checks_wrong`: checks whose answer differs anywhere: the overall
  verdict, the list of failing keys (jepsen.independent's assembly),
  any key, or that raised instead of answering;
- `off_device_keys`: keys, over every check of the run (set-up's
  included), that the device did not answer: a host fallback keeps the
  verdict, so only this number sees it (the entry's `path_notes`).
"""

from __future__ import annotations

LIMITS = {"keys_wrong": 0, "checks_wrong": 0, "off_device_keys": 0}


def reference_answers(ref, entry: list, crashed_dropped: bool = False) \
        -> dict:
    """The answers of the reference module `ref` for one pool entry
    ([(key, ops)]), in the form an entry's `answers` gives."""
    keys = {k: ref.check(ops, crashed_dropped) for k, ops in entry}
    valid = all(a["valid?"] for a in keys.values())
    failures = (None if entry[0][0] is None
                else [k for k, _ in entry if keys[k]["valid?"] is False])
    return {"valid?": valid, "failures": failures, "keys": keys}


def _keys_wrong(got, want) -> int:
    if got is None:
        return len(want["keys"])
    n = 0
    for k in set(want["keys"]) | set(got["keys"]):
        w, g = want["keys"].get(k), got["keys"].get(k)
        n += w is None or g is None or g != w
    return n


def _disagreements(got, want) -> dict:
    """The counts for one check."""
    keys = _keys_wrong(got, want)
    whole = got is None or (got["valid?"] != want["valid?"]
                            or got["failures"] != want["failures"])
    return {"keys_wrong": keys, "checks_wrong": int(whole or keys > 0)}


def compare(observed: list, refs: list, off_device: int = 0) -> dict:
    """`observed`: (pool index, answers or None) per check;
    `off_device`: keys the device did not answer. Returns
    {name: {"value": n, "limit": limit}}."""
    n = dict.fromkeys(LIMITS, 0)
    n["off_device_keys"] = off_device
    for i, got in observed:
        for k, v in _disagreements(got, refs[i]).items():
            n[k] += v
    return {k: {"value": v, "limit": LIMITS[k]} for k, v in n.items()}


def correct(numbers: dict) -> bool:
    return all(x["value"] <= x["limit"] for x in numbers.values())
