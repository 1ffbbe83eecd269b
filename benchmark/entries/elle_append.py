"""`elle_append`: `AppendChecker` (jepsen.tests.cycle.append's checker)
with the configuration's `checker` options, one check a call.

A result's answer is its verdict, its anomaly types, the number of
cases of each, and `cycles_hold`: whether every cycle it reports is a
cycle of the history whose edges meet its class, by the reference's
own edge definitions (`references/<model>.py`). Which cycle a search
finds depends on the order it walks the members, so cycles are checked
and not compared. The check counts as answered on the device only
where the result names the device SCC engine."""

from __future__ import annotations

import os

import named

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
MODEL = "elle-append"          # the reference that defines the edges
CYCLES = ("G0", "G1c", "G-single", "G2")
_EDGES: dict = {}


def checker(cfg: dict):
    from jepsen_tpu.workloads.cycle import AppendChecker
    return AppendChecker(dict(cfg["checker"]))


def check(built, history) -> dict:
    return built.check({}, history, {})


def wrap(entry: list):
    """The program's history for a one-history pool entry."""
    from jepsen_tpu.history import History
    return History.wrap(dict(o) for o in entry[0][1]).index()


def _edges(entry: list):
    """The reference's edges of one pool entry, built once."""
    hit = _EDGES.get(id(entry))
    if hit is None or hit[0] is not entry:
        ref = named.load(ROOT, "references", MODEL)
        hit = _EDGES[id(entry)] = (entry, ref, ref.Edges(entry[0][1]))
    return hit[1], hit[2]


def answers(result: dict, entry: list) -> dict:
    ref, edges = _edges(entry)
    cases = result.get("anomalies") or {}
    key = entry[0][0]
    a = {"valid?": result.get("valid?"),
         "anomaly-types": list(result.get("anomaly-types") or []),
         "counts": {t: len(cs) for t, cs in cases.items()},
         "cycles_hold": all(ref.cycle_holds(edges, t, c.get("cycle"))
                            for t in CYCLES for c in cases.get(t, ()))}
    return {"valid?": a["valid?"],
            "failures": None if key is None else
            ([key] if a["valid?"] is False else []),
            "keys": {key: a}}


def path_notes(result: dict) -> dict:
    """The SCC engine the result names; a check whose SCCs the device
    did not find counts as one key off the device."""
    engine = result.get("scc")
    return {"closures": [] if engine is None else [f"scc:{engine}"],
            "off_device": int(engine != "device")}
