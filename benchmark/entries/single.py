"""`single`: `linearizable(<model>, algorithm="jax")` on one key, one
check a call."""

from __future__ import annotations

import program

path_notes = program.path_notes


def checker(cfg: dict):
    return program.linearizable(cfg)


def check(built, history) -> dict:
    return built.check({}, history, {})


def wrap(entry: list):
    """The program's history for a single-key pool entry
    ([(None, ops)])."""
    from jepsen_tpu.history import History
    return History.wrap(dict(o) for o in entry[0][1]).index()


def answers(result: dict, entry: list) -> dict:
    a = program.key_answer(result)
    return {"valid?": a["valid?"], "failures": None, "keys": {None: a}}
