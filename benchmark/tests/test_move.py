"""The move of the checker entries, generators and reference into
files found by name changed nothing a cell runs: each older cell's
pool, at three seeds, is the one recorded from the code before the
move (`data/pools_before_move.json`), and so are the reference's
answers; at one loop, the window checks the pool in the same turn.
And a family the benchmark has never run comes in as files alone."""

import hashlib
import json
import os
import shutil
import time

import pytest

import compare
import harness
import named
import traffic
from conftest import CELLS, HERE, ROOT, shrink

with open(os.path.join(HERE, "data", "pools_before_move.json")) as f:
    RECORDED = json.load(f)


@pytest.mark.parametrize("seed", ["3", "8589934593", "1099511627783"])
@pytest.mark.parametrize("cell", CELLS)
def test_pool_and_reference_answers_are_as_recorded(cell, seed):
    want = RECORDED[cell][seed]
    w = next(w for w in harness.load_spec(ROOT)["workloads"]
             if w["name"] == cell)
    cfg = traffic.load(ROOT, "configs", w["config"])
    pool = traffic.pool(ROOT, cfg, traffic.load(ROOT, "traffic",
                                                w["traffic"]), int(seed))
    blob = json.dumps(pool, sort_keys=True).encode()
    assert hashlib.sha256(blob).hexdigest() == want["pool_sha256"]
    assert [traffic.n_invocations(e) for e in pool] == want["n_invocations"]
    ref = named.load(ROOT, "references", cfg["model"])
    got = [compare.reference_answers(ref, e) for e in pool]
    assert json.loads(json.dumps(got, default=str)) == want["reference"]


@pytest.mark.parametrize("cell", CELLS)
def test_one_loop_checks_the_pool_in_turn(cell, monkeypatch):
    """Set-up checks each pool entry once, in order; the window then
    cycles through the pool from its first entry, one check at a
    time."""
    spec = harness.load_spec(ROOT)
    w = next(w for w in spec["workloads"] if w["name"] == cell)
    entry = named.load(ROOT, "entries",
                       traffic.load(ROOT, "configs", w["config"])["entry"])
    seen = []
    check = entry.check

    def recording(built, history):
        seen.append(id(history))
        return check(built, history)

    monkeypatch.setattr(entry, "check", recording)
    histories = []
    wrap = entry.wrap

    def keep(e):
        histories.append(wrap(e))
        return histories[-1]

    monkeypatch.setattr(entry, "wrap", keep)
    out = harness.run_cell(ROOT, cell, 2**34 + 5, 1.0, False,
                           time.perf_counter(), require_accelerator=False,
                           shrink=shrink)
    order = [[id(h) for h in histories].index(x) for x in seen]
    n = len(histories)
    assert order[:n] == list(range(n))
    assert order[n:] == [i % n for i in range(len(order) - n)]
    assert out["attempted"] == len(order) - n and out["correct"] is True


MUTEX_GEN = '''"""Toy mutex histories: `rounds` rounds of three processes, each
taking the lock and giving it back; a process may ask while another
holds it and be granted after the release. Where `position` is odd,
one grant is moved before the holder's release (invalid)."""

import random


def entry(cfg, mix, seed, position):
    rng = random.Random(seed)
    ops, t = [], 0

    def emit(typ, p, f):
        nonlocal t
        t += 1
        ops.append({"type": typ, "process": p, "f": f, "value": None,
                    "time": t, "index": len(ops)})

    for _ in range(mix["rounds"]):
        a, b = rng.sample(range(3), 2)
        emit("invoke", a, "acquire")
        emit("ok", a, "acquire")
        emit("invoke", b, "acquire")
        emit("invoke", a, "release")
        emit("ok", a, "release")
        emit("ok", b, "acquire")
        emit("invoke", b, "release")
        emit("ok", b, "release")
    if position % 2:
        i = next(i for i, o in enumerate(ops) if o["f"] == "release")
        j = next(j for j, o in enumerate(ops) if j > i
                 and o["type"] == "ok" and o["f"] == "acquire")
        ops.insert(i, ops.pop(j))
        for k, o in enumerate(ops):
            o["index"] = k
    return [(None, ops)]
'''

MUTEX_REF = '''"""A plain mutex reference: a search over which open calls have taken
effect, in completion order (knossos's mutex: acquire needs it free,
release needs it held)."""


def _calls(ops, crashed_dropped):
    open_, out = {}, []
    for i, o in enumerate(ops):
        if o["type"] == "invoke":
            open_[o["process"]] = c = {"f": o["f"], "inv": i, "ret": None,
                                       "process": o["process"]}
            out.append(c)
        elif o["type"] == "ok":
            open_.pop(o["process"])["ret"] = i
        elif o["type"] == "fail":
            out.remove(open_.pop(o["process"]))
    return [c for c in out if c["ret"] is not None or not crashed_dropped]


def _ok(cs, horizon):
    req = [c for c in cs if c["ret"] is not None and c["ret"] < horizon]
    live = [c for c in cs if c["inv"] < horizon]
    seen = set()

    def go(done, held):
        if all(id(c) in done for c in req):
            return True
        if (done, held) in seen:
            return False
        seen.add((done, held))
        first = min((c["ret"] for c in req if id(c) not in done))
        for c in live:
            if id(c) in done or c["inv"] > first:
                continue
            if (c["f"] == "acquire") == held:
                continue
            if go(done | {id(c)}, not held):
                return True
        return False

    return go(frozenset(), False)


def check(ops, crashed_dropped=False):
    cs = _calls(ops, crashed_dropped)
    if _ok(cs, len(ops) + 1):
        return {"valid?": True}
    rets = sorted((c for c in cs if c["ret"] is not None),
                  key=lambda c: c["ret"])
    lo = next(r for r, c in enumerate(rets) if not _ok(cs, c["ret"] + 1))
    c = rets[lo]
    return {"valid?": False, "fail-event": lo,
            "op": {"process": c["process"], "f": c["f"], "value": None,
                   "index": c["inv"]}}
'''

MUTEX_ENTRY = '''"""A toy entry: `linearizable(<model>, algorithm="jax")` on one key."""

import program

path_notes = program.path_notes


def checker(cfg):
    return program.linearizable(cfg)


def check(built, history):
    return built.check({}, history, {})


def wrap(entry):
    from jepsen_tpu.history import History
    return History.wrap(dict(o) for o in entry[0][1]).index()


def answers(result, entry):
    a = program.key_answer(result)
    return {"valid?": a["valid?"], "failures": None, "keys": {None: a}}
'''


def test_a_new_family_comes_in_as_files_alone(tmp_path):
    shutil.copytree(os.path.join(ROOT, "benchmark"), tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns(".*", "__pycache__"))
    b = tmp_path / "benchmark"
    before = {p: p.read_bytes() for p in b.rglob("*") if p.is_file()}
    (b / "gens" / "toy_mutex.py").write_text(MUTEX_GEN)
    (b / "references" / "mutex.py").write_text(MUTEX_REF)
    (b / "entries" / "toy-single.py").write_text(MUTEX_ENTRY)
    (b / "configs" / "toy-mutex.json").write_text(json.dumps(
        {"name": "toy-mutex", "generator": "toy_mutex",
         "entry": "toy-single", "model": "mutex", "reduced": []}))
    (b / "traffic" / "rounds.json").write_text(json.dumps(
        {"rounds": 3, "pool": 2}))
    spec = harness.load_spec(ROOT)
    spec["configs"].append({"name": "toy-mutex", "source": "x",
                            "file": "benchmark/configs/toy-mutex.json",
                            "reduced": [], "why": "test"})
    spec["workloads"].append({"name": "toy-mutex.rounds",
                              "config": "toy-mutex", "traffic": "rounds",
                              "chips": 1, "why": "test"})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    out = harness.run_cell(str(tmp_path), "toy-mutex.rounds", 2**33 + 3,
                           1.0, False, time.perf_counter(),
                           require_accelerator=False)
    assert out["correct"] is True, out["compared"]
    assert out["attempted"] >= 2
    # the pool holds a valid and an invalid history, both answered
    mix = {"rounds": 3, "pool": 2}
    pool = traffic.pool(str(tmp_path), {"generator": "toy_mutex"}, mix, 1)
    ref = named.load(str(tmp_path), "references", "mutex")
    assert sorted(ref.check(e[0][1])["valid?"] for e in pool) == [False,
                                                                  True]
    # nothing the benchmark had was edited
    assert all(p.read_bytes() == body for p, body in before.items())
