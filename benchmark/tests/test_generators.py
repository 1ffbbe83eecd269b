"""The benchmark's generators are pinned to the program's originals at
the cells' parameters: while the two agree, a history made for a cell
is the history the program's corpus would make."""

import json
import os

import pytest

import generators
import named
import traffic
from conftest import ROOT
from jepsen_tpu import histories


def plain(h):
    return [dict(o) for o in h]


def open_window(ops: list) -> int:
    """The most calls open at once. A failed call never happened and a
    crashed read constrains nothing, so neither counts; any other
    crashed call stays open to the end."""
    ends: dict = {}
    kind: dict = {}
    start: dict = {}
    for i, o in enumerate(ops):
        p = o["process"]
        if o["type"] == "invoke":
            start[p] = i
            kind[i] = o["f"]
        else:
            j = start.pop(p)
            ends[j] = {"ok": i, "fail": None}.get(o["type"], len(ops))
    for j in start.values():
        ends[j] = len(ops)
    edges = []
    for j, end in ends.items():
        if end is None or (end == len(ops) and kind[j] == "read"):
            continue
        edges += [(j, 1), (end, -1)]
    width = most = 0
    for _, d in sorted(edges):
        width += d
        most = max(most, width)
    return most


def cfg(name):
    with open(os.path.join(ROOT, "benchmark", "configs", name + ".json")) as f:
        return json.load(f)


@pytest.mark.parametrize("seed", [0, 7, 45100, 2**31 + 5])
def test_register_copy_matches_original(seed):
    c = cfg("tendermint-cas")
    kw = dict(n_ops=c["ops_per_key"], n_processes=c["processes_per_key"],
              n_values=c["values"], crash_p=c["crash_p"],
              fail_p=c["fail_p"], busy=c["busy"], seed=seed)
    assert generators.rand_register_history(**kw) == plain(
        histories.rand_register_history(**kw))


@pytest.mark.parametrize("seed", [3, 2**33 + 1])
def test_adversarial_copy_matches_original(seed):
    c = cfg("crashed-writes-register")
    kw = dict(n_ops=c["ops"], k_crashed=c["crashed_writes"],
              n_values=c["values"], seed=seed)
    assert generators.adversarial_register_history(**kw) == plain(
        histories.adversarial_register_history(**kw))


@pytest.mark.parametrize("seed", [1, 99])
def test_corrupt_copy_matches_original(seed):
    ops = generators.rand_register_history(n_ops=120, n_processes=10,
                                           seed=seed)
    want = histories.corrupt_history(
        histories.rand_register_history(n_ops=120, n_processes=10,
                                        seed=seed), seed=seed)
    assert generators.corrupt_history(ops, seed=seed) == plain(want)


def test_readers_keep_their_role():
    ops = generators.rand_register_history(
        n_ops=400, n_processes=10, crash_p=0.05, seed=5, readers=5)
    thread = {p: p for p in range(10)}
    pending = {}
    fresh = 10
    for o in ops:
        p = o["process"]
        if o["type"] == "invoke":
            pending[p] = o
            assert (o["f"] == "read") == (thread[p] < 5), o
        elif o["type"] == "info":
            thread[fresh] = thread[p]
            fresh += 1
    assert any(o["f"] == "cas" for o in ops)


def test_every_seed_gives_the_same_shapes_in_another_order():
    c = cfg("tendermint-cas")
    mix = traffic.load(ROOT, "traffic", "short-run")

    def shapes(pool):
        return sorted(sorted((traffic.n_invocations([(k, ops)]),
                              open_window(ops)) for k, ops in e)
                      for e in pool)

    a, b = (traffic.pool(ROOT, c, mix, s) for s in (1, 2**40 + 7))
    assert shapes(a) == shapes(b)
    assert a != b


def test_keys_are_the_generators_draws_as_they_come():
    # nothing is redrawn: each key of a pool entry is the register
    # generator's history for its sub-seed, so the open-call windows
    # keep the source's distribution
    c = cfg("tendermint-cas")
    mix = dict(traffic.load(ROOT, "traffic", "10k-ops"), faults={})
    seeds = traffic.sub_seeds(mix["structure_seed"], mix["pool"])
    register = named.load(ROOT, "gens", "register")
    entry = register.entry(c, mix, seeds[0], 0)
    assert [ops for _, ops in entry] == [
        register._register_key(c, s)
        for s in traffic.sub_seeds(seeds[0], mix["keys"] + 2)[:mix["keys"]]]


def test_reordering_keeps_every_verdict_and_fail_event():
    reference = named.load(ROOT, "references", "cas-register")
    c = cfg("tendermint-cas")
    mix = traffic.load(ROOT, "traffic", "short-run")

    def answers(pool):
        out = []
        for e in pool:
            for _, ops in e:
                r = reference.check(ops)
                out.append((r["valid?"], r.get("fail-event"),
                            r.get("op", {}).get("index")))
        return sorted(out, key=str)

    assert answers(traffic.pool(ROOT, c, mix, 5)) == answers(
        traffic.pool(ROOT, c, mix, 2**33 + 1))
