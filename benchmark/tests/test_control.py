"""The control comes out not correct on every cell, at a test size, on
three seeds; and a run whose timed path is broken underneath comes out
not correct, once for each fault a cell can have."""

import time

import pytest

import control
import harness
from conftest import CELLS, ROOT, shrink


@pytest.mark.parametrize("seed", [11, 2**32 + 7, 2**40 + 1])
@pytest.mark.parametrize("cell", CELLS)
def test_control_is_not_correct(cell, seed):
    numbers = control.control_numbers(ROOT, cell, seed, shrink=shrink)
    assert numbers["keys_wrong"]["value"] > 0
    assert numbers["checks_wrong"]["value"] > 0


def run(cell):
    return harness.run_cell(ROOT, cell, 2**36 + 9, 1.0, False,
                            time.perf_counter(), require_accelerator=False,
                            shrink=shrink)


def flip_first(rs):
    r = dict(rs[0])
    r["valid?"] = not r["valid?"]
    return [r] + list(rs[1:])


@pytest.mark.parametrize("cell", CELLS)
def test_an_answer_altered_where_it_is_produced(cell, monkeypatch):
    # a keyed batch's answers are produced by the bitdense batch's
    # finalize; a single key's by engine.analysis
    from jepsen_tpu.parallel import bitdense, engine
    finalize = bitdense.PendingBitdenseBatch.finalize
    analysis = engine.analysis
    monkeypatch.setattr(bitdense.PendingBitdenseBatch, "finalize",
                        lambda self: flip_first(finalize(self)))
    monkeypatch.setattr(engine, "analysis",
                        lambda *a, **kw: flip_first([analysis(*a, **kw)])[0])
    out = run(cell)
    assert out["correct"] is False
    assert out["compared"]["keys_wrong"]["value"] > 0


@pytest.mark.parametrize("cell", ["tendermint-cas.10k-ops",
                                  "tendermint-cas.short-run"])
def test_half_the_batch_left_out(cell, monkeypatch):
    from jepsen_tpu.parallel import engine
    check_batch = engine.check_batch
    monkeypatch.setattr(
        engine, "check_batch",
        lambda model, hs, **kw: check_batch(model, hs[:len(hs) // 2], **kw))
    out = run(cell)
    assert out["correct"] is False
    assert out["compared"]["keys_wrong"]["value"] > 0


@pytest.mark.parametrize("cell", CELLS)
def test_a_host_fallback_is_not_correct(cell, monkeypatch):
    # every device dispatch fails, so the program answers each key on
    # the host; off_device_keys sees it whatever the host answers
    from jepsen_tpu.resilience import supervisor as sup

    def dead(site, thunk, backend=None, **kw):
        raise sup.DeviceUnavailable(site, "planted fault", backend)

    monkeypatch.setattr(sup, "dispatch", dead)
    out = run(cell)
    assert out["correct"] is False
    assert out["compared"]["off_device_keys"]["value"] > 0


def test_a_check_that_raises_is_not_an_answer(monkeypatch):
    import named
    program = named.load(ROOT, "entries", "independent")
    check = program.check
    calls = {"n": 0}

    def flaky(built, history):
        calls["n"] += 1
        if calls["n"] == 6:              # a check inside the window
            raise RuntimeError("device lost")
        return check(built, history)

    monkeypatch.setattr(program, "check", flaky)
    out = run("tendermint-cas.short-run")
    assert out["correct"] is False
    assert out["compared"]["checks_wrong"]["value"] == 1
