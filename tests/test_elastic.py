"""Elastic mesh work: the re-shard-on-escalation ladder
(JEPSEN_TPU_RESHARD, sharded.check_encoded_sharded_elastic), the
serve/stream key migration primitives (CheckerService.steal_key,
HistorySession.migrate), and the search report's per-device skew
column. The 2-D promotion integration is slow-marked (multi-minute CPU
compiles)."""

import numpy as np
import pytest

import jax
from jax.sharding import Mesh

from jepsen_tpu.histories import (adversarial_register_history,
                                  rand_register_history)
from jepsen_tpu.models import CASRegister
from jepsen_tpu.parallel import encode as enc_mod, engine
from jepsen_tpu.parallel import sharded

# the result fields a device migration must not move
PIN = ("valid?", "op", "fail-event", "max-frontier", "capacity",
       "configs-stepped", "dedupe")


def _pin(r):
    return {k: r.get(k) for k in PIN}


def _mesh():
    return Mesh(np.array(jax.devices()), ("key",))


# ----------------------------------------------------------- re-shard

# ONE adversarial shape shared by every re-shard test, and the static
# + elastic results computed once per session: sharded shard_map
# programs are the suite's most expensive CPU compiles, so the tests
# below assert different contracts against the same two runs.


@pytest.fixture(scope="module")
def reshard_runs():
    h = adversarial_register_history(n_ops=60, k_crashed=6, seed=7)
    e = enc_mod.encode(CASRegister(), h)
    mesh = _mesh()
    r_static = sharded.check_encoded_sharded(e, mesh, capacity=128,
                                             max_capacity=1 << 16)
    r_el = sharded.check_encoded_sharded_elastic(
        e, mesh, capacity=128, max_capacity=1 << 16)
    return e, mesh, r_static, r_el


def test_reshard_recruits_devices_with_identical_verdict(reshard_runs):
    """The elastic ladder answers overflow by recruiting devices at
    flat per-device capacity; verdict fields match the grow-the-table
    ladder and the rung trail is recorded."""
    _e, _mesh_, r_static, r_el = reshard_runs
    keys = ("valid?", "op", "fail-event", "max-frontier")
    assert {k: r_static.get(k) for k in keys} \
        == {k: r_el.get(k) for k in keys}
    trail = r_el["reshard"]
    assert trail["start-devices"] == 2
    assert trail["events"], r_el
    # every rung recruited more devices; per-device capacity flat
    devs = [trail["start-devices"]] + [ev["devices"][1]
                                       for ev in trail["events"]]
    assert devs == sorted(devs) and len(set(devs)) == len(devs)
    assert r_el["devices"] == devs[-1]
    # the static result never carries the key: flag-off schema parity
    assert "reshard" not in r_static


def test_reshard_flag_delegation(reshard_runs, monkeypatch):
    """check_encoded_sharded(reshard=True) delegates to the elastic
    ladder (same rungs as calling it directly); unset env keeps the
    plain ladder."""
    e, mesh, r_static, r_el = reshard_runs
    monkeypatch.delenv("JEPSEN_TPU_RESHARD", raising=False)
    r = sharded.check_encoded_sharded(e, mesh, capacity=128,
                                      max_capacity=1 << 16,
                                      reshard=True)
    assert r.get("reshard") == r_el["reshard"]
    assert r["valid?"] == r_static["valid?"]


def test_reshard_escalation_tier(reshard_runs):
    """A batch-overflow key escalating through _escalate_overflow with
    reshard on lands the same verdict as the static escalation, with
    the elastic sharded tier behind it."""
    e, mesh, r_static, _r_el = reshard_runs
    ref = engine._escalate_overflow(e, 64, mesh)
    got = engine._escalate_overflow(e, 64, mesh, reshard=True)
    assert ref["valid?"] == got["valid?"] == r_static["valid?"]
    assert got["escalated"] in ("single", "sharded")


def test_reshard_overflow_at_full_mesh_stays_unknown(reshard_runs):
    """Ceilings and overflow semantics unchanged: a shape the full
    recruited mesh still cannot hold lands the same structured
    unknown. max_capacity=512 reuses the shared runs' compiled rung
    shapes (128@2 -> 256@4 -> 512@8) — the next doubling is refused."""
    e, mesh, _r_static, r_el = reshard_runs
    # only meaningful if the shared shape really outgrows 512
    assert r_el["capacity"] > 512
    r = sharded.check_encoded_sharded_elastic(e, mesh, capacity=128,
                                              max_capacity=512)
    assert r["valid?"] == "unknown"
    assert "frontier overflow" in r["error"]
    assert r["reshard"]["events"]          # it did try recruiting


# ------------------------------------------- serve / session migration


def test_session_migrate_bit_identical():
    """HistorySession.migrate between devices mid-stream: the
    canonical checkpoint is host-side, so the next delta resumes on
    the new device bit-identically to an unmigrated session."""
    from jepsen_tpu.parallel import extend as ext
    h = list(rand_register_history(n_ops=24, n_processes=4, n_values=3,
                                   crash_p=0.05, seed=21))
    devs = jax.devices()
    model = CASRegister()

    def run(migrate):
        s = ext.HistorySession(model, capacity=128,
                               device=devs[0], key="k")
        s.extend(h[:12])
        r1 = s.check()
        if migrate:
            s.migrate(devs[-1])
            assert s.device is devs[-1]
        s.extend(h[12:])
        return r1, s.check()

    (a1, a2), (b1, b2) = run(False), run(True)
    assert _pin(a1) == _pin(b1) and _pin(a2) == _pin(b2)


def test_serve_steal_key_freeze_thaw_migration(tmp_path):
    """CheckerService.steal_key: the mid-stream serve migration —
    freeze through the eviction path (WAL/checkpoint store), re-pin
    the device, thaw on the next delta; finals bit-identical to the
    unmigrated stream. Also the in-memory variant (no WAL) via
    HistorySession.migrate."""
    from jepsen_tpu.serve.service import CheckerService
    h = list(rand_register_history(n_ops=24, n_processes=4, n_values=3,
                                   crash_p=0.05, seed=22))
    model = CASRegister()
    devs = jax.devices()

    def run(wal_dir, steal_to):
        svc = CheckerService(model, wal_dir=wal_dir, capacity=128)
        try:
            svc.submit("k", h[:12], wait=True, timeout=120)
            assert svc.drain(timeout=60)
            if steal_to is not None:
                assert svc.steal_key("k", steal_to) is True
                ks = svc._keys["k"]
                assert ks.device is steal_to
                if wal_dir is not None:
                    assert ks.session is None   # frozen, thaws on next
            svc.submit("k", h[12:], wait=True, timeout=120)
            f = svc.finalize("k", timeout=120)
            if steal_to is not None:
                sess = svc._keys["k"].session
                assert sess is not None and sess.device is steal_to
        finally:
            svc.close()
        return f

    base = run(str(tmp_path / "w0"), None)
    stolen = run(str(tmp_path / "w1"), devs[-1])
    in_mem = run(None, devs[-1])
    assert _pin(stolen) == _pin(base)
    assert _pin(in_mem) == _pin(base)


def test_serve_steal_key_refuses_with_pending_work(tmp_path):
    from jepsen_tpu.serve.service import CheckerService
    model = CASRegister()
    h = list(rand_register_history(n_ops=12, n_processes=3, n_values=3,
                                   seed=23))
    svc = CheckerService(model, wal_dir=str(tmp_path / "w"),
                         capacity=128, start_worker=False)
    try:
        assert svc.steal_key("missing") is False
        svc.submit("k", h, seq=1)
        # worker never ran: the delta is still pending — refuse
        assert svc.steal_key("k", jax.devices()[-1]) is False
    finally:
        # no worker: a draining close would wait on the pending
        # delta forever
        svc.close(drain=False)


# ------------------------------------------------- report skew column


def test_search_report_device_skew_column():
    from jepsen_tpu.obs import search_report as sr
    recs = [
        {"key": "hot", "engine": "sharded", "events": 10,
         "frontier-peak": 64, "load-factor-peak": 0.5,
         "per-device": {"load-factor-peak":
                        [0.8, 0.1, 0.1, 0.1, 0.1, 0.1, 0.1, 0.1]}},
        {"key": "flat", "engine": "sharded", "events": 10,
         "frontier-peak": 64, "load-factor-peak": 0.2,
         "per-device": {"load-factor-peak": [0.2] * 8}},
        {"key": "solo", "engine": "sparse", "events": 4,
         "frontier-peak": 8, "load-factor-peak": 0.1},
    ]
    assert sr.device_skew(recs[0]) == round(0.8 / (1.5 / 8), 4)
    assert sr.device_skew(recs[1]) == 1.0
    assert sr.device_skew(recs[2]) is None
    text = sr.render_search_report(recs)
    assert "dev-skew" in text
    assert "per-device skew" in text
    # the hot key ranks first in the skew table
    skew_section = text.split("per-device skew")[1]
    assert skew_section.index("hot") < skew_section.index("flat")


# ------------------------------------------------------- slow 2-D


@pytest.mark.slow
def test_reshard_2d_promotion_parity():
    """The 1-D -> 2-D promotion rung: on a 4x2 mesh the elastic
    ladder crosses from a flat slice onto recruited slices through
    _check_sharded_resume2d with verdicts identical to the static 2-D
    search. Slow tier: the hierarchical shard_map programs are
    multi-minute CPU compiles (the 2-D precedent in test_sharded)."""
    h = adversarial_register_history(n_ops=60, k_crashed=6, seed=7)
    e = enc_mod.encode(CASRegister(), h)
    devs = np.array(jax.devices()).reshape(4, 2)
    mesh2d = Mesh(devs, ("a", "b"))
    r_static = sharded.check_encoded_sharded(e, mesh2d, capacity=128,
                                             max_capacity=1 << 16)
    r_el = sharded.check_encoded_sharded_elastic(
        e, mesh2d, capacity=128, max_capacity=1 << 16)
    keys = ("valid?", "op", "fail-event", "max-frontier")
    assert {k: r_static.get(k) for k in keys} \
        == {k: r_el.get(k) for k in keys}
    # the trail crossed into the 2-D rungs (devices past one slice row)
    assert any(ev["devices"][1] > 2 for ev in
               r_el["reshard"]["events"])
