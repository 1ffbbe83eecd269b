"""Self-tuning strategy planner (JEPSEN_TPU_AUTO, ISSUE 20).

Pins the contracts docs/performance.md "Auto planner" documents:

- flag off (unset/"0"): no planner, no ``plan`` provenance, no
  ``plan_table.json``, no ``engine.plan.*`` metric movement — results
  identical to the pre-planner tree;
- flag on: axes the caller left None route through the per-shape
  decision table; explicit arguments are never overridden; every arm
  is parity-pinned, so a plan (including an exploration) can change
  wall-clock only, never the verdict;
- floor semantics: below ``JEPSEN_TPU_LEDGER_FLOOR`` samples the
  static defaults run (source ``floor-default``) while the dispatch
  still contributes EWMA evidence;
- durability: the table persists atomically beside the ledger
  segments; a truncated/garbage/stale-schema file degrades to a
  counted re-seed, never a crash;
- provenance: planned results carry the ``plan`` block, every
  decision mints a ``kind=plan`` ledger record, and the live table is
  served on the ops ``/plan`` endpoint.
"""

import json
import os

import pytest

from jepsen_tpu import envflags, obs
from jepsen_tpu.histories import corrupt_history, rand_register_history
from jepsen_tpu.models import CASRegister
from jepsen_tpu.obs import ledger
from jepsen_tpu.parallel import encode as enc_mod, engine, planner

# Dedupe arms legitimately differ in configs-stepped/explored — the
# cross-arm pin is the perf_ab/serve parity surface.
PIN = ("valid?", "op", "fail-event", "max-frontier")


def _pin(r):
    return {k: r.get(k) for k in PIN}


def _strip_plan(r):
    return {k: v for k, v in r.items() if k != "plan"}


def _mem_planner(**kw):
    """An in-memory planner: no durable root, no bench seeding."""
    kw.setdefault("bench_dir", "")
    return planner.Planner(None, **kw)


_G = ("sparse", "register_step", 6)   # a shape group for unit tests


@pytest.fixture(autouse=True)
def _fresh(monkeypatch):
    for flag in ("JEPSEN_TPU_AUTO", "JEPSEN_TPU_AUTO_EXPLORE",
                 "JEPSEN_TPU_LEDGER", "JEPSEN_TPU_LEDGER_FLOOR"):
        monkeypatch.delenv(flag, raising=False)
    planner.reset()
    ledger.reset()
    yield
    planner.reset()
    ledger.reset()


# ------------------------------------------------------- table mechanics


def test_floor_defaults_then_online_takeover():
    pl = _mem_planner(floor=3, explore_every=0)
    dec = pl.decide(*_G, {"dedupe": None})
    assert dec["plan"]["source"] == "floor-default"
    assert dec["strategy"] == {"dedupe": "sort"}
    # below-floor dispatches still contribute evidence ...
    for _ in range(2):
        pl.observe(*_G, {"dedupe": "hash"}, 0.01)
        pl.observe(*_G, {"dedupe": "sort"}, 0.50)
    dec = pl.decide(*_G, {"dedupe": None})
    assert dec["plan"]["source"] == "floor-default"   # n=2 < floor
    # ... and once a cell clears the floor the cheapest arm wins
    pl.observe(*_G, {"dedupe": "hash"}, 0.01)
    pl.observe(*_G, {"dedupe": "sort"}, 0.50)
    dec = pl.decide(*_G, {"dedupe": None})
    assert dec["strategy"] == {"dedupe": "hash"}
    assert dec["plan"]["source"] == "online"
    assert dec["plan"]["cell_n"] == 3
    assert dec["plan"]["explored"] is False


def test_explicit_axis_is_never_overridden():
    pl = _mem_planner(floor=1, explore_every=0)
    for _ in range(2):
        pl.observe("sparse", "f", 4, {"dedupe": "hash", "pack": True},
                   0.01)
    # the caller fixed dedupe=sort: the (faster) hash cell is
    # incompatible, so only pack is plannable and it floor-defaults
    dec = pl.decide("sparse", "f", 4, {"dedupe": "sort", "pack": None})
    assert "dedupe" not in dec["strategy"]
    assert dec["strategy"] == {"pack": False}
    assert dec["plan"]["source"] == "floor-default"
    # nothing plannable -> no decision at all
    assert pl.decide("sparse", "f", 4, {"dedupe": "sort"}) is None


def test_sanitize_never_pairs_pallas_with_sort():
    assert planner._sanitize({"dedupe": "sort", "pallas": True}) \
        == {"dedupe": "sort", "pallas": False}
    assert planner._sanitize({"dedupe": "hash", "pallas": True}) \
        == {"dedupe": "hash", "pallas": True}


def test_exploration_cadence_is_deterministic():
    pl = _mem_planner(floor=1, explore_every=2)
    for _ in range(2):
        pl.observe(*_G, {"dedupe": "hash"}, 0.01)
        pl.observe(*_G, {"dedupe": "sort"}, 0.50)
    before = obs.counter("engine.plan.explorations").value
    d1 = pl.decide(*_G, {"dedupe": None})
    d2 = pl.decide(*_G, {"dedupe": None})
    assert d1["plan"]["explored"] is False
    assert d1["strategy"] == {"dedupe": "hash"}      # the best arm
    assert d2["plan"]["explored"] is True
    assert d2["strategy"] == {"dedupe": "sort"}      # the alternative
    assert obs.counter("engine.plan.explorations").value == before + 1


def test_ewma_update_matches_planner_cells():
    # the planner's cost cells fold observations through ewma_update
    # (docs/performance.md "Auto planner")
    assert planner.ewma_update(None, 0.1) == pytest.approx(0.1)
    assert planner.ewma_update(0.1, 0.2) == pytest.approx(0.15)
    pl = _mem_planner(floor=1)
    pl.observe(*_G, {"dedupe": "sort"}, 0.1)
    pl.observe(*_G, {"dedupe": "sort"}, 0.2)
    cell = pl.table[planner.group_key(*_G)]["cells"]["dedupe=sort"]
    assert cell["ewma"] == pytest.approx(0.15)


# ---------------------------------------------------------- durability


def test_table_durable_roundtrip(tmp_path):
    root = str(tmp_path)
    pl = planner.Planner(root, bench_dir="", floor=1, explore_every=0)
    pl.observe("sparse", "f", 4, {"dedupe": "hash"}, 0.02)
    doc = planner.load_table(root)
    assert doc["version"] == planner.TABLE_VERSION
    cell = doc["groups"]["engine=sparse,family=f,C=4"]["cells"][
        "dedupe=hash"]
    assert cell["n"] == 1 and cell["ewma"] == pytest.approx(0.02)
    # a fresh process adopts the durable evidence
    pl2 = planner.Planner(root, bench_dir="", floor=1, explore_every=0)
    dec = pl2.decide("sparse", "f", 4, {"dedupe": None})
    assert dec["strategy"] == {"dedupe": "hash"}
    assert dec["plan"]["cell_n"] == 1


@pytest.mark.parametrize("payload", [
    '{"version": 1, "gro',                 # truncated mid-write
    "\x00\x01 not json at all",            # garbage bytes
    '{"version": 99, "groups": {}}',       # stale schema version
    "[1, 2, 3]",                           # wrong document shape
], ids=["truncated", "garbage", "stale-version", "wrong-shape"])
def test_corrupt_table_reseeds_counted_never_crashes(tmp_path, payload):
    root = str(tmp_path)
    with open(ledger.plan_table_path(root), "w") as fh:
        fh.write(payload)
    before = obs.counter("engine.plan.reseeds").value
    pl = planner.Planner(root, bench_dir="")
    assert obs.counter("engine.plan.reseeds").value == before + 1
    # the rewritten table is valid again and the planner is usable
    assert planner.load_table(root) is not None
    dec = pl.decide("e", "f", 4, {"dedupe": None})
    assert dec["plan"]["source"] == "floor-default"


def test_malformed_flag_raises_loudly(monkeypatch):
    monkeypatch.setenv("JEPSEN_TPU_AUTO", "yes")
    planner.reset()
    with pytest.raises(envflags.EnvFlagError):
        planner.active()


# -------------------------------------------------------- flag off/on


def test_flag_off_is_inert(tmp_path, monkeypatch):
    monkeypatch.setenv("JEPSEN_TPU_LEDGER", str(tmp_path))
    ledger.reset()
    assert planner.active() is None
    assert planner.plan_doc() == {"auto": {"enabled": False},
                                  "groups": {}}
    before = obs.counter("engine.plan.decisions").value
    h = rand_register_history(n_ops=40, n_processes=5, n_values=3,
                              crash_p=0.05, fail_p=0.05, seed=9)
    r = engine.check_encoded(enc_mod.encode(CASRegister(), h),
                             capacity=256, max_capacity=1024)
    assert "plan" not in r
    assert obs.counter("engine.plan.decisions").value == before
    assert not os.path.exists(ledger.plan_table_path(str(tmp_path)))
    led = ledger.active()
    led.sync()
    recs, _ = ledger.read_records(str(tmp_path))
    assert not any(rec.get("kind") == "plan" for rec in recs)


def test_auto_check_encoded_parity_and_exploration(monkeypatch):
    """Engine-level: planned dispatches (including forced every-turn
    exploration) pin the static verdict surface on clean AND
    corrupted histories."""
    m = CASRegister()
    clean = rand_register_history(n_ops=40, n_processes=5, n_values=3,
                                  crash_p=0.05, fail_p=0.05, seed=9)
    bad = corrupt_history(
        rand_register_history(n_ops=40, n_processes=5, n_values=3,
                              crash_p=0.05, fail_p=0.05, seed=10),
        seed=2, n_corruptions=2)
    for h in (clean, bad):
        e = enc_mod.encode(m, h)
        refs = {s: engine.check_encoded(e, capacity=256,
                                        max_capacity=1024, dedupe=s)
                for s in ("sort", "hash")}
        base = _pin(refs["sort"])
        assert _pin(refs["hash"]) == base
        monkeypatch.setenv("JEPSEN_TPU_AUTO", "1")
        monkeypatch.setenv("JEPSEN_TPU_AUTO_EXPLORE", "1")
        planner.reset()
        for _ in range(4):
            r = engine.check_encoded(e, capacity=256,
                                     max_capacity=1024)
            assert _pin(r) == base
            p = r["plan"]
            assert set(p) == {"vector", "cell_n", "source", "explored"}
            assert p["source"] in ("floor-default", "seeded", "online")
        monkeypatch.delenv("JEPSEN_TPU_AUTO")
        monkeypatch.delenv("JEPSEN_TPU_AUTO_EXPLORE")
        planner.reset()


def test_auto_check_batch_plans_executor_axes(monkeypatch):
    m = CASRegister()
    hs = [rand_register_history(n_ops=30, n_processes=4, n_values=3,
                                crash_p=0.05, fail_p=0.05, seed=s)
          for s in (21, 22)]
    ref = engine.check_batch(m, hs, capacity=256, max_capacity=1024)
    monkeypatch.setenv("JEPSEN_TPU_AUTO", "1")
    planner.reset()
    rs = engine.check_batch(m, hs, capacity=256, max_capacity=1024)
    assert [_pin(r) for r in rs] == [_pin(r) for r in ref]
    # the batch-level decision landed in its own shape group and the
    # dispatch fed the executor-arm cell
    tbl = planner.active().table
    grp = tbl[planner.group_key("batch", "CASRegister", None)]
    assert grp["decisions"] >= 1
    (cell,) = grp["cells"].values()
    assert cell["arm"] == {"pipeline": False}
    assert cell["n_live"] == 1


def test_auto_stream_parity_provenance_and_ledger(tmp_path,
                                                 monkeypatch):
    """A live HistorySession under AUTO is byte-identical to the
    static session once the plan provenance block is stripped, and
    the decision leaves the full durable trail (kind=plan record +
    plan_table.json beside the segments)."""
    from jepsen_tpu.parallel.extend import HistorySession
    m = CASRegister()
    ops = list(rand_register_history(n_ops=60, n_processes=5,
                                     n_values=4, crash_p=0.03,
                                     fail_p=0.05, seed=13))
    n = len(ops) // 3
    s = HistorySession(m, capacity=256)
    outs = []
    for i in range(3):
        s.extend(ops[i * n:(i + 1) * n if i < 2 else len(ops)])
        outs.append(s.check())

    monkeypatch.setenv("JEPSEN_TPU_AUTO", "1")
    monkeypatch.setenv("JEPSEN_TPU_LEDGER", str(tmp_path))
    planner.reset()
    ledger.reset()
    before = obs.counter("engine.plan.decisions").value
    s2 = HistorySession(m, capacity=256)
    outs2 = []
    for i in range(3):
        s2.extend(ops[i * n:(i + 1) * n if i < 2 else len(ops)])
        outs2.append(s2.check())
    for a, b in zip(outs, outs2):
        assert _strip_plan(b) == _strip_plan(a)
    # the plan is decided once per session and pinned for its lifetime
    assert obs.counter("engine.plan.decisions").value == before + 1
    for b in outs2:
        assert set(b["plan"]) == {"vector", "cell_n", "source",
                                  "explored"}
    # durable trail: a kind=plan record and the table beside the
    # segments
    ledger.active().sync()
    recs, corrupt = ledger.read_records(str(tmp_path))
    assert corrupt == 0
    plans = [r for r in recs if r.get("kind") == "plan"]
    assert len(plans) == 1
    assert plans[0]["engine"] == "stream"
    assert set(plans[0]["strategy"]) <= set(planner.AXES)
    assert planner.load_table(str(tmp_path)) is not None


# ------------------------------------------------------- ops surfaces


def test_plan_endpoint_off_and_on(monkeypatch):
    from jepsen_tpu.obs import httpd
    srv = httpd.start_ops_server(0)
    try:
        code, body = httpd._fetch(srv.url("/plan"))
        doc = json.loads(body)
        assert code == 200
        assert doc == {"auto": {"enabled": False}, "groups": {}}
        monkeypatch.setenv("JEPSEN_TPU_AUTO", "1")
        planner.reset()
        planner.active().observe(*_G, {"dedupe": "hash"}, 0.02)
        code, body = httpd._fetch(srv.url("/plan"))
        doc = json.loads(body)
        assert code == 200 and doc["auto"]["enabled"] is True
        cells = doc["groups"][planner.group_key(*_G)]["cells"]
        assert cells["dedupe=hash"]["n"] == 1
    finally:
        srv.close()


# ------------------------------------------------------- convergence


@pytest.mark.slow
def test_auto_converges_to_winning_arm_live(tmp_path, monkeypatch):
    """Convergence pin: prime both dedupe cells with real dispatches
    under AUTO (explicit arms — the planner only observes), then let
    it decide: it must route to whichever arm the table measured
    cheaper, with online provenance, and stay there with exploration
    off."""
    monkeypatch.setenv("JEPSEN_TPU_AUTO", "1")
    monkeypatch.setenv("JEPSEN_TPU_AUTO_EXPLORE", "0")
    monkeypatch.setenv("JEPSEN_TPU_LEDGER", str(tmp_path))
    planner.reset()
    ledger.reset()
    m = CASRegister()
    h = rand_register_history(n_ops=40, n_processes=5, n_values=3,
                              crash_p=0.05, fail_p=0.05, seed=9)
    e = enc_mod.encode(m, h)
    for arm in ("sort", "hash"):
        for _ in range(3):
            engine.check_encoded(e, capacity=256, max_capacity=1024,
                                 dedupe=arm, sparse_pallas=False,
                                 config_pack=False)
    pl = planner.active()
    grp = pl.table[planner.group_key("sparse", e.step_name,
                                     e.slot_f.shape[1])]
    cells = {sig: c for sig, c in grp["cells"].items()
             if c["ewma"] is not None and c["n"] >= pl.floor}
    assert len(cells) >= 2
    winner = min(cells, key=lambda s: (cells[s]["ewma"], s))
    for _ in range(3):
        r = engine.check_encoded(e, capacity=256, max_capacity=1024)
        assert r["plan"]["source"] == "online"
        assert r["plan"]["explored"] is False
        assert r["dedupe"] == cells[winner]["arm"]["dedupe"]
