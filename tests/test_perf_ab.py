"""The perf A/B harness's hardware-independent cost prior.

tools/perf_ab.py decides the closure defaults (while/fori/pallas) from
MEASURED ratios on the real chip; the trace-time XLA cost_analysis
prior (bitdense.cost_analysis_encoded/_batch) must be populated on any
backend — including CPU — so the decision has an analytical anchor
during hardware-dark rounds and a cross-check once measured.
"""

import json
import os
import subprocess
import sys

import pytest

from jepsen_tpu.histories import (adversarial_register_history,
                                  rand_register_history)
from jepsen_tpu.models import CASRegister
from jepsen_tpu.parallel import bitdense, encode as enc_mod

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _adv_encoded(n_ops=120, k=8):
    h = adversarial_register_history(n_ops=n_ops, k_crashed=k, seed=7)
    return enc_mod.encode(CASRegister(), h)


def test_cost_analysis_encoded_populated_on_cpu():
    e = _adv_encoded()
    cw = bitdense.cost_analysis_encoded(e, closure_mode="while")
    cf = bitdense.cost_analysis_encoded(e, closure_mode="fori")
    for c in (cw, cf):
        assert c["flops"] > 0, c
        assert c["bytes_accessed"] > 0, c
    # XLA's cost model counts each loop body once (trip counts are
    # data-dependent), so while and fori — same expansion body, only
    # the loop carried convergence test differs — must land close:
    # the prior ranks per-iteration variant cost, not totals
    assert abs(cf["flops"] - cw["flops"]) < 0.2 * cw["flops"], (cw, cf)


def test_cost_analysis_scales_with_config_width():
    # trip counts don't show (loop bodies count once), but the
    # expansion body's own tensors scale with the config-word width W
    # = 2^k/32: +2 crashed writes quadruples W and must dominate
    narrow = bitdense.cost_analysis_encoded(_adv_encoded(k=8))
    wide = bitdense.cost_analysis_encoded(_adv_encoded(k=10))
    assert wide["flops"] > 2 * narrow["flops"], (narrow, wide)


def test_cost_analysis_pallas_downgrades_like_execution_paths():
    """use_pallas=True on a kernel-unsupported shape must downgrade
    through the shared gate (as check_encoded_bitdense does), not
    raise a bare kernel assert; the 'program' field tells the caller
    what was actually costed."""
    e = _adv_encoded(k=2)    # W=1 word, far below kernel support
    c = bitdense.cost_analysis_encoded(e, use_pallas=True)
    assert c["program"] == "xla-while", c
    assert c["flops"] > 0, c


def test_cost_analysis_batch_populated_on_cpu():
    encs = [enc_mod.encode(
        CASRegister(),
        rand_register_history(n_ops=30, n_processes=4, crash_p=0.01,
                              fail_p=0.05, seed=100 + k))
        for k in range(4)]
    c = bitdense.cost_analysis_batch(encs, closure_mode="while")
    assert c["flops"] > 0 and c["bytes_accessed"] > 0, c


def test_disagreeing_flags_wrong_variants_only():
    """The correctness gate compares full results (verdict +
    counterexample) against the while baseline, ignoring the closure
    label, and names exactly the variants that differ."""
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "perf_ab", os.path.join(REPO, "tools", "perf_ab.py"))
    perf_ab = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(perf_ab)

    ok = {"valid?": True, "engine": "bitdense", "closure": "xla-while"}
    same = dict(ok, closure="xla-fori")
    wrong = dict(ok, closure="pallas")
    wrong["valid?"] = False
    assert perf_ab._disagreeing(
        {"while": [ok], "fori": [same], "pallas": [dict(ok)]}) == set()
    assert perf_ab._disagreeing(
        {"while": [ok], "fori": [same], "pallas": [wrong]}) == {"pallas"}
    # EVERY run counts: one early wrong answer flags even when the
    # final run agrees (nondeterministic kernels must not slip through)
    assert perf_ab._disagreeing(
        {"while": [ok, ok], "fori": [wrong, same]}) == {"fori"}
    # a nondeterministic BASELINE flags itself (vetoes everything)
    assert perf_ab._disagreeing(
        {"while": [ok, wrong], "fori": [same]}) == {"while"}
    # batch form: run lists hold per-key result lists
    assert perf_ab._disagreeing(
        {"while": [[ok, ok]], "fori": [[same, wrong]]}) == {"fori"}


def test_perf_ab_dedupe_unknown_strategy_raises():
    """A typo in PERF_AB_DEDUPE must abort the harness with the valid
    set listed — a silently-skipped 'hash-palas' would read as
    measured-and-lost on the chip session the flip decision waits on.
    The check runs at module import, before any backend probe, so the
    failure is fast and backend-independent."""
    env = dict(os.environ)
    env.update({"PERF_AB_DEDUPE": "sort,hash-palas",
                "JAX_PLATFORMS": "cpu"})
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "perf_ab.py")],
        capture_output=True, text=True, env=env, timeout=300)
    assert r.returncode != 0, r.stdout[-500:]
    assert "unknown strategy" in r.stderr, r.stderr[-500:]
    assert "hash-palas" in r.stderr
    # the message must NAME the valid set, so the fix is in the error
    assert "sort,hash,hash-pallas,hash-packed" in r.stderr, \
        r.stderr[-500:]


def _gate_coverage_for(k: int, n_ops: int = 1000):
    """The host-only gate record for one chip-matrix adversarial
    shape, at the capacity tier the perf_ab dedupe block uses
    (1 << (k + 4))."""
    from jepsen_tpu.parallel import sparse_kernels as sk
    e = _adv_encoded(n_ops=n_ops, k=k)
    return sk.gate_coverage(e.n_states, e.state_lo,
                            e.slot_f.shape[1], 1 << (k + 4))


def test_gate_coverage_schema_and_chip_matrix_coverage():
    """Pin the gate_coverage record schema (the evidence line perf_ab
    emits per dedupe shape) AND the ISSUE-11 acceptance claim: for
    every shape in the chip A/B matrix ([(1000, 12), (1000, 8)]), the
    would-run decision is "pallas" or "pallas-tiled" — NEVER a
    wholesale "xla-hash" — and the k=12 (L=1000) headline shape that
    previously degraded is admitted. Host-only: no chip, no tracing,
    just the width-aware gate math."""
    for k in (12, 8):
        rec = _gate_coverage_for(k)
        # schema pin: the chip campaign scripts read these fields
        assert set(rec) == {"C", "capacity", "budget", "packable",
                            "state_bits", "packed_width_bits",
                            "would_run", "bytes_per_row"}, rec
        assert set(rec["would_run"]) == {"packed", "unpacked"}
        assert rec["packable"] is True
        assert rec["bytes_per_row"]["unpacked"] == 48
        assert rec["bytes_per_row"]["packed"] < 48
        assert rec["packed_width_bits"] == rec["state_bits"] + rec["C"]
        for layout in ("packed", "unpacked"):
            assert rec["would_run"][layout] in ("pallas",
                                                "pallas-tiled"), \
                (k, layout, rec)
    # k=8 at capacity 4096 fits the fused kernel outright
    assert _gate_coverage_for(8)["would_run"]["packed"] == "pallas"
    # k=12 at capacity 65536 is past whole-event fusion but covered
    # by the tiled closure — the previously-degraded headline shape
    r12 = _gate_coverage_for(12)
    assert r12["would_run"]["packed"] in ("pallas", "pallas-tiled")
    assert r12["would_run"]["packed"] != "xla-hash"


def test_gate_coverage_unpackable_family():
    """A family whose word exceeds 64 bits reports packable=False with
    null packed fields — the overflow-to-unpacked evidence the record
    must carry rather than fabricate."""
    from jepsen_tpu.parallel import sparse_kernels as sk
    rec = sk.gate_coverage(n_states=1 << 30, state_lo=0, C=40, N=1024)
    assert rec["packable"] is False
    assert rec["packed_width_bits"] is None
    assert rec["would_run"]["packed"] is None
    assert rec["would_run"]["unpacked"] in ("pallas", "pallas-tiled")


@pytest.mark.slow
def test_perf_ab_emits_cost_table_on_cpu():
    """Full smoke run of the harness: the aggregated cost_table line
    carries populated while+fori priors (plus static trip counts) for
    every measured shape and precedes the verdict."""
    env = dict(os.environ)
    env.update({"BENCH_SMOKE": "1", "JAX_PLATFORMS": "cpu"})
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "perf_ab.py")],
        capture_output=True, text=True, env=env, timeout=900)
    assert r.returncode == 0, r.stderr[-2000:]
    lines = [json.loads(ln) for ln in r.stdout.splitlines()
             if ln.lstrip().startswith("{")]
    assert [l for l in lines if "shape" in l], lines
    table = next(l for l in lines if "cost_table" in l)["cost_table"]
    assert set(table) == {"single-200", "single-400", "batch"}
    for shape, cost in table.items():
        for variant in ("while", "fori"):
            assert cost[variant].get("flops", 0) > 0, (shape, cost)
            assert cost[variant]["program"] == f"xla-{variant}"
        assert cost["trips"]["scan_events"] > 0, (shape, cost)
        assert cost["trips"]["fori_closure"] > 0, (shape, cost)
    # all variants agreed on every shape (interpret-mode pallas and
    # the packed word included): the correctness gate stays silent
    assert not [l for l in lines if "correctness_mismatch" in l], lines
    # every dedupe shape ships its host-only gate-coverage evidence
    gc = [l for l in lines if "gate_coverage" in l]
    assert gc and all("would_run" in l["gate_coverage"] for l in gc)
    assert "config_pack_verdict" in lines[-1]
    assert "verdict" in lines[-1]


def test_perf_ab_elastic_unknown_arm_raises():
    """PERF_AB_ELASTIC gets the same typo-protection as the other
    selector envs: an unknown arm aborts at import with the valid set
    named, never a silent skip that reads as measured-and-lost."""
    env = dict(os.environ)
    env.update({"PERF_AB_ELASTIC": "steal,reshards",
                "JAX_PLATFORMS": "cpu"})
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "perf_ab.py")],
        capture_output=True, text=True, env=env, timeout=300)
    assert r.returncode != 0, r.stdout[-500:]
    assert "unknown arm" in r.stderr, r.stderr[-500:]
    assert "reshards" in r.stderr
    # steal is not an arm; the valid set names reshard alone
    assert "'steal'" in r.stderr, r.stderr[-500:]
    assert "valid: reshard" in r.stderr, r.stderr[-500:]


def _load_perf_ab():
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "perf_ab", os.path.join(REPO, "tools", "perf_ab.py"))
    perf_ab = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(perf_ab)
    return perf_ab


@pytest.mark.slow
def test_compile_record_warm_beats_cold_on_chip_matrix():
    """The compile-economics acceptance pin, at CPU scale: for both
    chip-matrix ks (the dedupe A/B's k=8 / k=12 pair, derated to tiny
    op counts), the warm-cache arm serves its FIRST dispatch with zero
    fresh compiles — the registry ledger proves it (compiles == 0,
    preloads >= 1, load_errors == 0), not a timing inference — and
    strictly faster than the cold arm, with a bit-identical verdict
    pin. The population record rides along: canonicalization must
    never *increase* the distinct-program count, and the jittered
    extra_rows here (three lengths, one quantum rung) must shrink it."""
    perf_ab = _load_perf_ab()
    out = perf_ab.compile_record([(200, 8), (200, 6)],
                                 extra_rows=[100, 101, 120])
    assert len(out["records"]) == 2
    for rec in out["records"]:
        assert "cold_error" not in rec and "warm_error" not in rec, rec
        assert "pin_mismatch" not in rec, rec
        assert rec["cold_compiles"] >= 1, rec
        assert rec["warm_compiles"] == 0, rec
        assert rec["warm_preloads"] >= 1, rec
        assert rec["warm_load_errors"] == 0, rec
        assert (rec["warm_first_dispatch_secs"]
                < rec["cold_first_dispatch_secs"]), rec
    pop = out["population"]
    assert pop["canon"] <= pop["exact"], pop
    assert pop["canon"] < pop["exact"], pop   # 100/101/120 share rungs
    assert pop["canon"] >= 1


def test_perf_ab_compile_invalid_value_raises():
    """PERF_AB_COMPILE gets the same typo-protection as the other
    selector envs: anything but 0/1 aborts at import with the valid
    set named."""
    env = dict(os.environ)
    env.update({"PERF_AB_COMPILE": "yes", "JAX_PLATFORMS": "cpu"})
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "perf_ab.py")],
        capture_output=True, text=True, env=env, timeout=300)
    assert r.returncode != 0, r.stdout[-500:]
    assert "PERF_AB_COMPILE" in r.stderr, r.stderr[-500:]
    assert "valid: 0,1" in r.stderr, r.stderr[-500:]
