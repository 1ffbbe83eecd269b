"""A/B/C perf harness: XLA while-closure vs XLA fori-closure vs the
VMEM-resident pallas kernel.

Decides (a) whether JEPSEN_TPU_PALLAS should default ON for the TPU
backend, and (b) whether the XLA closure's default loop shape should
flip to the fixed-trip fori variant (JEPSEN_TPU_CLOSURE=fori) — both
gated behind env flags until a hardware measurement exists ("flags do
not get to claim speedups", pallas_kernels.py docstring). Run on the
real chip:

    python tools/perf_ab.py              # full shapes
    BENCH_SMOKE=1 python tools/perf_ab.py  # tiny shapes (CI sanity)

Measures, per shape, steady-state wall time (cold run first to absorb
compiles; results fetched to host, so timings include the device sync),
and CORRECTNESS: each variant's result is compared against the while
baseline on every timed shape — a variant that ever disagrees is
vetoed from the verdict regardless of its speed (the on-chip gate the
pallas non-interpret lowering must pass before any default flip):

  single-key adversarial 1k / 10k   (the bench's headline shape)
  multi-key 84x120 batch            (the reference workload shape)

Prints one JSON line per measurement and a final verdict line with the
pallas:xla ratio per shape. The engine paths are driven through their
public entry points (check_encoded_bitdense / check_batch_bitdense)
with use_pallas explicitly set, so what is measured is exactly what the
flag would switch.
"""

from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

from jepsen_tpu import obs  # noqa: E402  (sys.path bootstrap above)

SMOKE = os.environ.get("BENCH_SMOKE") == "1"
REPEATS = 3

# PERF_AB_VARIANTS=while,pallas skips the others. Exists because a
# variant can crash the TPU *worker process* (observed r5: the fori
# closure on the 10k adversarial shape took down the worker), which
# poisons the whole PJRT client — no in-process try/except can recover
# it. A skipped variant keeps its prior verdict; 'while' (the baseline)
# is always measured.
_VARIANTS = {v.strip() for v in os.environ.get(
    "PERF_AB_VARIANTS", "while,fori,pallas").split(",") if v.strip()}
_UNKNOWN = _VARIANTS - {"while", "fori", "pallas"}
if _UNKNOWN:   # a typo must not silently skip a real variant
    raise SystemExit(f"PERF_AB_VARIANTS: unknown variant(s) "
                     f"{sorted(_UNKNOWN)}; valid: while,fori,pallas")
_VARIANTS.add("while")

# PERF_AB_DEDUPE=sort,hash,hash-pallas,hash-packed (default all four)
# selects the sparse-engine frontier-dedupe strategies the advisory
# A/B measures on the single-key adversarial shapes — the one-command
# measurement the JEPSEN_TPU_DEDUPE, JEPSEN_TPU_SPARSE_PALLAS, and
# JEPSEN_TPU_CONFIG_PACK flip-to-default decisions wait on
# ("hash-pallas" = the hash strategy through the VMEM frontier
# kernels, parallel.sparse_kernels — fused inside the width-aware
# gate, TILED past it, so no chip-matrix shape skips wholesale
# anymore; "hash-packed" = the hash strategy over the packed
# configuration word). Same skip-a-crashing-variant rationale as
# PERF_AB_VARIANTS; empty (PERF_AB_DEDUPE=) skips the block entirely.
# A typo raises with the valid set listed — an unknown name silently
# skipped would read as "measured and lost".
_DEDUPE_VALID = ("sort", "hash", "hash-pallas", "hash-packed")
_DEDUPE = [v.strip() for v in os.environ.get(
    "PERF_AB_DEDUPE",
    "sort,hash,hash-pallas,hash-packed").split(",") if v.strip()]
_UNKNOWN_D = set(_DEDUPE) - set(_DEDUPE_VALID)
if _UNKNOWN_D:
    raise SystemExit(f"PERF_AB_DEDUPE: unknown strategy(ies) "
                     f"{sorted(_UNKNOWN_D)}; valid: "
                     f"{','.join(_DEDUPE_VALID)}")

# PERF_AB_ELASTIC=reshard (the default; empty skips it) selects the
# elastic arm — the recorded A/B the JEPSEN_TPU_RESHARD flip decision
# waits on: "reshard" times the grow-the-table sharded ladder against
# the device-recruiting one on an escalating adversarial shape. Same
# validation posture as the other selector envs: a typo raises with
# the valid set listed.
_ELASTIC_VALID = ("reshard",)
_ELASTIC = [v.strip() for v in os.environ.get(
    "PERF_AB_ELASTIC", "reshard").split(",") if v.strip()]
_UNKNOWN_E = set(_ELASTIC) - set(_ELASTIC_VALID)
if _UNKNOWN_E:
    raise SystemExit(f"PERF_AB_ELASTIC: unknown arm(s) "
                     f"{sorted(_UNKNOWN_E)}; valid: "
                     f"{','.join(_ELASTIC_VALID)}")

# PERF_AB_COMPILE=0 skips the compile-economics record (default on) —
# cold-start vs warm-cache first-dispatch through a shared
# JEPSEN_TPU_COMPILE_CACHE dir, each arm its own subprocess so the
# in-process jit cache can't leak the cold arm's compile into the warm
# one. Same validation posture: an unrecognized value raises.
_COMPILE = os.environ.get("PERF_AB_COMPILE", "1")
if _COMPILE not in ("0", "1"):
    raise SystemExit(f"PERF_AB_COMPILE: {_COMPILE!r} invalid; "
                     f"valid: 0,1")

# PERF_AB_AUTO=1 adds the self-tuning planner arm (JEPSEN_TPU_AUTO,
# parallel.planner): the same adversarial sparse shape dispatched with
# every strategy axis left unset, so the online decision table routes
# it from the evidence the dispatches themselves mint. ADVISORY ONLY —
# the auto timings never feed a flip verdict (the planner only routes
# BETWEEN arms the static lines already measured); the line exists so
# the flag-flip campaign can see whether the table converges to the
# measured winner. Same validation posture: a typo raises.
_AUTO = os.environ.get("PERF_AB_AUTO", "0")
if _AUTO not in ("0", "1"):
    raise SystemExit(f"PERF_AB_AUTO: {_AUTO!r} invalid; valid: 0,1")


def _want(name: str) -> bool:
    return name in _VARIANTS


def emit(obj):
    print(json.dumps(obj), flush=True)


def _cost_priors(lower_one, pallas_ok: bool) -> dict:
    """Per-variant analytical prior from XLA's trace-time cost model
    (bitdense.cost_analysis_*): a ranking signal that exists even when
    no chip is reachable, and a cross-check on the measured ratios
    once one is. The while/fori rows are backend-independent; the
    pallas row is not (its 'program' field says what was costed).
    `lower_one(use_pallas, mode)` returns {"flops", "bytes_accessed",
    "program"}."""
    out = {}
    for name, (up, mode) in {"while": (False, "while"),
                             "fori": (False, "fori"),
                             "pallas": (True, "while")}.items():
        if name == "pallas" and not pallas_ok:
            out[name] = {"skipped": "unsupported shape"}
            continue
        try:
            out[name] = lower_one(up, mode)
        except Exception as err:  # noqa: BLE001 — the prior is
            out[name] = {"error": repr(err)}   # advisory, never fatal
    return out


def _cost_entry(lower_one, pallas_ok: bool, scan_events: int,
                C: int) -> dict:
    """One cost_table row: per-variant priors + the static trip counts
    (the cost model counts loop bodies once, so totals are modeled as
    body-cost x trips by the consumer)."""
    cost = _cost_priors(lower_one, pallas_ok)
    cost["trips"] = {"scan_events": scan_events,
                     "fori_closure": -(-C // 2)}
    return cost


def _steady(fn, shape: str = "", variant: str = ""):
    """Best-of-REPEATS steady wall time, measured through obs.timer so
    the recorded spans (with shape/variant attrs) and the emitted
    numbers are the same clock reads — run with JEPSEN_TPU_TRACE=1 and
    the measurement session itself opens in Perfetto. The best-of is
    also fed to the perf_ab.steady_secs registry histogram."""
    fn()                                    # cold: compile + warm cache
    best = float("inf")
    for _ in range(REPEATS):
        with obs.timer("perf_ab.run", shape=shape,
                       variant=variant) as tm:
            fn()
        best = min(best, tm.wall)
    obs.histogram("perf_ab.steady_secs").observe(best)
    return best


def _strip_closure(r):
    if isinstance(r, list):
        return [_strip_closure(x) for x in r]
    return {k: v for k, v in r.items() if k != "closure"}


PROFILE_DIR = os.environ.get("PERF_AB_PROFILE")


def _timed(res: dict, name: str, check, shape: str = "") -> float:
    """Time `check` via _steady, recording the result of EVERY
    execution (cold + each repeat) under res[name] — a
    nondeterministically-wrong kernel that happens to answer
    correctly on its last run must still flag.

    With PERF_AB_PROFILE=<dir>, one extra post-timing run per
    (shape, variant) is captured under jax.profiler.trace into its own
    subdirectory — the diagnosis artifact for WHERE the time goes
    (dispatch/sync vs compute; the r3 multikey regression suspicion),
    kept out of the timed runs so profiling overhead never skews the
    measured ratios."""
    def f():
        res.setdefault(name, []).append(check())
    t = _steady(f, shape=shape, variant=name)
    if PROFILE_DIR:
        try:
            import jax
            sub = os.path.join(
                PROFILE_DIR, _run_token(),
                f"{shape or 'shape'}-{name}".replace(" ", "_"))
            os.makedirs(sub, exist_ok=True)
            with jax.profiler.trace(sub):
                f()          # result feeds the correctness gate too
            emit({"profile": sub, "shape": shape, "variant": name})
        except Exception as err:  # noqa: BLE001 — the capture is
            # advisory, never fatal: timings and verdict already stand
            emit({"profile_error": repr(err), "shape": shape,
                  "variant": name})
    return t


_RUN_TOKEN = None


def _run_token() -> str:
    """One fresh subdirectory per harness invocation, so re-running
    into the same PERF_AB_PROFILE dir never mixes trace sessions."""
    global _RUN_TOKEN
    if _RUN_TOKEN is None:
        from datetime import datetime
        _RUN_TOKEN = (datetime.now().strftime("%Y%m%d-%H%M%S")
                      + f"-p{os.getpid()}")
    return _RUN_TOKEN


def _disagreeing(results: dict) -> set:
    """Correctness gate: every run of every measured variant must
    return the SAME result (verdict + counterexample fields; the
    closure label aside) as the while baseline's first run — a faster
    wrong kernel must never win. Returns the variant names with any
    disagreeing run (emitted; they veto the matching verdict below;
    'while' itself can flag, vetoing everything: it means the
    measurement is nondeterministic)."""
    vals = {k: [_strip_closure(r) for r in runs]
            for k, runs in results.items()}
    base = vals["while"][0]
    bad = {k for k, runs in vals.items()
           if any(r != base for r in runs)}
    if bad:
        emit({"correctness_mismatch":
              {k: vals[k] for k in sorted(bad | {"while"})}})
    return bad


# The compile record's program cache: one fixed path (under the
# gitignored store/), emptied before each shape's cold arm.
_COMPILE_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    "store", "perf_ab_programs")


def _compile_arm(L: int, k: int, cache: str) -> dict:
    """One compile-record arm, in this process: reset the program
    registry (what a restarted serve replica starts from), then time
    the FIRST check_encoded dispatch of the adversarial shape — trace +
    compile or program-cache load + run, fetched to host — and report
    the registry ledger, so a fresh compile (cold) is told from a
    deserialized executable (warm) without guessing."""
    from jepsen_tpu.histories import adversarial_register_history
    from jepsen_tpu.models import CASRegister
    from jepsen_tpu.parallel import encode as enc_mod
    from jepsen_tpu.parallel import engine as eng_mod
    from jepsen_tpu.parallel import programs
    flags = {"JEPSEN_TPU_COMPILE_CACHE": cache,
             "JEPSEN_TPU_CANON_SHAPES": "1",
             "JEPSEN_TPU_PRECOMPILE": "0"}
    saved = {f: os.environ.get(f) for f in flags}
    os.environ.update(flags)
    try:
        programs.reset()
        e = enc_mod.encode(CASRegister(), adversarial_register_history(
            n_ops=L, k_crashed=k, seed=7))
        cap = 1 << (k + 4)
        t0 = time.perf_counter()
        r = eng_mod.check_encoded(e, capacity=cap, max_capacity=cap * 4,
                                  dedupe="hash")
        secs = time.perf_counter() - t0
        reg = programs.registry()
        return {"first_dispatch_secs": secs,
                "stats": reg.stats() if reg is not None else None,
                "rows": int(e.slot_f.shape[0]),
                "pin": {f: r.get(f) for f in ("valid?", "op", "fail-event",
                                              "max-frontier",
                                              "configs-stepped")}}
    finally:
        for f, v in saved.items():
            if v is None:
                os.environ.pop(f, None)
            else:
                os.environ[f] = v
        programs.reset()


def compile_record(shapes, extra_rows=()):
    """The compile-economics record (docs/performance.md "Compile
    economics"): per chip-matrix shape, cold-start vs warm-cache
    first-dispatch seconds through one program-cache directory. Both
    arms run in this process (a chip belongs to one process at a
    time), each on a freshly reset program registry: the cold arm
    compiles and persists, the warm arm loads what it persisted — the
    restart a serve replica pays with and without a populated cache.
    Also emits the program-population arithmetic (distinct event-row
    shapes, exact vs canonicalized onto the EVENT_QUANTUM ladder) over
    every row count measured plus `extra_rows` — the
    JEPSEN_TPU_CANON_SHAPES sizing evidence, computable with no chip.
    Returns the per-shape records and the population dict;
    tests/test_perf_ab.py calls this directly on tiny shapes and
    asserts the warm arm is strictly faster with zero fresh
    compiles."""
    import shutil
    records = []
    rows = [int(r) for r in extra_rows]
    for L, k in shapes:
        shutil.rmtree(_COMPILE_CACHE_DIR, ignore_errors=True)
        line = {"shape": f"compile-{L}@2^{k}"}
        cold = _compile_arm(L, k, _COMPILE_CACHE_DIR)
        warm = _compile_arm(L, k, _COMPILE_CACHE_DIR)
        line.update(
            cold_first_dispatch_secs=round(cold["first_dispatch_secs"], 3),
            warm_first_dispatch_secs=round(warm["first_dispatch_secs"], 3),
            warm_speedup=round(cold["first_dispatch_secs"]
                               / max(warm["first_dispatch_secs"], 1e-9),
                               2),
            cold_compiles=(cold["stats"] or {}).get("compiles"),
            warm_compiles=(warm["stats"] or {}).get("compiles"),
            warm_preloads=(warm["stats"] or {}).get("preloads"),
            warm_load_errors=(warm["stats"] or {}).get("load_errors"))
        # a cache-loaded program that answers differently is a
        # correctness failure, not a perf detail — flag it like the
        # variant mismatches above
        if warm["pin"] != cold["pin"]:
            line["pin_mismatch"] = True
        rows.append(int(cold["rows"]))
        emit(line)
        records.append(line)
    from jepsen_tpu.parallel import programs
    pop = programs.population_counts(rows) if rows else None
    emit({"compile_population": pop,
          "rows_measured": sorted(set(rows)),
          "note": "distinct event-row shapes a workload compiles, "
                  "exact vs canonicalized onto the EVENT_QUANTUM "
                  "ladder — the per-process program count "
                  "JEPSEN_TPU_CANON_SHAPES buys down; pure quantum "
                  "arithmetic, no chip needed"})
    return {"records": records, "population": pop}


def main():
    from jepsen_tpu import probe
    health = probe.check_in_process(120.0)
    if health["verdict"] != "healthy":
        emit({"error": f"device runtime unusable "
                       f"({health['verdict']}: "
                       f"{health.get('error', 'hung')}); set "
                       f"JAX_PLATFORMS=cpu for an interpret-mode "
                       f"sanity run"})
        sys.exit(1)

    import jax
    backend = jax.default_backend()
    from jepsen_tpu.histories import (
        adversarial_register_history, rand_register_history)
    from jepsen_tpu.models import CASRegister
    from jepsen_tpu.parallel import bitdense, encode as enc_mod
    from jepsen_tpu.parallel import pallas_kernels as pk

    # off-TPU runs are interpret-mode sanity checks whose timings the
    # verdict ignores — full shapes would grind for hours producing
    # discarded numbers, so force the tiny shapes.
    smoke = SMOKE or not bitdense.is_tpu_platform(backend)
    if smoke and not SMOKE:
        emit({"note": f"non-tpu backend {backend!r}: forcing smoke "
                      f"shapes (interpret-mode timings, no verdict)"})
    model = CASRegister()
    ratios = {}
    fori_ratios = {}
    cost_table = {}
    bad_variants = set()       # variants that ever disagreed

    # ---- single-key adversarial ----
    adv_sizes = []           # the Ls measured — the dedupe A/B derates
    for L in ([200, 400] if smoke else [1000, 10000]):  # its own shapes
        # k=11 keeps the smoke shapes inside kernel support (C >= 12)
        k_crashed = 11 if smoke else 12
        h = adversarial_register_history(
            n_ops=L, k_crashed=k_crashed, seed=7)
        e = enc_mod.encode(model, h)
        adv_sizes.append(L)
        S, C = bitdense.n_states(e), max(5, e.n_slots)
        cost_table[f"single-{L}"] = _cost_entry(
            lambda up, mode: bitdense.cost_analysis_encoded(
                e, use_pallas=up, closure_mode=mode),
            pk.supported(S, C), e.n_returns, C)
        # while and fori are pure XLA: measured on EVERY shape — the
        # fori decision must never be settled by a pallas support skip.
        # Every execution's RESULT is captured for the correctness gate.
        res = {}

        def timed(name, **kw):
            return _timed(res, name,
                          lambda: bitdense.check_encoded_bitdense(
                              e, **kw),
                          shape=f"single-{L}")

        t_xla = timed("while", use_pallas=False, closure_mode="while")
        line = {"shape": f"single-key {L}-op adversarial", "S": S,
                "C": C,
                "xla_secs": round(t_xla, 3)}
        if _want("fori"):
            t_fori = timed("fori", use_pallas=False,
                           closure_mode="fori")
            fori_ratios[f"single-{L}"] = t_xla / t_fori
            line.update(fori_secs=round(t_fori, 3),
                        fori_speedup=round(t_xla / t_fori, 2))
        if _want("pallas") and pk.supported(S, C):
            # a variant that fails to COMPILE (e.g. a Mosaic lowering
            # gap only the real chip reveals — the r5 jnp.flip `rev`
            # find) must veto itself, not kill the while/fori
            # measurements the bench decision also needs
            try:
                t_pl = timed("pallas", use_pallas=True)
            except Exception as err:  # noqa: BLE001
                line["pallas_error"] = repr(err)[:300]
                bad_variants.add("pallas")
            else:
                ratios[f"single-{L}"] = t_xla / t_pl
                line.update(pallas_secs=round(t_pl, 3),
                            pallas_speedup=round(t_xla / t_pl, 2))
        elif _want("pallas"):
            line["pallas_skipped"] = f"unsupported S={S} C={C}"
        bad_variants |= _disagreeing(res)
        emit(line)

    # ---- sparse-engine frontier dedupe (advisory A/B) ----
    # sort (lexsort every closure iteration) vs hash (delta-frontier
    # closure over the device-resident visited set) on the SAME
    # adversarial shapes, through the public engine.check_encoded with
    # dedupe explicitly set — exactly what JEPSEN_TPU_DEDUPE would
    # switch. The configs-stepped counters are emitted alongside the
    # timings so the work reduction is visible even where the wall
    # times are noise (CPU). Verdict + localization + max-frontier must
    # agree between strategies (the counters differ by design); a
    # mismatch vetoes the dedupe verdict like any correctness failure.
    dedupe_ratios = {}
    sparse_pallas_ratios = {}
    config_pack_ratios = {}
    dedupe_bad = set()
    if _DEDUPE:
        from jepsen_tpu.parallel import engine as eng_mod
        from jepsen_tpu.parallel import sparse_kernels as sk
        # shape policy: the adversarial frontier peaks at ~10*2^k
        # configs, so full-k sparse runs cost minutes per strategy —
        # smoke (CPU) derates to k=6 (the delta asymptotics show at
        # any k; CI keeps the block exercised), the chip measures the
        # bench's real k at L=1000 (the representative sparse shape;
        # 10k at full k is tens of minutes per strategy and adds no
        # new information to the flip decision). The chip additionally
        # measures k=8 (capacity 4096) — the largest full-support
        # shape for the fused frontier kernel, whose VMEM gate excludes
        # the 2^16-capacity k=12 shape; the sparse-pallas flip decision
        # rides only shapes the kernel actually ran.
        if smoke:
            dedupe_shapes = [(L, 6) for L in adv_sizes]
        else:
            dedupe_shapes = [(1000, 12), (1000, 8)]
        for L, k_d in dedupe_shapes:
            e = enc_mod.encode(model, adversarial_register_history(
                n_ops=L, k_crashed=k_d, seed=7))
            cap = 1 << (k_d + 4)     # peak ~10*2^k configs, one tier
            shape_key = f"single-{L}@2^{k_d}"
            # the HOST-ONLY gate-coverage record (sparse_kernels.
            # gate_coverage): bytes/row, packed word width, and what
            # WOULD run (pallas / pallas-tiled / xla-hash) per layout
            # at this shape's capacity — computable with no chip, so
            # the flag-flip campaign inherits the sizing evidence
            # before a single on-chip measurement lands. Schema pinned
            # by tests/test_perf_ab.py.
            emit({"gate_coverage": sk.gate_coverage(
                      e.n_states, e.state_lo, e.slot_f.shape[1], cap),
                  "shape": shape_key})
            dres = {}
            dline = {"shape": f"single-key {L}-op adversarial "
                              f"sparse-dedupe (2^{k_d} open configs)"}
            for strat in _DEDUPE:
                if strat == "hash-pallas":
                    if bitdense.is_tpu_platform(backend):
                        dline["hash-pallas_skipped"] = (
                            "the sparse kernels do not compile for a "
                            "TPU (Only 2D gather is supported)")
                        continue
                    if not sk.supported(cap, e.slot_f.shape[1]) \
                            and sk.tiled_plan(
                                cap, e.slot_f.shape[1]) is None:
                        # only a shape even the TILED closure cannot
                        # cover skips — measuring the note-and-fallback
                        # path would time the XLA closure under the
                        # kernel's name. (The k=12 headline shape now
                        # runs: fused inside the gate, tiled past it.)
                        dline["hash-pallas_skipped"] = (
                            f"capacity {cap} past the kernel's VMEM "
                            f"gate even tiled")
                        continue
                    kw = {"dedupe": "hash", "sparse_pallas": True}
                elif strat == "hash-packed":
                    kw = {"dedupe": "hash", "config_pack": True}
                else:
                    kw = {"dedupe": strat}
                t = _timed(dres, strat,
                           lambda k=kw: eng_mod.check_encoded(
                               e, capacity=cap, max_capacity=cap * 4,
                               **k),
                           shape=f"dedupe-{L}-2^{k_d}")
                r0 = dres[strat][0]
                dline[f"{strat}_secs"] = round(t, 3)
                dline[f"{strat}_configs_stepped"] = \
                    r0.get("configs-stepped")
            pin = lambda r: {k_: r.get(k_) for k_ in  # noqa: E731
                             ("valid?", "op", "fail-event",
                              "max-frontier")}
            if dres:
                # dres can be empty: PERF_AB_DEDUPE=hash-pallas alone
                # on a shape past the kernel's VMEM gate skips the
                # only selected strategy — the line still emits (with
                # the skip note), the harness must not die on it
                base = pin(dres[next(iter(dres))][0])
                for strat, runs in dres.items():
                    if any(pin(r) != base for r in runs):
                        dline[f"{strat}_mismatch"] = True
                        dedupe_bad.add(strat)
            if "sort" in dres and "hash" in dres:
                dedupe_ratios[shape_key] = \
                    dline["sort_secs"] / max(dline["hash_secs"], 1e-9)
                dline["hash_speedup"] = round(
                    dedupe_ratios[shape_key], 2)
            if "hash" in dres and "hash-pallas" in dres:
                sparse_pallas_ratios[shape_key] = (
                    dline["hash_secs"]
                    / max(dline["hash-pallas_secs"], 1e-9))
                dline["hash_pallas_speedup"] = round(
                    sparse_pallas_ratios[shape_key], 2)
            if "hash" in dres and "hash-packed" in dres:
                config_pack_ratios[shape_key] = (
                    dline["hash_secs"]
                    / max(dline["hash-packed_secs"], 1e-9))
                dline["hash_packed_speedup"] = round(
                    config_pack_ratios[shape_key], 2)
            emit(dline)
            # the per-shape search-stats block (JEPSEN_TPU_SEARCH_
            # STATS machinery, forced on for this one untimed run so
            # the A/B JSONL ships probe/occupancy evidence alongside
            # the timings — ROADMAP items 2/5's sizing inputs): one
            # hash-dedupe run per shape, never timed, never part of
            # the flip decision
            if "hash" in dres:
                try:
                    rs = eng_mod.check_encoded(
                        e, capacity=cap, max_capacity=cap * 4,
                        dedupe="hash", search_stats=True)
                    st = dict(rs.get("stats") or {})
                    # trajectories are per-event lists — summarize for
                    # the JSONL record, the run dir keeps the full form
                    for key_ in ("frontier-width", "closure-iters",
                                 "configs-stepped-per-event",
                                 "closure-peak"):
                        st.pop(key_, None)
                    emit({"search_stats": st, "shape": shape_key})
                except Exception as err:  # noqa: BLE001 — advisory
                    # evidence must not kill the measurement run
                    emit({"search_stats_error": repr(err),
                          "shape": shape_key})

    # ---- auto planner arm (JEPSEN_TPU_AUTO — advisory only) ----
    # one adversarial sparse shape, every strategy axis left unset,
    # the planner routing from a throwaway ledger dir: measures the
    # cost of letting the online table pick vs. dispatching the static
    # default, and records the vector the table converged to. The
    # steady loop itself is the convergence driver — the cold run and
    # each repeat mint evidence, so by the best-of window the table
    # has samples past the floor. Never part of a flip verdict; the
    # same >=1.1x / never-disagreed reading is applied to the advisory
    # ratio so the JSONL is self-describing.
    auto_ratios = {}
    auto_bad = False
    auto_plan = None
    if _AUTO == "1":
        import shutil
        import tempfile
        from jepsen_tpu.obs import ledger as led_mod
        from jepsen_tpu.parallel import engine as eng_mod
        from jepsen_tpu.parallel import planner as pl_mod
        L_a, k_a = (adv_sizes[0], 6) if smoke else (1000, 8)
        e_a = enc_mod.encode(model, adversarial_register_history(
            n_ops=L_a, k_crashed=k_a, seed=7))
        cap_a = 1 << (k_a + 4)
        shape_key = f"auto-{L_a}@2^{k_a}"
        ares = {}
        t_static = _timed(ares, "static",
                          lambda: eng_mod.check_encoded(
                              e_a, capacity=cap_a,
                              max_capacity=cap_a * 4),
                          shape=shape_key)
        tmp = tempfile.mkdtemp(prefix="jepsen-perf-ab-auto-")
        saved = {k_: os.environ.get(k_)
                 for k_ in ("JEPSEN_TPU_AUTO", "JEPSEN_TPU_LEDGER")}
        os.environ["JEPSEN_TPU_AUTO"] = "1"
        os.environ["JEPSEN_TPU_LEDGER"] = tmp
        pl_mod.reset()
        led_mod.reset()
        try:
            t_auto = _timed(ares, "auto",
                            lambda: eng_mod.check_encoded(
                                e_a, capacity=cap_a,
                                max_capacity=cap_a * 4),
                            shape=shape_key)
        finally:
            # the arm must not leak AUTO routing (or the throwaway
            # table) into the elastic / batch blocks that follow
            for k_, v_ in saved.items():
                if v_ is None:
                    os.environ.pop(k_, None)
                else:
                    os.environ[k_] = v_
            pl_mod.reset()
            led_mod.reset()
            shutil.rmtree(tmp, ignore_errors=True)
        pin_a = lambda r: {k_: r.get(k_) for k_ in  # noqa: E731
                           ("valid?", "op", "fail-event",
                            "max-frontier")}
        base_a = pin_a(ares["static"][0])
        auto_bad = any(pin_a(r) != base_a
                       for r in ares["static"] + ares["auto"])
        auto_plan = ares["auto"][-1].get("plan")
        auto_ratios[shape_key] = t_static / max(t_auto, 1e-9)
        emit({"shape": f"single-key {L_a}-op adversarial auto-planner "
                       f"(2^{k_a} open configs)",
              "static_secs": round(t_static, 3),
              "auto_secs": round(t_auto, 3),
              "auto_speedup": round(auto_ratios[shape_key], 2),
              "auto_plan": auto_plan,
              "auto_mismatch": auto_bad})

    # ---- elastic re-sharding (reshard arm) ----
    reshard_ratios = {}
    reshard_bad = False
    if "reshard" in _ELASTIC:
        import numpy as _np
        from jax.sharding import Mesh as _Mesh
        mesh_all = _Mesh(_np.array(jax.devices()), ("key",))
        from jepsen_tpu.parallel import sharded as sh_mod
        L_r, k_r = (200, 6) if smoke else (1000, 8)
        e_r = enc_mod.encode(model, adversarial_register_history(
            n_ops=L_r, k_crashed=k_r, seed=7))
        cap_r = 128    # well under the ~10*2^k peak: both arms
        # must climb their ladders — that climb IS the measurement
        shape_key = f"sharded-reshard-{L_r}@2^{k_r}"
        res_r = {}
        t_st = _timed(res_r, "static",
                      # reshard pinned OFF: an exported
                      # JEPSEN_TPU_RESHARD=1 must not delegate
                      # the static arm to the elastic ladder and
                      # A/B it against itself
                      lambda: sh_mod.check_encoded_sharded(
                          e_r, mesh_all, capacity=cap_r,
                          max_capacity=1 << 16, reshard=False),
                      shape=shape_key)
        t_el = _timed(res_r, "reshard",
                      lambda: sh_mod.check_encoded_sharded_elastic(
                          e_r, mesh_all, capacity=cap_r,
                          max_capacity=1 << 16),
                      shape=shape_key)
        pin_r = lambda r: {k_: r.get(k_) for k_ in  # noqa: E731
                           ("valid?", "op", "fail-event",
                            "max-frontier")}
        base_r = pin_r(res_r["static"][0])
        if any(pin_r(r) != base_r for r in res_r["reshard"]):
            reshard_bad = True
            emit({"reshard_mismatch": True, "shape": shape_key})
        reshard_ratios[shape_key] = t_st / max(t_el, 1e-9)
        emit({"shape": shape_key,
              "static_secs": round(t_st, 3),
              "reshard_secs": round(t_el, 3),
              "reshard_speedup": round(
                  reshard_ratios[shape_key], 2),
              "reshard_events": (res_r["reshard"][0].get("reshard")
                                 or {}).get("events"),
              "devices_final": res_r["reshard"][0].get("devices")})

    # ---- multi-key batch ----
    n_keys, ops_per_key = (8, 40) if smoke else (84, 120)
    keys = [rand_register_history(
        n_ops=ops_per_key, n_processes=14, n_values=5, crash_p=0.005,
        fail_p=0.05, busy=0.8, seed=2024 + k) for k in range(n_keys)]
    encs = [enc_mod.encode(model, h) for h in keys]
    S = max(bitdense.n_states(e) for e in encs)
    C = max(5, max(e.n_slots for e in encs))
    cost_table["batch"] = _cost_entry(
        lambda up, mode: bitdense.cost_analysis_batch(
            encs, use_pallas=up, closure_mode=mode),
        pk.supported(S, C), max(e.n_returns for e in encs), C)
    res = {}

    def timed_batch(name, **kw):
        return _timed(res, name,
                      lambda: bitdense.check_batch_bitdense(encs, **kw),
                      shape="batch")

    t_xla = timed_batch("while", use_pallas=False, closure_mode="while")
    line = {"shape": f"batch {n_keys}x{ops_per_key}", "S": S, "C": C,
            "xla_secs": round(t_xla, 3)}
    if _want("fori"):
        t_fori = timed_batch("fori", use_pallas=False,
                             closure_mode="fori")
        fori_ratios["batch"] = t_xla / t_fori
        line.update(fori_secs=round(t_fori, 3),
                    fori_speedup=round(t_xla / t_fori, 2))
    if _want("pallas") and pk.supported(S, C):
        try:
            t_pl = timed_batch("pallas", use_pallas=True)
        except Exception as err:  # noqa: BLE001
            line["pallas_error"] = repr(err)[:300]
            bad_variants.add("pallas")
        else:
            ratios["batch"] = t_xla / t_pl
            line.update(pallas_secs=round(t_pl, 3),
                        pallas_speedup=round(t_xla / t_pl, 2))
    elif _want("pallas"):
        line["pallas_skipped"] = f"unsupported S={S} C={C}"
    bad_variants |= _disagreeing(res)
    emit(line)

    # ---- batch bucketing strategy (advisory measurement) ----
    # The bench's 84-key batch pads every key to the max slot count
    # (the r5 run: slots 11..15 -> one C=15 / W=1024 program; keys
    # needing W=64 pay 16x the word-work), because engine.check_batch's
    # power-of-two tiers put slots 9..16 in ONE tier. Exact-C grouping
    # trades ~2.9x less word-work against one compile + dispatch per
    # group. This measures that trade on the same encs; a measured win
    # here is the evidence for changing engine.check_batch's bucketing
    # (no default flips from this line — it's a strategy prior, and on
    # CPU it mostly measures compile count).
    from collections import defaultdict
    groups = defaultdict(list)           # C -> [(orig_idx, enc)]
    for i, e in enumerate(encs):
        groups[max(5, e.n_slots)].append((i, e))
    if len(groups) > 1:
        gres = {}

        def run_grouped(**kw):
            outs = [None] * len(encs)
            for cc in sorted(groups):
                idxs = [i for i, _ in groups[cc]]
                rs = bitdense.check_batch_bitdense(
                    [e for _, e in groups[cc]], **kw)
                for i, r in zip(idxs, rs):
                    outs[i] = r
            return outs

        def timed_grouped(name, **kw):
            return _timed(gres, name, lambda: run_grouped(**kw),
                          shape="batch-bucketed")

        t_gx = timed_grouped("while", use_pallas=False,
                             closure_mode="while")
        gline = {"shape": f"batch {n_keys}x{ops_per_key} exact-C "
                          f"bucketed ({len(groups)} groups)",
                 "groups": {str(cc): len(g)
                            for cc, g in sorted(groups.items())},
                 "xla_secs": round(t_gx, 3),
                 "xla_vs_padded": round(t_xla / t_gx, 2)}
        if _want("pallas"):
            # groups below the kernel floor (W < 128) downgrade to the
            # XLA closure inside _resolve_use_pallas — exactly what the
            # real-TPU default does per shape, so the mixed execution
            # IS the default path; the per-group closure labels say
            # which groups ran which
            try:
                t_gp = timed_grouped("pallas", use_pallas=True)
            except Exception as err:  # noqa: BLE001
                gline["pallas_error"] = repr(err)[:300]
            else:
                # label each group by the closure that actually RAN
                # (stamped on the result rows by the engine's own
                # resolve), not a harness-side re-derivation of the gate
                first_run = gres["pallas"][0]
                gline.update(
                    pallas_secs=round(t_gp, 3),
                    pallas_closures={
                        str(cc): first_run[g[0][0]]["closure"]
                        for cc, g in sorted(groups.items())})
                # ratio only against the PADDED BATCH's own pallas
                # timing ("pallas_secs" in line proves it completed);
                # res["pallas"] being non-empty is not enough — a
                # partial batch failure would leave t_pl holding the
                # single-key loop's value
                if "pallas_secs" in line:
                    gline["pallas_vs_padded"] = round(t_pl / t_gp, 2)
        # correctness: run_grouped restores original key order, so the
        # comparison against the padded batch's while baseline is exact
        base = _strip_closure(res["while"][0])
        for gname, gruns in gres.items():
            if any(_strip_closure(gr) != base for gr in gruns):
                gline[f"{gname}_mismatch"] = True
        emit(gline)

    # ---- compile economics (cold vs warm first-dispatch) ----
    # the JEPSEN_TPU_COMPILE_CACHE decision record: what a replica
    # restart costs with and without the populated AOT cache, on the
    # same chip-matrix shapes the sparse-dedupe A/B measures; the
    # batch encs' row counts feed the canonicalization population
    # arithmetic (84 keys of jittered lengths is where exact-shape
    # program count actually hurts)
    if _COMPILE == "1":
        compile_record(
            [(200, 8), (200, 6)] if smoke else [(1000, 12), (1000, 8)],
            extra_rows=[e.slot_f.shape[0] for e in encs])

    # analytical prior table: flops/bytes per (shape, variant) from
    # XLA's trace-time cost model — exists without any chip; once a
    # measurement lands, a large disagreement between the prior's
    # byte/flop ranking and the measured ratio flags dispatch/sync
    # overhead (not compute) as the bottleneck
    emit({"cost_table": cost_table,
          "note": "trace-time XLA cost_analysis (flops / bytes "
                  "accessed) per closure variant; advisory only — "
                  "defaults flip on MEASURED ratios, never on the "
                  "prior. Loop bodies are counted ONCE by the cost "
                  "model (trip counts are data-dependent): these rank "
                  "per-iteration variant cost; model totals via the "
                  "'trips' entry. The pallas row's 'program' field "
                  "says which program was costed (interpret emulation "
                  "off-TPU vs an uncountable kernel custom call on "
                  "it) — pallas priors are NOT comparable across "
                  "backends"})

    if not bitdense.is_tpu_platform(backend):
        # interpret-mode timings measure the interpreter, not the
        # kernel — never let them flip the default
        verdict = "no-verdict (non-tpu backend: interpret-mode timings)"
        fori_verdict = verdict
        dedupe_verdict = ("no-verdict (non-tpu backend: cpu timings "
                          "don't flip defaults; the configs_stepped "
                          "counters stand on any backend)")
        sparse_pallas_verdict = ("no-verdict (non-tpu backend: "
                                 "interpret-mode kernel timings "
                                 "measure the interpreter)")
        config_pack_verdict = ("no-verdict (non-tpu backend: cpu "
                               "timings don't flip defaults; the "
                               "gate_coverage records stand on any "
                               "backend)")
        reshard_verdict = ("no-verdict (non-tpu backend: cpu "
                           "timings don't flip defaults)")
        auto_verdict = ("not-measured (PERF_AB_AUTO=0)"
                        if _AUTO != "1" else
                        "no-verdict (non-tpu backend; advisory either "
                        "way — JEPSEN_TPU_AUTO stays opt-in)")
    else:
        # a variant filtered out by PERF_AB_VARIANTS was not measured —
        # its verdict line must say so, never a definitive keep/flip
        # (the run's reader would otherwise revert a default that this
        # run produced no evidence against)
        if not _want("pallas"):
            verdict = "not-measured (pallas skipped by PERF_AB_VARIANTS)"
        else:
            verdict = ("default-on"
                       if ratios and min(ratios.values()) >= 1.1
                       else "keep-opt-in")
        if not _want("fori"):
            fori_verdict = ("not-measured (fori skipped by "
                            "PERF_AB_VARIANTS)")
        else:
            fori_verdict = ("default-fori"
                            if fori_ratios
                            and min(fori_ratios.values()) >= 1.1
                            else "keep-while")
        # correctness vetoes speed: a variant that EVER disagreed with
        # the while baseline cannot become the default, whatever it won
        if "pallas" in bad_variants or "while" in bad_variants:
            verdict = "keep-opt-in (VARIANT VETOED — see the " \
                      "correctness_mismatch / pallas_error lines)"
        if "fori" in bad_variants or "while" in bad_variants:
            fori_verdict = "keep-while (VARIANT VETOED — see the " \
                           "correctness_mismatch lines)"
        if not ({"sort", "hash"} <= set(_DEDUPE)):
            dedupe_verdict = ("not-measured (a strategy skipped by "
                              "PERF_AB_DEDUPE)")
        elif dedupe_bad:
            dedupe_verdict = ("keep-sort (STRATEGY VETOED — see the "
                              "*_mismatch keys on the sparse-dedupe "
                              "lines)")
        else:
            dedupe_verdict = ("default-hash"
                              if dedupe_ratios
                              and min(dedupe_ratios.values()) >= 1.1
                              else "keep-sort")
        if not ({"hash", "hash-pallas"} <= set(_DEDUPE)):
            sparse_pallas_verdict = ("not-measured (a strategy skipped "
                                     "by PERF_AB_DEDUPE)")
        elif dedupe_bad & {"hash", "hash-pallas"}:
            sparse_pallas_verdict = ("keep-opt-in (STRATEGY VETOED — "
                                     "see the *_mismatch keys on the "
                                     "sparse-dedupe lines)")
        else:
            sparse_pallas_verdict = (
                "default-on"
                if sparse_pallas_ratios
                and min(sparse_pallas_ratios.values()) >= 1.1
                else "keep-opt-in")
        if not ({"hash", "hash-packed"} <= set(_DEDUPE)):
            config_pack_verdict = ("not-measured (a strategy skipped "
                                   "by PERF_AB_DEDUPE)")
        elif dedupe_bad & {"hash", "hash-packed"}:
            config_pack_verdict = ("keep-opt-in (STRATEGY VETOED — "
                                   "see the *_mismatch keys on the "
                                   "sparse-dedupe lines)")
        else:
            config_pack_verdict = (
                "default-on"
                if config_pack_ratios
                and min(config_pack_ratios.values()) >= 1.1
                else "keep-opt-in")
        if "reshard" not in _ELASTIC:
            reshard_verdict = "not-measured (reshard skipped by " \
                              "PERF_AB_ELASTIC)"
        elif reshard_bad:
            reshard_verdict = ("keep-opt-in (ARM VETOED — see "
                               "reshard_mismatch)")
        else:
            reshard_verdict = ("default-on"
                               if reshard_ratios
                               and min(reshard_ratios.values()) >= 1.1
                               else "keep-opt-in")
        # the auto arm is ADVISORY on every backend: the planner only
        # routes between already-measured strategies, so its verdict
        # line reports convergence quality, never a default flip
        if _AUTO != "1":
            auto_verdict = "not-measured (PERF_AB_AUTO=0)"
        elif auto_bad:
            auto_verdict = ("advisory-veto (ARM DISAGREED — see "
                            "auto_mismatch; the planner routed to a "
                            "path whose results diverged)")
        else:
            auto_verdict = (
                "advisory-win (auto matched or beat static >=1.1x "
                "and never disagreed — JEPSEN_TPU_AUTO stays opt-in)"
                if auto_ratios and min(auto_ratios.values()) >= 1.1
                else "advisory-keep-static")
    emit({"backend": backend, "verdict": verdict,
          "fori_verdict": fori_verdict,
          "dedupe_verdict": dedupe_verdict,
          "sparse_pallas_verdict": sparse_pallas_verdict,
          "config_pack_verdict": config_pack_verdict,
          "reshard_verdict": reshard_verdict,
          "auto_verdict": auto_verdict,
          "auto_measured": _AUTO == "1",
          "auto_ratios": {k: round(v, 2)
                          for k, v in auto_ratios.items()},
          "variants_measured": sorted(_VARIANTS),
          "dedupe_measured": sorted(_DEDUPE),
          "elastic_measured": sorted(_ELASTIC),
          "reshard_ratios": {k: round(v, 2)
                             for k, v in reshard_ratios.items()},
          "ratios": {k: round(v, 2) for k, v in ratios.items()},
          "dedupe_ratios": {k: round(v, 2)
                            for k, v in dedupe_ratios.items()},
          "sparse_pallas_ratios": {k: round(v, 2)
                                   for k, v in
                                   sparse_pallas_ratios.items()},
          "config_pack_ratios": {k: round(v, 2)
                                 for k, v in
                                 config_pack_ratios.items()},
          "fori_ratios": {k: round(v, 2) for k, v in fori_ratios.items()},
          "rule": "pallas default-on iff it wins >=1.1x on EVERY "
                  "measured shape on the tpu backend AND never "
                  "disagreed with the while baseline's results; fori "
                  "likewise vs the while closure (flip "
                  "bitdense._resolve_closure_mode). If both win, "
                  "pallas takes precedence (it replaces the XLA loop "
                  "entirely). dedupe=hash flips JEPSEN_TPU_DEDUPE's "
                  "default (engine._resolve_dedupe) under the same "
                  ">=1.1x-on-every-shape + never-disagreed rule, "
                  "measured on the sparse engine's sparse-dedupe "
                  "lines above; hash-pallas (the VMEM frontier "
                  "kernels vs the XLA hash strategy — fused inside "
                  "the width-aware gate, TILED past it, so every "
                  "chip-matrix shape measures) flips "
                  "JEPSEN_TPU_SPARSE_PALLAS's default "
                  "(engine._resolve_sparse_pallas) under the same "
                  "rule; hash-packed (the packed configuration word "
                  "vs the unpacked triple) flips "
                  "JEPSEN_TPU_CONFIG_PACK's default "
                  "(engine._resolve_config_pack) likewise — the "
                  "gate_coverage lines record, per shape and layout, "
                  "bytes/row and what would run, chip-free. reshard "
                  "(the device-recruiting sharded ladder vs the "
                  "grow-the-table one) flips JEPSEN_TPU_RESHARD "
                  "(engine._resolve_reshard) under the same "
                  ">=1.1x-on-every-shape + never-disagreed rule. The "
                  "PERF_AB_AUTO=1 arm (JEPSEN_TPU_AUTO planner "
                  "routing all axes) is ADVISORY under the same "
                  ">=1.1x / never-disagreed reading: it reports "
                  "whether the online table converged to the "
                  "measured winner, and flips nothing"})


if __name__ == "__main__":
    try:
        main()
    except Exception as err:  # noqa: BLE001
        import traceback
        traceback.print_exc()
        emit({"error": repr(err)})
        sys.exit(1)
