"""Benchmark: linearizability-check throughput, device engines vs host.

The north-star metric (BASELINE.md): knossos ops/sec checked and max
history length verified @ 60s budget, target >= 100x a 32-core host on
adversarial histories. Emits one JSON line per sub-metric, HEADLINE
LAST (the driver parses `{"metric", "value", "unit", "vs_baseline"}`):

1. multi-key north-star shape — 84 keys x 120 ops (the reference's
   cas-register workload: 120-op keys via jepsen.independent,
   tendermint/src/jepsen/tendermint/core.clj:351-361), device
   end-to-end with the encode/device split reported, vs a measured
   host-engine baseline scaled to a MODELED 32-core box (ideal linear
   scaling — generous to the host; per-key checks parallelize
   perfectly, so 32x is the host's true ceiling).
2. adversarial single-key histories at 1k/5k/10k/50k ops
   (histories.adversarial_register_history: k crashed writes held open
   forever -> the host search carries 2^k configs through every event,
   the regime where knossos dies; SURVEY.md §2.10). Host runs under a
   cooperative deadline and reports real progress (events done), from
   which its full-run time is estimated. NOTE: a single key cannot be
   parallelized by knossos (linear/wgl are single-threaded per key),
   so no 32x scaling is applied to this baseline — stated in the
   methodology field.
3. frontier-sharded engine on the same 10k history over all local
   devices (1-device mesh on a single chip; the 8-device path is
   exercised by tests/test_sharded.py and the driver dryrun).
4. max history length verified within a 60s device budget
   (steady-state device time; compiles excluded and reported).

The host baseline is `checker.linear_packed` — the same
JIT-linearization algorithm knossos.linear runs (checker.clj:194-200)
over the same int encoding the device uses: our fastest fair CPU
implementation (4-6x the Model-object `checker.linear`; a slow
baseline would flatter the speedup). Caveat, stated rather than
fudged: a JVM knossos would run a Python baseline some constant factor
faster; the adversarial speedups measured here are orders of magnitude
above that factor.

HANG ISOLATION. Every section runs in its OWN subprocess under a hard
wall-clock timeout (the parent process never imports jax). A wedged
device runtime — one that blocks forever in backend start-up or a
device sync with no Python-level signal delivery — therefore costs
exactly one section, not the bench: the parent kills the child, emits
a machine-readable `{"skipped": "timeout/hang"}` line, and moves on.
The headline is computed by the parent from whichever sections
completed. Children re-emit their JSON lines on stdout; the parent
forwards them verbatim and parses them to thread host-baseline
estimates between sections.

NO DEVICE, NO NUMBER. A bounded pre-probe child touches the backend
before any device section (and refuses a CPU-only backend unless
JAX_PLATFORMS asks for the CPU). If it fails twice, or if no device
section completes, the bench prints an error line and exits non-zero:
it never substitutes a CPU number for a device one.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import threading
from time import monotonic, perf_counter

from jepsen_tpu import envflags
from jepsen_tpu import obs

# set in child_main when JEPSEN_TPU_TRACE is on: the repo-relative path
# this section's Chrome trace will be written to. emit() stamps it onto
# every JSON line the section produces ("trace": <relpath>) so the
# BENCH_* record points at the span evidence; with tracing off the key
# is absent and the line schema is byte-for-byte the historical one
# (pinned by tests/test_bench.py).
TRACE_REL = None

# -------- north-star multi-key shape (reference workload dimensions)
SMOKE = os.environ.get("BENCH_SMOKE") == "1"   # tiny shapes for CI/CPU
N_KEYS = 8 if SMOKE else 84
OPS_PER_KEY = 40 if SMOKE else 120
N_PROCESSES = 14
BUSY = 0.8
HOST_SAMPLE_KEYS = 2 if SMOKE else 4
SEED = 2024

# -------- adversarial single-key shape
ADV_K = 8 if SMOKE else int(os.environ.get("BENCH_ADV_K", "12"))
# ^ crashed writes held open: 2^k configs. Host cost scales ~4x per +2k;
#   the bit-packed device's scales ~4x per +2k only in W (memory), with
#   far smaller constants — raise k to widen the regime gap.
ADV_SIZES = [200, 400] if SMOKE else [1000, 5000, 10000, 50000]
HOST_DEADLINES = ({200: 10.0, 400: 5.0} if SMOKE
                  else {1000: 45.0, 5000: 20.0, 10000: 25.0, 50000: 15.0})
BUDGET_SECS = float(os.environ.get("BENCH_BUDGET_SECS", "900"))

# Per-section wall-clock timeouts (seconds). Generous against measured
# runtimes (compile + cold + steady + host deadline), tight against the
# global budget; tuned so a single hang leaves room for what follows.
TIMEOUT_SCALE = float(os.environ.get("BENCH_TIMEOUT_SCALE", "1"))
SEC_TIMEOUTS = {
    "multikey": 60 if SMOKE else 300,
    "adv": ({200: 60, 400: 60} if SMOKE
            else {1000: 180, 5000: 240, 10000: 300, 50000: 480}),
    "sharded": 90 if SMOKE else 300,
    "maxlen": 120 if SMOKE else 360,
    "stream": 90 if SMOKE else 240,
}


def sec_timeout(key: str, L: int | None = None) -> float:
    """Scaled per-section timeout. TIMEOUT_SCALE applies HERE — before
    the callers clamp by the remaining global budget — so a scale > 1
    can never push a section past BUDGET_SECS."""
    base = SEC_TIMEOUTS["adv"][L] if key == "adv" else SEC_TIMEOUTS[key]
    return base * TIMEOUT_SCALE


def emit(obj):
    if TRACE_REL is not None and "trace" not in obj:
        obj = {**obj, "trace": TRACE_REL}
    print(json.dumps(obj), flush=True)


def note(msg):
    print(f"# {msg}", file=sys.stderr, flush=True)


def emit_search_stats(section: str, results, extra=None):
    """The stats-gated occupancy/pad-waste advisory line: emitted ONLY
    under JEPSEN_TPU_SEARCH_STATS=1 (results then already carry the
    device-computed "stats" blocks — zero extra compute), so the
    default bench schema is byte-identical (gating pinned in
    test_bench.py). `results` is a result dict or a list of them."""
    if not envflags.env_bool("JEPSEN_TPU_SEARCH_STATS", default=False):
        return
    rs = results if isinstance(results, list) else [results]
    blocks = [r.get("stats") for r in rs if isinstance(r, dict)
              and r.get("stats")]
    if not blocks:
        return
    occ = [b["peak-occupancy"] for b in blocks
           if b.get("peak-occupancy") is not None]
    lf = [b["load-factor-peak"] for b in blocks
          if b.get("load-factor-peak") is not None]
    waste = [b["pad-waste"] for b in blocks
             if b.get("pad-waste") is not None]
    hist: dict = {}
    for b in blocks:
        for lab, n in (b.get("probe-hist") or {}).items():
            hist[lab] = hist.get(lab, 0) + int(n)
    line = {"metric": f"{section} search stats (advisory, "
                      f"JEPSEN_TPU_SEARCH_STATS)",
            "value": round(max(occ), 6) if occ else None,
            "unit": "peak-occupancy",
            "keys": len(blocks),
            "engine": blocks[0].get("engine"),
            "frontier_peak": max(b.get("frontier-peak") or 0
                                 for b in blocks),
            "load_factor_peak": round(max(lf), 6) if lf else None,
            "pad_waste_max": round(max(waste), 6) if waste else None,
            "probe_hist": hist or None,
            "escalated_keys": sum(1 for b in blocks
                                  if b.get("capacity-tier")),
            "note": "device-computed occupancy/probe evidence for "
                    "ROADMAP items 2/5 (docs/observability.md "
                    "'Search telemetry'); absent without the flag — "
                    "default schema unchanged"}
    if extra:
        line.update(extra)
    emit(line)


def emit_reshard_advisory(e, mesh, cap0: int, max_cap: int,
                          static_r: dict, static_secs: float):
    """The flag-gated re-shard ladder advisory (JEPSEN_TPU_RESHARD=1
    only — default schema byte-identical, pinned in test_bench.py):
    the sharded section's shape re-run through
    check_encoded_sharded_elastic, which answers capacity overflow by
    recruiting devices at flat per-device capacity instead of growing
    tables. Reports the rung trail plus per-device skew evidence from
    the static run's stats block when JEPSEN_TPU_SEARCH_STATS is also
    armed."""
    if not envflags.env_bool("JEPSEN_TPU_RESHARD", default=False):
        return
    from jepsen_tpu.parallel import sharded
    sharded.check_encoded_sharded_elastic(e, mesh, capacity=cap0,
                                          max_capacity=max_cap)  # warm
    with obs.timer("bench.sharded.reshard") as tm:
        r = sharded.check_encoded_sharded_elastic(
            e, mesh, capacity=cap0, max_capacity=max_cap)
    assert r["valid?"] == static_r["valid?"], (r, static_r)
    st = static_r.get("stats") or {}
    pd = (st.get("per-device") or {}).get("load-factor-peak")
    skew = None
    if pd and any(v is not None for v in pd):
        vals = [v for v in pd if v is not None]
        mean = sum(vals) / len(vals)
        skew = round(max(vals) / mean, 4) if mean else None
    emit({"metric": "sharded re-shard ladder (advisory, "
                    "JEPSEN_TPU_RESHARD)",
          "value": round(tm.wall, 3), "unit": "secs",
          "static_secs": round(static_secs, 3),
          "reshard_speedup": round(static_secs / max(tm.wall, 1e-9),
                                   2),
          "devices_final": r.get("devices"),
          "capacity_final": r.get("capacity"),
          "reshard_events": (r.get("reshard") or {}).get("events"),
          "per_device_load_factor_static": pd,
          "device_skew_static": skew,
          "verdict_match": r["valid?"] == static_r["valid?"],
          "note": "escalation recruits devices at flat per-device "
                  "capacity (1-D -> wider 1-D -> 2-D promotion) "
                  "before growing tables; absent without the flag — "
                  "default schema unchanged"})


def _adv_encoded(L):
    """(model, history, encoded, encode_secs) — encode timed so every
    device section can report its encode/transfer/device split."""
    from jepsen_tpu.histories import adversarial_register_history
    from jepsen_tpu.models import CASRegister
    from jepsen_tpu.parallel import encode as enc_mod
    model = CASRegister()
    h = adversarial_register_history(n_ops=L, k_crashed=ADV_K, seed=7)
    with obs.timer("bench.adv.encode", L=L) as tm:
        e = enc_mod.encode(model, h)
    return model, h, e, tm.wall


# ======================= child sections ============================

def sec_probe():
    """Minimal device touch: backend init + one tiny compiled op.

    Runs FIRST under its own short timeout so a wedged runtime (backend
    start-up blocking forever) costs the bench ONE bounded probe
    instead of one full timeout per device section. A backend that
    offers only the CPU fails the probe unless JAX_PLATFORMS asks for
    the CPU: the bench measures devices, not the host."""
    import jax
    import jax.numpy as jnp
    from jepsen_tpu.probe import cpu_requested
    devs = jax.devices()
    if devs[0].platform == "cpu" and not cpu_requested():
        raise SystemExit("device pre-probe: jax found only the CPU "
                         "backend (set JAX_PLATFORMS=cpu to bench the "
                         "CPU)")
    x = jnp.ones((8, 8), jnp.float32)
    # one-shot jit in a probe subprocess that exits right after: there
    # is no second call for a module-level wrapper's cache to serve
    # jepsen-lint: disable=recompile-closure-capture
    jax.jit(lambda a: a @ a)(x).block_until_ready()
    emit({"metric": "device pre-probe", "value": 1.0, "unit": "ok",
          "platform": devs[0].platform, "n_devices": len(devs)})


def sec_multikey():
    from jepsen_tpu.histories import rand_register_history
    from jepsen_tpu.models import CASRegister
    from jepsen_tpu.checker import linear_packed
    from jepsen_tpu.parallel import bitdense, encode as enc_mod

    model = CASRegister()
    keys = [rand_register_history(
        n_ops=OPS_PER_KEY, n_processes=N_PROCESSES, n_values=5,
        crash_p=0.005, fail_p=0.05, busy=BUSY, seed=SEED + k)
        for k in range(N_KEYS)]
    total_ops = N_KEYS * OPS_PER_KEY

    # obs.timer: the recorded span and the emitted seconds are the
    # SAME clock reads — the split line and the trace cannot disagree
    with obs.timer("bench.multikey.encode", keys=N_KEYS) as tm:
        pre = [enc_mod.encode(model, h) for h in keys]
    encode_secs = tm.wall
    S_max = max(bitdense.n_states(e) for e in pre)
    C_max = max(e.n_slots for e in pre)
    assert bitdense.fits_bitdense(S_max, C_max), (S_max, C_max)
    bitdense.check_batch_bitdense(pre)          # warm up (jit compile)
    # measured via the dispatch/finalize split so the JSONL carries the
    # pad+place (transfer) vs search (device) separation; their sum is
    # the same wall the old single check_batch_bitdense call measured
    with obs.timer("bench.multikey.serial", keys=N_KEYS) as tm:
        pending = bitdense.dispatch_batch_bitdense(pre)
        rs = pending.finalize()
    batch_secs = tm.wall
    transfer_secs = pending.transfer_secs
    device_secs = batch_secs - transfer_secs
    assert all(r["valid?"] is True for r in rs), rs[:3]
    closure = rs[0].get("closure")
    e2e_secs = encode_secs + batch_secs
    dev_rate = total_ops / e2e_secs

    # Host baseline = checker.linear_packed: int-config frontier over
    # the SAME encoding the device uses — our fastest fair CPU
    # implementation of the search (4-6x the Model-object engine; a
    # slow baseline would flatter the speedup). Sequential single-core
    # measurement, then an EXPLICIT x32 ideal-scaling model. (A thread
    # pool would be GIL-bound here — pure-Python search threads
    # serialize — so measuring "parallel" wall time would just
    # re-measure one core and, on a many-core box, silently present a
    # single-core rate as the 32-core baseline.)
    with obs.timer("bench.multikey.host", keys=HOST_SAMPLE_KEYS) as tm:
        for h in keys[:HOST_SAMPLE_KEYS]:
            rh = linear_packed.analysis(model, h,
                                        deadline=monotonic() + 60)
            assert rh["valid?"] is True, rh
    host_secs = tm.wall
    host_rate = HOST_SAMPLE_KEYS * OPS_PER_KEY / host_secs
    host32_rate = host_rate * 32

    emit({"metric": f"multi-key {N_KEYS}x{OPS_PER_KEY}-op cas-register "
                    f"(north-star shape), device end-to-end",
          "value": round(dev_rate, 1), "unit": "ops/sec",
          "vs_baseline": round(dev_rate / host32_rate, 2),
          "closure": closure,
          # uniform dedupe keys (docs/performance.md "Dedup
          # strategies"): bitdense sections report "dense" (the
          # reachable-set tensor is a complete visited set; no sparse
          # counter exists) — real counters live on the sparse/sharded
          # lines and the adv section's dedupe A/B advisory
          "dedupe": rs[0].get("dedupe"),
          "configs_stepped": rs[0].get("configs-stepped"),
          "device_only_secs": round(batch_secs, 3),
          "encode_secs": round(encode_secs, 3),
          "transfer_secs": round(transfer_secs, 4),
          "device_secs": round(device_secs, 3),
          "device_only_ops_per_sec": round(total_ops / batch_secs, 1),
          "host_seq_ops_per_sec": round(host_rate, 1),
          "host_cpus": os.cpu_count() or 1,
          "baseline": "packed int-config host engine (our fastest CPU "
                      "implementation of the same search), single-core "
                      "measured sequentially, x32 ideal scaling modeled "
                      "(per-key checks parallelize perfectly, so 32x is "
                      "the host's true ceiling)"})
    emit_search_stats(f"multi-key {N_KEYS}x{OPS_PER_KEY}-op", rs)

    # -- pipelined e2e: the same batch through the pipelined executor
    # (encode/transfer overlapped with device work, parallel.pipeline),
    # with the encode/transfer/device split reported PER BUCKET. Run
    # once cache-less to warm the chunk-shape compiles, then measure a
    # steady cache-less pass (the overlap win) and a cache-hit pass
    # (the re-analysis win). Verdict parity with the serial line is
    # asserted — a pipelined speedup that changed answers would be a
    # bug report, not a result.
    from jepsen_tpu.parallel import engine, pipeline as pipe_mod
    engine.check_batch(model, keys, pipeline=True, cache=False)  # warm
    pstats = {}
    with obs.timer("bench.multikey.pipelined", keys=N_KEYS) as tm:
        rs_p = engine.check_batch(model, keys, pipeline=True,
                                  cache=False, pipeline_stats=pstats)
    pipe_secs = tm.wall
    assert [r["valid?"] for r in rs_p] == [r["valid?"] for r in rs]
    # explicit capacity: the cached pass must measure cache hits even
    # under JEPSEN_TPU_ENCODE_CACHE=0 in the ambient env (an explicit
    # arg overrides the flag, same contract as the other perf flags)
    cache = pipe_mod.EncodeCache(max_entries=N_KEYS + 8)
    engine.check_batch(model, keys, pipeline=True, cache=cache)  # fill
    cstats = {}
    with obs.timer("bench.multikey.cached", keys=N_KEYS) as tm:
        rs_c = engine.check_batch(model, keys, pipeline=True,
                                  cache=cache, pipeline_stats=cstats)
    cached_secs = tm.wall
    assert [r["valid?"] for r in rs_c] == [r["valid?"] for r in rs]
    assert cstats["cache"]["encodes"] == 0, cstats["cache"]
    emit({"metric": f"multi-key {N_KEYS}x{OPS_PER_KEY}-op cas-register "
                    f"(north-star shape), pipelined {what}",
          "value": round(total_ops / pipe_secs, 1), "unit": "ops/sec",
          "vs_baseline": round(total_ops / pipe_secs / host32_rate, 2),
          **line_extra,
          "closure": closure,
          "dedupe": cstats.get("dedupe"),
          "configs_stepped": None,   # bitdense buckets: see above
          "serial_e2e_secs": round(e2e_secs, 3),
          "pipelined_e2e_secs": round(pipe_secs, 3),
          "cached_e2e_secs": round(cached_secs, 3),
          "cache": cstats["cache"],
          "buckets": pstats["buckets"],
          "note": "pipelined = encode + transfer overlapped with "
                  "device search (JEPSEN_TPU_PIPELINE); cached = "
                  "second pass over the same histories, zero "
                  "re-encodes; buckets carry the per-bucket "
                  "encode/transfer/device split"})


def sec_adv(L: int, host_deadline: float, skip_host: bool,
            host_est_hint: float | None):
    from jepsen_tpu.checker import linear_packed
    from jepsen_tpu.parallel import bitdense

    _, _, e, encode_secs = _adv_encoded(L)
    assert bitdense.fits_bitdense(bitdense.n_states(e), e.n_slots)
    with obs.timer("bench.adv.cold", L=L) as tm:
        r = bitdense.check_encoded_bitdense(e)  # cold (compile per R)
    warm_secs = tm.wall
    tms = {}
    with obs.timer("bench.adv.steady", L=L) as tm:
        r = bitdense.check_encoded_bitdense(e, timings=tms)  # steady
    steady_secs = tm.wall
    # dev_secs keeps the HISTORICAL meaning (whole steady call — the
    # quantity the r5 artifacts recorded and the rate/speedup below
    # use); the split keys are uniform across sections: device_secs =
    # search only, transfer_secs reported separately
    dev_secs = steady_secs
    assert r["valid?"] is True, r
    closure = r.get("closure")
    R = e.n_returns

    host_info = {"deadline_secs": host_deadline}
    host_est = None
    if skip_host:
        # parent ran out of budget for a host run: it passes the
        # previous size's measured rate scaled by L as the estimate
        host_est = host_est_hint
        host_info.update({"skipped": "bench budget",
                          "est_total_secs": round(host_est, 1)
                          if host_est else None})
    else:
        t0 = perf_counter()
        rh = linear_packed.check_encoded(
            e, deadline=monotonic() + host_deadline)
        host_wall = perf_counter() - t0
        if rh["valid?"] == "unknown":
            # deadline OR config-budget exhaustion: either way the
            # host's measured progress rate is the estimate
            done = max(1, rh.get("events-done", 1))
            host_est = host_wall * R / done
            host_info.update({"timeout": bool(rh.get("timeout")),
                              "stopped": rh.get("error", "deadline"),
                              "events_done": done, "of_events": R,
                              "est_total_secs": round(host_est, 1)})
        else:
            assert rh["valid?"] is True, rh
            host_est = host_wall
            host_info.update({"timeout": False,
                              "total_secs": round(host_wall, 1)})

    speedup = round(host_est / dev_secs, 1) if host_est else None
    emit({"metric": f"adversarial single-key {L}-op cas-register "
                    f"(2^{ADV_K} open configs), device",
          "value": round(L / dev_secs, 1), "unit": "ops/sec",
          "vs_baseline": speedup,
          "L": L,
          "closure": closure,
          "dedupe": r.get("dedupe"),
          "configs_stepped": r.get("configs-stepped"),
          # split keys, uniform across sections: device_secs = search
          # only; steady_secs = the whole steady call (the r5
          # artifacts' old "device_secs"), which value/vs_baseline use
          "device_secs": round(tms["device_secs"], 3),
          "encode_secs": round(encode_secs, 3),
          "transfer_secs": round(tms["transfer_secs"], 4),
          "steady_secs": round(steady_secs, 3),
          "device_compile_secs": round(warm_secs - dev_secs, 2),
          "host_est_secs": round(host_est, 1) if host_est else None,
          "host": host_info,
          "baseline": "packed int-config host engine, single-"
                      "threaded — a single key cannot be "
                      "parallelized by knossos linear/wgl, so no "
                      "32x scaling applies"})
    emit_search_stats(f"adversarial single-key {L}-op", r, {"L": L})

    # -- sparse-engine dedupe A/B (advisory): the frontier engine's
    # sort vs hash strategies on the same encoded history, with the
    # configs-stepped counters that make the delta-frontier work
    # reduction visible even on CPU. Emitted AFTER the section's main
    # line (the parent harvests partial output, so a slow advisory can
    # never cost the headline) and bounded to L <= 1000 — the sparse
    # engine at 10k+ is the pre-bitdense cost profile, and the 1k
    # counters already show the asymptotics. Flip decisions belong to
    # tools/perf_ab.py's dedupe line; this records the counters in the
    # BENCH_* record.
    if L <= 1000:
        from jepsen_tpu.histories import adversarial_register_history
        from jepsen_tpu.models import CASRegister
        from jepsen_tpu.parallel import encode as enc_mod, engine
        # derated k: the full-k sparse frontier peaks at ~10*2^k
        # configs (k=12 -> capacity 2^16), minutes per strategy on a
        # CPU advisory run — the DELTA asymptotics show at any k, and
        # the full-k wall-clock decision belongs to tools/perf_ab.py
        # on a healthy chip
        k_ab = min(ADV_K, 6)
        e_ab = enc_mod.encode(CASRegister(), adversarial_register_history(
            n_ops=L, k_crashed=k_ab, seed=7))
        cap = 1 << (k_ab + 4)        # one tier: peak ~10*2^k configs
        ab = {}
        strategies = ["sort", "hash"]
        from jepsen_tpu import envflags
        if envflags.env_bool("JEPSEN_TPU_SPARSE_PALLAS", default=False):
            # the fused VMEM frontier kernel rides the A/B only when
            # its flag is on, so the default bench schema stays
            # byte-identical (the kernel is opt-in until the chip A/B;
            # tools/perf_ab.py's hash-pallas strategy owns the flip)
            strategies.append("hash-pallas")
        if envflags.env_bool("JEPSEN_TPU_CONFIG_PACK", default=False):
            # same opt-in gating for the packed configuration word:
            # flag off => schema byte-identical; tools/perf_ab.py's
            # hash-packed strategy owns the flip decision
            strategies.append("hash-packed")
        if envflags.env_bool("JEPSEN_TPU_AUTO", default=False):
            # the self-tuning planner rides the A/B the same opt-in
            # way: an "auto" arm with every strategy axis left unset,
            # so the live decision table routes it (docs/performance.md
            # "Auto planner") — flag off => schema byte-identical;
            # tools/perf_ab.py's PERF_AB_AUTO arm owns the advisory
            # convergence reading
            strategies.append("auto")
        for strat in strategies:
            kw = {"dedupe": strat}
            if strat == "hash-pallas":
                kw = {"dedupe": "hash", "sparse_pallas": True}
            elif strat == "hash-packed":
                kw = {"dedupe": "hash", "config_pack": True}
            elif strat == "auto":
                kw = {}
            engine.check_encoded(e_ab, capacity=cap,
                                 max_capacity=cap * 4, **kw)  # compile
            with obs.timer("bench.adv.dedupe_ab", L=L,
                           strategy=strat) as tm:
                ra = engine.check_encoded(e_ab, capacity=cap,
                                          max_capacity=cap * 4, **kw)
            ab[strat] = {"secs": round(tm.wall, 3),
                         "configs_stepped": ra.get("configs-stepped"),
                         "valid": ra.get("valid?")}
            if strat == "auto" and ra.get("plan"):
                # the provenance block says which vector the table
                # routed to, and from what evidence
                ab[strat]["plan"] = ra["plan"]
        assert all(v["valid"] is True for v in ab.values()), ab
        emit({"metric": f"adversarial single-key {L}-op sparse-engine "
                        f"dedupe A/B (advisory, 2^{k_ab} open configs)",
              "value": ab["hash"]["secs"], "unit": "secs",
              "vs_baseline": None, "L": L,
              "dedupe": ab,
              "hash_vs_sort_secs": round(
                  ab["sort"]["secs"] / max(ab["hash"]["secs"], 1e-9), 2),
              "note": "sparse frontier engine only (the bitdense line "
                      "above is the measured path); configs_stepped is "
                      "the closure work actually paid — hash steps the "
                      "delta, sort re-steps the whole frontier every "
                      "closure iteration. Flip decisions ride "
                      "tools/perf_ab.py's full-k sparse-dedupe lines"})


def sec_sharded(L: int, host_est: float | None,
                cap_log: int | None = None):
    import jax
    import numpy as np
    from jax.sharding import Mesh
    from jepsen_tpu.parallel import sharded

    _, _, e, encode_secs = _adv_encoded(L)
    mesh = Mesh(np.array(jax.devices()), ("frontier",))
    # H2D split: an explicit replicated placement of the event tables
    # onto the mesh, blocked on — the same arrays the engine ships
    # (its own internal placement is what the device_secs then pays)
    from jax.sharding import NamedSharding, PartitionSpec as P
    t0 = perf_counter()
    placed = jax.device_put(
        {"slot_f": e.slot_f, "slot_a0": e.slot_a0, "slot_a1": e.slot_a1,
         "slot_wild": e.slot_wild, "slot_occ": e.slot_occ,
         "ev_slot": e.ev_slot}, NamedSharding(mesh, P()))
    jax.block_until_ready(placed)
    transfer_secs = perf_counter() - t0
    del placed
    # cap_log is the parent's downshift lever: the r5 chip session saw
    # the 2^17-capacity program crash the TPU *worker process* on its
    # first hardware contact, so a crashed first attempt is retried in
    # a fresh child at a smaller tier — an overflowed "unknown" line
    # still beats no line. The downshift also caps GROWTH below the
    # known-fatal tier (2^17): overflow-doubling from 2^13 would
    # otherwise climb right back into it.
    if cap_log is not None:
        cap0, max_cap = 1 << cap_log, 1 << min(cap_log + 3, 16)
    else:
        cap0, max_cap = ((1 << 12) if SMOKE else (1 << 17)), 1 << 20
    t0 = perf_counter()
    # reshard pinned OFF: the section's main line measures the static
    # engine even when JEPSEN_TPU_RESHARD=1 arms the advisory below —
    # the A/B needs a static arm to compare against
    r = sharded.check_encoded_sharded(e, mesh, capacity=cap0,
                                      max_capacity=max_cap,
                                      reshard=False)
    warm = perf_counter() - t0
    cap = r.get("capacity", cap0)
    if cap != cap0:
        # capacity grew during the warm run: compile the final tier
        # before measuring, so the steady number holds no compile
        sharded.check_encoded_sharded(e, mesh, capacity=cap,
                                      max_capacity=max_cap,
                                      reshard=False)
    with obs.timer("bench.sharded.steady", L=L, capacity=cap) as tm:
        r = sharded.check_encoded_sharded(e, mesh, capacity=cap,
                                          max_capacity=max_cap,
                                          reshard=False)
    dev_secs = tm.wall
    line = {"metric": f"adversarial {L}-op via frontier-sharded engine",
            "value": round(L / dev_secs, 1), "unit": "ops/sec",
            "vs_baseline": round(host_est / dev_secs, 1)
            if host_est else None,
            "devices": r.get("devices"), "valid": r.get("valid?"),
            "dedupe": r.get("dedupe"),
            "configs_stepped": r.get("configs-stepped"),
            "device_secs": round(dev_secs, 2),
            "encode_secs": round(encode_secs, 3),
            "transfer_secs": round(transfer_secs, 4),
            "warm_secs": round(warm, 2),
            "note": "owner-routed all-to-all exchange; multi-device "
                    "behavior exercised on the 8-way CPU mesh in CI; "
                    "the sharded engine has no transfer/search seam, "
                    "so device_secs includes its internal placement "
                    "and transfer_secs is a separate explicit "
                    "measurement of the same arrays"}
    if cap == cap0:
        # warm and steady runs share one shape, so the difference IS
        # the compile; after tier growth it would also contain whole
        # searches at smaller capacities — omitted rather than fudged
        line["device_compile_secs"] = round(max(warm - dev_secs, 0.0), 2)
    else:
        line["capacity_grew_to"] = cap
    emit(line)
    emit_search_stats(f"sharded {L}-op", r, {"L": L})
    emit_reshard_advisory(e, mesh, cap0, max_cap, r, dev_secs)


MAXLEN_RUN_BUDGET = 5 if SMOKE else 60   # the metric's "@ 60s" budget


def sec_maxlen(budget_secs: float):
    """Max length verified @ 60s device budget, within budget_secs."""
    from jepsen_tpu.parallel import bitdense

    t_start = monotonic()

    def left():
        return budget_secs - (monotonic() - t_start)

    max_len = 0
    budget_per_run = MAXLEN_RUN_BUDGET
    L = 400 if SMOKE else 10000
    prev_dt = None
    split = {}   # encode/transfer/device of the last PASSING probe
    while left() > 2.5 * budget_per_run:
        if prev_dt is not None and prev_dt * 2 > 1.5 * budget_per_run:
            break   # doubling would clearly blow the budget; stop early
        _, _, e, encode_secs = _adv_encoded(L)
        bitdense.check_encoded_bitdense(e)          # compile, uncounted
        tms = {}
        with obs.timer("bench.maxlen.probe", L=L) as tm:
            r = bitdense.check_encoded_bitdense(e, timings=tms)
        dt = tm.wall
        assert r["valid?"] is True, r
        note(f"max-length probe L={L}: {dt:.1f}s steady")
        if dt <= budget_per_run:
            max_len = L
            L *= 2
            prev_dt = dt
            split = {"encode_secs": round(encode_secs, 3),
                     "transfer_secs": round(tms["transfer_secs"], 4),
                     "device_secs": round(tms["device_secs"], 3),
                     "dedupe": r.get("dedupe"),
                     "configs_stepped": r.get("configs-stepped")}
        else:
            break
    if max_len:
        emit({"metric": f"max adversarial (2^{ADV_K}-config) history "
                        f"length verified @ {budget_per_run}s device "
                        f"budget",
              "value": max_len, "unit": "ops",
              "vs_baseline": None,
              **split,
              "note": "steady-state device time; per-shape compile "
                      "excluded (one-time, cached); "
                      "encode/transfer/device split is the verified "
                      "(largest passing) length's"})


def sec_stream():
    """Advisory (BENCH_STREAM=1 only): incremental frontier extension
    (parallel.extend.HistorySession) vs a full re-encode + re-check of
    every prefix, on a growing history fed as deltas — the streaming
    checker's economics (docs/streaming.md). Emitted only when the
    flag is on, so the default bench schema stays byte-identical
    (pinned by tests/test_bench.py). `full_secs` includes each
    prefix's compile — that IS the cost full re-checking re-pays,
    while the incremental path reuses a handful of quantized chunk
    shapes."""
    from jepsen_tpu.histories import rand_register_history
    from jepsen_tpu.models import CASRegister
    from jepsen_tpu.parallel import encode as enc_mod, engine
    from jepsen_tpu.parallel import extend as ext
    from jepsen_tpu.history import History

    n_ops = int(os.environ.get("BENCH_STREAM_OPS",
                               "200" if SMOKE else "2000"))
    deltas = int(os.environ.get("BENCH_STREAM_DELTAS",
                                "8" if SMOKE else "20"))
    m = CASRegister()
    h = list(rand_register_history(n_ops=n_ops, n_processes=6,
                                   n_values=4, crash_p=0.03,
                                   fail_p=0.05, busy=0.7, seed=11))
    step = -(-len(h) // deltas)
    cuts = [min(len(h), (i + 1) * step) for i in range(deltas)]
    with obs.timer("bench.stream.incremental") as ti:
        s = ext.HistorySession(m, capacity=1024)
        lo = 0
        for cut in cuts:
            s.extend(h[lo:cut])
            lo = cut
            ri = s.check()
    with obs.timer("bench.stream.full") as tf:
        for cut in cuts:
            e = enc_mod.encode(m, History.wrap(h[:cut]))
            rf = engine.check_encoded(e, capacity=1024)
    emit({"metric": f"streaming incremental extension vs full "
                    f"re-check ({len(h)}-op history in {deltas} "
                    f"deltas) [advisory]",
          "value": round(len(h) / max(ti.wall, 1e-9), 1),
          "unit": "ops/sec", "vs_baseline": None,
          "stream": {"deltas": deltas, "ops": len(h),
                     "incremental_secs": round(ti.wall, 4),
                     "full_secs": round(tf.wall, 4),
                     "speedup": round(tf.wall / max(ti.wall, 1e-9), 2),
                     "verdicts_match": ri["valid?"] == rf["valid?"],
                     "final_resume_event":
                         ri["stream"]["resumed-from-event"]}})


# ======================= parent orchestrator =======================

def run_section(argv: list, timeout: float, env_extra: dict = None,
                trace_suffix: str = ""):
    """Spawn `python bench.py --section ...`; forward the child's
    stdout lines as they arrive, parse the JSON ones, kill on timeout.
    The ACTUAL timeout rides along as the final `--timeout` argv so
    the child can schedule its pre-kill stack dump just before it.
    `trace_suffix` joins the child's chrome-trace filename — retries
    MUST pass one, or the retry child would overwrite the file the
    first attempt's already-emitted lines point at.
    Returns (parsed JSON objects, status) — status in
    {"ok", "crash", "hung"}. parsed holds whatever JSON lines arrived
    BEFORE a kill — a child can emit its result line and then hang in
    a later phase (e.g. the host baseline), and callers rely on
    harvesting those partial results."""
    cmd = [sys.executable, os.path.abspath(__file__), "--section"] + \
        [str(a) for a in argv] + ["--timeout", f"{timeout:.0f}"]
    if trace_suffix:
        cmd += ["--trace-suffix", trace_suffix]
    env = None
    if env_extra:
        env = dict(os.environ)
        env.update(env_extra)
    parsed = []
    try:
        proc = subprocess.Popen(cmd, stdout=subprocess.PIPE,
                                stderr=sys.stderr, text=True, env=env)
    except OSError as err:
        emit({"metric": f"section {argv[0]}", "value": None,
              "unit": "ops/sec", "error": repr(err)})
        return parsed, "crash"

    def pump():
        for line in proc.stdout:
            line = line.rstrip("\n")
            if not line:
                continue
            print(line, flush=True)            # forward verbatim
            if line.lstrip().startswith("{"):
                try:
                    parsed.append(json.loads(line))
                except ValueError:
                    pass

    t = threading.Thread(target=pump, daemon=True)
    t.start()
    try:
        rc = proc.wait(timeout=timeout)
        t.join(timeout=10)
        if rc != 0:
            emit({"metric": f"section {argv[0]}", "value": None,
                  "unit": "ops/sec",
                  "error": f"child exited rc={rc}"})
            return parsed, "crash"
        return parsed, "ok"
    except subprocess.TimeoutExpired:
        proc.kill()
        proc.wait()
        t.join(timeout=10)
        emit({"metric": f"section {argv[0]}", "value": None,
              "unit": "ops/sec",
              "skipped": f"timeout/hang after {timeout:.0f}s "
                         f"(section isolated in a subprocess; "
                         f"bench continues)"})
        return parsed, "hung"


def main():
    t_start = monotonic()

    def left():
        return BUDGET_SECS - (monotonic() - t_start)

    hung = []              # (kind, L) sections killed on timeout
    mk_line = None
    adv_results = {}       # L -> parsed line (with L, device_secs, host)

    # ---------------- 0. bounded device pre-probe ------------------
    # Fail a dead runtime ONCE: a single short child touches the
    # backend; if it hangs/crashes twice, the bench stops there with
    # an error line and a non-zero exit. A wedge that develops
    # MID-bench is still caught by the per-section isolation + retry.
    probe_to = min(max(1.0,
                       float(os.environ.get("BENCH_PROBE_TIMEOUT", "120"))
                       * TIMEOUT_SCALE), BUDGET_SECS)

    def probe_once(trace_suffix=""):
        parsed, st = run_section(["probe"], probe_to,
                                 trace_suffix=trace_suffix)
        probe_parsed[:] = parsed
        ok = (st == "ok"
              and any(p.get("metric") == "device pre-probe"
                      and p.get("value") for p in parsed))
        return ok, st

    def probe_platform():
        return next((p.get("platform") for p in probe_parsed
                     if p.get("metric") == "device pre-probe"), None)

    probe_parsed = []
    probe_ok, st = probe_once()
    if not probe_ok and left() > probe_to + 60:
        # one retry: a single probe hang/crash must not fail a
        # healthy-chip round over a transient blip — mid-bench hangs
        # get a retry for the same reason
        note(f"device pre-probe failed ({st}) — retrying once")
        probe_ok, st = probe_once("retry")
    if not probe_ok:
        how = (f"hung past {probe_to:.0f}s" if st == "hung"
               else f"child {st}ed")
        emit({"metric": "linearizability check throughput",
              "value": None, "unit": "ops/sec", "vs_baseline": None,
              "error": f"device pre-probe {how} (twice): no device to "
                       f"measure on"})
        return 1

    # ---------------- 1. multi-key north-star shape ----------------
    multikey, st = run_section(
        ["multikey"], min(sec_timeout("multikey"), BUDGET_SECS))
    mk_line = next((p for p in multikey if p.get("value")), None)
    if st == "hung":
        hung.append(("multikey", None))

    # ---------------- 1b. streaming advisory (flag-gated) ----------
    # BENCH_STREAM=1 only: an advisory incremental-extend vs full
    # re-check line — gated so the default bench schema (and its
    # budget) stays byte-identical when off
    if os.environ.get("BENCH_STREAM") == "1" \
            and left() > 90:
        run_section(["stream"], min(sec_timeout("stream"), left()))

    # ---------------- 2. adversarial single-key --------------------
    def run_adv(L, trace_suffix=""):
        deadline = HOST_DEADLINES[L]
        skip_host = left() < deadline + 90
        hint = ""
        if skip_host:
            # scale the largest completed size's host estimate
            prev = max((p for p in adv_results.values()
                        if p.get("host_est_secs")),
                       key=lambda p: p["L"], default=None)
            if prev:
                hint = prev["host_est_secs"] * (L / prev["L"])
        args = ["adv", L, deadline, int(skip_host), hint]
        parsed, st = run_section(
            args, min(sec_timeout("adv", L), max(left(), 60)),
            trace_suffix=trace_suffix)
        for p in parsed:
            if p.get("L") == L and p.get("value") is not None:
                adv_results[L] = p
        return st

    for L in ADV_SIZES:
        if left() < min(90, sec_timeout("adv", L)):
            emit({"metric": f"adversarial single-key {L}-op",
                  "value": None,
                  "unit": "ops/sec", "skipped": "bench budget exhausted"})
            continue
        if run_adv(L) == "hung":
            hung.append(("adv", L))

    # ---------------- retry hung sections once ---------------------
    # a hang can be a transient device-runtime flake rather than a
    # hard outage; retry BEFORE sections 3-4 so a recovered result can
    # still feed the sharded section and the headline, and so maxlen
    # (which deliberately consumes the remaining budget) hasn't eaten
    # the retry's slot. Largest adversarial size first, then the
    # multi-key shape; a second hang just re-emits the skip line.
    for kind, L in sorted(hung, key=lambda k: -(k[1] or 0)):
        if kind == "adv":
            if L in adv_results or left() < 120:
                continue
            note(f"retrying hung adv L={L} (transient flake?)")
            run_adv(L, trace_suffix="retry")
        elif kind == "multikey" and mk_line is None and left() > 120:
            note("retrying hung multikey section (transient flake?)")
            parsed, _ = run_section(
                ["multikey"], min(sec_timeout("multikey"), left()),
                trace_suffix="retry")
            mk_line = next((p for p in parsed if p.get("value")), None)

    # ---------------- 3. sharded engine on the local mesh ----------
    pick = 10000 if not SMOKE else (400 if 400 in adv_results else None)
    if pick in adv_results and left() > 120:
        parsed, st = run_section(
            ["sharded", pick,
             adv_results[pick].get("host_est_secs") or ""],
            min(sec_timeout("sharded"), left()))
        if st != "ok" and not any(p.get("value") for p in parsed) \
                and not SMOKE and left() > 180:
            # r5 on-chip: the default 2^17-capacity program crashed
            # the TPU worker (child rc=1, PJRT client dead). A fresh
            # child at a smaller tier can still land a sharded line —
            # possibly an "unknown" overflow, which is honest evidence.
            # SMOKE already runs the smallest sensible tier (2^12), so
            # a downshift retry only exists for the production shape.
            # A HUNG child usually means the runtime wedged (a wedge
            # survives worker restarts), where any retry just burns
            # another timeout — a crashed worker restarts, a wedge
            # doesn't, so gate the retry on a short re-probe.
            retry_ok = True
            if st == "hung":
                probe2, p2st = run_section(["probe"], 90,
                                           trace_suffix="sharded-gate")
                if p2st != "ok" or not any(
                        p.get("value") for p in probe2):
                    note("sharded section hung and the runtime no "
                         "longer answers a probe — skipping the "
                         "downshift retry (wedged, not crashed)")
                    retry_ok = False
            if retry_ok:
                note("sharded section crashed/hung; retrying in a "
                     "fresh child at capacity 2^13")
                run_section(
                    ["sharded", pick,
                     adv_results[pick].get("host_est_secs") or "",
                     "13"],
                    min(sec_timeout("sharded"), left()),
                    trace_suffix="retry13")

    # ---------------- 4. max length verified @ 60s -----------------
    # the child's own probe budget sits INSIDE the kill timeout, with
    # margin; only spawn when that budget clears the probe loop's own
    # floor (2.5x the per-run budget), so a child is never started
    # that could not run a single probe
    to = min(sec_timeout("maxlen"), left())
    if to - 30 > 2.5 * MAXLEN_RUN_BUDGET:
        run_section(["maxlen", to - 30], to)

    # ---------------- HEADLINE (last line: the driver's record) ----
    # prefer 10k (the BASELINE.md config); else the largest that ran
    ten_k = adv_results.get(10000)
    if ten_k is None and adv_results:
        ten_k = adv_results[max(adv_results)]
    if ten_k is not None:
        L = ten_k["L"]
        emit({"metric": f"adversarial {L}-op single-key "
                        f"cas-register linearizability check "
                        f"(2^{ADV_K} open configs)",
              "value": ten_k["value"],
              "unit": "ops/sec",
              "vs_baseline": ten_k.get("vs_baseline"),
              "backend": probe_platform(),
              "closure": ten_k.get("closure"),
              "methodology": "vs this repo's packed int-config host "
                             "engine (same algorithm and encoding as "
                             "the device; our fastest CPU "
                             "implementation) measured under a deadline "
                             "on the same history; single-key search "
                             "does not parallelize, so the single-core "
                             "host rate IS the 32-core rate"})
    elif mk_line is not None:
        # no adversarial size finished (budget/hang): fall back to the
        # multi-key line so the driver still records a headline
        emit({"metric": f"multi-key {N_KEYS}x{OPS_PER_KEY}-op "
                        f"cas-register, device end-to-end",
              "value": mk_line["value"],
              "unit": "ops/sec",
              "vs_baseline": mk_line.get("vs_baseline"),
              "backend": probe_platform(),
              "closure": mk_line.get("closure")})
    else:
        # EVERY device section hung or crashed: no number to report
        emit({"metric": "linearizability check throughput",
              "value": None, "unit": "ops/sec", "vs_baseline": None,
              "error": "no device section completed — see the "
                       "per-section lines above"})
        return 1
    return 0


def child_main(argv: list) -> None:
    # a child that hangs in device code cannot deliver Python signals;
    # dump a stack to stderr shortly before the parent's ACTUAL kill
    # time (threaded through as --timeout) so the hang site is
    # diagnosable from the bench log
    import faulthandler
    to = 300.0
    if "--timeout" in argv:
        i = argv.index("--timeout")
        to = float(argv[i + 1])
        argv = argv[:i] + argv[i + 2:]
    trace_suffix = ""
    if "--trace-suffix" in argv:
        i = argv.index("--trace-suffix")
        trace_suffix = argv[i + 1]
        argv = argv[:i] + argv[i + 2:]
    sec = argv[0]
    faulthandler.dump_traceback_later(max(20, to - 10), exit=False)
    from jepsen_tpu.resilience import faults as _faults
    _wedge = _faults.decide("child")
    if (_wedge is not None and _wedge.kind == "wedge"
            and os.environ.get("JAX_PLATFORMS") != "cpu"):
        # fault seam (resilience.faults, site "child"): simulate a
        # wedged device runtime (backend start-up blocking forever,
        # uninterruptible by Python signals) in every child not pinned
        # to cpu — mirroring production, where cpu-pinned children
        # survive an outage. JEPSEN_TPU_FAULTS=wedge@child
        # drives it; the legacy JEPSEN_TPU_TEST_WEDGE=1 maps onto the
        # same rule (faults.active_plan), so existing automation keeps
        # working.
        import time
        while True:
            time.sleep(3600)
    from jepsen_tpu.compile_cache import enable_compile_cache
    enable_compile_cache()
    global TRACE_REL
    flusher = None
    if obs.enabled():
        # the pointer is computed BEFORE the section runs so every
        # line it emits carries it; the trace itself is written after
        # (and on a crash — partial spans still diagnose the hang).
        # The first section arg (adv/sharded L) joins
        # the filename: four adv children must not overwrite each
        # other's evidence while their lines point at it; a parent
        # retry passes --trace-suffix for the same reason.
        tag = "_".join([sec] + [str(a) for a in argv[1:2] if a])
        if trace_suffix:
            tag += "_" + trace_suffix
        tag = "".join(ch if ch.isalnum() or ch in "-_" else "-"
                      for ch in tag)
        TRACE_REL = os.path.join("store", "bench_traces",
                                 f"bench_{tag}.trace.json")

        # A HUNG child never reaches the finally below — the parent's
        # proc.kill() is SIGKILL — so flush the partial trace shortly
        # before the kill time (alongside the faulthandler stack
        # dump): the spans recorded so far are exactly the evidence a
        # hang diagnosis needs, and the pointer the child already
        # stamped on its lines must not dangle. write_chrome_trace
        # reads a copy of the span buffer, so the normal end-of-
        # section write below simply supersedes this one.
        def _flush_partial():
            try:
                obs.write_chrome_trace(TRACE_REL)
            except Exception:  # noqa: BLE001 — best-effort, pre-kill
                pass
        flusher = threading.Timer(max(10.0, to - 10.0), _flush_partial)
        flusher.daemon = True
        flusher.start()
    try:
        if sec == "probe":
            sec_probe()
        elif sec == "multikey":
            sec_multikey()
        elif sec == "adv":
            L, deadline, skip_host = int(argv[1]), float(argv[2]), \
                bool(int(argv[3]))
            hint = float(argv[4]) if len(argv) > 4 and argv[4] else None
            sec_adv(L, deadline, skip_host, hint)
        elif sec == "sharded":
            L = int(argv[1])
            host_est = float(argv[2]) if len(argv) > 2 and argv[2] \
                else None
            cap_log = int(argv[3]) if len(argv) > 3 and argv[3] else None
            sec_sharded(L, host_est, cap_log)
        elif sec == "maxlen":
            sec_maxlen(float(argv[1]))
        elif sec == "stream":
            sec_stream()
        else:
            raise SystemExit(f"unknown section {sec!r}")
    finally:
        if flusher is not None:
            # cancel() is a no-op once the timer callback is already
            # executing — join so a section finishing right at the
            # flush deadline can't interleave two writers on one file
            flusher.cancel()
            flusher.join(timeout=30)
        if TRACE_REL is not None:
            obs.write_chrome_trace(TRACE_REL)


if __name__ == "__main__":
    try:
        if len(sys.argv) > 1 and sys.argv[1] == "--section":
            child_main(sys.argv[2:])
        else:
            sys.exit(main())
    except Exception as err:  # noqa: BLE001
        # JSON-line consumers must see a machine-readable trace of any
        # crash rather than bare stderr
        import traceback
        traceback.print_exc()
        emit({"metric": "bench crashed", "value": None, "unit": "ops/sec",
              "vs_baseline": None, "error": repr(err)})
        sys.exit(1)
