"""Microbench for the host encode fast path (the pipeline's stage 1).

The batched checker's end-to-end constant is the Python encode loop
(round 5 on chip: device-only 9,189.9 ops/s vs 8,558.4 e2e — the gap is
encode + transfer, not search). PR 2 attacks it three ways; this tool
measures each in isolation with no device runtime — encode is pure
numpy (jax gets imported transitively but no backend is ever
initialized), so the numbers are portable and CI-safe:

  bulk         spec.encode_calls (one call per history, preallocated
               arrays) vs the row-wise spec.encode_call loop — same
               arrays bit for bit (asserted here via history_digest)
  stage split  prepare_encode (packing + slot walk) vs finish_encode
               (the [R, C] snapshot fill) — the fractions that decide
               how much of the encode the pipeline can overlap
  cache        EncodeCache miss vs hit vs store-dir (disk) hit

    python tools/perf_encode.py            # full shapes
    PERF_ENCODE_REPS=3 python tools/perf_encode.py

One JSON line per measurement, same consumption contract as bench.py
(machine-parsable, metric/value/unit keys).
"""

from __future__ import annotations

import json
import os
import sys
import tempfile
from time import perf_counter

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

REPS = int(os.environ.get("PERF_ENCODE_REPS", "5"))


def emit(obj):
    print(json.dumps(obj), flush=True)


def _best(f, reps=REPS):
    """Best-of-N wall time (microbenches want the noise floor, not the
    scheduler's mood)."""
    best = float("inf")
    for _ in range(reps):
        t0 = perf_counter()
        f()
        best = min(best, perf_counter() - t0)
    return best


def _shapes():
    from jepsen_tpu.histories import (adversarial_register_history,
                                      rand_fifo_history,
                                      rand_gset_history,
                                      rand_queue_history,
                                      rand_register_history)
    from jepsen_tpu.models import (CASRegister, FIFOQueue, GSet,
                                   UnorderedQueue)
    yield ("cas-register 84x120 batch-key", CASRegister(),
           [rand_register_history(n_ops=120, n_processes=14, n_values=5,
                                  crash_p=0.005, fail_p=0.05, busy=0.8,
                                  seed=2024 + k) for k in range(84)])
    yield ("cas-register adversarial 10k", CASRegister(),
           [adversarial_register_history(n_ops=10_000, k_crashed=12,
                                         seed=7)])
    yield ("gset 500-op", GSet(),
           [rand_gset_history(n_ops=500, n_processes=6, n_elements=12,
                              crash_p=0.05, seed=3)])
    yield ("unordered-queue 500-op", UnorderedQueue(),
           [rand_queue_history(n_ops=500, n_processes=6, n_values=4,
                               crash_p=0.05, seed=4)])
    # fifo keys stay short: the packed depth bound (B*v <= 31 bits)
    # rejects long single-key fifo histories, so the realistic shape
    # is many short keys — same total ops
    yield ("fifo 40x40-op batch-key", FIFOQueue(),
           [rand_fifo_history(n_ops=40, n_processes=5, n_values=3,
                              crash_p=0.05, seed=500 + k)
            for k in range(40)])


def main():
    from jepsen_tpu.parallel import encode as enc_mod
    from jepsen_tpu.parallel import pipeline as pipe_mod
    from jepsen_tpu.parallel.engine import history_digest

    for name, model, hs in _shapes():
        n_ops = sum(len(h) for h in hs)

        # correctness first: bulk and row-wise paths must be
        # array-identical on every shape they are about to be timed on
        for h in hs:
            d_bulk = history_digest(enc_mod.encode(model, h))
            d_loop = history_digest(enc_mod.encode(model, h,
                                                   use_bulk=False))
            assert d_bulk == d_loop, (name, d_bulk, d_loop)

        bulk_secs = _best(lambda: [enc_mod.encode(model, h)
                                   for h in hs])
        # the bulk hook lives in stage 1 (prepare_encode) — compare
        # the stages head to head so the hook's effect is not diluted
        # by the (identical) snapshot fill
        prep_loop_secs = _best(
            lambda: [enc_mod.prepare_encode(model, h, use_bulk=False)
                     for h in hs])
        prep_secs = _best(lambda: [enc_mod.prepare_encode(model, h)
                                   for h in hs])
        preps = [enc_mod.prepare_encode(model, h) for h in hs]
        fill_secs = _best(lambda: [enc_mod.finish_encode(p)
                                   for p in preps])
        emit({"metric": f"encode {name}", "unit": "ops/sec",
              "value": round(n_ops / bulk_secs, 1),
              "n_keys": len(hs), "n_ops": n_ops,
              "encode_secs": round(bulk_secs, 4),
              "prepare_secs": round(prep_secs, 4),
              "prepare_loop_secs": round(prep_loop_secs, 4),
              "bulk_speedup": round(prep_loop_secs /
                                    max(prep_secs, 1e-9), 2),
              "fill_secs": round(fill_secs, 4),
              "overlappable_frac": round(fill_secs /
                                         max(bulk_secs, 1e-9), 3)})

    # cache: miss vs memory hit vs disk hit, on the bench batch shape
    name, model, hs = next(_shapes())
    with tempfile.TemporaryDirectory() as d:
        cache = pipe_mod.EncodeCache(max_entries=len(hs) + 1,
                                     store_dir=d)
        keys = [pipe_mod.encode_cache_key(model, h) for h in hs]

        def miss():
            for h, k in zip(hs, keys):
                e = cache.get(k, model) or enc_mod.encode(model, h)

        t_miss = _best(miss, reps=1)          # first pass: all misses
        for h, k in zip(hs, keys):
            cache.put(k, enc_mod.encode(model, h))
        t_hit = _best(lambda: [cache.get(k, model) for k in keys])
        disk = pipe_mod.EncodeCache(max_entries=len(hs) + 1,
                                    store_dir=d)
        t_disk = _best(
            lambda: [disk.get(k, model) for k in keys], reps=1)
        assert all(disk.get(k, model) is not None for k in keys)
        emit({"metric": f"encode cache, {name}", "unit": "x",
              "value": round(t_miss / max(t_hit, 1e-9), 1),
              "miss_secs": round(t_miss, 4),
              "memory_hit_secs": round(t_hit, 5),
              "disk_hit_secs": round(t_disk, 4),
              "note": "value = miss/memory-hit ratio; disk hit is a "
                      "fresh cache instance over the same store_dir"})


if __name__ == "__main__":
    main()
