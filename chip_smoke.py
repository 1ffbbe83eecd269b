#!/usr/bin/env python
"""Chip smoke: the :jax linearizability checker end to end on a TPU.

Runs the checker's main path once, through the entry points a user
calls, at the size the north star names (~10k-op cas-register
histories, BASELINE.json "Target"), and checks every answer against a
reference. Every history is generated here from a fixed seed; nothing
is read from disk. One process, no children: a chip belongs to one
process at a time.

    python chip_smoke.py             # one chip: phases (a)-(d)
    python chip_smoke.py --chips 4   # four chips: the key-sharded batch
                                     # and the frontier-sharded engine,
                                     # each against device 0 alone

Phases on one chip:
  (a) device: jax must report a TPU, else exit non-zero;
  (b) multi-key: 84 keys x 120 ops, one key corrupted, through
      independent.checker(linearizable(CASRegister(), algorithm="jax"))
      — the checker the Tendermint suite builds — against the host
      `packed` engine on sampled keys;
  (c) adversarial single key: 10k ops with 12 crashed writes, plus an
      invalid twin, through engine.analysis, against the XLA closure on
      the same chip (the host cannot decide k=12 at this length);
  (d) served path: 4 keys x 3 deltas through an in-process
      serve.CheckerService, against the one-shot engine.check_encoded.

Each phase prints one timing line (first call, compile included, and a
warm second call; the sharded leg runs once): bring-up observations,
not a benchmark. Progress notes go to stderr. The last
line of stdout is exactly
{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}.
Any failed check raises and exits non-zero. JAX's compilation cache
goes where JAX_COMPILATION_CACHE_DIR says, else <repo>/.jax_cache.
"""

from __future__ import annotations

import argparse
import json
import sys
import time

SEED = 2024
N_KEYS, OPS_PER_KEY, N_PROCESSES = 84, 120, 14
ADV_OPS, ADV_K, ADV_SEED = 10000, 12, 7
SERVED_KEYS, SERVED_OPS, SERVED_DELTAS, SERVED_PROCESSES = 4, 120, 3, 5
SAMPLED_KEYS = (0, 21, 42, 63)
PIN = ("valid?", "op", "fail-event")


def require_tpu(chips: int):
    """Phase (a): the devices JAX reports must be TPUs, at least
    `chips` of them. Exits non-zero otherwise — there is no CPU
    mode."""
    import importlib.metadata

    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        sys.exit(f"chip_smoke: no TPU found — jax.devices() reports "
                 f"platform {devs[0].platform!r}")
    if len(devs) < chips:
        sys.exit(f"chip_smoke: {chips} chips asked for, "
                 f"{len(devs)} found")
    versions = {}
    for pkg in ("jax", "jaxlib", "libtpu"):
        try:
            versions[pkg] = importlib.metadata.version(pkg)
        except importlib.metadata.PackageNotFoundError:
            versions[pkg] = None
    print(json.dumps({"phase": "device", "platform": devs[0].platform,
                      "kind": devs[0].device_kind, "count": len(devs),
                      **versions}), flush=True)
    return devs


def secs(fn):
    """(fn(), wall seconds)."""
    t0 = time.perf_counter()
    out = fn()
    return out, time.perf_counter() - t0


def timed(fn):
    """Run `fn` twice: (first result, {"first_secs": compile included,
    "warm_secs": the second call})."""
    out, first = secs(fn)
    _, warm = secs(fn)
    return out, {"first_secs": first, "warm_secs": warm}


def report(phase: str, **fields) -> None:
    print(json.dumps({"phase": phase, **fields}), flush=True)


_T0 = time.perf_counter()


def note(msg: str) -> None:
    """Progress on stderr, so a run cut by its time limit shows where."""
    print(f"chip_smoke +{time.perf_counter() - _T0:.1f}s: {msg}",
          file=sys.stderr, flush=True)


def _pin(r: dict) -> dict:
    return {k: r.get(k) for k in PIN}


# ------------------------------------------------ (b) multi-key


def multikey_histories(n_keys: int = N_KEYS,
                       ops_per_key: int = OPS_PER_KEY,
                       seed: int = SEED):
    """Per-key cas-register histories (the bench's 84x120 shape) with
    the last key corrupted, and the one keyed history that interleaves
    them the way jepsen.independent tags ops: value = KV(key, v), each
    key on its own processes. Returns (per_key, keyed, corrupted_key)."""
    from jepsen_tpu.histories import corrupt_history, rand_register_history
    from jepsen_tpu.history import History
    from jepsen_tpu.independent import KV

    per_key = {k: rand_register_history(
        n_ops=ops_per_key, n_processes=N_PROCESSES, n_values=5,
        crash_p=0.005, fail_p=0.05, busy=0.8, seed=seed + k)
        for k in range(n_keys)}
    bad = n_keys - 1
    per_key[bad] = corrupt_history(per_key[bad], seed=seed)
    ops = []
    for k, h in per_key.items():
        for o in h:
            o = dict(o)
            o["process"] = o["process"] + k * N_PROCESSES
            o["value"] = KV(k, o.get("value"))
            ops.append(o)
    return per_key, History.wrap(ops).index(), bad


def phase_multikey(n_keys: int = N_KEYS, ops_per_key: int = OPS_PER_KEY,
                   sampled=SAMPLED_KEYS) -> dict:
    """Phase (b): the Tendermint checker path on the multi-key shape."""
    from jepsen_tpu import independent
    from jepsen_tpu.checker import linear_packed, linearizable
    from jepsen_tpu.models import CASRegister

    per_key, keyed, bad = multikey_histories(n_keys, ops_per_key)
    checker = independent.checker(linearizable(CASRegister(),
                                               algorithm="jax"))
    note("(b) multi-key check")
    r, t = timed(lambda: checker.check({}, keyed, {}))
    assert "device-fallback" not in r and "resilience" not in r, r
    assert r["valid?"] is False and r["failures"] == [bad], \
        (r["valid?"], r["failures"])
    for k, rk in r["results"].items():
        assert rk["analyzer"] == "jax" and rk["closure"] == "pallas", \
            (k, rk)
    assert r["results"][bad]["op"] is not None, r["results"][bad]
    for k in [s for s in sampled if s < n_keys] + [bad]:
        host = linear_packed.analysis(CASRegister(), per_key[k])
        assert host["valid?"] == r["results"][k]["valid?"], \
            (k, host, r["results"][k])
    report("multikey", **t, keys=n_keys, ops=len(keyed) // 2)
    return r


# ------------------------------------- (c) adversarial single key


def adversarial_pair(n_ops: int = ADV_OPS, k: int = ADV_K):
    """The knossos-killer history (k crashed writes open forever) and
    its invalid twin ending in an impossible read."""
    from jepsen_tpu.histories import (adversarial_register_history,
                                      with_impossible_read)
    h = adversarial_register_history(n_ops=n_ops, k_crashed=k,
                                      seed=ADV_SEED)
    return h, with_impossible_read(h)


def phase_adversarial(n_ops: int = ADV_OPS, k: int = ADV_K) -> dict:
    """Phase (c): engine.analysis on the adversarial pair, against the
    XLA closure on the same device."""
    from jepsen_tpu import obs
    from jepsen_tpu.models import CASRegister
    from jepsen_tpu.parallel import bitdense, engine
    from jepsen_tpu.parallel import encode as enc_mod

    model = CASRegister()
    valid_h, invalid_h = adversarial_pair(n_ops, k)
    fallbacks = obs.counter("engine.host_fallbacks").value
    note("(c) valid adversarial key")
    rv, t = timed(lambda: engine.analysis(model, valid_h))
    note("(c) invalid twin")
    # the invalid twin also pays counterexample extraction: a device
    # re-scan of the prefix plus a host search of the last window
    ri, t["invalid_secs"] = secs(lambda: engine.analysis(model, invalid_h))
    assert obs.counter("engine.host_fallbacks").value == fallbacks
    for r, want in ((rv, True), (ri, False)):
        assert r["engine"] == "bitdense" and r["closure"] == "pallas", r
        assert "fallback" not in r and "resilience" not in r, r
        assert r["valid?"] is want, r
    assert ri["op"] is not None, ri
    note("(c) XLA-closure references")
    for h, r in ((valid_h, rv), (invalid_h, ri)):
        ref = bitdense.check_encoded_bitdense(enc_mod.encode(model, h),
                                              use_pallas=False)
        assert ref["closure"] == "xla-while", ref
        assert _pin(ref) == _pin(r), (_pin(ref), _pin(r))
    report("adversarial", **t, ops=n_ops, crashed=k)
    return {"valid": rv, "invalid": ri}


# ------------------------------------------------ (d) served path


def served_histories(n_keys: int = SERVED_KEYS,
                     n_ops: int = SERVED_OPS):
    """Streamed test runs with 5 client processes each (the served
    path's frontiers stay within the service's first capacity tier, so
    the phase times the stream, not capacity escalation)."""
    from jepsen_tpu.histories import corrupt_history, rand_register_history
    hs = {f"k{i}": list(rand_register_history(
        n_ops=n_ops, n_processes=SERVED_PROCESSES, n_values=5,
        crash_p=0.005, fail_p=0.05, busy=0.8, seed=SEED + 100 + i))
        for i in range(n_keys)}
    last = f"k{n_keys - 1}"
    hs[last] = list(corrupt_history(hs[last], seed=SEED))
    return hs


def _serve_once(hs: dict, deltas: int, capacity: int) -> dict:
    from jepsen_tpu.models import CASRegister
    from jepsen_tpu.serve import CheckerService

    svc = CheckerService(CASRegister(), capacity=capacity, dedupe="sort")
    try:
        for i in range(deltas):
            for key, h in hs.items():
                step = -(-len(h) // deltas)
                r = svc.submit(key, h[i * step:(i + 1) * step],
                               wait=True, timeout=600)
                assert "valid?" in r, (key, i, r)
        return {k: svc.finalize(k, timeout=600) for k in hs}
    finally:
        svc.close()


def phase_served(n_keys: int = SERVED_KEYS, n_ops: int = SERVED_OPS,
                 deltas: int = SERVED_DELTAS,
                 capacity: int = 1024) -> dict:
    """Phase (d): deltas through an in-process CheckerService; final
    verdicts pinned to the one-shot engine.check_encoded."""
    from jepsen_tpu.history import History
    from jepsen_tpu.models import CASRegister
    from jepsen_tpu.parallel import encode as enc_mod
    from jepsen_tpu.parallel import engine

    hs = served_histories(n_keys, n_ops)
    note("(d) served deltas")
    finals, t = timed(lambda: _serve_once(hs, deltas, capacity))
    note("(d) one-shot references")
    for key, h in hs.items():
        ref = engine.check_encoded(
            enc_mod.encode(CASRegister(), History.wrap(h)),
            capacity=capacity, dedupe="sort")
        assert "resilience" not in finals[key], finals[key]
        assert finals[key]["seq"] == deltas, finals[key]
        assert _pin(finals[key]) == _pin(ref), \
            (key, _pin(finals[key]), _pin(ref))
    assert finals[f"k{n_keys - 1}"]["valid?"] is False
    report("served", **t, keys=n_keys, deltas=deltas)
    return finals


# ------------------------------------------------ four chips


def phase_four_chips(devs, n_keys: int = N_KEYS,
                     ops_per_key: int = OPS_PER_KEY,
                     adv_ops: int = ADV_OPS, adv_k: int = ADV_K) -> dict:
    """The paths that exist only across chips, each against the same
    histories on devs[0] alone: the key-sharded batch through
    engine.check_batch(mesh=...), and the invalid adversarial key
    through the frontier-sharded engine."""
    import numpy as np
    from jax.sharding import Mesh

    from jepsen_tpu.models import CASRegister
    from jepsen_tpu.parallel import bitdense, engine, sharded
    from jepsen_tpu.parallel import encode as enc_mod

    model = CASRegister()
    mesh = Mesh(np.array(devs[:4]), ("keys",))
    one = Mesh(np.array(devs[:1]), ("keys",))
    per_key, _, bad = multikey_histories(n_keys, ops_per_key)
    hs = [per_key[k] for k in sorted(per_key)]
    note("4 chips: key-sharded batch")
    rs4, t = timed(lambda: engine.check_batch(model, hs, mesh=mesh))
    note("4 chips: the same batch on device 0")
    rs1 = engine.check_batch(model, hs, mesh=one)
    for k, (r4, r1) in enumerate(zip(rs4, rs1)):
        assert r4["closure"] == "pallas" and "closure-note" not in r4, r4
        assert _pin(r4) == _pin(r1), (k, _pin(r4), _pin(r1))
    assert rs4[bad]["valid?"] is False
    report("batch-4chip", **t, keys=n_keys, devices=4)

    # the invalid twin: verdict, fail event and op all compared. The
    # reference is the bitdense engine on devs[0] (the sharded engine
    # on one device would cost a second ~100 s compile of the same
    # code); the capacity covers the ~2^(k+3) peak frontier, so no
    # escalation recompiles
    _, invalid_h = adversarial_pair(adv_ops, adv_k)
    e = enc_mod.encode(model, invalid_h)
    # one call: at 10k events a sharded run costs minutes of four chips
    note("4 chips: frontier-sharded engine")
    r4, first = secs(lambda: sharded.check_encoded_sharded(
        e, mesh, capacity=1 << (adv_k + 4)))
    note("4 chips: bitdense reference on device 0")
    ref = bitdense.check_encoded_bitdense(e)
    assert r4["devices"] == 4 and r4["valid?"] is False, r4
    assert _pin(r4) == _pin(ref), (_pin(r4), _pin(ref))
    report("sharded-4chip", first_secs=first, ops=adv_ops, crashed=adv_k,
           devices=4, capacity=r4.get("capacity"))
    return {"batch": rs4, "sharded": r4}


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--chips", type=int, choices=(1, 4), default=1,
                   help="4: run only the four-chip paths")
    args = p.parse_args(argv)
    from jepsen_tpu.compile_cache import enable_compile_cache
    enable_compile_cache()
    devs = require_tpu(args.chips)
    if args.chips == 4:
        phase_four_chips(devs)
    else:
        phase_multikey()
        phase_adversarial()
        phase_served()
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
