"""Bounded-subprocess bisect of the frontier-sharded engine on a real
TPU.

Round-5 finding (bench_results/bench_r05_onchip.jsonl, "section
sharded"): the sharded engine's first-ever hardware contact crashed
the TPU worker process (`bench.py sec_sharded`, capacity 2^17, 10k
adversarial history, 1-device mesh), and a follow-up in-process repro
wedged the device runtime.
The engine is fully green on the 8-way CPU mesh (tests/test_sharded.py)
— whatever breaks is a TPU-runtime interaction no CPU test reaches.

Each probe below runs in its OWN subprocess under a hard timeout, so a
worker crash or a runtime wedge costs one probe, never the session:
the parent never imports jax, so each child in turn holds the chip. Probes escalate from primitives to the full
engine:

  p1  shard_map + psum on the 1-device mesh        (collective floor)
  p2  lexsort at Nd=2^12 / 2^17                     (the dedupe's sort)
  p3  all_to_all on a 1-device axis                 (the exchange)
  p4  _check_sharded, 60-op history, cap 2^12       (tiny end-to-end)
  p5  _check_sharded, 1k history, cap 2^12
  p6  _check_sharded, 10k history, cap 2^12
  p7  _check_sharded, 10k history, cap 2^17         (the bench shape)

Run: python tools/bisect_sharded.py [--timeout 240]
One JSON line per probe: {"probe", "ok", "secs" | "error"/"hung"}.
A "hung"/crashed probe names the narrowest failing layer.
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
from time import perf_counter

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

PRELUDE = """
import os, numpy as np, jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
mesh = Mesh(np.array(jax.devices()[:1]), ("frontier",))
"""

PROBES = {
    "p1-shardmap-psum": PRELUDE + """
f = jax.shard_map(lambda x: lax.psum(x, "frontier"), mesh=mesh,
                  in_specs=P(), out_specs=P(), check_vma=False)
out = f(jnp.ones((8,), jnp.int32))
assert int(out[0]) == 1
""",
    "p2-lexsort-2e12": PRELUDE + """
rng = np.random.default_rng(0)
M = 1 << 12
st = jnp.asarray(rng.integers(0, 40, M, dtype=np.int32))
ml = jnp.asarray(rng.integers(0, 2**32, M, dtype=np.uint32))
mh = jnp.asarray(rng.integers(0, 2**32, M, dtype=np.uint32))
live = jnp.asarray(rng.integers(0, 2, M).astype(bool))
o = jax.jit(lambda a, b, c, l: jnp.lexsort(
    (c, b, a, (~l).astype(jnp.int8))))(st, ml, mh, live)
o.block_until_ready()
""",
    "p2-lexsort-2e17": PRELUDE + """
rng = np.random.default_rng(0)
M = 1 << 17
st = jnp.asarray(rng.integers(0, 40, M, dtype=np.int32))
ml = jnp.asarray(rng.integers(0, 2**32, M, dtype=np.uint32))
mh = jnp.asarray(rng.integers(0, 2**32, M, dtype=np.uint32))
live = jnp.asarray(rng.integers(0, 2, M).astype(bool))
o = jax.jit(lambda a, b, c, l: jnp.lexsort(
    (c, b, a, (~l).astype(jnp.int8))))(st, ml, mh, live)
o.block_until_ready()
""",
    "p3-all-to-all-1dev": PRELUDE + """
def body(x):
    return lax.all_to_all(x, "frontier", split_axis=0, concat_axis=0,
                          tiled=True)
f = jax.shard_map(body, mesh=mesh, in_specs=P("frontier"),
                  out_specs=P("frontier"), check_vma=False)
out = f(jnp.arange(64, dtype=jnp.int32))
out.block_until_ready()
""",
}


def _engine_probe(n_ops: int, cap_log: int) -> str:
    return PRELUDE + f"""
from jepsen_tpu.histories import adversarial_register_history
from jepsen_tpu.models import CASRegister
from jepsen_tpu.parallel import sharded, encode as enc_mod
h = adversarial_register_history(n_ops={n_ops}, k_crashed=12, seed=7)
e = enc_mod.encode(CASRegister(), h)
r = sharded.check_encoded_sharded(e, mesh, capacity=1 << {cap_log},
                                  max_capacity=1 << 20)
print("RESULT", r.get("valid?"), r.get("capacity"), r.get("max-frontier"))
"""


PROBES["p4-engine-60op-cap12"] = _engine_probe(60, 12)
PROBES["p5-engine-1k-cap12"] = _engine_probe(1000, 12)
PROBES["p6-engine-10k-cap12"] = _engine_probe(10000, 12)
PROBES["p7-engine-10k-cap17"] = _engine_probe(10000, 17)


def run_probe(name: str, code: str, timeout: float) -> dict:
    t0 = perf_counter()
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    try:
        p = subprocess.run([sys.executable, "-c", code],
                           capture_output=True, text=True,
                           timeout=timeout, env=env)
    except subprocess.TimeoutExpired:
        return {"probe": name, "ok": False, "hung": True,
                "timeout_secs": timeout}
    out = {"probe": name, "ok": p.returncode == 0,
           "secs": round(perf_counter() - t0, 1)}
    if p.returncode != 0:
        tail = (p.stderr or "").strip().splitlines()
        out["error"] = " | ".join(tail[-3:])[-400:]
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--timeout", type=float, default=240.0)
    ap.add_argument("--only", help="comma-separated probe-name filter")
    args = ap.parse_args()
    only = set(args.only.split(",")) if args.only else None
    for name, code in PROBES.items():
        if only and name not in only:
            continue
        res = run_probe(name, code, args.timeout)
        print(json.dumps(res), flush=True)
        if not res["ok"]:
            print(json.dumps(
                {"stop": f"first failure at {name} — layers above it "
                         f"are exonerated; this one owns the crash"}),
                flush=True)
            break


if __name__ == "__main__":
    main()
