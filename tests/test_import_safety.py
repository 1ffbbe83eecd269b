"""Engine modules must be import-safe: importing them may not touch a
JAX backend. With a wedged device runtime backend init hangs forever, so a module-level device
array turns `import jepsen_tpu.parallel.bitdense` into a hang before
any device call — the exact failure recorded in BENCH_r03's sec_adv.

Parity note: the reference has no analogue (JVM classloading is lazy
by construction); this pins the same property for our JAX modules.
"""

import subprocess
import sys

ENGINE_MODULES = [
    "jepsen_tpu.parallel.encode",
    "jepsen_tpu.parallel.steps",
    "jepsen_tpu.parallel.dense",
    "jepsen_tpu.parallel.bitdense",
    "jepsen_tpu.parallel.engine",
    "jepsen_tpu.parallel.sharded",
    "jepsen_tpu.parallel.pallas_kernels",
    "jepsen_tpu.parallel.extend",
    # the mesh planner must import (and plan) without touching a
    # backend — the gated jax.distributed handshake only runs inside
    # distributed_init
    "jepsen_tpu.parallel.meshplan",
    "jepsen_tpu.models",
    "jepsen_tpu.independent",
    "jepsen_tpu.serve.service",
    # the multi-tenant admission/transport/routing layers must stand
    # up (and refuse/route traffic) while the runtime is wedged
    "jepsen_tpu.serve.tenancy",
    "jepsen_tpu.serve.ingress",
    "jepsen_tpu.serve.ring",
    # the ops surface must ANSWER while the runtime is wedged — its
    # import (and the probe watch's) can never touch a backend
    "jepsen_tpu.obs.httpd",
    "jepsen_tpu.probe",
]

_PROBE = r"""
import sys
for m in {mods!r}:
    __import__(m)
import jax
backends = jax._src.xla_bridge._backends
assert not backends, f"import initialized backend(s): {{list(backends)}}"
print("IMPORT-CLEAN")
"""


def test_engine_imports_touch_no_backend():
    # Fresh interpreter, the real environment:
    # if any module creates a device value at import this either trips
    # the _backends assert (healthy runtime) or hangs into the timeout
    # (wedged runtime) — both fail loudly.
    proc = subprocess.run(
        [sys.executable, "-c", _PROBE.format(mods=ENGINE_MODULES)],
        capture_output=True, text=True, timeout=60,
    )
    assert proc.returncode == 0, proc.stderr
    assert "IMPORT-CLEAN" in proc.stdout
