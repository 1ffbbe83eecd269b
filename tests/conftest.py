"""Test bootstrap: force JAX onto a virtual 8-device CPU mesh.

Multi-chip TPU hardware isn't available in CI; sharded code paths are
exercised on 8 virtual CPU devices instead (the driver separately
dry-runs the multi-chip path via __graft_entry__.dryrun_multichip).
Must run before the first `import jax` anywhere.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax  # noqa: E402

from jepsen_tpu.compile_cache import enable_compile_cache  # noqa: E402

jax.config.update("jax_platforms", "cpu")

# Persistent compilation cache for the suite, placed as every entry
# point places it: the sharded shard_map programs cost 5-20s each to
# compile on the CPU backend, and re-runs of the suite re-pay every one
# of them.
enable_compile_cache()
jax.config.update("jax_persistent_cache_min_compile_time_secs", 1.0)
