"""The main path's device programs compile for a TPU v5e.

No chip is needed: the TPU compiler compiles for a described v5e:2x2
topology, and refuses here what it would refuse on the chip (a kernel
it cannot partition, a gather it does not support, more fast memory
than a kernel may use). Shapes are the chip smoke's real sizes: the
10k-op adversarial key with 12 crashed writes, the 84-key x 120-op
batch, the sparse engine at capacity 1024.

The topology is described inside a module fixture, never at import:
only one process may load the TPU library, and every test worker
imports this file. The persistent compilation cache is off around the
compiles — an entry written for a described chip cannot be read back
without one.
"""

import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def mesh4(topo):
    from jax.sharding import Mesh
    return Mesh(np.array(topo.devices[:4]), ("keys",))


@pytest.fixture(scope="module", autouse=True)
def no_persistent_cache():
    from jax.experimental.compilation_cache import compilation_cache
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


def _shapes(tree, sharding):
    return jax.tree.map(
        lambda v: jax.ShapeDtypeStruct(np.shape(v), np.asarray(v).dtype,
                                       sharding=sharding), tree)


def _batch(n_keys=84, ops_per_key=120):
    import chip_smoke
    from jepsen_tpu.models import CASRegister
    from jepsen_tpu.parallel import encode as enc_mod
    per_key, _, _ = chip_smoke.multikey_histories(n_keys, ops_per_key)
    encs = [enc_mod.encode(CASRegister(), per_key[k]) for k in per_key]
    xs, state0, S, C, _ = enc_mod.pad_batch(encs, min_slots=5)
    return encs, xs, state0, S, C


def test_adversarial_10k_pallas_compiles_for_one_chip(one_chip):
    import chip_smoke
    from jepsen_tpu.models import CASRegister
    from jepsen_tpu.parallel import bitdense, pallas_kernels as pk
    from jepsen_tpu.parallel import encode as enc_mod
    from jepsen_tpu.parallel.dense import _xs_dense

    h, _ = chip_smoke.adversarial_pair()
    e = enc_mod.encode(CASRegister(), h)
    S, C = bitdense.n_states(e), max(5, e.n_slots)
    assert (S, C, e.n_returns) == (18, 13, 9988)
    assert pk.supported(S, C)
    compiled = bitdense._check_bitdense.lower(
        _shapes(_xs_dense(e, C), one_chip),
        jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip),
        e.step_name, S, C, e.state_lo, True, False, "while",
        False).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_multikey_batch_pallas_compiles_for_one_chip(one_chip):
    from jepsen_tpu.parallel import bitdense, pallas_kernels as pk
    encs, xs, state0, S, C = _batch()
    assert pk.supported(S, C)
    compiled = bitdense._check_bitdense_batch.lower(
        _shapes(xs, one_chip), _shapes(state0, one_chip),
        encs[0].step_name, S, C, encs[0].state_lo, True, False,
        "while").compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_multikey_batch_pallas_compiles_on_a_four_chip_mesh(mesh4):
    """The key-sharded batch: without shard_map the compiler refuses
    ("Mosaic kernels cannot be automatically partitioned")."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from jepsen_tpu.parallel import bitdense
    encs, xs, state0, S, C = _batch()
    assert len(encs) % 4 == 0
    keys = NamedSharding(mesh4, P("keys"))
    fn = bitdense._sharded_batch_fn(mesh4, len(encs), encs[0].step_name,
                                    S, C, encs[0].state_lo, True, False,
                                    "while", False)
    compiled = fn.lower(_shapes(xs, keys), _shapes(state0, keys)).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_sparse_sort_engine_compiles_for_one_chip(one_chip):
    from jepsen_tpu.parallel import engine
    encs, _, _, _, _ = _batch(n_keys=1)
    e = encs[0]
    xs = {k: getattr(e, k) for k in ("slot_f", "slot_a0", "slot_a1",
                                     "slot_wild", "slot_occ", "ev_slot")}
    compiled = engine._check_device.lower(
        _shapes(xs, one_chip),
        jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip),
        step_name=e.step_name, N=1024, dedupe="sort", probe_limit=32,
        sparse_pallas="off", search_stats=False, pack=()).compile()
    assert compiled.memory_analysis() is not None
