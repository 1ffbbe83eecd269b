"""The key-sharded batch on the 8-way CPU mesh against the host
reference: ``engine.check_batch_encoded`` / ``engine.check_batch`` with
the key axis placed across devices must give, key by key, the verdict
of both host searches (``checker.wgl`` and ``checker.linear``) and,
for an invalid key, the failing op of the host frontier search
(``linear.analysis``: like the device, it fails at the first
completion no configuration survives; WGL's depth-first search, and
the ``Linearizable`` checker that reports WGL's op, may name another
op of the same invalid history) — across the packable
families, both dedupe strategies, the packed configuration word, a
batch whose heavy keys climb the capacity ladder, and a ragged batch
(K not a device multiple)."""

import numpy as np
import pytest

import jax
from jax.sharding import Mesh

from jepsen_tpu.checker import linear, wgl
from jepsen_tpu.histories import (corrupt_history, rand_fifo_history,
                                  rand_gset_history, rand_queue_history,
                                  rand_register_history)
from jepsen_tpu.models import (CASRegister, FIFOQueue, GSet, Mutex,
                               UnorderedQueue)
from jepsen_tpu.parallel import encode as enc_mod, engine


def _mesh():
    return Mesh(np.array(jax.devices()), ("key",))


def _assert_matches_host(model, hs, rs):
    assert len(rs) == len(hs)
    for j, (h, r) in enumerate(zip(hs, rs)):
        ref = linear.analysis(model, h)
        assert r["valid?"] is ref["valid?"], (j, r, ref)
        assert wgl.analysis(model, h)["valid?"] is ref["valid?"], j
        if ref["valid?"] is False:
            assert r["op"] == ref["op"], (j, r, ref)


FAMILIES = [
    ("cas-register", CASRegister,
     lambda: rand_register_history(n_ops=40, n_processes=5, n_values=3,
                                   crash_p=0.06, fail_p=0.08, seed=31)),
    ("gset", GSet,
     lambda: rand_gset_history(n_ops=36, n_processes=4, n_elements=9,
                               crash_p=0.06, seed=33)),
    ("uqueue", UnorderedQueue,
     lambda: rand_queue_history(n_ops=26, n_processes=4, n_values=3,
                                crash_p=0.06, seed=34)),
    ("fifo", FIFOQueue,
     lambda: rand_fifo_history(n_ops=24, n_processes=4, n_values=3,
                               crash_p=0.05, seed=35)),
]


@pytest.mark.parametrize("name,Model,gen", FAMILIES,
                         ids=[c[0] for c in FAMILIES])
@pytest.mark.parametrize("dedupe", ["sort", "hash"])
def test_mesh_batch_matches_host_clean_and_corrupted(name, Model, gen,
                                                     dedupe):
    """Clean and value-corrupted histories of each packable family,
    K=8 on the 8-way mesh (divisible, so no ragged program compiles):
    per key, the host's verdict and failing op."""
    h = gen()
    model = Model()
    hs = [h, corrupt_history(h, seed=7, n_corruptions=2)]
    pre = [enc_mod.encode(model, v) for v in hs]
    hs, pre = (hs * 4)[:8], (pre * 4)[:8]
    rs = engine.check_batch_encoded(model, pre, capacity=128,
                                    mesh=_mesh(), dedupe=dedupe)
    _assert_matches_host(model, hs, rs)
    # the corrupted variant is invalid (otherwise the op pin is idle)
    assert rs[1]["valid?"] is False


def test_mesh_batch_mutex_invalid_packed_and_unpacked():
    """An invalid mutex history through the packed configuration word
    and the unpacked triple on the mesh: both localize the host's
    counterexample."""
    from jepsen_tpu.history import History, invoke_op, ok_op
    h = History.wrap([
        invoke_op(0, "acquire", None), ok_op(0, "acquire", None),
        invoke_op(1, "acquire", None), ok_op(1, "acquire", None),
    ]).index()
    pre = [enc_mod.encode(Mutex(), h)] * 8
    for pack in (False, True):
        rs = engine.check_batch_encoded(Mutex(), pre, capacity=64,
                                        mesh=_mesh(), config_pack=pack)
        _assert_matches_host(Mutex(), [h] * 8, rs)
        assert rs[0]["valid?"] is False


# Pinned seeds for a skewed unordered-queue batch: heavy seeds are
# crash-riddled histories that climb the capacity ladder from the
# base tier with deep closures; light seeds stay near the base. All
# land in one slot-window bucket, and the queue model's multiset state
# space keeps that bucket on the sparse engine.
_SKEW_HEAVY_SEEDS = (1, 7)
_SKEW_LIGHT_SEEDS = (101, 102, 103, 104, 105, 106)
SKEW_CAPACITY = 32   # the ladder's base tier for the pinned shape


def forced_skew_histories():
    """(model, histories), heavy keys first."""
    hs = [rand_queue_history(n_ops=32, n_processes=6, n_values=3,
                             crash_p=0.22, seed=s)
          for s in _SKEW_HEAVY_SEEDS]
    hs += [rand_queue_history(n_ops=32, n_processes=6, n_values=3,
                              crash_p=0.0, seed=s)
           for s in _SKEW_LIGHT_SEEDS]
    return UnorderedQueue(), hs


def test_mesh_batch_capacity_ladder_per_key():
    """Per-key capacities do not depend on the batch: heavy keys that
    escalate in a mixed mesh batch land the tier a single-key
    check_encoded run lands, and every verdict is the host's."""
    model, hs = forced_skew_histories()
    pre = [enc_mod.encode(model, h) for h in hs]
    rs = engine.check_batch_encoded(model, pre, capacity=SKEW_CAPACITY,
                                    max_capacity=1 << 16, mesh=_mesh())
    for e, r in zip(pre, rs):
        single = engine.check_encoded(e, capacity=SKEW_CAPACITY,
                                      max_capacity=1 << 16)
        assert r["capacity"] == single["capacity"], (r, single)
    _assert_matches_host(model, hs, rs)
    # the heavy keys really escalated past the light ones (otherwise
    # this pins nothing)
    n_heavy = len(_SKEW_HEAVY_SEEDS)
    assert min(r["capacity"] for r in rs[:n_heavy]) \
        > max(r["capacity"] for r in rs[n_heavy:]) >= SKEW_CAPACITY


def test_check_batch_ragged_mesh_matches_host():
    """K=5 on the 8-way mesh through the public check_batch (encode
    included): key by key the host's verdict and failing op."""
    model = CASRegister()
    hs = [rand_register_history(n_ops=30, n_processes=4, n_values=3,
                                crash_p=0.05, seed=40 + i)
          for i in range(5)]
    hs[1] = corrupt_history(hs[1], seed=3, n_corruptions=2)
    hs[3] = corrupt_history(hs[3], seed=4, n_corruptions=2)
    rs = engine.check_batch(model, hs, mesh=_mesh())
    _assert_matches_host(model, hs, rs)
    assert any(r["valid?"] is False for r in rs)
