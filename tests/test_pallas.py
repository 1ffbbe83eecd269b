"""Pallas closure kernel (parallel.pallas_kernels) — interpreter-mode
differential tests on the CPU backend. Three oracles:

1. a pure-Python SEMANTIC fixpoint over (state, mask) pairs, written
   from the closure's definition, not its bit-twiddling realisation;
2. the XLA bitdense closure (same algebra, different execution);
3. the host WGL engine, via full check_encoded_bitdense runs with the
   pallas path forced on.
"""

import numpy as np
import pytest

from jepsen_tpu.parallel import bitdense, pallas_kernels as pk

FULL = np.uint32(0xFFFFFFFF)


def _semantic_fixpoint(sel, B, C):
    """Reference closure: from every reachable (state s, mask m), for
    every slot j not in m with a legal transition s->t, (t, m | 1<<j)
    is reachable. Iterate to fixpoint. sel [C,S,S], B [S,W] words."""
    S, W = B.shape
    reach = set()
    for s in range(S):
        for w in range(W):
            word = int(B[s, w])
            for b in range(32):
                if (word >> b) & 1:
                    reach.add((s, w * 32 + b))
    changed = True
    while changed:
        changed = False
        for (s, m) in list(reach):
            for j in range(C):
                if (m >> j) & 1:
                    continue
                for t in range(S):
                    if sel[j, s, t] and (t, m | (1 << j)) not in reach:
                        reach.add((t, m | (1 << j)))
                        changed = True
    out = np.zeros((S, W), np.uint32)
    for (s, m) in reach:
        out[s, m // 32] |= np.uint32(1) << np.uint32(m % 32)
    return out


from jepsen_tpu.histories import with_impossible_read as _with_impossible_read


def _rand_case(seed, S=5, C=12, n_seeds=3, p_legal=0.08):
    rng = np.random.default_rng(seed)
    W = (1 << C) // 32
    sel = np.where(rng.random((C, S, S)) < p_legal, FULL,
                   np.uint32(0)).astype(np.uint32)
    B = np.zeros((S, W), np.uint32)
    for _ in range(n_seeds):
        s, m = rng.integers(S), rng.integers(1 << C)
        B[s, m // 32] |= np.uint32(1) << np.uint32(m % 32)
    return sel, B, C


@pytest.mark.parametrize("seed", range(4))
def test_pallas_closure_vs_semantic_oracle(seed):
    sel, B, C = _rand_case(seed)
    got = np.asarray(pk.closure_fixpoint(sel, B, C, interpret=True))
    want = _semantic_fixpoint(sel, B, C)
    assert (got == want).all(), f"seed {seed}: {int((got != want).sum())} "\
                                f"words differ"


def test_xor_shuffle_is_the_xor_permutation():
    """_xor_shuffle must realise y[..., w] = x[..., w ^ jb] exactly,
    for every power-of-two stride the kernel uses (jb = 1 .. W/2).
    Guards the r5 rewrite: the original reshape/flip spelling was
    semantically identical but uncompilable by Mosaic (no `rev`, no
    4-D lane reshape), so the spelling changed on-chip — this pins the
    permutation itself, independent of the full-kernel differential."""
    import jax

    rng = np.random.default_rng(11)
    for S, W in ((13, 256), (6, 128)):
        x = rng.integers(0, 2**32, size=(S, W), dtype=np.uint32)
        jb = 1
        while jb <= W // 2:
            got = np.asarray(jax.jit(
                pk._xor_shuffle, static_argnums=1)(x, jb))
            want = x[:, np.arange(W) ^ jb]
            np.testing.assert_array_equal(got, want, err_msg=f"jb={jb}")
            jb <<= 1


def test_pallas_supported_gate():
    assert pk.supported(6, 12)       # W=128
    assert not pk.supported(6, 11)   # W=64: below one lane tile
    assert not pk.supported(100, 12) # S too large to unroll


def test_bitdense_pallas_path_differential():
    """Full engine runs with the pallas closure forced on vs the XLA
    closure and the host oracle — valid and invalid histories. C must
    be >= 12 for kernel support, so the histories carry 11 crashed
    writes to widen the slot window."""
    from jepsen_tpu.checker import wgl
    from jepsen_tpu.histories import adversarial_register_history
    from jepsen_tpu.models import CASRegister
    from jepsen_tpu.parallel import encode as enc_mod

    h = adversarial_register_history(n_ops=60, k_crashed=11, seed=5)
    e = enc_mod.encode(CASRegister(), h)
    assert pk.supported(bitdense.n_states(e), e.n_slots), \
        (bitdense.n_states(e), e.n_slots)
    r_xla = bitdense.check_encoded_bitdense(e, use_pallas=False)
    r_pl = bitdense.check_encoded_bitdense(e, use_pallas=True)
    assert r_pl["closure"] == "pallas" and r_xla["closure"] == "xla-while"
    assert r_xla["valid?"] is r_pl["valid?"] is True

    # invalid: impossible read appended
    hb = _with_impossible_read(h)
    eb = enc_mod.encode(CASRegister(), hb)
    rb_xla = bitdense.check_encoded_bitdense(eb, use_pallas=False)
    rb_pl = bitdense.check_encoded_bitdense(eb, use_pallas=True)
    assert rb_xla["valid?"] is rb_pl["valid?"] is False
    assert rb_xla["fail-event"] == rb_pl["fail-event"]
    assert wgl.analysis(CASRegister(), hb)["valid?"] is False


def test_batch_pallas_path_differential():
    """check_batch_bitdense with the vmapped pallas closure vs the XLA
    closure on a mixed valid/invalid key batch (padded C >= 12 for
    kernel support)."""
    from jepsen_tpu.histories import adversarial_register_history
    from jepsen_tpu.models import CASRegister
    from jepsen_tpu.parallel import encode as enc_mod

    encs = []
    for seed in range(3):
        h = adversarial_register_history(n_ops=40, k_crashed=11,
                                         seed=seed)
        encs.append(enc_mod.encode(CASRegister(), h))
    # one invalid key: impossible read appended
    h = adversarial_register_history(n_ops=40, k_crashed=11, seed=9)
    encs.append(enc_mod.encode(CASRegister(), _with_impossible_read(h)))

    # the differential is vacuous unless the PADDED batch dims clear
    # the kernel's support gate (check_batch downgrades silently)
    S_pad = max(bitdense.n_states(e) for e in encs)
    C_pad = max(5, max(e.n_slots for e in encs))
    assert pk.supported(S_pad, C_pad), (S_pad, C_pad)

    rs_xla = bitdense.check_batch_bitdense(encs, use_pallas=False)
    rs_pl = bitdense.check_batch_bitdense(encs, use_pallas=True)
    assert all(r["closure"] == "xla-while" for r in rs_xla)
    assert all(r["closure"] == "pallas" for r in rs_pl)
    assert [r["valid?"] for r in rs_xla] == [True, True, True, False]
    for rx, rp in zip(rs_xla, rs_pl):
        assert rx["valid?"] is rp["valid?"]
        assert rx.get("fail-event") == rp.get("fail-event")


def test_only_the_tpu_platform_counts_as_tpu():
    """Platform gates accept the name JAX gives a TPU, "tpu", and
    nothing else: any other backend runs the kernel in interpret
    mode."""
    assert bitdense.is_tpu_platform("tpu")
    for other in ("cpu", "cuda", "rocm", "TPU", ""):
        assert not bitdense.is_tpu_platform(other), other
    # the gate's interpret decision follows it
    _, interp = bitdense._resolve_use_pallas(True, 17, 12, "tpu")
    assert interp is False
    _, interp = bitdense._resolve_use_pallas(True, 17, 12, "cpu")
    assert interp is True


# --------------------------------------------- SPMD / mesh lowering

def test_pallas_closure_under_shard_map_interpret():
    """The kernel's per-device SPMD lowering, exercised the way a
    mesh-sharded TPU batch would run it: shard_map over the 8-device
    CPU mesh, one closure per local key, interpret mode. Must equal
    the same kernel run unsharded per key. (On-chip non-interpret A/B
    is the remaining hardware-only step — PARITY §2.20.)"""
    import jax
    from jax.sharding import Mesh, PartitionSpec as P

    S, C = 8, 12
    K = 8
    sels, bs = [], []
    for k in range(K):
        sel, B, _ = _rand_case(100 + k, S=S, C=C)
        sels.append(sel)
        bs.append(B)
    sel_all = np.stack(sels)           # [K, C, S, S]
    b_all = np.stack(bs)               # [K, S, W]

    mesh = Mesh(np.array(jax.devices()[:8]), ("keys",))

    def per_shard(sel_k, b_k):
        # local leading axis: K/8 = 1 key per device
        return jax.vmap(
            lambda s, b: pk.closure_call(s, b, C, interpret=True)
        )(sel_k, b_k)

    # check_vma=False: pallas_call's ShapeDtypeStruct carries no vma
    # annotation; the value check would reject it under shard_map.
    sharded_fn = jax.jit(jax.shard_map(
        per_shard, mesh=mesh,
        in_specs=(P("keys"), P("keys")), out_specs=P("keys"),
        check_vma=False))
    out_sharded = np.asarray(sharded_fn(sel_all, b_all))

    for k in range(K):
        ref = np.asarray(pk.closure_fixpoint(sel_all[k], b_all[k], C,
                                             interpret=True))
        np.testing.assert_array_equal(out_sharded[k], ref)


def test_batch_pallas_on_mesh_differential():
    """check_batch_bitdense with the key axis sharded over the 8-device
    mesh and the pallas closure forced on (on this CPU mesh the default
    resolves to XLA; on a TPU mesh it is pallas, one kernel per device
    through the shard_map program): verdicts and fail events must
    match the XLA path on the same mesh."""
    import jax
    from jax.sharding import Mesh

    from jepsen_tpu.histories import adversarial_register_history
    from jepsen_tpu.models import CASRegister
    from jepsen_tpu.parallel import encode as enc_mod

    encs = []
    for seed in range(7):
        h = adversarial_register_history(n_ops=40, k_crashed=11,
                                         seed=seed)
        encs.append(enc_mod.encode(CASRegister(), h))
    h = adversarial_register_history(n_ops=40, k_crashed=11, seed=9)
    encs.append(enc_mod.encode(CASRegister(), _with_impossible_read(h)))
    assert len(encs) == 8              # divisible: key axis SHARDS

    S_pad = max(bitdense.n_states(e) for e in encs)
    C_pad = max(5, max(e.n_slots for e in encs))
    assert pk.supported(S_pad, C_pad), (S_pad, C_pad)

    mesh = Mesh(np.array(jax.devices()[:8]), ("keys",))
    rs_xla = bitdense.check_batch_bitdense(encs, mesh=mesh,
                                           use_pallas=False)
    rs_pl = bitdense.check_batch_bitdense(encs, mesh=mesh,
                                          use_pallas=True)
    assert all(r["closure"] == "pallas" for r in rs_pl)
    assert [r["valid?"] for r in rs_pl] == [r["valid?"] for r in rs_xla]
    assert rs_pl[-1]["valid?"] is False
    for rx, rp in zip(rs_xla, rs_pl):
        assert rx.get("fail-event") == rp.get("fail-event")

    # default resolution after the r5 on-chip A/B verdict (default-on,
    # every shape won, zero disagreements): a real-TPU platform gets
    # pallas non-interpret by default, JEPSEN_TPU_PALLAS=0 opts out,
    # and non-TPU platforms stay off unless the flag forces interpret
    import os as _os
    import unittest.mock as mock
    env = dict(_os.environ)
    env.pop("JEPSEN_TPU_PALLAS", None)   # hermetic: a developer's
    with mock.patch.dict(_os.environ, env, clear=True):   # exported
        # flag must not flip these default-resolution asserts
        assert bitdense._resolve_use_pallas(None, 17, 12, "tpu") \
            == (True, False)
        assert bitdense._resolve_use_pallas(None, 17, 12, "cpu") \
            == (False, True)
        # unsupported shapes still downgrade regardless of platform
        assert bitdense._resolve_use_pallas(None, 128, 12, "tpu")[0] \
            is False
    with mock.patch.dict(_os.environ, {"JEPSEN_TPU_PALLAS": "0"}):
        assert bitdense._resolve_use_pallas(None, 17, 12, "tpu") \
            == (False, False)
    with mock.patch.dict(_os.environ, {"JEPSEN_TPU_PALLAS": "1"}):
        assert bitdense._resolve_use_pallas(None, 17, 12, "cpu") \
            == (True, True)


@pytest.mark.slow
def test_fori_closure_mode_differential():
    """The fixed-trip fori closure must be verdict- and fail-event-
    equal to the converge-and-stop while closure (its trip bound
    ceil(C/2) double-expansions is a worst-case convergence proof — a
    wrong bound shows up here as a missed expansion on deep chains).

    slow-marked: ~3 minutes of k=11 adversarial + crashy-FIFO device
    searches differentially testing an OPT-IN closure mode (fori lost
    the r5 on-chip A/B 0.3x and stays non-default; fori correctness
    also rides tools/perf_ab.py's gate on every measured run) — the
    single second-largest sink in the default suite."""
    from jepsen_tpu.histories import (adversarial_register_history,
                                      rand_fifo_history)
    from jepsen_tpu.models import CASRegister, FIFOQueue
    from jepsen_tpu.parallel import encode as enc_mod

    cases = []
    for seed in range(3):
        h = adversarial_register_history(n_ops=60, k_crashed=11,
                                         seed=seed)
        cases.append((CASRegister(), h))
    cases.append((CASRegister(), _with_impossible_read(
        adversarial_register_history(n_ops=60, k_crashed=11, seed=9))))
    # deep-chain shape: crashy FIFO keys linearize long suffixes at
    # once, the regime where an undersized trip bound would diverge
    for seed in (1, 5):
        cases.append((FIFOQueue(),
                      rand_fifo_history(n_ops=24, n_processes=4,
                                        n_values=3, crash_p=0.15,
                                        seed=seed)))
    for model, h in cases:
        e = enc_mod.encode(model, h)
        rw = bitdense.check_encoded_bitdense(e, closure_mode="while")
        rf = bitdense.check_encoded_bitdense(e, closure_mode="fori")
        assert rw["closure"] == "xla-while"
        assert rf["closure"] == "xla-fori"
        assert rw["valid?"] is rf["valid?"], (rw, rf)
        assert rw.get("fail-event") == rf.get("fail-event")


def test_batch_pallas_error_on_a_mesh_propagates(monkeypatch):
    """A kernel error on a multi-device mesh is raised — on the default
    path too. There is no quiet switch to the XLA closure and no
    closure-note: a TPU run either runs the kernel or fails. Simulated
    by failing the mesh program whenever the pallas variant is
    requested (the real trigger was the compiler's refusal to
    partition a Mosaic kernel)."""
    import jax
    from jax.sharding import Mesh

    from jepsen_tpu.histories import adversarial_register_history
    from jepsen_tpu.models import CASRegister
    from jepsen_tpu.parallel import encode as enc_mod

    hs = [adversarial_register_history(n_ops=40, k_crashed=11, seed=s)
          for s in range(3)]
    hs.append(_with_impossible_read(hs[0]))
    encs = [enc_mod.encode(CASRegister(), h) for h in hs]
    mesh = Mesh(np.array(jax.devices()[:4]), ("keys",))

    real = bitdense._sharded_batch_fn

    def failing_on_pallas(mesh, K, **statics):
        if statics["use_pallas"]:
            raise NotImplementedError(
                "Mosaic kernels cannot be automatically partitioned "
                "(simulated)")
        return real(mesh, K, **statics)

    monkeypatch.setattr(bitdense, "_sharded_batch_fn", failing_on_pallas)
    # true DEFAULT path: use_pallas=None, no env flag, and the
    # platform gate resolving ON (as it does on a TPU mesh)
    monkeypatch.delenv("JEPSEN_TPU_PALLAS", raising=False)
    monkeypatch.setattr(bitdense, "_resolve_use_pallas",
                        lambda up, S, C, platform: (True, True))
    with pytest.raises(NotImplementedError, match="Mosaic"):
        bitdense.check_batch_bitdense(encs, mesh=mesh)
    # an explicit request surfaces it the same way
    with pytest.raises(NotImplementedError, match="Mosaic"):
        bitdense.check_batch_bitdense(encs, mesh=mesh, use_pallas=True)


def test_batch_pallas_single_device_error_propagates(monkeypatch):
    """Without a mesh the batch program is the plain jit; a kernel
    error there is raised as well."""
    from jepsen_tpu.histories import adversarial_register_history
    from jepsen_tpu.models import CASRegister
    from jepsen_tpu.parallel import encode as enc_mod

    encs = [enc_mod.encode(CASRegister(), adversarial_register_history(
        n_ops=40, k_crashed=11, seed=s)) for s in range(2)]
    real = bitdense._check_bitdense_batch

    def failing_on_pallas(*args, **statics):
        if statics["use_pallas"]:
            raise RuntimeError("Mosaic lowering gap (simulated)")
        return real(*args, **statics)

    monkeypatch.setattr(bitdense, "_check_bitdense_batch",
                        failing_on_pallas)
    monkeypatch.delenv("JEPSEN_TPU_PALLAS", raising=False)
    monkeypatch.setattr(bitdense, "_resolve_use_pallas",
                        lambda up, S, C, platform: (True, True))
    with pytest.raises(RuntimeError, match="Mosaic"):
        bitdense.check_batch_bitdense(encs)
