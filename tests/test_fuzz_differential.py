"""Randomized differential fuzz: every engine vs the host WGL oracle.

All six model families, clean + corrupted histories, every engine whose
gate admits the shape (host linear / packed, device sparse / dense /
bitdense). The reference runs its expensive tiers outside the default
selection (`lein test` excludes :perf/:integration —
jepsen/project.clj:36-41); likewise this tier is deselected by default
(pytest.ini addopts) and run explicitly:

    python -m pytest tests/test_fuzz_differential.py -m fuzz -q

Seed count via JEPSEN_FUZZ_SEEDS (default 3 per model-variant; for a
deep sweep run e.g. `JEPSEN_FUZZ_SEEDS=30 ... -m fuzz`). Any verdict
disagreement or engine crash fails the test with the (model, seed,
variant) triple — enough to reproduce deterministically.
"""

import os
import traceback
from time import monotonic

import numpy as np
import pytest

from jepsen_tpu.checker import competition, linear, linear_packed, wgl
from jepsen_tpu.histories import (
    corrupt_history, rand_fifo_history, rand_gset_history,
    rand_queue_history, rand_register_history)
from jepsen_tpu.history import History
from jepsen_tpu.models import (
    CASRegister, FIFOQueue, GSet, Mutex, Register, UnorderedQueue)
from jepsen_tpu.parallel import bitdense, dense, encode as enc_mod, engine

N_SEEDS = int(os.environ.get("JEPSEN_FUZZ_SEEDS", "3"))


def rand_mutex_history(n_ops, n_processes, crash_p, seed):
    """Random acquire/release attempts; validity NOT guaranteed —
    the differential compares verdicts, it does not assert them.
    Crashed (info) workers retire their process id for a fresh one,
    matching the interpreter's renumbering convention (History.pairs
    assumes one open op per process id)."""
    rng = np.random.default_rng(seed)
    ops, t = [], 0
    pid_of = dict(enumerate(range(n_processes)))   # worker -> live pid
    next_pid = n_processes
    open_w = {}                                    # worker -> open f
    for _ in range(n_ops):
        w = int(rng.integers(n_processes))
        if w in open_w:
            f = open_w.pop(w)
            typ = "info" if rng.random() < crash_p else "ok"
            ops.append({"index": len(ops), "time": t,
                        "process": pid_of[w], "type": typ, "f": f,
                        "value": None})
            if typ == "info":
                pid_of[w] = next_pid
                next_pid += 1
        else:
            f = "acquire" if rng.random() < 0.5 else "release"
            open_w[w] = f
            ops.append({"index": len(ops), "time": t,
                        "process": pid_of[w], "type": "invoke", "f": f,
                        "value": None})
        t += 1
    for w, f in open_w.items():
        ops.append({"index": len(ops), "time": t, "process": pid_of[w],
                    "type": "info", "f": f, "value": None})
        t += 1
    return History.wrap(ops).index()


CASES = [
    ("cas-register", CASRegister,
     lambda s: rand_register_history(n_ops=44, n_processes=5, n_values=3,
                                     crash_p=0.06, fail_p=0.08, seed=s)),
    ("register", Register,
     lambda s: rand_register_history(n_ops=40, n_processes=4, n_values=3,
                                     crash_p=0.05, fail_p=0.05, seed=s,
                                     cas=False)),
    ("mutex", Mutex,
     lambda s: rand_mutex_history(36, 4, 0.05, s)),
    ("gset", GSet,
     lambda s: rand_gset_history(n_ops=40, n_processes=4, n_elements=6,
                                 crash_p=0.06, seed=s)),
    # queue families stay small: proving a corrupted queue history
    # invalid forces the host searches to exhaust the interleaving
    # space, which grows brutally with length (the device engines
    # don't care — but the oracle must terminate)
    ("uqueue", UnorderedQueue,
     lambda s: rand_queue_history(n_ops=26, n_processes=4, n_values=3,
                                  crash_p=0.06, seed=s)),
    ("fifo", FIFOQueue,
     lambda s: rand_fifo_history(n_ops=24, n_processes=4, n_values=3,
                                 crash_p=0.05, seed=s)),
]


@pytest.mark.fuzz
@pytest.mark.parametrize("name,Model,gen", CASES,
                         ids=[c[0] for c in CASES])
def test_fuzz_engines_agree_with_wgl(name, Model, gen):
    import jax

    failures = []
    runs = 0
    for seed in range(N_SEEDS):
        if seed and seed % 25 == 0:
            # every distinct (R, S, C) shape is a separate compiled
            # executable; hundreds of seeds accumulate thousands of
            # them and the XLA CPU backend has been observed to
            # SEGFAULT under that pressure (200-seed sweep crash in
            # backend_compile_and_load; all shapes pass in isolation)
            jax.clear_caches()
        # mutex ops carry no values, so corrupt_history has nothing to
        # flip — its invalid coverage comes from the clean variant,
        # where random acquire/release interleavings are often already
        # invalid (the oracle decides); every other family gets a
        # value-corrupted variant (reads and dequeues)
        variants = ("clean",) if name == "mutex" else ("clean", "corrupt")
        for variant in variants:
            h = gen(seed)
            if variant == "corrupt":
                h = corrupt_history(h, seed=seed, n_corruptions=2)
            model = Model()
            # pure-Python search: some seeds are pathologically
            # expensive (exponential in open calls) — bound the oracle
            # and skip undecided cases rather than hang the tier
            oracle = wgl.analysis(model, h, max_states=1_000_000,
                                  deadline=monotonic() + 8)["valid?"]
            if oracle == "unknown":
                continue
            engines = {"linear": lambda: linear.analysis(
                model, h, deadline=monotonic() + 10),
                # the full first-decisive-wins race (jax+packed+wgl or
                # linear+wgl): whatever arm wins must agree with the
                # oracle — this is the DEFAULT analyzer users get
                "competition": lambda: competition.analysis(
                    model, h, timeout=30)}
            try:
                e = enc_mod.encode(model, h)
            except enc_mod.EncodeError:
                e = None
            if e is not None:
                engines["packed"] = lambda: linear_packed.analysis(
                    model, h, deadline=monotonic() + 10)
                # check_encoded directly: engine.analysis would route
                # to bitdense for most of these shapes, silently
                # re-testing what the separate bitdense entry covers.
                # Invalid queue histories never prune, so the sparse
                # frontier escalates tier-by-tier (minutes on the CPU
                # mesh); cap it — overflow returns "unknown", skipped
                # by the loop below
                engines["sparse"] = lambda: engine.check_encoded(
                    e, max_capacity=1 << 15)
                # the delta-frontier hash visited-set variant
                # (JEPSEN_TPU_DEDUPE=hash) against the same oracle on
                # every family, clean + corrupted — the randomized arm
                # of the dedupe parity matrix (tests/test_dedupe.py is
                # the deterministic pin)
                engines["sparse-hash"] = lambda: engine.check_encoded(
                    e, max_capacity=1 << 15, dedupe="hash")
                if seed == 0:
                    # the fused-frontier-kernel arm of the same matrix
                    # (tests/test_sparse_pallas.py is the deterministic
                    # pin). First seed only: every distinct (R, S, C)
                    # is its own interpret-kernel compile, and this
                    # tier rides tier-1's budget; capacity tiers past
                    # the kernel's VMEM gate degrade to the XLA hash
                    # transparently (note-tagged), which is itself the
                    # fallback contract under test
                    engines["sparse-hash-pallas"] = \
                        lambda: engine.check_encoded(
                            e, max_capacity=1 << 15, dedupe="hash",
                            sparse_pallas=True)
                if dense.fits_dense(dense.n_states(e), e.n_slots):
                    engines["dense"] = lambda: dense.check_encoded_dense(e)
                if bitdense.fits_bitdense(bitdense.n_states(e),
                                          e.n_slots):
                    engines["bitdense"] = \
                        lambda: bitdense.check_encoded_bitdense(e)
            for ename, fn in engines.items():
                try:
                    got = fn()["valid?"]
                except Exception:  # noqa: BLE001 — a crash IS a finding
                    failures.append((ename, name, seed, variant,
                                     "crash", traceback.format_exc()))
                    continue
                if got == "unknown":
                    continue    # engine hit its own budget: undecided
                runs += 1
                if got is not oracle:
                    failures.append((ename, name, seed, variant,
                                     f"oracle={oracle} got={got}", ""))
    assert not failures, failures
    assert runs > 0


@pytest.mark.fuzz
def test_fuzz_fake_device_invalid_ends_in_correct_verdict():
    """Randomized disagreement-escalation sweep (VERDICT r3 next#7): a
    fabricated device-invalid at a random fail event of a genuinely
    VALID history must end in the correct verdict via the host
    escalation ladder — never ship counterexample paths for a valid
    key. max_seeds covers the whole frontier so the surviving lineage
    is always sampled (the bounded default is sampling-dependent)."""
    from jepsen_tpu.models import CASRegister

    failures = []
    for seed in range(max(3, N_SEEDS)):
        rng = np.random.default_rng(1000 + seed)
        # alternate between the short-history whole-prefix branch
        # (<= 500 calls) and the windowed device-seeded branch (> 500
        # calls) — the latter is where a fabricated invalid could ship
        # fake paths from dead-end seeds, and where max_seeds matters.
        # The long size is FIXED so the frontier re-scan's compiled
        # shapes repeat across seeds (each distinct chunk length is a
        # fresh XLA CPU compile; random lengths made this tier crawl)
        long_branch = seed % 2 == 1
        n_ops = 1100 if long_branch else int(rng.integers(60, 140))
        # the long branch keeps crash_p low: every crashed call stays
        # an open slot forever, and ~30 open slots make the frontier
        # re-scan TPU-sized (capacity tiers to 2^20) — fine on a chip,
        # unaffordable in a CPU fuzz iteration
        h = rand_register_history(n_ops=n_ops, n_processes=4,
                                  n_values=3,
                                  crash_p=0.005 if long_branch else 0.03,
                                  fail_p=0.05, seed=2000 + seed)
        model = CASRegister()
        oracle = wgl.analysis(model, h, max_states=1_000_000,
                              deadline=monotonic() + 8)["valid?"]
        if oracle is not True:
            continue
        e = enc_mod.encode(model, h)
        n_samples = 1 if long_branch else min(3, e.n_returns)
        for fail_r in rng.choice(e.n_returns, size=n_samples,
                                 replace=False):
            r = engine.extract_final_paths(model, e, int(fail_r),
                                           max_seeds=4096)
            if r.get("valid?") is True:
                continue                      # overridden: correct
            if "final-paths-note" in r and not r.get("final-paths"):
                continue                      # indecisive, no fake paths
            failures.append((seed, int(fail_r), n_ops,
                             {k: r[k] for k in r
                              if k != "final-paths"}))
    assert not failures, failures


@pytest.mark.fuzz
def test_fuzz_sharded_hash_parity_on_mesh():
    """Randomized sort-vs-hash parity for the frontier-SHARDED engine
    on the 8-way CPU mesh: per-device open-addressed visited sets fed
    by the owner-routed all-to-all must land the exact sort-path
    result — verdict, failing op/event, max-frontier — on clean and
    value-corrupted histories (the order-independent pins; row order
    and configs-stepped differ by design)."""
    import jax
    from jax.sharding import Mesh

    from jepsen_tpu.models import CASRegister
    from jepsen_tpu.parallel import sharded

    mesh = Mesh(np.array(jax.devices()), ("frontier",))
    failures = []
    pin = lambda r: {k: r.get(k) for k in  # noqa: E731
                     ("valid?", "op", "fail-event", "max-frontier",
                      "capacity")}
    for seed in range(max(3, N_SEEDS)):
        # FIXED op count so compiled shapes repeat across seeds (each
        # distinct (R, C) is a fresh XLA CPU compile of the whole
        # sharded scan)
        h = rand_register_history(n_ops=48, n_processes=5, n_values=3,
                                  crash_p=0.06, fail_p=0.06,
                                  seed=4000 + seed)
        for variant in ("clean", "corrupt"):
            hv = h if variant == "clean" else corrupt_history(
                h, seed=seed, n_corruptions=2)
            e = enc_mod.encode(CASRegister(), hv)
            rs = sharded.check_encoded_sharded(e, mesh, capacity=512,
                                               dedupe="sort")
            rh = sharded.check_encoded_sharded(e, mesh, capacity=512,
                                               dedupe="hash")
            if pin(rs) != pin(rh) \
                    or rh.get("configs-stepped", 0) \
                    > rs.get("configs-stepped", 0):
                failures.append((seed, variant, rs, rh))
    assert not failures, failures


@pytest.mark.fuzz
def test_fuzz_elastic_engines_agree_with_wgl():
    """Randomized differential for the mesh-placed batch and the
    elastic re-shard ladder: the static key-sharded batch (key axis on
    the 8-way mesh) and the re-shard sharded ladder must agree with
    the host WGL oracle on clean and value-corrupted histories —
    placement and device recruiting must never touch a verdict. Fixed
    op counts so the compiled shapes repeat across seeds (the
    sharded-mesh sweep's precedent)."""
    import jax
    from jax.sharding import Mesh

    from jepsen_tpu.models import CASRegister
    from jepsen_tpu.parallel import engine, sharded

    mesh = Mesh(np.array(jax.devices()), ("key",))
    model = CASRegister()
    failures = []
    runs = 0
    for seed in range(max(2, N_SEEDS // 2)):
        hs, oracles = [], []
        for j in range(8):
            h = rand_register_history(n_ops=40, n_processes=5,
                                      n_values=3, crash_p=0.06,
                                      fail_p=0.06,
                                      seed=5000 + seed * 8 + j)
            if j % 2:
                h = corrupt_history(h, seed=j, n_corruptions=2)
            hs.append(h)
            oracles.append(wgl.analysis(
                model, h, max_states=1_000_000,
                deadline=monotonic() + 8)["valid?"])
        pre = [enc_mod.encode(model, h) for h in hs]
        rs = engine.check_batch_encoded(model, pre, capacity=128,
                                        max_capacity=1 << 15,
                                        mesh=mesh)
        for j, (r, oracle) in enumerate(zip(rs, oracles)):
            if oracle == "unknown" or r["valid?"] == "unknown":
                continue
            runs += 1
            if r["valid?"] is not oracle:
                failures.append(("batch-oracle", seed, j, oracle, r))
        # the elastic sharded ladder vs the oracle on one key per seed
        e0 = pre[0]
        re = sharded.check_encoded_sharded_elastic(
            e0, mesh, capacity=64, max_capacity=1 << 15)
        if oracles[0] != "unknown" and re["valid?"] != "unknown":
            runs += 1
            if re["valid?"] is not oracles[0]:
                failures.append(("reshard-oracle", seed, oracles[0],
                                 re))
    assert not failures, failures
    assert runs > 0


@pytest.mark.fuzz
def test_fuzz_pallas_agrees_with_xla_closure():
    """Randomized pallas-vs-XLA-closure differential on kernel-
    supported shapes. The main fuzz loop's shapes sit below the pallas
    gate (C >= 12 means 2^12-config mask spaces, where the WGL oracle
    cannot terminate), so the kernel's fuzz oracle is the XLA while
    closure itself — the same algebra under a different execution,
    exactly the equivalence the r5 on-chip A/B correctness gate
    enforced. Verdicts AND fail events must match on clean (valid by
    construction) and value-corrupted variants; pallas now being the
    real-TPU default makes this the default-path fuzz."""
    from jepsen_tpu.histories import adversarial_register_history
    from jepsen_tpu.models import CASRegister
    from jepsen_tpu.parallel import pallas_kernels as pk

    failures = []
    runs = 0
    for seed in range(max(3, N_SEEDS)):
        # FIXED op counts so compiled shapes repeat across seeds (each
        # distinct length is a fresh XLA CPU compile); k varies the
        # mask-space width across the kernel's W tiers
        n_ops = (48, 96, 144)[seed % 3]
        k = (11, 12)[seed % 2]
        for variant in ("clean", "corrupt"):
            h = adversarial_register_history(
                n_ops=n_ops, k_crashed=k, seed=3000 + seed)
            if variant == "corrupt":
                h = corrupt_history(h, seed=seed, n_corruptions=1)
            e = enc_mod.encode(CASRegister(), h)
            S, C = bitdense.n_states(e), max(5, e.n_slots)
            if not pk.supported(S, C):
                continue
            r_xla = bitdense.check_encoded_bitdense(
                e, use_pallas=False, closure_mode="while")
            r_pl = bitdense.check_encoded_bitdense(e, use_pallas=True)
            # guard vacuity: if the resolve logic ever downgrades an
            # explicit use_pallas=True, this would silently compare
            # xla against xla
            assert r_pl["closure"] == "pallas", r_pl
            runs += 1
            strip = lambda r: {k_: v for k_, v in r.items()  # noqa: E731
                               if k_ != "closure"}
            if strip(r_xla) != strip(r_pl):
                failures.append((seed, variant, n_ops, k, r_xla, r_pl))
    assert not failures, failures
    assert runs >= 2 * max(3, N_SEEDS) - 1, runs
