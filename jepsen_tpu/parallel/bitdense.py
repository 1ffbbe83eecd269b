"""Bit-packed dense linearizability engine — the fast path.

Same algorithm as parallel.dense (whole config space materialised), but
the mask axis is bit-packed: the reachable-set tensor is

    B: uint32[S, W],  W = 2^C / 32

where bit b of word w encodes mask m = w*32 + b. All closure/filter
operations become VPU-friendly bitwise algebra with *static* index
tables — no sorts, no big float intermediates, no HBM streaming
(B for an entire 84-key batch at C=15 is ~2 MB, vs ~1 GB of f32
intermediates in the unpacked engine):

  * "configs that haven't linearized slot j" = B & clear_j, where
    clear_j is an intra-word constant (j < 5) or a word-index mask
    (j >= 5) — both trace-time constants;
  * the state transition OR_{s -> t} is a tiny [S,S] bitwise select;
  * "OR into m | bit_j" is a left-shift by 2^j inside words (j < 5) or
    a static word gather (j >= 5); the return-filter is the mirror
    right-shift/gather.

This is the engine the bench rides; parallel.dense remains as the
readable unpacked reference and parallel.engine as the sparse fallback
for windows too wide to materialise (C > ~24).
"""

from __future__ import annotations

import functools

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

from jepsen_tpu import envflags
from jepsen_tpu import obs
from jepsen_tpu.obs import ledger as _ledger
from jepsen_tpu.parallel import programs
from jepsen_tpu.parallel.encode import EncodedHistory
from jepsen_tpu.parallel.steps import STEPS
from jepsen_tpu.resilience import supervisor as sup


MAX_C = 24  # 2^24 masks = 512k words per state row

U32 = jnp.uint32
# np (not jnp): a module-level jnp scalar initializes the default
# backend at import — with a wedged device runtime that turns a bare
# `import bitdense` into a hang before any device call. Engine modules
# must be import-safe; numpy constants fold into traces identically.
FULL = np.uint32(0xFFFFFFFF)


MAX_S = 128  # the closure trace unrolls over slots and states; its sel
# tensor is [C, S, S] per event and its cost O(C*S^2*W) — histories with
# many distinct values (unique-write registers) go to the sparse engine


def fits_bitdense(n_states: int, n_slots: int,
                  budget_words: int = 1 << 22) -> bool:
    if n_slots > MAX_C or n_states > MAX_S:
        return False
    W = max(1, (1 << n_slots) // 32)
    # bound both the reachable-set tensor and the per-round work
    return n_states * W <= budget_words \
        and n_slots * n_states * n_states * W <= (1 << 26)


def _intra_clear(j: int) -> np.uint32:  # jepsen-lint: disable=purity-numpy-call
    """32-bit constant with 1s at bit-positions whose mask-bit j is 0.
    np is deliberate: pure trace-time constants (see module header)."""
    out = 0
    for p in range(32):
        if (p >> j) & 1 == 0:
            out |= 1 << p
    return np.uint32(out)


def _plan(C: int):  # jepsen-lint: disable=purity-numpy-call
    """Static per-slot tables for shift/filter/select, as numpy — np is
    deliberate here: the tables fold into traces as constants and must
    not touch a (possibly wedged) device backend at build time."""
    W = max(1, (1 << C) // 32)
    widx = np.arange(W, dtype=np.int32)
    plan = []
    for j in range(C):
        if j < 5:
            plan.append({
                "intra": True,
                "clear": _intra_clear(j),     # positions with bit j clear
                "shift": np.int32(1 << j),
            })
        else:
            jb = 1 << (j - 5)
            clear_words = ((widx >> (j - 5)) & 1) == 0
            plan.append({
                "intra": False,
                # word-mask: FULL where mask-bit j clear
                "clearw": np.where(clear_words, 0xFFFFFFFF, 0).astype(np.uint32),
                # gather for OR-into-bit-j: target word i (bit set) reads i^jb
                "fwd_idx": (widx ^ jb).astype(np.int32),
                "setw": np.where(~clear_words, 0xFFFFFFFF, 0).astype(np.uint32),
            })
    return W, plan


def is_tpu_platform(platform: str) -> bool:
    """Whether a platform name (jax.default_backend(), Device.platform)
    is a TPU: the kernels compile for it, and run in interpret mode
    everywhere else."""
    return platform == "tpu"


def _resolve_closure_mode(closure_mode, use_pallas: bool = False):
    """XLA closure loop shape: "while" (converge-and-stop; extra
    device-visible `changed` reduction per iteration) or "fori" (fixed
    ceil(C/2) double-expansions; no convergence sync — the per-event
    cost on tiny tensors is suspected to be dispatch/sync latency, and
    only a hardware A/B (tools/perf_ab.py) gets to flip the default).
    Env override: JEPSEN_TPU_CLOSURE=fori. With pallas the XLA-loop
    branches are dead: the mode is pinned to "while" AFTER validation,
    so a bogus value fails on every platform and env toggles cannot
    split the compile cache."""
    if closure_mode is None:
        closure_mode = envflags.env_choice(
            "JEPSEN_TPU_CLOSURE", ("while", "fori"), default="while",
            what="closure mode")
    if closure_mode not in ("while", "fori"):
        raise ValueError(f"unknown closure mode {closure_mode!r}")
    return "while" if use_pallas else closure_mode


def _resolve_use_pallas(use_pallas, S: int, C: int, platform: str):
    """Shared gate for the single and batch paths: default ON for a
    real-TPU platform (JEPSEN_TPU_PALLAS=0 opts out; =1 forces it on
    elsewhere, in interpret mode), downgraded to False for shapes the
    kernel doesn't support. Returns (use_pallas, interpret) — interpret
    mode whenever the DATA's platform isn't a real TPU (keyed off where
    the arrays actually live, not the process default backend: a batch
    pinned to a CPU mesh must never trace a TPU kernel just because a
    TPU runtime happens to be the default).

    Default history: opt-in until a hardware measurement existed
    ("flags do not get to claim speedups"); flipped to default-on by
    the r5 on-chip tools/perf_ab.py verdict — pallas beat the XLA
    while closure on every measured shape (single-1k 18.9x,
    single-10k 54.4x, batch 84x120 1.42x) with bit-identical results
    on every run, incl. the counterexample fields."""
    if use_pallas is None:
        # strict tri-state read: only "0" opts out, only "1" forces on.
        # Anything else raises (envflags.EnvFlagError) instead of
        # silently counting as an opt-out — with the old `flag == "1"`
        # read, a stray JEPSEN_TPU_PALLAS=yes would have silently
        # reverted the measured r5 54x default.
        flag = envflags.env_bool("JEPSEN_TPU_PALLAS")
        use_pallas = flag if flag is not None \
            else is_tpu_platform(platform)
    if use_pallas:
        from jepsen_tpu.parallel import pallas_kernels as pk
        use_pallas = pk.supported(S, C)
    return use_pallas, not is_tpu_platform(platform)


def _bitdense_impl(xs, state0, step_name: str, S: int, C: int,
                   lo: int = -1, use_pallas: bool = False,
                   pallas_interpret: bool = True,
                   closure_mode: str = "while",
                   search_stats: bool = False,
                   frontier: bool = False):
    step = STEPS[step_name]
    W, plan = _plan(C)
    state_codes = jnp.arange(S, dtype=jnp.int32) + lo

    # per-event transition tables [C, S]
    step_js = jax.vmap(
        jax.vmap(step, in_axes=(0, None, None, None, None)),
        in_axes=(None, 0, 0, 0, 0),
    )

    # trace-time constants, STACKED over slots so the closure is a
    # handful of big tensor ops instead of C*(S+3) kernel launches —
    # the while_loop is dispatch-latency-bound on small [S, W] tiles.
    # np (not jnp) on this block is deliberate: the _plan tables fold
    # into the trace as constants, nothing here derives from a tracer.
    J0 = min(5, C)                    # intra-word slots (bit j < 32)
    J1 = C - J0                       # word-level slots
    # jepsen-lint: disable=purity-numpy-call
    clr5 = jnp.asarray(np.array([plan[j]["clear"] for j in range(J0)],
                                np.uint32))                    # [J0]
    # jepsen-lint: disable=purity-numpy-call
    shift5 = jnp.asarray(np.array([plan[j]["shift"] for j in range(J0)],
                                  np.uint32))                  # [J0]
    if J1:
        # jepsen-lint: disable=purity-numpy-call
        clw = jnp.asarray(np.stack([plan[j]["clearw"]
                                    for j in range(J0, C)]))   # [J1, W]
        # jepsen-lint: disable=purity-numpy-call
        fwd = jnp.asarray(np.stack([plan[j]["fwd_idx"]
                                    for j in range(J0, C)]))   # [J1, W]
        # jepsen-lint: disable=purity-numpy-call
        setw = jnp.asarray(np.stack([plan[j]["setw"]
                                     for j in range(J0, C)]))  # [J1, W]

    def _or_over(x, axis):
        return lax.reduce(x, U32(0), lax.bitwise_or, (axis,))

    def compute_sel(ev):
        nxt, okj = step_js(state_codes, ev["slot_f"], ev["slot_a0"],
                           ev["slot_a1"], ev["slot_wild"])
        legal = okj & ev["slot_occ"][:, None]                  # [C, S]
        # sel[j, s, t] = FULL if legal[j,s] and nxt[j,s]==t
        t_idx = jnp.arange(S)
        return jnp.where(
            legal[:, :, None] & ((nxt - lo)[:, :, None] == t_idx[None, None, :]),
            FULL, U32(0))                                      # [C, S, S]

    def make_expand(sel):
        def expand(B):
            # intra-word slots: ext[j,s,w] = B & clr5[j]; G[j,t,w] =
            # OR_s ext & sel; contribution = (G & clr5) << (1 << j)
            ext5 = B[None, :, :] & clr5[:, None, None]         # [J0, S, W]
            g5 = _or_over(ext5[:, :, None, :] & sel[:J0, :, :, None], 1)
            c5 = _or_over((g5 & clr5[:, None, None])
                          << shift5[:, None, None], 0)         # [S, W]
            out = B | c5
            if J1:
                # word-level slots: same algebra with word masks/gathers
                extw = B[None, :, :] & clw[:, None, :]         # [J1, S, W]
                gw = _or_over(extw[:, :, None, :] & sel[J0:, :, :, None], 1)
                moved = jnp.take_along_axis(
                    gw, jnp.broadcast_to(fwd[:, None, :], gw.shape), axis=2)
                out = out | _or_over(moved & setw[:, None, :], 0)
            return out
        return expand

    def make_closure_body(sel):
        expand = make_expand(sel)

        def body(c):
            B, _ = c
            # Two expansions per while iteration: the loop is latency-
            # bound by the `changed` reduction + condition sync, not by
            # the bitwise algebra, so halving the iteration count wins
            # ~1.5x even when the second expansion is sometimes a no-op
            # (measured on v5e: 8.9k -> 12.9k ops/s on the bench batch).
            B2 = expand(expand(B))
            return B2, jnp.any(B2 != B)
        return body

    def closure_cond(c):
        return c[1]

    # filter tables: per possible returning slot, applied via lax.switch
    # (np builds static index tables — trace-time constants only)
    def filter_at(s: int, B):  # jepsen-lint: disable=purity-numpy-call
        if s < 5:
            clear = U32(_intra_clear(s))
            return (B >> (1 << s)) & clear
        jb = 1 << (s - 5)
        widx = np.arange(max(1, (1 << C) // 32), dtype=np.int32)
        idx = jnp.asarray((widx | jb).astype(np.int32))
        clearw = jnp.asarray(
            np.where(((widx >> (s - 5)) & 1) == 0, 0xFFFFFFFF, 0)
            .astype(np.uint32))
        return jnp.take(B, idx, axis=1) & clearw[None, :]

    filter_branches = [functools.partial(filter_at, s) for s in range(C)]

    def scan_step(carry, ev):
        B, ok, fail_r, r_idx = carry
        run = ok & (ev["ev_slot"] >= 0)
        sel = compute_sel(ev)
        iters = jnp.int32(-1)   # unknown unless a counted loop ran
        if use_pallas:
            # the entire fixpoint runs inside one VMEM-resident pallas
            # kernel (parallel.pallas_kernels); skipped on pad events.
            # Its iteration count never leaves the kernel — the stats
            # block reports closure-iters -1 (unknown) on this path.
            from jepsen_tpu.parallel import pallas_kernels as pk
            B2 = lax.cond(
                run,
                lambda b: pk.closure_call(sel, b, C,
                                          interpret=pallas_interpret),
                lambda b: b, B)
        elif closure_mode == "fori":
            # fixed trip count, no convergence check: the fixpoint is
            # reached in <= C single expansions (each round adds every
            # one-step extension; chains are at most C slots long), so
            # ceil(C/2) double-expansion bodies always suffice. Trades
            # wasted post-convergence expansions for the removal of the
            # per-iteration `changed` reduction + cond sync. Pad events
            # need no guard: their sel is all-zero, expand is identity.
            expand = make_expand(sel)
            B2 = lax.fori_loop(0, (C + 1) // 2,
                               lambda _, b: expand(expand(b)), B)
            iters = jnp.int32(2 * ((C + 1) // 2))
        elif search_stats:
            # counted variant of the while closure: same fixpoint,
            # plus the double-expansion count (x2 = expansions) the
            # stats block reports
            body = make_closure_body(sel)

            def body_n(c):
                B2, changed = body((c[0], c[1]))
                return B2, changed, c[2] + 1

            B2, _, n = lax.while_loop(lambda c: c[1], body_n,
                                      (B, run, jnp.int32(0)))
            iters = 2 * n
        else:
            B2, _ = lax.while_loop(closure_cond, make_closure_body(sel),
                                   (B, run))
        s = jnp.clip(ev["ev_slot"], 0, C - 1)
        B3 = lax.switch(s, filter_branches, B2)
        alive = jnp.any(B3 != 0)
        failed_here = run & ~alive
        B_o = jnp.where(run, B3, B)
        ok_o = jnp.where(run, ~failed_here, ok)
        fail_o = jnp.where(failed_here & (fail_r < 0), r_idx, fail_r)
        carry_o = (B_o, ok_o, fail_o, r_idx + 1)
        if not search_stats:
            return carry_o, jnp.uint8(0)
        # frontier width = popcount of the post-filter reachable-set
        # tensor — the dense engine's exact live-config count
        width = jnp.sum(lax.population_count(B3)).astype(jnp.int32)
        return carry_o, {
            "width": jnp.where(run, width, -1).astype(jnp.int32),
            "iters": jnp.where(run, iters, 0).astype(jnp.int32),
        }

    B0 = jnp.zeros((S, W), U32).at[state0 - lo, 0].set(U32(1))
    carry0 = (B0, jnp.array(True), jnp.int32(-1), jnp.int32(0))
    (B, ok, fail_r, _), ys = lax.scan(scan_step, carry0, xs)
    if frontier:
        return B
    valid = ok & jnp.any(B != 0)
    if search_stats:
        return valid, fail_r, ys
    return valid, fail_r


# donation decision (recompile-donate-argnums), DECIDED: nothing
# donatable — donate_argnums=() records it. The xs event tables are
# the only frontier-scale inputs and callers reuse them across
# env/closure-mode variants (tools/perf_ab.py runs the same xs through
# while/fori/pallas back to back); the B tensor is built in-trace, so
# there is no caller buffer to reclaim, and every output is a scalar
# no event table could alias.
_check_bitdense = jax.jit(_bitdense_impl,
                          donate_argnums=(),
                          static_argnames=("step_name", "S", "C", "lo",
                                           "use_pallas",
                                           "pallas_interpret",
                                           "closure_mode",
                                           "search_stats"))


# same (decided) donation as _check_bitdense above
@functools.partial(jax.jit,
                   donate_argnums=(),
                   static_argnames=("step_name", "S", "C", "lo",
                                    "use_pallas", "pallas_interpret",
                                    "closure_mode", "search_stats"))
def _check_bitdense_batch(xs, state0, step_name: str, S: int, C: int,
                          lo: int = -1, use_pallas: bool = False,
                          pallas_interpret: bool = True,
                          closure_mode: str = "while",
                          search_stats: bool = False):
    # under vmap the per-event lax.cond around the pallas closure
    # becomes run-both-and-select, so pad events cost one extra kernel
    # run per key — harmless: their result is discarded by the select
    return jax.vmap(
        lambda x, s0: _bitdense_impl(x, s0, step_name, S, C, lo,
                                     use_pallas=use_pallas,
                                     pallas_interpret=pallas_interpret,
                                     closure_mode=closure_mode,
                                     search_stats=search_stats)
    )(xs, state0)


@functools.lru_cache(maxsize=None)
def _sharded_batch_fn(mesh, K: int, step_name: str, S: int, C: int,
                      lo: int, use_pallas: bool, pallas_interpret: bool,
                      closure_mode: str, search_stats: bool):
    """The batch check over a mesh as one program per device: the key
    axis is split over the mesh's first axis as encode.place_batch
    placed it (sharded when K divides evenly, else every device checks
    every key), and each device runs the per-key search on its own
    keys. check_vma=False: pallas_call's output carries no
    varying-manual-axes annotation."""
    from jax.sharding import PartitionSpec as P
    ax = mesh.axis_names[0]
    spec = P(ax) if K % mesh.shape[ax] == 0 else P()

    def per_device(xs, state0):
        return jax.vmap(
            lambda x, s0: _bitdense_impl(x, s0, step_name, S, C, lo,
                                         use_pallas=use_pallas,
                                         pallas_interpret=pallas_interpret,
                                         closure_mode=closure_mode,
                                         search_stats=search_stats)
        )(xs, state0)

    # one wrapper per (mesh, statics): the lru_cache above memoizes it.
    # Donation: the same (decided) nothing-donatable as
    # _check_bitdense_batch — every output is a per-key scalar
    # jepsen-lint: disable=recompile-closure-capture
    return jax.jit(jax.shard_map(per_device, mesh=mesh,
                                 in_specs=(spec, spec), out_specs=spec,
                                 check_vma=False),
                   donate_argnums=())


# same (decided) donation as _check_bitdense above
@functools.partial(jax.jit,
                   donate_argnums=(),
                   static_argnames=("step_name", "S", "C", "lo",
                                    "use_pallas", "pallas_interpret"))
def _check_bitdense_frontier(xs, state0, step_name: str, S: int, C: int,
                             lo: int, use_pallas: bool,
                             pallas_interpret: bool):
    """The reachable-set tensor B [S, W] after the scan of `xs`."""
    return _bitdense_impl(xs, state0, step_name, S, C, lo,
                          use_pallas=use_pallas,
                          pallas_interpret=pallas_interpret,
                          frontier=True)


def frontier_rows(e: EncodedHistory, n_events: int) -> list:
    """The complete set of configurations live after return events
    [0, n_events), as (state code, mask_lo, mask_hi) rows — what
    counterexample extraction seeds its host window search from. One
    bitdense scan of the prefix: the dense tensor is the whole visited
    set, so there is no capacity to escalate."""
    from jepsen_tpu.parallel.dense import _xs_dense
    S = n_states(e)
    C = max(5, e.n_slots)
    up, interpret = _resolve_use_pallas(None, S, C, jax.default_backend())
    xs = {k: v[:n_events] for k, v in _xs_dense(e, C).items()}
    B = sup.dispatch(
        "search",
        lambda: np.asarray(_check_bitdense_frontier(
            xs, jnp.int32(e.state0), e.step_name, S, C, e.state_lo, up,
            interpret)))
    bits = np.unpackbits(B.view(np.uint8), axis=1, bitorder="little")
    s_idx, m_idx = np.nonzero(bits[:, :1 << C])
    return [(int(s) + e.state_lo, int(m) & 0xFFFFFFFF, int(m) >> 32)
            for s, m in zip(s_idx, m_idx)]


def n_states(e: EncodedHistory) -> int:
    return e.n_states


def _stats_block_bitdense(ys, S: int, C: int,
                          extra: dict = None) -> dict:
    """The bitdense arm of the JEPSEN_TPU_SEARCH_STATS block: the
    reachable-set tensor IS a complete visited set, so the trajectory
    is the per-event popcount (exact live-config count) and occupancy
    is measured against the S * 2^C config space. Hash-table fields
    stay None — there is no table on this engine, and the uniform
    schema keeps the sinks' consumers simple."""
    w = np.asarray(ys["width"]).reshape(-1)
    real = w >= 0
    widths = [int(x) for x in w[real]]
    iters = [int(x) for x in np.asarray(ys["iters"]).reshape(-1)[real]]
    peak = max(widths, default=0)
    space = S * (1 << C)
    block = {
        "engine": "bitdense",
        "events": len(widths),
        "frontier-width": widths,
        "closure-iters": iters,
        "frontier-peak": peak,
        "config-space": space,
        "peak-occupancy": round(peak / space, 9) if space else None,
        "dedupe": "dense",
        "delta-split-ratio": None,
        "table-capacity": None,
        "load-factor-peak": None,
        "probe-hist": None,
    }
    if extra:
        block.update(extra)
    return block


def check_encoded_bitdense(e: EncodedHistory,
                           use_pallas: bool = None,
                           closure_mode: str = None,
                           timings: dict = None,
                           search_stats: bool = None) -> dict:
    """Single-key bit-packed check. `use_pallas` routes the closure
    through the VMEM-resident pallas kernel (parallel.pallas_kernels);
    default: ON for a real-TPU platform (r5 on-chip A/B verdict;
    JEPSEN_TPU_PALLAS=0/1 overrides), and only for shapes the kernel
    supports (the same default governs the batch path).
    `closure_mode` picks the XLA loop shape ("while"/"fori", see
    _resolve_closure_mode); ignored when pallas runs.

    `timings`, when a dict, receives a `transfer_secs`/`device_secs`
    split (bench's per-section JSONL keys): the event tables are then
    explicitly placed and BLOCKED on before the search is issued, so
    the two numbers are a clean H2D / search separation — at the cost
    of serializing transfer against compute, which is why the default
    (timings=None) path is untouched."""
    if e.n_returns == 0:
        return {"valid?": True, "engine": "bitdense"}
    from time import perf_counter

    from jepsen_tpu.parallel import engine as eng_mod
    from jepsen_tpu.parallel.dense import _xs_dense
    S = n_states(e)
    C = max(5, e.n_slots)  # at least one full word
    platform = jax.default_backend()
    use_pallas, interpret = _resolve_use_pallas(
        use_pallas, S, C, platform)
    closure_mode = _resolve_closure_mode(closure_mode, use_pallas)
    ss = eng_mod._resolve_search_stats(search_stats)
    with obs.span("transfer.place", keys=1):
        xs = _xs_dense(e, C)
    if timings is not None:
        t0 = perf_counter()
        xs = {k: jnp.asarray(v) for k, v in xs.items()}
        jax.block_until_ready(xs)
        timings["transfer_secs"] = perf_counter() - t0
        t0 = perf_counter()
    ts0 = perf_counter()
    # bitdense programs are not AOT-managed (the pallas closure path);
    # the registry still counts their shape tuples so the fleet-wide
    # program population perf_ab records covers every engine
    programs.track("bitdense.check", xs,
                   (e.step_name, S, C, e.state_lo, use_pallas,
                    interpret, closure_mode, ss))
    with obs.span("bitdense.check", S=S, C=C):
        def _search():
            with obs.span("bitdense.issue", K=1, S=S, C=C):
                out = _check_bitdense(xs, jnp.int32(e.state0),
                                      e.step_name, S, C,
                                      e.state_lo, use_pallas,
                                      interpret, closure_mode, ss)
            # the device wait ends here, inside the supervised window
            # (async failures and hangs surface in it); what follows is
            # the copy of the results to the host
            jax.block_until_ready(out)
            with obs.span("bitdense.fetch"):
                if ss:
                    valid, fail_r, ys = out
                    return (bool(valid), int(fail_r),
                            jax.tree.map(np.asarray, ys))
                valid, fail_r = out
                return bool(valid), int(fail_r)

        res = sup.dispatch("dispatch", _search, backend=platform)
        valid_b, fail_r = res[0], res[1]
    if timings is not None:
        timings["device_secs"] = perf_counter() - t0
    with obs.span("bitdense.results"):
        out = {"valid?": valid_b, "engine": "bitdense",
               "states": S, "slots": C,
               # the dense reachable-set tensor IS a complete visited
               # set — the sparse sort/hash strategies
               # (JEPSEN_TPU_DEDUPE) have nothing to select here; the
               # tag keeps result schemas uniform across engines
               "dedupe": "dense",
               "closure": "pallas" if use_pallas
               else f"xla-{closure_mode}"}
        if ss:
            out["stats"] = eng_mod.finish_stats_block(
                _stats_block_bitdense(res[2], S, C), ts0, perf_counter())
        if not out["valid?"]:
            from jepsen_tpu.parallel.encode import fail_op_fields
            out.update(fail_op_fields(e, fail_r))
    return out


def _normalize_cost(ca) -> dict:
    # a backend may not implement the cost model and return None; the
    # prior is advisory, so report that rather than raising
    if ca is None:
        return {"unavailable": "cost_analysis returned None "
                               "(backend does not implement it)"}
    return {"flops": float(ca.get("flops", 0.0)),
            "bytes_accessed": float(ca.get("bytes accessed", 0.0))}


def cost_analysis_encoded(e: EncodedHistory,
                          use_pallas: bool = None,
                          closure_mode: str = "while") -> dict:
    """Hardware-independent analytical prior: flops / bytes accessed
    from XLA's cost model over the LOWERED (traced, uncompiled) HLO of
    a check of `e` under the given closure variant. No device
    execution — usable on CPU to rank while/fori/pallas before any
    chip measurement exists (tools/perf_ab.py emits this as each
    shape's cost prior and cross-checks it once measured).

    CAVEATS the callers must carry: (1) XLA's HLO cost model counts
    every loop BODY once — trip counts are data-dependent — so these
    numbers are per-iteration work (they rank closure VARIANTS, whose
    bodies differ), not end-to-end totals; model totals by multiplying
    with the known static trip counts (n_returns scan steps, exactly
    ceil(C/2) closure trips for fori). (2) The pallas row is NOT
    backend-independent: off-TPU the interpret-mode EMULATION is
    costed, on TPU the kernel body is a custom call the cost model
    cannot see — the "program" field says which program the numbers
    describe, and cross-backend pallas comparisons are invalid."""
    from jepsen_tpu.parallel.dense import _xs_dense
    S = n_states(e)
    C = max(5, e.n_slots)
    use_pallas, interpret, mode = _resolve_cost_variant(
        use_pallas, S, C, closure_mode)
    lowered = _check_bitdense.lower(
        _xs_dense(e, C), jnp.int32(e.state0), e.step_name, S, C,
        e.state_lo, use_pallas, interpret, mode)
    return _annotate_cost(lowered.cost_analysis(), use_pallas,
                          interpret, mode)


def cost_analysis_batch(encs, use_pallas: bool = None,
                        closure_mode: str = "while") -> dict:
    """Batch-path analogue of cost_analysis_encoded (same padded
    program check_batch_bitdense would run, meshless)."""
    from jepsen_tpu.parallel.encode import pad_batch
    xs, state0, S, C, _ = pad_batch(encs, min_slots=5)
    use_pallas, interpret, mode = _resolve_cost_variant(
        use_pallas, S, C, closure_mode)
    lowered = _check_bitdense_batch.lower(
        xs, state0, encs[0].step_name, S, C, encs[0].state_lo,
        use_pallas, interpret, mode)
    return _annotate_cost(lowered.cost_analysis(), use_pallas,
                          interpret, mode)


def _resolve_cost_variant(use_pallas, S, C, closure_mode):
    """The same gates the execution paths use (no bare kernel asserts
    on unsupported shapes — an explicit use_pallas=True downgrades
    exactly like check_encoded_bitdense would)."""
    use_pallas, interpret = _resolve_use_pallas(
        use_pallas, S, C, jax.default_backend())
    return use_pallas, interpret, _resolve_closure_mode(closure_mode,
                                                        use_pallas)


def _annotate_cost(ca, use_pallas, interpret, mode) -> dict:
    out = _normalize_cost(ca)
    out["program"] = (("pallas-interpret-emulation" if interpret
                       else "pallas-kernel-custom-call "
                            "(body uncounted by the HLO cost model)")
                      if use_pallas else f"xla-{mode}")
    return out


class PendingBitdenseBatch:
    """A batched bitdense check that has been ISSUED but not consumed.

    JAX dispatch is async: construction pads + places the batch
    (`transfer_secs` records that host-side cost) and enqueues the
    device program, returning while it runs; `finalize()` blocks on
    the results and builds the per-key dicts (`device_wait_secs`
    records the blocked wait). The pipelined executor
    (parallel.pipeline) leans on this split to overlap the next
    chunk's host encode with this chunk's device search;
    check_batch_bitdense() is dispatch + finalize back to back."""

    def __init__(self, encs, xs, state0, S, C, up, interpret, mode,
                 mesh, transfer_secs, platform=None, R=None,
                 search_stats: bool = False):
        self.encs = encs
        self.xs = xs
        self.state0 = state0
        self.S = S
        self.C = C
        self.R = R if R is not None else max(e.n_returns for e in encs)
        self.up = up
        self.interpret = interpret
        self.mode = mode
        self.mesh = mesh
        self.transfer_secs = transfer_secs
        self.platform = platform
        self.search_stats = bool(search_stats)
        self.device_wait_secs = None
        self._results = None
        self._ys = None
        self._t_issue = None
        self._issue()

    def _issue(self):
        from time import perf_counter
        self._t_issue = perf_counter()
        # population tracking only — the batch closure program is not
        # AOT-managed (see the single-key site)
        statics = dict(step_name=self.encs[0].step_name, S=self.S,
                       C=self.C, lo=self.encs[0].state_lo,
                       use_pallas=self.up, pallas_interpret=self.interpret,
                       closure_mode=self.mode,
                       search_stats=self.search_stats)
        programs.track("bitdense.check_batch", self.xs,
                       tuple(statics.values()))
        fn = (functools.partial(_check_bitdense_batch, **statics)
              if self.mesh is None
              else _sharded_batch_fn(self.mesh, len(self.encs), **statics))
        with obs.span("bitdense.issue", K=len(self.encs), S=self.S,
                      C=self.C):
            # supervised (resilience.supervisor): faults inject here,
            # the breaker records the outcome; the program is ISSUED
            # inside the window, the async wait is finalize()'s own
            # supervised window
            out = sup.dispatch("dispatch",
                               lambda: fn(self.xs, self.state0),
                               backend=self.platform)
        if self.search_stats:
            self._valid, self._fail_r, self._ys = out
        else:
            self._valid, self._fail_r = out

    def finalize(self) -> list:
        if self._results is not None:
            return self._results
        # same single-measurement-site contract as dispatch: the
        # bitdense.finalize span IS the device_wait_secs clock reads
        with obs.timer("bitdense.finalize", keys=len(self.encs)) as tm:
            # materialize inside a supervised window: this wait is
            # where a wedged runtime actually hangs, and async dispatch
            # surfaces runtime failures here, not at the issue
            valid, fail_r = sup.dispatch("dispatch", self._fetch,
                                         backend=self.platform)
        self.device_wait_secs = tm.wall
        with obs.span("bitdense.results", keys=len(self.encs)):
            self._results = self._build(valid, fail_r)
        return self._results

    def _fetch(self):
        # the wait ends at block_until_ready; the span is the copy of
        # the results to the host that follows
        jax.block_until_ready((self._valid, self._fail_r))
        with obs.span("bitdense.fetch"):
            return np.asarray(self._valid), np.asarray(self._fail_r)

    def _build(self, valid, fail_r) -> list:
        """The per-key result dicts, their stats blocks and the ledger
        record, from the fetched verdicts."""
        closure = "pallas" if self.up else f"xla-{self.mode}"
        ys = None
        if self.search_stats and self._ys is not None:
            import jax as _jax
            ys = _jax.tree.map(np.asarray, self._ys)
        out = []
        from time import perf_counter
        t1 = perf_counter()
        for k, e in enumerate(self.encs):
            r = {"valid?": bool(valid[k]), "engine": "bitdense",
                 "dedupe": "dense",  # complete visited set by
                 "closure": closure}  # construction (see check_encoded)
            if ys is not None:
                from jepsen_tpu.parallel import engine as eng_mod
                waste = 1.0 - ((e.n_returns * max(5, e.n_slots))
                               / max(1, self.R * self.C))
                block = _stats_block_bitdense(
                    {"width": ys["width"][k], "iters": ys["iters"][k]},
                    self.S, self.C,
                    extra={"pad-waste": round(waste, 6),
                           "pad-events": int(self.R - e.n_returns),
                           "pad-slots": int(self.C - max(5, e.n_slots))})
                r["stats"] = eng_mod.finish_stats_block(
                    block, self._t_issue, t1, key=k)
            if not r["valid?"]:
                from jepsen_tpu.parallel.encode import fail_op_fields
                r.update(fail_op_fields(e, int(fail_r[k])))
            out.append(r)
        led = _ledger.active()
        if led is not None:
            # decision-ledger evidence: one record per bitdense batch
            # dispatch — issue-to-materialize wall from the same reads
            # the stats blocks use ("N" is S, the dense table rows)
            n_valid = sum(1 for r in out if r["valid?"])
            led.record(
                "dispatch", engine="bitdense",
                shape={"family": self.encs[0].step_name,
                       "N": int(self.S), "R": int(self.R),
                       "C": int(self.C), "tier": 0, "pack": False},
                strategy={"dedupe": "dense", "closure": closure},
                secs=round(t1 - self._t_issue, 6),
                keys=len(self.encs),
                stats=_ledger.stats_digest(
                    [r["stats"] for r in out if r.get("stats")]),
                outcome={"valid": n_valid,
                         "invalid": len(out) - n_valid,
                         "overflow": 0})
        return out


def dispatch_batch_bitdense(encs, mesh=None, use_pallas: bool = None,
                            closure_mode: str = None,
                            min_states: int = 0,
                            min_slots: int = 5,
                            min_returns: int = 0,
                            search_stats: bool = None
                            ) -> PendingBitdenseBatch:
    """Pad, place, and ISSUE a batched per-key check without consuming
    the results — returns a PendingBitdenseBatch whose finalize()
    blocks and builds the per-key dicts.
    `min_states`/`min_slots`/`min_returns` floor the padded dims so a
    CHUNK of a larger bucket compiles and resolves (pallas gating
    included) at the bucket's (S, C, R) — without the R floor every
    chunk's local max n_returns would be its own compile."""
    from jepsen_tpu.parallel.encode import pad_batch
    obs.counter("bitdense.dispatches").inc()
    # gate on where the batch actually lives: pad_batch pins it to the
    # mesh when one is given, regardless of the process default backend
    platform = (mesh.devices.flat[0].platform if mesh is not None
                else jax.default_backend())
    # obs.timer: one clock-read pair serves both the recorded span and
    # the transfer_secs the stats/bench lines report — they cannot
    # disagree (the same contract bench.py rides). The placement runs
    # through the supervised seam (site "transfer"): H2D against a
    # wedged runtime hangs exactly like a dispatch does.
    with obs.timer("bitdense.pad_place", keys=len(encs)) as tm:
        xs, state0, S, C, R = sup.dispatch(
            "transfer",
            lambda: pad_batch(encs, mesh=mesh, min_slots=min_slots,
                              min_states=min_states,
                              min_returns=min_returns),
            backend=platform)
    transfer_secs = tm.wall
    # Mesh-sharded batches follow the same default as the rest
    # (_resolve_use_pallas: ON for a TPU platform). The compiler cannot
    # partition a Mosaic kernel on its own, so a mesh batch runs as a
    # shard_map: each device checks its own key shard
    # (_sharded_batch_fn).
    up, interpret = _resolve_use_pallas(use_pallas, S, C, platform)
    mode = _resolve_closure_mode(closure_mode, up)
    from jepsen_tpu.parallel import engine as eng_mod
    ss = eng_mod._resolve_search_stats(search_stats)
    return PendingBitdenseBatch(encs, xs, state0, S, C, up, interpret,
                                mode, mesh, transfer_secs,
                                platform=platform, R=R, search_stats=ss)


def check_batch_bitdense(encs, mesh=None, use_pallas: bool = None,
                         closure_mode: str = None,
                         search_stats: bool = None) -> list:
    """Batched per-key check. Callers must ensure the COMBINED padded
    dims fit (fits_bitdense(max S, max C)) — individually-fitting keys
    can combine into an over-budget program; engine.check_batch does
    this check and falls back to per-key dispatch otherwise.
    `use_pallas` routes each key's closure through the VMEM-resident
    kernel (vmapped over keys); default: ON for a real-TPU platform
    (r5 on-chip A/B; JEPSEN_TPU_PALLAS=0/1 overrides), gated to shapes
    the kernel supports at the PADDED dims.
    `closure_mode` picks the XLA loop shape ("while"/"fori")."""
    if not encs:
        return []
    return dispatch_batch_bitdense(encs, mesh=mesh, use_pallas=use_pallas,
                                   closure_mode=closure_mode,
                                   search_stats=search_stats).finalize()
