"""Frontier-sharded linearizability search over a device mesh.

`engine.check_batch` parallelises over *keys* (data parallel). This
module parallelises over the *frontier* of a single giant key — the
capability CPU knossos fundamentally lacks (SURVEY.md §5.7: "shard the
search frontier, not the sequence"):

  * each of the D devices on the mesh owns N/D configuration rows;
  * the closure expands locally (vmap over local configs × slots);
  * dedupe is global: every config is **owned** by the device
    `hash(config) % D`. A config can therefore exist on exactly one
    device — the union of per-device frontiers is the exact global
    config set. This is the "device-sharded hash set deduped over the
    ICI mesh" of BASELINE.json, realised with XLA collectives instead
    of NCCL;
  * candidates travel by **owner-routed segmented all-to-all**: each
    device sorts its legal candidates by owner, packs them into D
    equal buckets of width B ≈ 2×(local candidates)/D (hash-uniform,
    overflow psum-checked), and one `lax.all_to_all` delivers every
    bucket to its owner. Per-device traffic is O(2·global/D) per round,
    vs O(global) for the naive full all-gather — a D/2× reduction that
    grows with mesh size (SURVEY.md §7.1 step 4's work exchange;
    `exchange="gather"` keeps the broadcast path for A/B measurement);
  * liveness / convergence / overflow decisions ride `psum`s.

The whole event scan runs inside one `shard_map` region: slot tables are
replicated, frontier arrays stay device-local, and the only cross-device
traffic is the closure's exchange + psums.

**Multi-slice (DCN):** give `check_encoded_sharded` a mesh whose device
array is 2-D — axis 0 = slices (DCN between them), axis 1 = chips
within a slice (ICI) — and the owner routing goes HIERARCHICAL: stage 1
delivers candidates to the owner's chip column over ICI, stage 2
crosses slices with rows pre-aggregated into ONE bucket per destination
slice. Every row still crosses DCN exactly once, but as n_slice large
messages per device per round instead of n_slice*n_chip small ones —
DCN latency punishes message count, not bytes. CI exercises this on
2x4 and 4x2 CPU meshes; psums ride both axes.
"""

from __future__ import annotations

import functools
import logging

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from jepsen_tpu import obs
from jepsen_tpu.obs import ledger as _ledger
from jepsen_tpu.parallel import programs
from jepsen_tpu.parallel.encode import EncodedHistory
from jepsen_tpu.parallel.engine import (N_PROBE_BUCKETS, _empty_table,
                                        _hash_insert_append, _next_pow2,
                                        _rep, _resolve_config_pack,
                                        _resolve_dedupe,
                                        _resolve_probe_limit,
                                        _resolve_reshard,
                                        _resolve_search_stats,
                                        _rows_concat, _rows_prev_same,
                                        _rows_take, _rows_where,
                                        _tag_config_pack,
                                        _tag_sparse_closure,
                                        _xs_from_encoded, pack_lanes,
                                        pack_rows_np, pack_spec_for)
from jepsen_tpu.parallel.steps import STEPS
from jepsen_tpu.parallel.meshplan import (AXIS, AX_CHIP, AX_SLICE,
                                          MeshPlan)
from jepsen_tpu.resilience import supervisor as sup

_log = logging.getLogger(__name__)


def _owned_dedupe_compact(rows, live, Nd, n_dev, my_idx, rep):
    """Keep rows owned by this device, sort-dedupe, compact to [Nd].
    Lane-generic: rows is the representation's lane tuple (the
    historical triple or the packed word) — ownership hashes, sort
    keys, and scatters all run per lane."""
    owner = rep.owner_hash(rows) % jnp.uint32(n_dev)
    live = live & (owner == my_idx)
    M = rows[0].shape[0]
    order = jnp.lexsort((*reversed(rows), (~live).astype(jnp.int8)))
    rows_s = _rows_take(rows, order)
    live_s = live[order]
    uniq = live_s & ~_rows_prev_same(rows_s)
    count = jnp.sum(uniq)
    pos = jnp.where(uniq, jnp.cumsum(uniq) - 1, M + Nd)
    new_rows = tuple(z.at[pos].set(r, mode="drop")
                     for z, r in zip(rep.zeros(Nd), rows_s))
    new_live = jnp.arange(Nd) < count
    return new_rows, new_live, count, count > Nd


def _route_stage(rows, live, dest, n_dest: int, B: int, axis: str):
    """One segmented all-to-all stage (runs INSIDE shard_map): deliver
    each live row to position `dest` along the mesh axis `axis`.

    Rows are sorted by destination (dead rows sink past bucket
    n_dest-1), each destination's bucket is padded/truncated to the
    static width B, and `lax.all_to_all(tiled)` swaps bucket d to
    device d. Returns the received rows [n_dest*B] plus a local
    overflow flag (some bucket exceeded B — the caller escalates to a
    capacity retry). Lane-generic: under JEPSEN_TPU_CONFIG_PACK the
    exchange payload is the packed word — 1-2 lanes over the
    ICI/DCN wire instead of 3, a proportional traffic cut."""
    L = rows[0].shape[0]
    key = jnp.where(live, dest.astype(jnp.int32), n_dest)
    order = jnp.argsort(key)
    rows_s = _rows_take(rows, order)
    key_s = key[order]
    starts = jnp.searchsorted(key_s, jnp.arange(n_dest))
    rank = jnp.arange(L) - starts[jnp.clip(key_s, 0, n_dest - 1)]
    in_bucket = (key_s < n_dest) & (rank < B)
    ovf = jnp.any((key_s < n_dest) & (rank >= B))
    pos = jnp.where(in_bucket, key_s * B + rank, n_dest * B)  # OOB -> drop
    bufs = tuple(
        jnp.zeros(n_dest * B, r.dtype).at[pos].set(r, mode="drop")
        for r in rows_s)
    buf_lv = jnp.zeros(n_dest * B, jnp.uint8).at[pos].set(
        in_bucket.astype(jnp.uint8), mode="drop")
    a2a = lambda a: lax.all_to_all(a, axis, split_axis=0, concat_axis=0,
                                   tiled=True)
    return (tuple(a2a(b) for b in bufs), a2a(buf_lv).astype(bool), ovf)


def _route_to_owners(rows, legal, n_dev: int, B: int, rep):
    """Flat owner routing over the 1-D mesh: one stage, dest =
    hash(row) % n_dev."""
    owner = rep.owner_hash(rows) % jnp.uint32(n_dev)
    return _route_stage(rows, legal, owner, n_dev, B, AXIS)


def _sharded_scan(xs, carry0, step_name: str, Nd: int, n_dev: int,
                  my_idx, axes, route_cand, route_front,
                  dedupe: str = "sort", probe_limit: int = 0,
                  sparse_pallas: str = "off",
                  search_stats: bool = False, pack: tuple = ()):
    """The topology-independent event scan (runs INSIDE shard_map),
    from an explicit initial carry — shared by the fresh-start core and
    the resumable chunk runner.

    `axes` names the mesh axes reductions ride; `route_cand(st, ml, mh,
    live)` / `route_front(...)` deliver candidate / surviving rows to
    their hash-owner devices (returning an overflow flag) — the ONLY
    things that differ between the flat 1-D mesh, the all-gather A/B
    path, and the hierarchical multi-slice topology.

    dedupe="hash" replaces the per-iteration sort-dedupe with the
    delta-frontier closure over per-device open-addressed visited sets
    (engine._hash_insert): each device's table holds exactly the
    configs it owns, so the union of tables IS the device-sharded hash
    set of BASELINE.json, and the owner-routed all-to-all feeds
    inserts directly. Only the rows discovered last iteration expand;
    membership is cumulative across the closure iterations of one
    return event. The per-event post-filter re-route (ownership moves
    when the slot bit clears) keeps the sort-based compact — it runs
    once per event, not once per closure iteration.

    `sparse_pallas` ("off"/"on"/"interpret") fuses each iteration's
    visited-set transaction — probe, scatter-min claim, loser
    re-check, fresh-row append — into one pallas_call per device
    (sparse_kernels.hash_insert_call), keeping the received candidate
    buffer, the owned table, and the frontier tile VMEM-resident for
    the whole claim loop. The expansion and the owner routing stay in
    XLA: the all-to-all collective cannot live inside a kernel. A
    call-site whose (statically known) buffer shape exceeds the VMEM
    gate downgrades itself to the plain XLA insert.

    `pack` (static) selects the configuration-row layout
    (engine._rep): lane-generic throughout, so under
    JEPSEN_TPU_CONFIG_PACK the per-device tables, the frontier
    shards, AND the owner-routed all-to-all payloads all carry the
    packed word — 1-2 u32 lanes over the wire instead of 3."""
    step = STEPS[step_name]
    C = xs["slot_f"].shape[1]
    rep = _rep(pack, C)
    L = rep.lanes
    if probe_limit <= 0:
        # host entry points resolve eagerly (the value keys the jit
        # cache); this is the safety net for default-arg callers
        probe_limit = _resolve_probe_limit(0)
    Td = _next_pow2(2 * Nd)

    def insert_append(c_rows, c_live, f_rows, count, table):
        """One visited-set transaction — fused kernel when enabled and
        the static shapes fit, the plain XLA form otherwise. Under
        `search_stats` an extra trailing element: the probe-length
        histogram (zeros on the fused-kernel path — the probe offsets
        never leave the kernel; the stats block notes which
        implementation ran via the result's closure tag)."""
        if sparse_pallas in ("on", "interpret"):
            from jepsen_tpu.parallel import sparse_kernels as sk
            if sk.insert_supported(int(c_rows[0].shape[0]), Nd, L):
                out = sk.hash_insert_call(
                    c_rows, c_live, f_rows, count, table,
                    probe_limit, Nd, C, pack,
                    interpret=(sparse_pallas == "interpret"))
                if search_stats:
                    return out + (jnp.zeros(N_PROBE_BUCKETS,
                                            jnp.int32),)
                return out
        return _hash_insert_append(c_rows, c_live, f_rows, count,
                                   table, probe_limit, Nd, rep,
                                   stats=search_stats)

    step_cc = jax.vmap(
        jax.vmap(step, in_axes=(None, 0, 0, 0, 0)),
        in_axes=(0, None, None, None, None),
    )

    def closure_cond(c):
        return c["changed"] & ~c["ovf"]

    def make_closure_body(ev):
        def body(c):
            rows, live = c["rows"], c["live"]
            cand_st, cand_ok = step_cc(
                rep.state(rows), ev["slot_f"], ev["slot_a0"],
                ev["slot_a1"], ev["slot_wild"])
            already = rep.mask_test(rows)
            legal = (live[:, None] & ev["slot_occ"][None, :]
                     & ~already & cand_ok)
            c_rows, c_live, route_ovf = route_cand(
                rep.candidates(rows, cand_st), legal.reshape(-1))
            all_rows = _rows_concat(rows, c_rows)
            all_live = jnp.concatenate([live, c_live])
            old_n = lax.psum(jnp.sum(live), axes)
            rows2, live2, cnt, ovf = _owned_dedupe_compact(
                all_rows, all_live, Nd, n_dev, my_idx, rep)
            new_n = lax.psum(cnt, axes)
            g_ovf = lax.psum((ovf | route_ovf).astype(jnp.int32), axes) > 0
            out = {"rows": rows2, "live": live2,
                   "changed": new_n > old_n, "ovf": g_ovf,
                   "stepped": c["stepped"] + old_n}
            if search_stats:
                out["iters"] = c["iters"] + 1
            return out
        return body

    def hash_closure_cond(c):
        return c["changed"] & ~c["ovf"]

    def make_hash_closure_body(ev):
        def body(c):
            rows = c["rows"]
            n_old, count = c["n_old"], c["count"]
            cand_st, cand_ok = step_cc(
                rep.state(rows), ev["slot_f"], ev["slot_a0"],
                ev["slot_a1"], ev["slot_wild"])
            row = jnp.arange(Nd)
            delta = (row >= n_old) & (row < count)
            already = rep.mask_test(rows)
            legal = (delta[:, None] & ev["slot_occ"][None, :]
                     & ~already & cand_ok)
            c_rows, c_live, route_ovf = route_cand(
                rep.candidates(rows, cand_st), legal.reshape(-1))
            # the gather A/B exchange broadcasts EVERY candidate to
            # every device; inserting only owned rows is what keeps
            # each table (and the frontier) a partition, not a replica
            owner = rep.owner_hash(c_rows) % jnp.uint32(n_dev)
            c_live = c_live & (owner == my_idx)
            ins = insert_append(c_rows, c_live, rows, count,
                                c["table"])
            rows2, table, count2, n_fresh, ins_ovf = ins[:5]
            l_ovf = (ins_ovf | route_ovf).astype(jnp.int32)
            g_new, g_delta, g_ovf = lax.psum(
                (n_fresh, count - n_old, l_ovf), axes)
            out = {
                "rows": rows2,
                "n_old": count,
                "count": count2,
                "table": table,
                "changed": g_new > 0,
                "ovf": c["ovf"] | (g_ovf > 0),
                "stepped": c["stepped"] + g_delta,
            }
            if search_stats:
                out["iters"] = c["iters"] + 1
                # the sort-equivalent work: the whole GLOBAL frontier
                # this iteration (what sort would have re-stepped)
                out["swork"] = c["swork"] + lax.psum(count, axes)
                out["phist"] = c["phist"] + ins[5]
            return out
        return body

    def run_closure(ev, rows, live, run, stepped):
        """-> (rows2, live2, ovf, stepped2, extras) with extras =
        (iters, swork, phist_local) under search_stats, else None."""
        if dedupe == "sort":
            carry0 = {"rows": rows, "live": live, "changed": run,
                      "ovf": jnp.array(False), "stepped": stepped}
            if search_stats:
                carry0["iters"] = jnp.int32(0)
            out = lax.while_loop(closure_cond, make_closure_body(ev),
                                 carry0)
            extras = ((out["iters"], out["stepped"] - stepped,
                       jnp.zeros(N_PROBE_BUCKETS, jnp.int32))
                      if search_stats else None)
            return (out["rows"], out["live"], out["ovf"],
                    out["stepped"], extras)
        # seed the per-event visited set with the local frontier
        # (owned rows by invariant), compacting it in the same pass;
        # the append overflow arm of insert_append is unreachable here
        # (at most Nd seed rows fit an Nd frontier), so its flag is
        # the pure probe-exhaustion signal the sort of carry expects
        seed = insert_append(rows, live, rep.zeros(Nd), jnp.int32(0),
                             _empty_table(Td, rep))
        rows0, table, m0, _, p0 = seed[:5]
        g_p0 = lax.psum(p0.astype(jnp.int32), axes) > 0
        carry0 = {
            "rows": rows0,
            "n_old": jnp.int32(0), "count": m0, "table": table,
            "changed": run, "ovf": g_p0, "stepped": stepped}
        if search_stats:
            carry0["iters"] = jnp.int32(0)
            carry0["swork"] = jnp.int32(0)
            carry0["phist"] = seed[5]
        out = lax.while_loop(
            hash_closure_cond, make_hash_closure_body(ev), carry0)
        live2 = jnp.arange(Nd) < out["count"]
        extras = ((out["iters"], out["swork"], out["phist"])
                  if search_stats else None)
        return out["rows"], live2, out["ovf"], out["stepped"], extras

    def scan_step(carry, ev):
        rows = carry[:L]
        live, ok, fail_r, r_idx, maxf, stepped = carry[L:]
        run = ok & (ev["ev_slot"] >= 0)
        rows2, live2, ovf, stepped2, extras = run_closure(
            ev, rows, live, run, stepped)
        # the hash prologue runs unconditionally (lax.scan cannot skip
        # an event): gate its probe flag so a pad/settled event never
        # leaks into the capacity-escalation decision
        ovf = run & ovf
        s = jnp.maximum(ev["ev_slot"], 0).astype(jnp.uint32)
        bits = rep.event_bits(s)
        has = rep.has_event_bit(rows2, bits)
        live3 = live2 & has
        rows3 = rep.clear_event_bit(rows2, bits, live3)
        n_live = lax.psum(jnp.sum(live3), axes)
        failed_here = run & (n_live == 0)
        # clearing the slot bit changed every survivor's hash — re-route
        # each config to its new owner device before the next closure
        r_rows, r_live, rt_ovf = route_front(rows3, live3)
        rows3, live3, _, r_ovf = _owned_dedupe_compact(
            r_rows, r_live, Nd, n_dev, my_idx, rep)
        ovf = ovf | (run & (lax.psum((r_ovf | rt_ovf).astype(jnp.int32),
                                     axes) > 0))
        new_ok = jnp.where(run, ~failed_here & ~ovf, ok)
        new_fail = jnp.where(failed_here & (fail_r < 0), r_idx, fail_r)
        rows_o = _rows_where(run, rows3, rows)
        live_o = jnp.where(run, live3, live)
        maxf = jnp.maximum(maxf, jnp.where(run,
                                           lax.psum(jnp.sum(live2), axes),
                                           0))
        stepped_o = jnp.where(run, stepped2, stepped)
        carry_o = rows_o + (live_o, new_ok, new_fail,
                            r_idx + 1, maxf, stepped_o)
        if not search_stats:
            return carry_o, ovf
        # per-event stats: width/peak/phist are DEVICE-LOCAL (the
        # per-device variants the host sums/maxes into the
        # mesh-reduced block); iters/stepped/swork are already global
        # (the closure's psums synchronize every device)
        y = {
            "ovf": ovf,
            "width": jnp.where(run, jnp.sum(live3),
                               -1).astype(jnp.int32),
            "peak": jnp.where(run, jnp.sum(live2), 0).astype(jnp.int32),
            "iters": jnp.where(run, extras[0], 0).astype(jnp.int32),
            "stepped": jnp.where(run, stepped2 - stepped,
                                 0).astype(jnp.int32),
            "swork": jnp.where(run, extras[1], 0).astype(jnp.int32),
            "phist": jnp.where(run, extras[2], 0).astype(jnp.int32),
        }
        return carry_o, y

    carry, ys = lax.scan(scan_step, carry0, xs)
    if search_stats:
        return carry, jnp.any(ys["ovf"]), ys
    return carry, jnp.any(ys)


def _sharded_core(xs, state0, step_name: str, Nd: int, n_dev: int,
                  my_idx, axes, route_cand, route_front,
                  dedupe: str = "sort", probe_limit: int = 0,
                  sparse_pallas: str = "off",
                  search_stats: bool = False, pack: tuple = ()):
    """Fresh-start wrapper over _sharded_scan: seed the initial config
    on its hash-owner device, scan the whole history, reduce to the
    (valid, fail, overflow, maxf, stepped) scalars — plus, under
    `search_stats`, the per-event stats dict (width/peak/phist with a
    leading per-device axis of 1, stacked to [n_dev, R] by the
    shard_map out_specs; iters/stepped/swork replicated)."""
    rep = _rep(pack, xs["slot_f"].shape[1])
    # initial config lives on its hash-owner device
    rows0 = rep.initial_full(state0, Nd)
    owner0 = rep.owner_hash(
        tuple(r[:1] for r in rows0))[0] % jnp.uint32(n_dev)
    live0 = (jnp.arange(Nd) < 1) & (owner0 == my_idx)
    carry0 = rows0 + (live0, jnp.array(True), jnp.int32(-1),
                      jnp.int32(0), jnp.int32(1), jnp.int32(0))
    out = _sharded_scan(xs, carry0, step_name, Nd, n_dev,
                        my_idx, axes, route_cand, route_front,
                        dedupe, probe_limit, sparse_pallas,
                        search_stats, pack)
    carry, overflow = out[0], out[1]
    live, ok, fail_r, _, maxf, stepped = carry[rep.lanes:]
    valid = ok & (lax.psum(jnp.sum(live), axes) > 0) & ~overflow
    if not search_stats:
        return valid, fail_r, overflow, maxf, stepped
    ys = out[2]
    stats = {
        "width": ys["width"][None, :],
        "peak": ys["peak"][None, :],
        "phist": ys["phist"][None, :, :],
        "iters": ys["iters"],
        "stepped": ys["stepped"],
        "swork": ys["swork"],
    }
    return valid, fail_r, overflow, maxf, stepped, stats


def _flat_routes(Nd: int, C: int, n_dev: int, rep):
    """(route_cand, route_front) for the flat 1-D topology.
    Owner-bucket widths: 2x the uniform share (hash-uniform slack),
    floored so tiny frontiers never trip the overflow path."""
    B_cand = max(64, -(-2 * Nd * C // n_dev))
    B_front = max(64, -(-2 * Nd // n_dev))
    route_cand = lambda rows, lv: _route_to_owners(
        rows, lv, n_dev, B_cand, rep)
    route_front = lambda rows, lv: _route_to_owners(
        rows, lv, n_dev, B_front, rep)
    return route_cand, route_front


def _sharded_impl(xs, state0, step_name: str, Nd: int, n_dev: int,
                  exchange: str = "route", dedupe: str = "sort",
                  probe_limit: int = 0, sparse_pallas: str = "off",
                  search_stats: bool = False, pack: tuple = ()):
    """1-D topology adapter: flat owner routing over AXIS, or the
    all-gather broadcast (A/B measurement path)."""
    C = xs["slot_f"].shape[1]
    rep = _rep(pack, C)
    my_idx = lax.axis_index(AXIS).astype(jnp.uint32)
    if exchange == "route":
        route_cand, route_front = _flat_routes(Nd, C, n_dev, rep)
    else:
        def _bcast(rows, lv):
            g = lambda a: lax.all_gather(a, AXIS, tiled=True)
            return tuple(g(r) for r in rows), g(lv), jnp.array(False)
        route_cand = route_front = _bcast
    return _sharded_core(xs, state0, step_name, Nd, n_dev, my_idx,
                         (AXIS,), route_cand, route_front, dedupe,
                         probe_limit, sparse_pallas, search_stats,
                         pack)


def _sharded2d_impl(xs, state0, step_name: str, Nd: int,
                    n_slice: int, n_chip: int, dedupe: str = "sort",
                    probe_limit: int = 0, sparse_pallas: str = "off",
                    search_stats: bool = False, pack: tuple = ()):
    """2-D topology adapter (slice x chip): the multi-slice story.
    Owner routing is HIERARCHICAL — stage 1 delivers candidates to the
    owner's chip COLUMN over the intra-slice axis (ICI); stage 2
    crosses slices (DCN) with rows already aggregated into one bucket
    per destination slice. Each row still crosses the slice boundary
    exactly once, but DCN sees n_slice large buckets per device instead
    of n_slice*n_chip small ones — message-count, not byte-count, is
    what DCN latency punishes."""
    C = xs["slot_f"].shape[1]
    rep = _rep(pack, C)
    D = n_slice * n_chip
    my_idx = (lax.axis_index(AX_SLICE) * n_chip
              + lax.axis_index(AX_CHIP)).astype(jnp.uint32)
    # bucket widths: 2x the uniform share at each stage; stage-2 input
    # is the stage-1 receive buffer (n_chip * B1 rows)
    B1c = max(64, -(-2 * Nd * C // n_chip))
    B2c = max(64, -(-2 * n_chip * B1c // n_slice))
    B1f = max(64, -(-2 * Nd // n_chip))
    B2f = max(64, -(-2 * n_chip * B1f // n_slice))

    def route2(rows, live, B1, B2):
        owner = rep.owner_hash(rows) % jnp.uint32(D)
        rows, live, o1 = _route_stage(
            rows, live, owner % jnp.uint32(n_chip), n_chip, B1,
            AX_CHIP)
        owner = rep.owner_hash(rows) % jnp.uint32(D)
        rows, live, o2 = _route_stage(
            rows, live, owner // jnp.uint32(n_chip), n_slice, B2,
            AX_SLICE)
        return rows, live, o1 | o2

    return _sharded_core(
        xs, state0, step_name, Nd, D, my_idx, (AX_SLICE, AX_CHIP),
        lambda rows, lv: route2(rows, lv, B1c, B2c),
        lambda rows, lv: route2(rows, lv, B1f, B2f),
        dedupe, probe_limit, sparse_pallas, search_stats, pack)


def _stats_out_specs(dev_axes):
    """out_specs for the per-event stats dict: width/peak/phist stack
    their leading per-device axis over the mesh; the psum-synchronized
    scalars stay replicated."""
    return {"width": P(dev_axes), "peak": P(dev_axes),
            "phist": P(dev_axes), "iters": P(), "stepped": P(),
            "swork": P()}


# donation decision (recompile-donate-argnums) for the two tier jits
# below, DECIDED: nothing donatable — xs/state0 are replicated inputs
# reused across the capacity-doubling retry loop in
# check_encoded_sharded (the SAME device arrays re-dispatch at doubled
# Nd), and every output is a replicated scalar, so no input could
# alias an output anyway.
@functools.partial(jax.jit,
                   donate_argnums=(),
                   static_argnames=("step_name", "Nd", "n_slice",
                                    "n_chip", "mesh", "dedupe",
                                    "probe_limit", "sparse_pallas",
                                    "search_stats", "pack"))
def _check_sharded2d(xs, state0, step_name: str, Nd: int, n_slice: int,
                     n_chip: int, mesh: Mesh, dedupe: str = "sort",
                     probe_limit: int = 0, sparse_pallas: str = "off",
                     search_stats: bool = False, pack: tuple = ()):
    out_specs = (P(), P(), P(), P(), P())
    if search_stats:
        out_specs = out_specs + (
            _stats_out_specs((AX_SLICE, AX_CHIP)),)
    fn = jax.shard_map(
        lambda x, s0: _sharded2d_impl(x, s0, step_name, Nd, n_slice,
                                      n_chip, dedupe, probe_limit,
                                      sparse_pallas, search_stats,
                                      pack),
        mesh=mesh,
        in_specs=(P(), P()),
        out_specs=out_specs,
        check_vma=False,
    )
    return fn(xs, state0)


# same (decided) donation as _check_sharded2d above
@functools.partial(jax.jit,
                   donate_argnums=(),
                   static_argnames=("step_name", "Nd", "n_dev",
                                    "mesh", "exchange", "dedupe",
                                    "probe_limit", "sparse_pallas",
                                    "search_stats", "pack"))
def _check_sharded(xs, state0, step_name: str, Nd: int, n_dev: int,
                   mesh: Mesh, exchange: str = "route",
                   dedupe: str = "sort", probe_limit: int = 0,
                   sparse_pallas: str = "off",
                   search_stats: bool = False, pack: tuple = ()):
    out_specs = (P(), P(), P(), P(), P())
    if search_stats:
        out_specs = out_specs + (_stats_out_specs(AXIS),)
    fn = jax.shard_map(
        lambda x, s0: _sharded_impl(x, s0, step_name, Nd, n_dev, exchange,
                                    dedupe, probe_limit, sparse_pallas,
                                    search_stats, pack),
        mesh=mesh,
        in_specs=(P(), P()),       # tables + state replicated
        out_specs=out_specs,
        check_vma=False,
    )
    return fn(xs, state0)


def _sharded_resume_impl(xs, carry, step_name: str, Nd: int,
                         n_dev: int, dedupe: str = "sort",
                         probe_limit: int = 0,
                         sparse_pallas: str = "off",
                         pack: tuple = ()):
    """Resume-from-carry adapter (runs INSIDE shard_map), 1-D topology.

    Restored rows arrive laid out however the host scattered them — a
    checkpoint may be resumed on a DIFFERENT mesh size — so the first
    act is one owner-routing round delivering every row to its current
    hash-owner; from then on the invariant the scan relies on (each
    live row lives on its owner device) holds. Returns the final carry
    (frontier sharded, scalars replicated) plus the overflow flag."""
    C = xs["slot_f"].shape[1]
    rep = _rep(pack, C)
    L = rep.lanes
    my_idx = lax.axis_index(AXIS).astype(jnp.uint32)
    route_cand, route_front = _flat_routes(Nd, C, n_dev, rep)
    rows, rest = carry[:L], carry[L:]
    live = rest[0]

    # the restore route's destinations are maximally SKEWED, not
    # hash-uniform — on the same mesh every one of a device's rows goes
    # back to that one device — so it gets worst-case buckets (B = Nd)
    # rather than route_front's 2x-uniform slack; it runs once per
    # chunk, so the O(n_dev * Nd) receive buffer is fine
    r_rows, r_live, rt_ovf = _route_to_owners(rows, live, n_dev, Nd,
                                              rep)
    rows2, live2, _, d_ovf = _owned_dedupe_compact(
        r_rows, r_live, Nd, n_dev, my_idx, rep)
    pre_ovf = lax.psum((rt_ovf | d_ovf).astype(jnp.int32), (AXIS,)) > 0

    carry0 = rows2 + (live2,) + rest[1:]
    carry, scan_ovf = _sharded_scan(xs, carry0, step_name, Nd, n_dev,
                                    my_idx, (AXIS,), route_cand,
                                    route_front, dedupe, probe_limit,
                                    sparse_pallas, pack=pack)
    return carry, scan_ovf | pre_ovf


# donation decision, DECIDED: the resumable carry tuple DONATES — the
# host places fresh device arrays from the (canonical, host-side)
# FrontierCheckpoint on every chunk dispatch including the
# overflow-retry, and the output carry aliases it shape-for-shape; at
# the top capacity tiers the carry is the peak-HBM buffer. xs stays
# undonated (replicated event tables, nothing to alias).
@functools.partial(jax.jit,
                   donate_argnames=("carry",),
                   static_argnames=("step_name", "Nd", "n_dev",
                                    "mesh", "dedupe", "probe_limit",
                                    "sparse_pallas", "pack"))
def _check_sharded_resume(xs, carry, step_name: str, Nd: int,
                          n_dev: int, mesh: Mesh, dedupe: str = "sort",
                          probe_limit: int = 0,
                          sparse_pallas: str = "off",
                          pack: tuple = ()):
    L = pack_lanes(pack, xs["slot_f"].shape[1])
    carry_specs = tuple([P(AXIS)] * L) + (P(AXIS),) \
        + tuple([P()] * 5)
    fn = jax.shard_map(
        lambda x, c: _sharded_resume_impl(x, c, step_name, Nd, n_dev,
                                          dedupe, probe_limit,
                                          sparse_pallas, pack),
        mesh=mesh,
        in_specs=(P(), carry_specs),
        out_specs=(carry_specs, P()),
        check_vma=False,
    )
    return fn(xs, carry)


def _sharded_resume2d_impl(xs, carry, step_name: str, Nd: int,
                           n_slice: int, n_chip: int,
                           dedupe: str = "sort", probe_limit: int = 0,
                           sparse_pallas: str = "off",
                           pack: tuple = ()):
    """Resume-from-carry adapter for the HIERARCHICAL 2-D topology —
    the 2-D twin of _sharded_resume_impl, built so the elastic ladder
    can promote a mid-search frontier from a 1-D slice onto extra
    slices (the DCN axis) without restarting the scan.

    The restore route gets worst-case buckets at both stages (the
    rows arrive laid out however the previous — possibly narrower,
    possibly flat — topology left them): stage 1 may send all of a
    device's Nd rows to one chip column, stage 2 all of the received
    n_chip*Nd rows to one slice. It runs once per chunk, so the
    O(N)-row receive buffer is the same posture as the 1-D restore."""
    C = xs["slot_f"].shape[1]
    rep = _rep(pack, C)
    L = rep.lanes
    D = n_slice * n_chip
    my_idx = (lax.axis_index(AX_SLICE) * n_chip
              + lax.axis_index(AX_CHIP)).astype(jnp.uint32)
    B1c = max(64, -(-2 * Nd * C // n_chip))
    B2c = max(64, -(-2 * n_chip * B1c // n_slice))
    B1f = max(64, -(-2 * Nd // n_chip))
    B2f = max(64, -(-2 * n_chip * B1f // n_slice))

    def route2(rows, live, B1, B2):
        owner = rep.owner_hash(rows) % jnp.uint32(D)
        rows, live, o1 = _route_stage(
            rows, live, owner % jnp.uint32(n_chip), n_chip, B1,
            AX_CHIP)
        owner = rep.owner_hash(rows) % jnp.uint32(D)
        rows, live, o2 = _route_stage(
            rows, live, owner // jnp.uint32(n_chip), n_slice, B2,
            AX_SLICE)
        return rows, live, o1 | o2

    rows, rest = carry[:L], carry[L:]
    live = rest[0]
    r_rows, r_live, pre1 = _route_stage(
        rows, live,
        (rep.owner_hash(rows) % jnp.uint32(D)) % jnp.uint32(n_chip),
        n_chip, Nd, AX_CHIP)
    owner2 = rep.owner_hash(r_rows) % jnp.uint32(D)
    r_rows, r_live, pre2 = _route_stage(
        r_rows, r_live, owner2 // jnp.uint32(n_chip), n_slice,
        n_chip * Nd, AX_SLICE)
    rows2, live2, _, d_ovf = _owned_dedupe_compact(
        r_rows, r_live, Nd, D, my_idx, rep)
    pre_ovf = lax.psum((pre1 | pre2 | d_ovf).astype(jnp.int32),
                       (AX_SLICE, AX_CHIP)) > 0

    carry0 = rows2 + (live2,) + rest[1:]
    carry, scan_ovf = _sharded_scan(
        xs, carry0, step_name, Nd, D, my_idx, (AX_SLICE, AX_CHIP),
        lambda r, lv: route2(r, lv, B1c, B2c),
        lambda r, lv: route2(r, lv, B1f, B2f),
        dedupe, probe_limit, sparse_pallas, pack=pack)
    return carry, scan_ovf | pre_ovf


# donation decision, DECIDED: same as _check_sharded_resume — the
# carry tuple donates (rebuilt per chunk from the host checkpoint,
# output aliases it), xs stays undonated (replicated event tables).
@functools.partial(jax.jit,
                   donate_argnames=("carry",),
                   static_argnames=("step_name", "Nd", "n_slice",
                                    "n_chip", "mesh", "dedupe",
                                    "probe_limit", "sparse_pallas",
                                    "pack"))
def _check_sharded_resume2d(xs, carry, step_name: str, Nd: int,
                            n_slice: int, n_chip: int, mesh: Mesh,
                            dedupe: str = "sort",
                            probe_limit: int = 0,
                            sparse_pallas: str = "off",
                            pack: tuple = ()):
    L = pack_lanes(pack, xs["slot_f"].shape[1])
    dev_axes = (AX_SLICE, AX_CHIP)
    carry_specs = tuple([P(dev_axes)] * L) + (P(dev_axes),) \
        + tuple([P()] * 5)
    fn = jax.shard_map(
        lambda x, c: _sharded_resume2d_impl(x, c, step_name, Nd,
                                            n_slice, n_chip, dedupe,
                                            probe_limit, sparse_pallas,
                                            pack),
        mesh=mesh,
        in_specs=(P(), carry_specs),
        out_specs=(carry_specs, P()),
        check_vma=False,
    )
    return fn(xs, carry)


def check_encoded_sharded_elastic(e: EncodedHistory, mesh: Mesh,
                                  capacity: int = 8192,
                                  max_capacity: int = 1 << 22,
                                  start_devices: int = 0,
                                  checkpoint_every: int = 256,
                                  dedupe=None, probe_limit: int = 0,
                                  sparse_pallas=None,
                                  search_stats=None,
                                  config_pack=None) -> dict:
    """Re-shard-on-escalation (JEPSEN_TPU_RESHARD): the sharded search
    with the elastic capacity ladder. Where check_encoded_sharded
    answers every overflow by doubling per-device tables on a FIXED
    device set, this arm starts on a narrow slice of the mesh
    (``start_devices``, default 2) and each overflow first RECRUITS
    devices along MeshPlan.ladder's rungs — wider 1-D within the first
    slice, then whole extra slices via the hierarchical 2-D exchange —
    holding per-device capacity flat, so escalation costs ICI/DCN
    fan-out instead of per-device HBM. Only once the full mesh is
    recruited does capacity growth fall back to the historical
    table-doubling; ``max_capacity`` and the overflow->unknown
    semantics are unchanged.

    The scan runs in checkpointed chunks (the resumable machinery —
    CONTRACT TWIN of check_encoded_sharded_resumable's loop: same
    supervised dispatch, same overflow re-run-the-chunk rule). A
    re-shard re-dispatches the current chunk on the wider rung; the
    restore route's owner-routed all-to-all is what redistributes the
    checkpointed visited set onto the new device slice. Results carry
    the verdict fields of check_encoded_sharded plus a ``"reshard"``
    block ({start-devices, events: [{event, devices, capacity}, ...]})
    — the key exists only on this arm, so flag-off results stay
    byte-identical. Per-event search-stats blocks are not produced on
    the resumable jits (the resumable-arm precedent); ``search_stats``
    is accepted for signature compatibility and ignored."""
    from time import perf_counter as _pc

    from jepsen_tpu.parallel.engine import (FrontierCheckpoint,
                                            carry_fields_np,
                                            history_digest)
    if e.n_returns == 0:
        return {"valid?": True, "max-frontier": 0, "capacity": 0}
    del search_stats   # no stats outputs on the resumable jits
    dedupe = _resolve_dedupe(dedupe)
    probe_limit = _resolve_probe_limit(probe_limit)
    pack_req = _resolve_config_pack(config_pack)
    C_enc = e.slot_f.shape[1]
    pack = pack_spec_for(e) if pack_req else ()
    plan_full = MeshPlan.from_mesh(mesh, "route")
    if start_devices <= 0:
        start_devices = min(2, plan_full.n_dev)
    rungs = plan_full.ladder(start_devices)
    rung = 0
    n_dev = rungs[0].n_dev
    # per-device capacity held flat across the recruiting rungs: the
    # global capacity of rung r is Nd0 * n_dev(r)
    Nd0 = -(-max(64, capacity) // n_dev)
    N = Nd0 * n_dev
    platform = plan_full.platform
    digest = history_digest(e)
    cp = FrontierCheckpoint(
        0, N, e.step_name, digest,
        np.full(N, e.state0, np.int32), np.zeros(N, np.uint32),
        np.zeros(N, np.uint32), np.arange(N) < 1, True, -1, 1, 0)
    reshard_events: list = []
    xs_np = {
        "slot_f": e.slot_f, "slot_a0": e.slot_a0, "slot_a1": e.slot_a1,
        "slot_wild": e.slot_wild, "slot_occ": e.slot_occ,
        "ev_slot": e.ev_slot,
    }
    R = e.n_returns
    mode, note = "off", None
    led = _ledger.active()
    t_start = _pc()
    with obs.span("sharded.elastic", devices=plan_full.n_dev,
                  dedupe=dedupe, returns=R) as sp:
        while cp.event_index < R and cp.ok:
            plan = rungs[rung]
            n_dev = plan.n_dev
            sub_mesh = plan.mesh()
            Nd = N // n_dev
            mode, note = _resolve_sparse_pallas(
                sparse_pallas, Nd, C_enc, plan.n_chip, plan.n_slice,
                "route", platform, dedupe, pack)
            lo = cp.event_index
            hi = min(R, lo + checkpoint_every)
            rep_sh = NamedSharding(sub_mesh, P())
            shard = NamedSharding(
                sub_mesh, P((AX_SLICE, AX_CHIP) if plan.hierarchical
                            else AXIS))

            def _chunk(cp=cp, Nd=Nd, plan=plan, mode=mode, lo=lo,
                       hi=hi, sub_mesh=sub_mesh, rep_sh=rep_sh,
                       shard=shard):
                chunk = {k: jax.device_put(np.asarray(v[lo:hi]),
                                           rep_sh)
                         for k, v in xs_np.items()}
                if pack:
                    rows = pack_rows_np(pack, C_enc, cp.st, cp.ml,
                                        cp.mh)
                else:
                    rows = (cp.st, cp.ml, cp.mh)
                # owned placement before the resume jit donates the
                # carry (engine._place_owned documents the hazard)
                carry_in = jax.tree.map(jnp.copy, tuple(
                    jax.device_put(np.asarray(r), shard)
                    for r in rows)
                    + (jax.device_put(cp.live, shard),
                       jax.device_put(np.bool_(cp.ok), rep_sh),
                       jax.device_put(np.int32(cp.fail_r), rep_sh),
                       jax.device_put(np.int32(cp.event_index),
                                      rep_sh),
                       jax.device_put(np.int32(cp.maxf), rep_sh),
                       jax.device_put(np.int32(cp.stepped), rep_sh)))
                if plan.hierarchical:
                    carry, overflow = _check_sharded_resume2d(
                        chunk, carry_in, e.step_name, Nd,
                        plan.n_slice, plan.n_chip, sub_mesh, dedupe,
                        probe_limit, mode, pack)
                else:
                    carry, overflow = _check_sharded_resume(
                        chunk, carry_in, e.step_name, Nd, n_dev,
                        sub_mesh, dedupe, probe_limit, mode, pack)
                return [np.asarray(x) for x in carry], bool(overflow)

            try:
                carry, overflow = sup.dispatch("sharded", _chunk,
                                               backend=platform)
            except sup.DISPATCH_FAILURES as err:
                err.checkpoint = cp
                raise
            if bool(overflow):
                if rung + 1 < len(rungs):
                    # recruit devices: per-device capacity stays Nd0,
                    # the wider rung's restore route redistributes the
                    # checkpointed visited set over the new slice
                    rung += 1
                    new_n = rungs[rung].n_dev
                    N = Nd0 * new_n
                    reshard_events.append(
                        {"event": cp.event_index,
                         "devices": [n_dev, new_n], "capacity": N})
                    obs.counter("engine.reshard_escalations").inc()
                    if led is not None:
                        led.record(
                            "reshard", engine="sharded",
                            shape={"family": e.step_name, "R": R,
                                   "C": C_enc},
                            rung=rung, devices=[n_dev, new_n],
                            capacity=N, event=cp.event_index)
                    if N > cp.capacity:
                        cp = cp.grown(N)
                    continue
                # full mesh recruited: the historical table-doubling
                if N * 2 > max_capacity:
                    out = _tag_sparse_closure(
                        {"valid?": "unknown",
                         "error": f"frontier overflow at capacity {N}",
                         "capacity": N, "devices": n_dev,
                         "dedupe": dedupe, "checkpoint": cp}, mode,
                        note)
                    out["reshard"] = {"start-devices": start_devices,
                                      "events": reshard_events}
                    return out
                Nd0 *= 2
                N *= 2
                obs.counter("engine.capacity_escalations").inc()
                cp = cp.grown(N)
                continue
            st, ml, mh, live, ok, fail_r, r_idx, maxf, stepped = \
                carry_fields_np(carry, pack, C_enc)
            cp = FrontierCheckpoint(int(r_idx), N, e.step_name, digest,
                                    st, ml, mh, live, bool(ok),
                                    int(fail_r), int(maxf), cp.steps_n,
                                    int(stepped))
        sp.set(capacity=N, devices=n_dev)
    obs.counter("engine.configs_stepped").inc(int(cp.stepped))
    out = {"valid?": cp.ok and bool(cp.live.any()),
           "max-frontier": cp.maxf, "capacity": cp.capacity,
           "devices": n_dev, "dedupe": dedupe,
           "configs-stepped": cp.stepped,
           "reshard": {"start-devices": start_devices,
                       "events": reshard_events}}
    _tag_sparse_closure(out, mode, note)
    _tag_config_pack(out, pack, pack_req, C_enc)
    if led is not None:
        led.record(
            "dispatch", engine="sharded",
            shape={"family": e.step_name, "N": N, "R": R,
                   "C": C_enc, "tier": len(reshard_events),
                   "pack": bool(pack)},
            strategy={"dedupe": dedupe, "closure": mode,
                      "pack": pack_req, "probe_limit": probe_limit,
                      "reshard": True, "devices": start_devices},
            secs=round(_pc() - t_start, 6), keys=1,
            outcome={"verdict": _ledger.verdict_class(out),
                     "devices": n_dev,
                     "resharded": len(reshard_events)})
    if not out["valid?"]:
        from jepsen_tpu.parallel.encode import fail_op_fields
        out.update(fail_op_fields(e, cp.fail_r))
    return out


def _resolve_sparse_pallas(sparse_pallas, Nd: int, C: int, n_chip: int,
                           n_slice: int, exchange: str, platform: str,
                           dedupe: str, pack=()):
    """Sharded arm of engine._resolve_sparse_pallas — same flag, same
    tri-state, but gated on the per-device INSERT shapes: the largest
    candidate buffer a device receives from the exchange (flat route:
    n_dev buckets of the 2x-uniform width; hierarchical: the stage-2
    receive; gather: every candidate on every device) plus its own
    Nd-row frontier tile. Width-aware like the engine's (packed rows
    clear the gate at larger Nd), but with no tiled arm — the
    received candidate buffer is transient exchange output, so a
    past-gate tier degrades to the XLA insert with a note, as before.
    Returns (mode, note) like the engine's."""
    from jepsen_tpu.parallel.engine import \
        _resolve_sparse_pallas as engine_resolve
    # flag / tri-state / platform / dedupe-contradiction resolution on
    # a trivially-supported shape; the buffer gate below is the
    # sharded-specific part
    mode, _ = engine_resolve(sparse_pallas, 1, 1, platform, dedupe)
    if mode == "off":
        return mode, None
    mode = "interpret"   # the resolver refuses the kernels on a TPU
    n_dev = n_chip * n_slice
    if exchange == "gather":
        M = n_dev * Nd * C
    elif n_slice > 1:
        B1 = max(64, -(-2 * Nd * C // n_chip))
        M = n_slice * max(64, -(-2 * n_chip * B1 // n_slice))
    else:
        M = n_dev * max(64, -(-2 * Nd * C // n_dev))
    from jepsen_tpu.parallel import sparse_kernels as sk
    lanes = pack_lanes(pack, C)
    if not sk.insert_supported(M, Nd, lanes):
        obs.counter("engine.sparse_pallas_fallbacks").inc()
        note = (f"sparse insert kernel skipped at per-device capacity "
                f"{Nd} (C={C}, exchange buffer {M} rows, {lanes} row "
                f"lanes): probe state would exceed the kernel's VMEM "
                f"budget — fell back to the XLA hash insert for this "
                f"tier")
        _log.warning("%s", note)
        return "off", note
    return mode, None


def check_encoded_sharded_resumable(e: EncodedHistory, mesh: Mesh,
                                    capacity: int = 8192,
                                    max_capacity: int = 1 << 22,
                                    checkpoint_every: int = 256,
                                    checkpoint_cb=None,
                                    resume=None,
                                    dedupe=None,
                                    probe_limit: int = 0,
                                    sparse_pallas=None,
                                    config_pack=None) -> dict:
    """check_encoded_sharded with mid-search checkpointing — the
    sharded arm of the checker's checkpoint/resume capability
    (SURVEY.md §5.4; engine.check_encoded_resumable is the single-
    device arm). Events run in chunks of `checkpoint_every`; after
    each chunk the GLOBAL frontier is gathered to host and handed to
    checkpoint_cb(engine.FrontierCheckpoint). The checkpoint is
    topology-independent: `capacity` is the GLOBAL frontier size, rows
    are stored unsharded, and resuming re-routes every row to its
    hash-owner on the CURRENT mesh — a search checkpointed on D
    devices resumes on any other device count (elastic recovery).
    Overflow inside a chunk (including the restore re-route) re-runs
    that chunk at doubled capacity; the prior checkpoint stays valid.

    Topology caveat: this path always runs the FLAT 1-D exchange — a
    2-D multi-slice mesh is flattened (with a warning), unlike
    check_encoded_sharded, which would pick the hierarchical DCN-aware
    exchange for it. `explored` is likewise not tracked across arms:
    sharded checkpoints carry the resume's steps_n through unchanged."""
    from jepsen_tpu.parallel.engine import (FrontierCheckpoint,
                                            history_digest)

    if e.n_returns == 0:
        return {"valid?": True, "max-frontier": 0, "capacity": 0}
    devs = np.asarray(mesh.devices)
    if devs.ndim == 2 and devs.shape[0] > 1 and devs.shape[1] > 1:
        _log.warning(
            "resumable sharded check flattens the 2-D mesh to the flat "
            "1-D exchange — the hierarchical multi-slice routing of "
            "check_encoded_sharded is not used on this path")
    devs = devs.reshape(-1)
    mesh = Mesh(devs, (AXIS,))
    n_dev = devs.size
    dedupe = _resolve_dedupe(dedupe)
    probe_limit = _resolve_probe_limit(probe_limit)
    pack_req = _resolve_config_pack(config_pack)
    C_enc = e.slot_f.shape[1]
    pack = pack_spec_for(e) if pack_req else ()
    platform = devs[0].platform
    digest = history_digest(e)
    if resume is not None:
        if resume.history_digest != digest:
            raise ValueError(
                f"checkpoint is for a different history "
                f"(digest {resume.history_digest} != {digest})")
        if resume.step_name != e.step_name:
            raise ValueError("checkpoint is for a different model")
        cp = resume
    else:
        N0 = max(64 * n_dev, capacity)
        cp = FrontierCheckpoint(
            0, N0, e.step_name, digest,
            np.full(N0, e.state0, np.int32), np.zeros(N0, np.uint32),
            np.zeros(N0, np.uint32), np.arange(N0) < 1,
            True, -1, 1, 0)

    rep = NamedSharding(mesh, P())
    shard = NamedSharding(mesh, P(AXIS))
    xs_np = {
        "slot_f": e.slot_f, "slot_a0": e.slot_a0, "slot_a1": e.slot_a1,
        "slot_wild": e.slot_wild, "slot_occ": e.slot_occ,
        "ev_slot": e.ev_slot,
    }
    R = e.n_returns
    mode, note = "off", None
    while cp.event_index < R and cp.ok:
        # global capacity must divide the mesh; grow to the next
        # multiple when the checkpoint came from a different topology
        N = -(-cp.capacity // n_dev) * n_dev
        if N != cp.capacity:
            cp = cp.grown(N)
        Nd = N // n_dev
        # re-resolve per chunk: capacity growth can cross the kernel's
        # VMEM gate mid-search (degrade-with-note, never an error)
        mode, note = _resolve_sparse_pallas(
            sparse_pallas, Nd, e.slot_f.shape[1], n_dev, 1, "route",
            platform, dedupe, pack)
        lo, hi = cp.event_index, min(R, cp.event_index + checkpoint_every)

        def _chunk(cp=cp, Nd=Nd, mode=mode, lo=lo, hi=hi):
            chunk = {k: jax.device_put(np.asarray(v[lo:hi]), rep)
                     for k, v in xs_np.items()}
            # the checkpoint is canonical-unpacked; rows pack at this
            # boundary when the engine runs the packed layout. The
            # jnp.copy makes every buffer device-OWNED before the
            # resume jit DONATES it — a zero-copy device_put would
            # hand XLA a window onto memory the live checkpoint still
            # owns (engine._place_owned documents the hazard).
            if pack:
                rows = pack_rows_np(pack, C_enc, cp.st, cp.ml, cp.mh)
            else:
                rows = (cp.st, cp.ml, cp.mh)
            carry_in = jax.tree.map(jnp.copy, tuple(
                jax.device_put(np.asarray(r), shard) for r in rows)
                + (jax.device_put(cp.live, shard),
                   jax.device_put(np.bool_(cp.ok), rep),
                   jax.device_put(np.int32(cp.fail_r), rep),
                   jax.device_put(np.int32(cp.event_index), rep),
                   jax.device_put(np.int32(cp.maxf), rep),
                   jax.device_put(np.int32(cp.stepped), rep)))
            carry, overflow = _check_sharded_resume(
                chunk, carry_in, e.step_name, Nd, n_dev, mesh, dedupe,
                probe_limit, mode, pack)
            # materialize inside the supervised window
            return [np.asarray(x) for x in carry], bool(overflow)

        try:
            carry, overflow = sup.dispatch("sharded", _chunk,
                                           backend=platform)
        except sup.DISPATCH_FAILURES as err:
            # the mid-search contract: no work lost — the checkpoint
            # taken before this chunk rides the exception so the
            # caller can resume (on any topology; the checkpoint is
            # topology-independent) once the runtime recovers
            err.checkpoint = cp
            raise
        if bool(overflow):
            if N * 2 > max_capacity:
                return _tag_sparse_closure(
                    {"valid?": "unknown",
                     "error": f"frontier overflow at capacity {N}",
                     "capacity": N, "devices": n_dev,
                     "dedupe": dedupe, "checkpoint": cp}, mode, note)
            cp = cp.grown(N * 2)    # N extra dead rows
            continue                # re-run the same chunk
        from jepsen_tpu.parallel.engine import carry_fields_np
        st, ml, mh, live, ok, fail_r, r_idx, maxf, stepped = \
            carry_fields_np(carry, pack, C_enc)
        cp = FrontierCheckpoint(int(r_idx), N, e.step_name, digest,
                                st, ml, mh, live, bool(ok),
                                int(fail_r), int(maxf), cp.steps_n,
                                int(stepped))
        if checkpoint_cb is not None:
            checkpoint_cb(cp)
    out = {"valid?": cp.ok and bool(cp.live.any()),
           "max-frontier": cp.maxf, "capacity": cp.capacity,
           "devices": n_dev, "dedupe": dedupe,
           "configs-stepped": cp.stepped}
    _tag_sparse_closure(out, mode, note)
    _tag_config_pack(out, pack, pack_req, C_enc)
    if not out["valid?"]:
        from jepsen_tpu.parallel.encode import fail_op_fields
        out.update(fail_op_fields(e, cp.fail_r))
    return out


def _sharded_stats_block(stats, N: int, Nd: int, n_dev: int,
                         dedupe: str, n_esc: int) -> dict:
    """The sharded arm of the JEPSEN_TPU_SEARCH_STATS block:
    mesh-reduced trajectories (global width/peak per event = sum over
    devices) plus the per-device variants skew questions need (which
    device's table runs hottest; whether bucket skew idles part of
    the mesh)."""
    width = np.asarray(stats["width"])          # [n_dev, R]
    peak = np.asarray(stats["peak"])
    phist = np.asarray(stats["phist"])          # [n_dev, R, B]
    iters = np.asarray(stats["iters"]).reshape(-1)
    stepped = np.asarray(stats["stepped"]).reshape(-1)
    swork = np.asarray(stats["swork"]).reshape(-1)
    mask = width[0] >= 0   # run is psum-synchronized: all rows agree
    g_width = width[:, mask].sum(axis=0)
    g_peak = peak[:, mask].sum(axis=0)
    frontier_peak = int(g_peak.max()) if g_peak.size else 0
    stepped_total = int(stepped[mask].sum())
    swork_total = int(swork[mask].sum())
    block = {
        "engine": "sharded",
        "events": int(mask.sum()),
        "frontier-width": [int(x) for x in g_width],
        "closure-iters": [int(x) for x in iters[mask]],
        "configs-stepped-per-event": [int(x) for x in stepped[mask]],
        "closure-peak": [int(x) for x in g_peak],
        "frontier-peak": frontier_peak,
        "capacity": N,
        "capacity-tier": n_esc,
        "peak-occupancy": round(frontier_peak / N, 6) if N else None,
        "dedupe": dedupe,
        "devices": n_dev,
        "delta-split-ratio": (round(stepped_total / swork_total, 6)
                              if swork_total else None),
        "table-capacity": None,
        "load-factor-peak": None,
        "load-factor-final": None,
        "probe-hist": None,
        "probes": None,
        "per-device": {
            "width-peak": [int(width[d, mask].max()) if mask.any()
                           else 0 for d in range(width.shape[0])],
        },
    }
    if dedupe == "hash":
        from jepsen_tpu.parallel.engine import PROBE_HIST_LABELS
        Td = _next_pow2(2 * Nd)
        dev_peak = [int(peak[d, mask].max()) if mask.any() else 0
                    for d in range(peak.shape[0])]
        block["table-capacity"] = Td * n_dev   # union of owned tables
        block["per-device"]["table-capacity"] = Td
        block["per-device"]["load-factor-peak"] = [
            round(p / Td, 6) for p in dev_peak]
        block["load-factor-peak"] = (round(max(dev_peak) / Td, 6)
                                     if dev_peak else None)
        if mask.any():
            block["load-factor-final"] = round(
                int(peak[:, mask][:, -1].max()) / Td, 6)
        hist = phist[:, mask].sum(axis=(0, 1)).astype(np.int64)
        block["probe-hist"] = {lab: int(n) for lab, n in
                               zip(PROBE_HIST_LABELS, hist)}
        block["probes"] = int(hist.sum())
    return block


def check_encoded_sharded(e: EncodedHistory, mesh: Mesh,
                          capacity: int = 8192,
                          max_capacity: int = 1 << 22,
                          exchange: str = "route",
                          dedupe=None,
                          probe_limit: int = 0,
                          sparse_pallas=None,
                          search_stats=None,
                          config_pack=None,
                          reshard=None) -> dict:
    """Check one encoded history with the frontier sharded over `mesh`.

    Topology: a mesh whose device array is 2-D (both dims > 1) with
    exchange="route" selects the HIERARCHICAL multi-slice path — axis 0
    is treated as the slice (DCN) axis, axis 1 as intra-slice chips
    (ICI), candidates route in two stages (see the module docstring),
    and the result carries a "mesh" key. Any other mesh is flattened
    onto a 1-D axis; exchange="gather" (the all-gather A/B measurement
    path) always flattens.

    `capacity` is the GLOBAL frontier capacity; it doubles on overflow
    (frontier past capacity, an owner bucket past its 2x-uniform
    slack, or — under dedupe="hash" — a visited-set probe exhaustion)
    by re-jitting at the next tier, like `engine.check_encoded`.

    `dedupe` picks the per-iteration dedupe: "sort" (owner-filtered
    lexsort) or "hash" (delta-frontier closure over per-device
    open-addressed visited sets — the device-sharded hash set of
    BASELINE.json); None defers to JEPSEN_TPU_DEDUPE. Verdicts and
    counterexample fields are identical; "configs-stepped" records
    the global closure work actually paid.

    `sparse_pallas` (None = JEPSEN_TPU_SPARSE_PALLAS) fuses each
    closure iteration's per-device visited-set transaction into one
    pallas kernel (sparse_kernels.hash_insert_call) — probe, claim
    arbitration, and fresh-row append run VMEM-resident; the
    owner-routing collectives stay in XLA. `probe_limit` as in
    engine.check_encoded (one knob for every hash path).

    `reshard` (None = JEPSEN_TPU_RESHARD) replaces the grow-the-table
    escalation with the elastic device ladder: the search starts on a
    NARROW slice of the mesh and each overflow recruits more devices
    (per-device capacity held flat) before it ever grows per-device
    tables — check_encoded_sharded_elastic's docstring has the
    contract. Flag off = the historical ladder, byte-identical."""
    if e.n_returns == 0:
        return {"valid?": True, "max-frontier": 0, "capacity": 0}
    if _resolve_reshard(reshard) and exchange == "route" \
            and np.asarray(mesh.devices).size > 1:
        return check_encoded_sharded_elastic(
            e, mesh, capacity=capacity, max_capacity=max_capacity,
            dedupe=dedupe, probe_limit=probe_limit,
            sparse_pallas=sparse_pallas, search_stats=search_stats,
            config_pack=config_pack)
    dedupe = _resolve_dedupe(dedupe)
    probe_limit = _resolve_probe_limit(probe_limit)
    ss = _resolve_search_stats(search_stats)
    pack_req = _resolve_config_pack(config_pack)
    pack = pack_spec_for(e) if pack_req else ()
    led = _ledger.active()
    # A 2-D device array + "route" = the multi-slice topology: axis 0
    # is the slice (DCN) axis, axis 1 the intra-slice chip (ICI) axis,
    # and the exchange goes hierarchical. Anything else flattens onto
    # a 1-D mesh named AXIS. MeshPlan owns that decision (the elastic
    # ladder and the multi-host seam read the same one).
    plan = MeshPlan.from_mesh(mesh, exchange)
    hier = plan.hierarchical
    mesh = plan.mesh()
    n_dev = plan.n_dev
    if hier:
        n_slice, n_chip = plan.n_slice, plan.n_chip
    # replicate inputs onto the mesh explicitly: nothing may be created
    # on the default backend (it can be a broken TPU runtime while we
    # deliberately run on a CPU mesh — the MULTICHIP_r01 crash mode)
    rep = NamedSharding(mesh, P())
    platform = np.asarray(mesh.devices).flat[0].platform
    # supervised H2D placement — a wedged runtime hangs here exactly
    # like it does at dispatch (site "transfer")
    xs, state0 = sup.dispatch(
        "transfer",
        lambda: (_xs_from_encoded(e, device=rep),
                 jax.device_put(np.int32(e.state0), rep)),
        backend=platform)
    N = max(64 * n_dev, capacity)
    n_esc = 0
    from time import perf_counter as _pc
    t0 = _pc()
    with obs.span("sharded.search", devices=n_dev, dedupe=dedupe,
                  returns=e.n_returns) as sp:
        while True:
            Nd = (N + n_dev - 1) // n_dev
            mode, note = _resolve_sparse_pallas(
                sparse_pallas, Nd, e.slot_f.shape[1],
                n_chip if hier else n_dev, n_slice if hier else 1,
                exchange, platform, dedupe, pack)
            # one span per capacity-tier attempt, per-device capacity
            # attached — the escalation ladder renders as widening
            # steps in the trace
            with obs.span("sharded.tier", capacity=N, per_device=Nd), \
                    obs.device_annotation(f"sharded N{N} D{n_dev}"):
                def _tier(Nd=Nd, mode=mode):
                    if hier:
                        out = _check_sharded2d(xs, state0, e.step_name,
                                               Nd, n_slice, n_chip,
                                               mesh, dedupe,
                                               probe_limit, mode, ss,
                                               pack)
                    else:
                        out = _check_sharded(xs, state0, e.step_name,
                                             Nd, n_dev, mesh, exchange,
                                             dedupe, probe_limit, mode,
                                             ss, pack)
                    # materialize inside the supervised window: async
                    # failures/hangs surface here, not at a host read
                    return jax.tree.map(np.asarray, out)

                # population tracking only: shard_map programs carry
                # mesh-bound layouts the AOT serializer does not
                # round-trip — the registry counts their shape tuples
                # (per tier) without managing the executables
                programs.track(
                    "sharded.check2d" if hier else "sharded.check",
                    xs,
                    (e.step_name, Nd, n_slice if hier else n_dev,
                     n_chip if hier else 1, exchange, dedupe,
                     probe_limit, mode, ss, pack))
                # supervised dispatch (resilience.supervisor): site
                # "sharded" so the fault matrix can target the tier
                # path; failures degrade at the callers (analysis /
                # engine._escalate_overflow)
                res = sup.dispatch("sharded", _tier, backend=platform)
                valid, fail_r, overflow, maxf, stepped = res[:5]
                overflow = bool(overflow)
            if not overflow:
                break
            if N * 2 > max_capacity:
                return _tag_sparse_closure(
                    {"valid?": "unknown",
                     "error": f"frontier overflow at capacity {N}",
                     "capacity": N, "dedupe": dedupe}, mode, note)
            N *= 2
            n_esc += 1
            obs.counter("engine.capacity_escalations").inc()
        sp.set(capacity=N)
        if mode != "off":
            # only when the kernel was requested (engine.check_encoded
            # precedent): flag-off trace schema stays identical
            sp.set(closure="pallas")
    obs.counter("engine.configs_stepped").inc(int(stepped))
    out = {"valid?": bool(valid), "max-frontier": int(maxf),
           "capacity": N, "devices": n_dev, "dedupe": dedupe,
           "configs-stepped": int(stepped)}
    if ss:
        from jepsen_tpu.parallel import engine as eng_mod
        block = _sharded_stats_block(res[5], N, Nd, n_dev, dedupe,
                                     n_esc)
        out["stats"] = eng_mod.finish_stats_block(block, t0, _pc())
    _tag_sparse_closure(out, mode, note)
    _tag_config_pack(out, pack, pack_req, e.slot_f.shape[1])
    if led is not None:
        led.record(
            "dispatch", engine="sharded",
            shape={"family": e.step_name, "N": N,
                   "R": e.n_returns, "C": e.slot_f.shape[1],
                   "tier": n_esc, "pack": bool(pack)},
            strategy={"dedupe": dedupe, "closure": mode,
                      "pack": pack_req, "probe_limit": probe_limit,
                      "reshard": False, "devices": n_dev,
                      "exchange": exchange},
            secs=round(_pc() - t0, 6), keys=1,
            stats=(_ledger.stats_digest([out["stats"]])
                   if ss else None),
            outcome={"verdict": _ledger.verdict_class(out),
                     "escalations": n_esc})
    if hier:
        out["mesh"] = f"{n_slice}x{n_chip} (hierarchical exchange)"
    if not out["valid?"]:
        from jepsen_tpu.parallel.encode import fail_op_fields
        out.update(fail_op_fields(e, int(fail_r)))
    return out


def analysis(model, history, mesh: Mesh, capacity: int = 8192,
             max_capacity: int = 1 << 22, exchange: str = "route",
             dedupe=None, sparse_pallas=None, search_stats=None,
             config_pack=None) -> dict:
    """knossos-style (model, history) -> result with the frontier
    sharded over `mesh`; on failure, counterexample paths come from the
    same windowed host re-search as `engine.analysis` (the seed frontier
    is re-derived on one device — the sharded union equals the
    single-device frontier by construction)."""
    from jepsen_tpu.history import History
    from jepsen_tpu.parallel import encode as enc, engine
    h = history if isinstance(history, History) else History.wrap(history)
    try:
        e = enc.encode(model, h)
    except enc.EncodeError as err:
        # same host fallback as engine.analysis — the two entry points
        # must be interchangeable for non-packable inputs
        from jepsen_tpu.checker import wgl
        obs.counter("engine.host_fallbacks").inc()
        _log.warning(
            "history not device-checkable (%s) — using the host WGL "
            "engine; expect it to be orders of magnitude slower", err)
        r = wgl.analysis(model, h)
        r["fallback"] = str(err)
        return r
    try:
        r = check_encoded_sharded(e, mesh, capacity=capacity,
                                  max_capacity=max_capacity,
                                  exchange=exchange, dedupe=dedupe,
                                  sparse_pallas=sparse_pallas,
                                  search_stats=search_stats,
                                  config_pack=config_pack)
    except sup.DISPATCH_FAILURES as err:
        # degradation contract (docs/resilience.md): a dead sharded
        # tier degrades to the host WGL engine, verdict preserved,
        # with a structured resilience note — same as engine.analysis
        from jepsen_tpu.resilience import recovery
        return recovery.host_check_encoded(
            model, e, getattr(err, "site", "sharded"),
            f"{type(err).__name__}: {err}")
    if r["valid?"] is False:
        engine.apply_final_paths(r, model, e)
    return r
