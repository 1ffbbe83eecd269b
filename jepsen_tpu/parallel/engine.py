"""The TPU linearizability engine — batched frontier expansion under jit.

This is the north star (BASELINE.json): the knossos linear/wgl search
re-designed for the MXU/VPU instead of translated. The algorithm is the
JIT-linearization frontier of `jepsen_tpu.checker.linear` (its docstring
is the spec; differential tests pin the two together), mapped to XLA:

  * a configuration is (state: i32, mask: 2×u32) — 96 bits, fixed width;
  * the frontier is a fixed-capacity struct-of-arrays [N] with a live
    mask; capacity doubles on overflow by re-jitting (SURVEY.md §7.3
    hard part #1: capacity-tiered buffers);
  * one closure round = a single vmap'd evaluation of the model step
    over all N×C (config, open-slot) pairs — millions of candidate
    configs per chip per round;
  * dedupe is sort-based (lexsort + adjacent-compare + cumsum scatter):
    static shapes, no host round-trips. The sorted frontier *is* the
    visited set — in this formulation the full config set at the current
    event subsumes knossos's visited cache;
  * the outer loop over return events is a lax.scan; the inner closure
    a lax.while_loop. Nothing data-dependent escapes the device: the
    host gets back (valid, fail_event, stats) scalars only.

Multi-chip: `check_batch` vmaps over keys and shards the key axis over a
mesh (data parallel — P5 in SURVEY.md §2.20); `jepsen_tpu.parallel.sharded`
shards the *frontier* axis with collective dedupe for giant single keys.
"""

from __future__ import annotations

import functools
import logging
from typing import Optional

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

from jepsen_tpu import envflags
from jepsen_tpu import obs
from jepsen_tpu.obs import ledger as _ledger
from jepsen_tpu.parallel import planner as _planner
from jepsen_tpu.parallel import encode as enc_mod
from jepsen_tpu.parallel import programs
from jepsen_tpu.parallel.encode import EncodedHistory, EncodeError
from jepsen_tpu.parallel.steps import STEPS
from jepsen_tpu.resilience import supervisor as sup

_log = logging.getLogger(__name__)


# ------------------------------------------------------------ device core


def _resolve_probe_limit(probe_limit: int = 0) -> int:
    """Bounded linear-probe length for the hash visited-set. A positive
    argument (the test seam threaded through the jits) wins; otherwise
    the validated JEPSEN_TPU_PROBE_LIMIT flag, default 32. At the
    table's <= 50% load factor (capacity 2N for an N-row frontier) a
    32-probe cluster is vanishingly rare under the mixed hash;
    exhaustion raises the overflow flag and rides the existing
    capacity-escalation retry (doubling N doubles the table, halving
    the load factor) instead of ever dropping a config. One knob for
    BOTH the XLA and the pallas hash paths — the host entry points
    resolve it eagerly so the value keys the jit cache (an env change
    between calls recompiles instead of going stale)."""
    if probe_limit and probe_limit > 0:
        return int(probe_limit)
    return envflags.env_int("JEPSEN_TPU_PROBE_LIMIT", default=32,
                            min_value=1, what="probe limit")


def _resolve_config_pack(config_pack) -> bool:
    """JEPSEN_TPU_CONFIG_PACK: pack each configuration's (state,
    mask_lo, mask_hi) triple into the minimal word the event actually
    needs (docs/performance.md "VMEM economics"). Strict tri-state
    (envflags.env_bool), default OFF until the chip A/B records the
    win — the PIPELINE/DEDUPE precedent; flag off means the engine
    runs the historical 3-lane layout byte-identically. An explicit
    argument wins over the env flag, like every other perf knob.
    Resolution yields only the REQUEST; whether a given event family
    actually packs is per-encode (pack_spec_for)."""
    if config_pack is None:
        return bool(envflags.env_bool("JEPSEN_TPU_CONFIG_PACK",
                                      default=False))
    return bool(config_pack)


def pack_layout(n_states: int, state_lo: int, C: int):
    """The packed-word layout for an event family whose states live in
    [state_lo, state_lo + n_states) with a C-slot open-call window, or
    None when the family cannot pack. The word is
    ``(state - state_lo) | mask << state_bits`` — state field in the
    low bits, the C mask bits above it — carried as one or two uint32
    lanes (Mosaic's native width). Packable iff the whole word fits 64
    bits and the state field fits one lane:
    ``state_bits + C <= 64 and state_bits <= 32``. Returns the static
    ``(state_bits, state_lo)`` pair that keys the jit cache."""
    if n_states <= 0 or C <= 0:
        return None
    state_bits = max(1, int(n_states - 1).bit_length())
    if state_bits > 32 or state_bits + C > 64:
        return None
    return (state_bits, int(state_lo))


def pack_spec_for(encs, C: Optional[int] = None):
    """The COMMON packed layout for one or more encoded histories that
    will share a device program (a batch pads to one slot width and
    traces one layout), or () when any of them cannot pack. The state
    field must cover every member's domain, so the layout uses the
    union range [min state_lo, max state_lo + n_states)."""
    if not isinstance(encs, (list, tuple)):
        encs = [encs]
    if not encs:
        return ()
    if any(e.n_states <= 0 for e in encs):
        return ()
    lo = min(e.state_lo for e in encs)
    hi = max(e.state_lo + e.n_states for e in encs)
    Cw = C if C is not None else max(e.slot_f.shape[1] for e in encs)
    lay = pack_layout(hi - lo, lo, Cw)
    return lay if lay is not None else ()


def pack_lanes(pack, C: int) -> int:
    """uint32 lanes one configuration row occupies under `pack` (the
    static (state_bits, state_lo) pair, or () for the historical
    unpacked triple). The VMEM gates price probe state per lane, so
    this is the number the width-aware kernel gates consume."""
    if not pack:
        return 3
    return 1 if pack[0] + C <= 32 else 2


def _resolve_sparse_pallas(sparse_pallas, N: int, C: int, platform: str,
                           dedupe: str, pack=()):
    """The sparse engine's fused-frontier-kernel gate -> (mode, note)
    with mode one of "off" / "interpret" / "tiled-interpret".

    `sparse_pallas` None defers to the strict tri-state
    JEPSEN_TPU_SPARSE_PALLAS flag (default OFF until a chip A/B
    records the win — the JEPSEN_TPU_PIPELINE / JEPSEN_TPU_DEDUPE
    precedent; "1" forces it on, in interpret mode off-TPU like
    JEPSEN_TPU_PALLAS). The kernel is the hash path's fused form, so
    requesting it under dedupe="sort" is a contradiction and raises.

    The gate is WIDTH-AWARE: probe state is priced per row lane
    (pack_lanes — 3 unpacked, 1-2 packed), so packed shapes clear it
    at ~3x the capacity. A shape past the whole-event fusion gate no
    longer degrades wholesale: it runs the TILED closure
    (sparse_kernels.tiled_insert_call — the hash table streams
    HBM<->VMEM in double-buffered tiles, mode "tiled-interpret"), and only a
    shape past the tiled planner too falls back to the XLA hash
    closure with a note.

    On a TPU the kernels are refused with NotImplementedError: the
    Mosaic compiler rejects their in-kernel 1-D gathers ("Only 2D
    gather is supported"), so they run only in interpret mode, off the
    chip — never interpreted on a chip, never swapped for XLA
    there."""
    if dedupe != "hash":
        if sparse_pallas:
            raise ValueError(
                "sparse_pallas=True requires dedupe='hash' — the fused "
                "frontier kernel is the hash path's implementation")
        if sparse_pallas is None and envflags.env_bool(
                "JEPSEN_TPU_SPARSE_PALLAS", default=False):
            # the env-only misconfiguration must be LOUD: "=1 forces it
            # on" with the dedupe flag left at sort would otherwise
            # read as kernel-measured while the kernel never ran — the
            # 'measured and lost' trap the perf_ab typo-guard closes
            _log.warning(
                "JEPSEN_TPU_SPARSE_PALLAS=1 has no effect under "
                "dedupe=%r — the fused frontier kernel is the hash "
                "path's implementation; set JEPSEN_TPU_DEDUPE=hash",
                dedupe)
        return "off", None
    if sparse_pallas is None:
        sparse_pallas = envflags.env_bool("JEPSEN_TPU_SPARSE_PALLAS",
                                          default=False)
    if not sparse_pallas:
        return "off", None
    from jepsen_tpu.parallel import sparse_kernels as sk
    from jepsen_tpu.parallel.bitdense import is_tpu_platform
    if is_tpu_platform(platform):
        raise NotImplementedError(
            "JEPSEN_TPU_SPARSE_PALLAS: the fused sparse frontier "
            "kernels do not compile for a TPU (Mosaic: \"Only 2D gather "
            "is supported\" for their in-kernel 1-D gathers); they run "
            "only in interpret mode off the chip. Unset the flag to use "
            "the XLA hash closure.")
    lanes = pack_lanes(pack, C)
    if sk.supported(N, C, lanes):
        return "interpret", None
    if sk.tiled_plan(N, C, lanes) is not None:
        return "tiled-interpret", None
    obs.counter("engine.sparse_pallas_fallbacks").inc()
    note = (f"sparse frontier kernel skipped at capacity {N} "
            f"(C={C}, {lanes} row lanes): probe state would exceed "
            f"the kernel's VMEM budget even tiled — fell back to the "
            f"XLA hash closure for this tier")
    _log.warning("%s", note)
    return "off", note


def _next_pow2(n: int) -> int:
    return 1 << max(1, (int(n) - 1).bit_length())


def _resolve_search_stats(search_stats) -> bool:
    """JEPSEN_TPU_SEARCH_STATS: device-resident search telemetry.
    Strict tri-state (envflags.env_bool), default OFF — the stats-off
    results, bench schema, and trace output are byte-identical to the
    pre-stats engine (parity-pinned). When on, every engine jit
    additionally returns a compact per-event stats block computed ON
    DEVICE (frontier width, closure iterations, delta split, hash
    load, probe-length histogram — docs/observability.md "Search
    telemetry"); an explicit argument wins over the env flag, the
    same contract as the other perf/telemetry knobs."""
    if search_stats is None:
        return bool(envflags.env_bool("JEPSEN_TPU_SEARCH_STATS",
                                      default=False))
    return bool(search_stats)


# Probe-length histogram buckets (final linear-probe offset per
# attempted insert): upper-exclusive split at these edges, plus an
# overflow bucket. Labels are the host-side vocabulary — the result
# "stats" dict, the engine.search.probe_len.* counters, and the bench
# line all use them, so the device bucketing and every sink agree.
PROBE_HIST_EDGES = (1, 2, 4, 8, 16, 32)
PROBE_HIST_LABELS = ("0", "1", "2-3", "4-7", "8-15", "16-31", "32+")
N_PROBE_BUCKETS = len(PROBE_HIST_LABELS)


def _probe_hist(off, attempted):
    """Bucketed histogram [N_PROBE_BUCKETS] of final probe offsets for
    the attempted inserts — the per-iteration increment the stats
    closure accumulates. Scalar-comparison bucketing (not a
    searchsorted over an edge array): this function runs INSIDE the
    fused pallas kernel, which cannot capture non-scalar constants."""
    idx = jnp.zeros_like(off)
    for edge in PROBE_HIST_EDGES:
        idx = idx + (off >= edge).astype(jnp.int32)
    return jnp.zeros(N_PROBE_BUCKETS, jnp.int32).at[
        jnp.where(attempted, idx, N_PROBE_BUCKETS)].add(1, mode="drop")


def _resolve_dedupe(dedupe: Optional[str]) -> str:
    """The frontier dedupe strategy: "sort" (lexsort + adjacent-compare,
    the historical path) or "hash" (delta-frontier closure over a
    device-resident open-addressed visited set). Default: the
    JEPSEN_TPU_DEDUPE env flag, else "sort" — opt-in until bench
    records the win, the same precedent as JEPSEN_TPU_PIPELINE
    (docs/performance.md "Dedup strategies")."""
    if dedupe is None:
        dedupe = envflags.env_choice("JEPSEN_TPU_DEDUPE",
                                     ("sort", "hash"), default="sort",
                                     what="dedupe strategy")
    if dedupe not in ("sort", "hash"):
        raise ValueError(f"unknown dedupe strategy {dedupe!r}")
    return dedupe


# --------------------------------------- configuration representation
#
# A configuration row travels the engine as a TUPLE OF LANE ARRAYS.
# The historical layout is three lanes — (state i32, mask_lo u32,
# mask_hi u32), 96 bits per config. Under JEPSEN_TPU_CONFIG_PACK the
# row is the minimal word the event family actually needs:
# (state - state_lo) in the low state_bits, the C mask bits above it,
# carried as one or two uint32 lanes (docs/performance.md "VMEM
# economics"). Everything that stores or moves rows — the hash
# visited-set, sort-dedupe compaction, frontier carries, the sharded
# owner-routed all-to-all payloads, the fused kernels — is generic
# over the lane tuple; only the few semantic touch points (the model
# step's state input, slot-bit tests) go through the ConfigRep below,
# so the packed and unpacked paths share one implementation and
# cannot diverge.


class _UnpackedRep:
    """The historical (state, mask_lo, mask_hi) triple. Its methods
    reproduce the pre-pack spellings verbatim — the flag-off engine is
    bit-identical by construction, not merely by test pin."""

    lanes = 3
    pack = ()

    def __init__(self, C: int):
        self.C = C

    def zeros(self, n: int):
        return (jnp.zeros(n, jnp.int32), jnp.zeros(n, jnp.uint32),
                jnp.zeros(n, jnp.uint32))

    def initial_at0(self, state0, N: int):
        return (jnp.zeros(N, jnp.int32).at[0].set(state0),
                jnp.zeros(N, jnp.uint32), jnp.zeros(N, jnp.uint32))

    def initial_full(self, state0, N: int):
        return (jnp.full(N, state0, jnp.int32),
                jnp.zeros(N, jnp.uint32), jnp.zeros(N, jnp.uint32))

    def state(self, rows):
        return rows[0]

    def table_hash(self, rows):
        """Slot mixing for the open-addressed visited set. Deliberately
        a DIFFERENT mix than owner_hash: the sharded engine buckets
        ownership by that hash mod n_dev, so a device's owned configs
        all share its low bits — reusing it for table slots would turn
        every per-device table into one giant collision cluster."""
        st, ml, mh = rows
        h = (st.astype(jnp.uint32) * jnp.uint32(0x85EBCA6B)) \
            ^ (ml * jnp.uint32(0xC2B2AE35)) \
            ^ (mh * jnp.uint32(0x27D4EB2F))
        h ^= h >> 16
        h = h * jnp.uint32(0x165667B1)
        h ^= h >> 13
        return h

    def owner_hash(self, rows):
        """sharded ownership mix (historically sharded._hash_config)."""
        st, ml, mh = rows
        h = (st.astype(jnp.uint32) * jnp.uint32(0x9E3779B1)) \
            ^ (ml * jnp.uint32(0x85EBCA77)) \
            ^ (mh * jnp.uint32(0xC2B2AE3D))
        h ^= h >> 15
        h = h * jnp.uint32(0x2C1B3C6D)
        h ^= h >> 12
        return h

    def slot_mask_bits(self):
        """Per-slot mask-lane bit arrays ([C] u32 per mask lane)."""
        return _slot_bits(self.C)

    def mask_test(self, rows):
        """[N, C] bool: slot j already linearized in row n."""
        _, ml, mh = rows
        bit_lo, bit_hi = self.slot_mask_bits()
        return ((ml[:, None] & bit_lo[None, :])
                | (mh[:, None] & bit_hi[None, :])) != 0

    def candidates(self, rows, cand_st):
        """Flattened [N*C] candidate rows: state from the model step,
        mask with slot j's bit set."""
        _, ml, mh = rows
        bit_lo, bit_hi = self.slot_mask_bits()
        return (cand_st.reshape(-1),
                (ml[:, None] | bit_lo[None, :]).reshape(-1),
                (mh[:, None] | bit_hi[None, :]).reshape(-1))

    def event_bits(self, s):
        """Per-mask-lane bit of the (traced u32 scalar) slot s."""
        one = jnp.uint32(1)
        blo = jnp.where(s < 32, one << jnp.minimum(s, 31),
                        jnp.uint32(0)).astype(jnp.uint32)
        bhi = jnp.where(s >= 32,
                        one << jnp.minimum(jnp.where(s >= 32, s - 32, 0),
                                           jnp.uint32(31)),
                        jnp.uint32(0)).astype(jnp.uint32)
        return blo, bhi

    def has_event_bit(self, rows, bits):
        _, ml, mh = rows
        blo, bhi = bits
        return ((ml & blo) | (mh & bhi)) != 0

    def clear_event_bit(self, rows, bits, where):
        st, ml, mh = rows
        blo, bhi = bits
        return (st, jnp.where(where, ml & ~blo, ml),
                jnp.where(where, mh & ~bhi, mh))


class _PackedRep:
    """The packed single-word layout: state field in bits
    [0, state_bits), mask bits at [state_bits, state_bits + C), one
    uint32 lane when the word fits 32 bits, two lanes (lo, hi of the
    uint64 word) otherwise."""

    def __init__(self, state_bits: int, state_lo: int, C: int):
        self.s_bits = int(state_bits)
        self.state_lo = int(state_lo)
        self.C = C
        self.width = self.s_bits + C
        assert self.s_bits <= 32 and self.width <= 64
        self.lanes = 1 if self.width <= 32 else 2
        self.pack = (self.s_bits, self.state_lo)
        self._smask = (1 << self.s_bits) - 1

    @property
    def smask(self):
        # constructed lazily so kernel bodies create the constant
        # INSIDE their trace — a stored jnp scalar would be a captured
        # constant, which pallas_call rejects
        return jnp.uint32(self._smask)

    def zeros(self, n: int):
        return tuple(jnp.zeros(n, jnp.uint32)
                     for _ in range(self.lanes))

    def _field(self, st):
        # legal states are in [state_lo, state_lo + n_states) — the
        # same bound bitdense's bitmap indexing relies on; the mask
        # keeps a garbage state on a dead candidate from spilling into
        # the mask bits (dead rows are never inserted, but their lanes
        # must not poison scatters' defensive reads)
        return (st - self.state_lo).astype(jnp.uint32) & self.smask

    def initial_at0(self, state0, N: int):
        lo = jnp.zeros(N, jnp.uint32).at[0].set(self._field(state0))
        return (lo,) if self.lanes == 1 else (lo,
                                              jnp.zeros(N, jnp.uint32))

    def initial_full(self, state0, N: int):
        lo = jnp.full(N, 1, jnp.uint32) * self._field(state0)
        return (lo,) if self.lanes == 1 else (lo,
                                              jnp.zeros(N, jnp.uint32))

    def state(self, rows):
        return (rows[0] & self.smask).astype(jnp.int32) + self.state_lo

    def table_hash(self, rows):
        h = rows[0] * jnp.uint32(0x85EBCA6B)
        if self.lanes == 2:
            h = h ^ (rows[1] * jnp.uint32(0xC2B2AE35))
        h ^= h >> 16
        h = h * jnp.uint32(0x165667B1)
        h ^= h >> 13
        return h

    def owner_hash(self, rows):
        h = rows[0] * jnp.uint32(0x9E3779B1)
        if self.lanes == 2:
            h = h ^ (rows[1] * jnp.uint32(0x85EBCA77))
        h ^= h >> 15
        h = h * jnp.uint32(0x2C1B3C6D)
        h ^= h >> 12
        return h

    def slot_mask_bits(self):
        js = jnp.arange(self.C, dtype=jnp.uint32) \
            + jnp.uint32(self.s_bits)
        one = jnp.uint32(1)
        blo = jnp.where(js < 32, one << jnp.minimum(js, 31),
                        jnp.uint32(0)).astype(jnp.uint32)
        if self.lanes == 1:
            return (blo,)
        bhi = jnp.where(js >= 32,
                        one << jnp.minimum(js - 32, jnp.uint32(31)),
                        jnp.uint32(0)).astype(jnp.uint32)
        return blo, bhi

    def mask_test(self, rows):
        bits = self.slot_mask_bits()
        acc = (rows[0][:, None] & bits[0][None, :])
        if self.lanes == 2:
            acc = acc | (rows[1][:, None] & bits[1][None, :])
        return acc != 0

    def candidates(self, rows, cand_st):
        bits = self.slot_mask_bits()
        lo = (((rows[0][:, None] & ~self.smask)
               | self._field(cand_st)) | bits[0][None, :]).reshape(-1)
        if self.lanes == 1:
            return (lo,)
        hi = (rows[1][:, None] | bits[1][None, :]).reshape(-1)
        return lo, hi

    def event_bits(self, s):
        p = s + jnp.uint32(self.s_bits)
        one = jnp.uint32(1)
        blo = jnp.where(p < 32, one << jnp.minimum(p, 31),
                        jnp.uint32(0)).astype(jnp.uint32)
        if self.lanes == 1:
            return (blo,)
        bhi = jnp.where(p >= 32,
                        one << jnp.minimum(jnp.where(p >= 32, p - 32, 0),
                                           jnp.uint32(31)),
                        jnp.uint32(0)).astype(jnp.uint32)
        return blo, bhi

    def has_event_bit(self, rows, bits):
        acc = rows[0] & bits[0]
        if self.lanes == 2:
            acc = acc | (rows[1] & bits[1])
        return acc != 0

    def clear_event_bit(self, rows, bits, where):
        return tuple(jnp.where(where, r & ~b, r)
                     for r, b in zip(rows, bits))


def _rep(pack, C: int):
    """The ConfigRep for a static (pack, C) pair — pack is () for the
    historical triple, (state_bits, state_lo) for the packed word."""
    if pack:
        return _PackedRep(pack[0], pack[1], C)
    return _UnpackedRep(C)


def pack_rows_np(pack, C: int, st, ml, mh):
    """Host-side (numpy) packing of canonical (st, ml, mh) rows into
    the lane tuple the (pack, C) layout describes — the
    FrontierCheckpoint boundary: checkpoints store the canonical
    triple (so v1/v2 files, serve freeze/thaw, host_resume seeds, and
    cross-representation resume all keep working) and the engine packs
    at the carry build. Lane count is STATIC (1 when the word fits 32
    bits, else 2) — it must match what the traced program expects."""
    s_bits, s_lo = pack
    word = ((np.asarray(st).astype(np.int64) - s_lo)
            .astype(np.uint64) & np.uint64((1 << s_bits) - 1))
    mask = (np.asarray(ml).astype(np.uint64)
            | (np.asarray(mh).astype(np.uint64) << np.uint64(32)))
    word = word | (mask << np.uint64(s_bits))
    lo = (word & np.uint64(0xFFFFFFFF)).astype(np.uint32)
    if s_bits + C <= 32:
        return (lo,)
    return lo, (word >> np.uint64(32)).astype(np.uint32)


def unpack_rows_np(pack, C: int, rows):
    """Inverse of pack_rows_np: lane tuple -> canonical (st, ml, mh)
    numpy triple."""
    s_bits, s_lo = pack
    lo = np.asarray(rows[0]).astype(np.uint64)
    word = lo if len(rows) == 1 else \
        lo | (np.asarray(rows[1]).astype(np.uint64) << np.uint64(32))
    st = (word & np.uint64((1 << s_bits) - 1)).astype(np.int64) + s_lo
    mask = (word >> np.uint64(s_bits)) \
        & np.uint64((1 << C) - 1 if C < 64 else 0xFFFFFFFFFFFFFFFF)
    ml = (mask & np.uint64(0xFFFFFFFF)).astype(np.uint32)
    mh = (mask >> np.uint64(32)).astype(np.uint32)
    return st.astype(np.int32), ml, mh


def _rows_eq(a_rows, b_rows):
    acc = a_rows[0] == b_rows[0]
    for a, b in zip(a_rows[1:], b_rows[1:]):
        acc = acc & (a == b)
    return acc


def _rows_take(rows, idx):
    return tuple(r[idx] for r in rows)


def _rows_concat(a_rows, b_rows):
    return tuple(jnp.concatenate([a, b])
                 for a, b in zip(a_rows, b_rows))


def _rows_where(cond, a_rows, b_rows):
    return tuple(jnp.where(cond, a, b)
                 for a, b in zip(a_rows, b_rows))


def _rows_at_set(rows, pos, vals):
    return tuple(r.at[pos].set(v, mode="drop")
                 for r, v in zip(rows, vals))


def _empty_table(T: int, rep):
    return (rep.zeros(T), jnp.zeros(T, bool))


def _hash_insert(c_rows, c_live, table, probe_limit: int, rep,
                 h0=None):
    """Parallel bounded-linear-probe insert of candidate config rows
    (a lane tuple, `rep`'s layout) into the open-addressed visited set
    `table` ((rows, occ) with lane arrays of one power-of-two length
    T).

    Each live candidate probes from rep.table_hash(row) & (T-1) (or
    from the caller-supplied `h0` start slots — the tiled kernel
    probes within a table tile); per round it drops on an equal
    occupant (already visited), claims an empty slot (racing claimants
    are arbitrated by a scatter-min of the candidate index; losers
    RE-CHECK the same slot next round, because the winner there may
    hold an equal key — a duplicate inside this same batch), or
    advances past an occupied different slot. The loop runs until
    every candidate resolves or exhausts `probe_limit` probes
    (<= 2*probe_limit rounds: every pending candidate resolves or
    advances at least every second round).

    Returns (table', fresh, overflow, off): `fresh` flags candidates
    that claimed a slot (first sighting), `overflow` that some
    candidate exhausted its probes — the caller escalates capacity, it
    never silently drops a config. `off` is each candidate's final
    probe offset (the stats path histograms it; other callers ignore
    it — dead code under jit)."""
    t_rows, t_occ = table
    M = c_rows[0].shape[0]
    T = t_rows[0].shape[0]
    maskT = jnp.uint32(T - 1)
    if h0 is None:
        h0 = rep.table_hash(c_rows)
    idx = jnp.arange(M, dtype=jnp.int32)

    def cond(s):
        return jnp.any(s["pending"] & (s["off"] < probe_limit))

    def body(s):
        t_rows, t_occ = s["table"]
        pending, off, fresh = s["pending"], s["off"], s["fresh"]
        act = pending & (off < probe_limit)
        slot = ((h0 + off.astype(jnp.uint32)) & maskT).astype(jnp.int32)
        occ = t_occ[slot]
        same = occ & _rows_eq(_rows_take(t_rows, slot), c_rows)
        try_claim = act & ~occ
        claim = jnp.full(T, M, jnp.int32).at[
            jnp.where(try_claim, slot, T)].min(idx, mode="drop")
        won = try_claim & (claim[slot] == idx)
        wslot = jnp.where(won, slot, T)
        t_rows = _rows_at_set(t_rows, wslot, c_rows)
        t_occ = t_occ.at[wslot].set(True, mode="drop")
        return {"table": (t_rows, t_occ),
                "pending": pending & ~(act & same) & ~won,
                "off": off + (act & occ & ~same).astype(jnp.int32),
                "fresh": fresh | won}

    out = lax.while_loop(cond, body, {
        "table": (t_rows, t_occ), "pending": c_live,
        "off": jnp.zeros(M, jnp.int32), "fresh": jnp.zeros(M, bool)})
    return out["table"], out["fresh"], jnp.any(out["pending"]), out["off"]


def _append_fresh(c_rows, fresh, f_rows, count, N: int):
    """The append half of one visited-set transaction: fresh rows land
    contiguously after `count` in the frontier lane arrays. Returns
    (rows2, count2, n_fresh, append_ovf)."""
    n_fresh = jnp.sum(fresh)
    pos = jnp.where(fresh, count + jnp.cumsum(fresh) - 1, N)
    rows2 = _rows_at_set(f_rows, pos, c_rows)
    return (rows2, jnp.minimum(count + n_fresh, N), n_fresh,
            count + n_fresh > N)


def _hash_insert_append(c_rows, c_live, f_rows, count, table,
                        probe_limit: int, N: int, rep,
                        stats: bool = False):
    """_hash_insert plus the contiguous append of the fresh rows after
    `count` — one closure iteration's whole visited-set transaction.
    Shared verbatim by the XLA hash path, the fused frontier kernel
    (sparse_kernels.frontier_closure_call via _hash_event_closure), and
    the sharded per-device insert kernel (sparse_kernels.
    hash_insert_call), so the implementations cannot diverge.

    Returns (rows2, table2, count2, n_fresh, ovf): `ovf` is probe
    exhaustion OR the append running past the N-row frontier (rows
    past N scatter-drop; the flag aborts before anything consumes
    them). With `stats` (static; JEPSEN_TPU_SEARCH_STATS), a sixth
    element: the bucketed probe-length histogram [N_PROBE_BUCKETS] of
    this transaction's attempted inserts."""
    table2, fresh, p_ovf, off = _hash_insert(c_rows, c_live, table,
                                             probe_limit, rep)
    rows2, count2, n_fresh, a_ovf = _append_fresh(c_rows, fresh,
                                                  f_rows, count, N)
    out = (rows2, table2, count2, n_fresh, p_ovf | a_ovf)
    if stats:
        return out + (_probe_hist(off, c_live),)
    return out


def _hash_event_closure(rep, step_cc, ev, rows, live, run, N: int,
                        T: int, probe_limit: int, stats: bool = False,
                        insert=None):
    """The whole per-event delta-frontier closure (dedupe="hash") on
    plain lane arrays: seed the fresh visited set with the live
    frontier (compacting it in the same pass — post-filter frontiers
    have holes; iteration 0's delta is the whole frontier, exactly the
    rows the sort path would step first), then expand only the delta
    until no fresh configs appear. Shared VERBATIM by the XLA path
    (_scan_step_factory), the fused pallas kernel
    (sparse_kernels.frontier_closure_call runs exactly this function
    over VMEM-resident values), and — via the `insert` hook — the
    tiled closure, whose per-iteration visited-set transaction streams
    the table through sparse_kernels.tiled_insert_call while the
    expansion stays here. The implementations cannot diverge.

    Returns (rows2, count, ovf, iters, stepped) with `stepped` the
    configs expanded during THIS event's closure. With `stats`
    (static), two more: `swork` — the configs a SORT closure would
    have stepped for the same event (whole frontier per iteration; the
    delta-split ratio's denominator) — and the probe-length histogram
    [N_PROBE_BUCKETS] accumulated over the seed insert and every
    iteration's transaction."""
    if insert is None:
        def insert(c_rows, c_live, f_rows, count, table):
            return _hash_insert_append(c_rows, c_live, f_rows, count,
                                       table, probe_limit, N, rep,
                                       stats=stats)
    seed = insert(rows, live, rep.zeros(N), jnp.int32(0),
                  _empty_table(T, rep))
    rows0, table, m0, _, p0 = seed[:5]

    def cond(c):
        return c["changed"] & ~c["ovf"]

    def body(c):
        rows = c["rows"]
        n_old, count = c["n_old"], c["count"]
        cand_st, cand_ok = step_cc(rep.state(rows), ev["slot_f"],
                                   ev["slot_a0"], ev["slot_a1"],
                                   ev["slot_wild"])
        row = jnp.arange(N)
        delta = (row >= n_old) & (row < count)
        already = rep.mask_test(rows)
        legal = (delta[:, None] & ev["slot_occ"][None, :]
                 & ~already & cand_ok)
        ins = insert(rep.candidates(rows, cand_st), legal.reshape(-1),
                     rows, count, c["table"])
        rows2, table2, count2, n_fresh, ins_ovf = ins[:5]
        out = {"rows": rows2,
               "n_old": count, "count": count2, "table": table2,
               "changed": n_fresh > 0,
               "ovf": c["ovf"] | ins_ovf,
               "iters": c["iters"] + 1,
               "stepped": c["stepped"] + (count - n_old)}
        if stats:
            # swork: what sort would have re-stepped — the WHOLE live
            # frontier this iteration, not just the delta
            out["swork"] = c["swork"] + count
            out["phist"] = c["phist"] + ins[5]
        return out

    carry0 = {
        "rows": rows0,
        "n_old": jnp.int32(0), "count": m0, "table": table,
        "changed": run, "ovf": p0, "iters": jnp.int32(0),
        "stepped": jnp.int32(0)}
    if stats:
        carry0["swork"] = jnp.int32(0)
        carry0["phist"] = seed[5]
    out = lax.while_loop(cond, body, carry0)
    base = (out["rows"], out["count"], out["ovf"], out["iters"],
            out["stepped"])
    if stats:
        return base + (out["swork"], out["phist"])
    return base


def _slot_bits(C: int):
    js = jnp.arange(C, dtype=jnp.uint32)
    one = jnp.uint32(1)
    bit_lo = jnp.where(js < 32, one << jnp.minimum(js, 31),
                       jnp.uint32(0)).astype(jnp.uint32)
    bit_hi = jnp.where(js >= 32, one << jnp.minimum(js - 32, jnp.uint32(31)),
                       jnp.uint32(0)).astype(jnp.uint32)
    return bit_lo, bit_hi


def _rows_prev_same(rows_s):
    acc = rows_s[0][1:] == rows_s[0][:-1]
    for r in rows_s[1:]:
        acc = acc & (r[1:] == r[:-1])
    return jnp.concatenate([jnp.zeros(1, bool), acc])


def _dedupe_compact(rows, live, N, rep):
    """Sort rows by (dead, lanes major-to-minor), flag first
    occurrences, compact into a fresh [N] frontier. Returns (rows,
    live, count, overflow). Lane-generic: the unpacked triple sorts by
    (state, mask_lo, mask_hi) exactly as before; the packed word sorts
    by its lanes."""
    M = rows[0].shape[0]
    order = jnp.lexsort((*reversed(rows), (~live).astype(jnp.int8)))
    rows_s = _rows_take(rows, order)
    live_s = live[order]
    uniq = live_s & ~_rows_prev_same(rows_s)
    count = jnp.sum(uniq)
    pos = jnp.where(uniq, jnp.cumsum(uniq) - 1, M + N)  # OOB -> dropped
    new_rows = _rows_at_set(rep.zeros(N), pos, rows_s)
    new_live = jnp.arange(N) < count
    return new_rows, new_live, count, count > N


def _initial_carry(state0, N: int, rep):
    """The scan carry at event 0: one live config (the initial model
    state, nothing linearized). The trailing int32 is the
    configs-stepped counter (closure work actually paid, in configs
    expanded — see _scan_step_factory). The carry is
    (*row_lanes, live, ok, fail_r, r_idx, maxf, steps_n, stepped) —
    lane count is the representation's (3 unpacked, 1-2 packed)."""
    rows0 = rep.initial_at0(state0, N)
    live0 = jnp.arange(N) < 1
    return rows0 + (live0, jnp.array(True), jnp.int32(-1),
                    jnp.int32(0), jnp.int32(1), jnp.int32(0),
                    jnp.int32(0))


def _scan_step_factory(step_name: str, N: int, C: int,
                       dedupe: str = "sort", probe_limit: int = 0,
                       sparse_pallas: str = "off",
                       search_stats: bool = False,
                       pack: tuple = ()):
    """The per-return-event scan step, parameterized by model step,
    frontier capacity, slot-window width, and dedupe strategy. Shared
    by the one-shot and the resumable (checkpointed) entry points.

    dedupe="sort": every closure iteration re-steps the WHOLE live
    frontier and dedupes by a full lexsort over all N*(C+1) candidate
    rows — the historical path.

    dedupe="hash": the delta-frontier closure (_hash_event_closure).
    The frontier is kept compacted, the closure carry holds a split
    index (rows [0, n_old) were expanded in earlier iterations, rows
    [n_old, count) are the delta discovered last iteration), only the
    delta expands, and membership is an open-addressed hash
    visited-set (capacity _next_pow2(2N), _hash_insert) reused across
    all closure iterations of one return event — each configuration is
    expanded exactly once per event, the Wing&Gong/Lowe seen-set
    realised on-device. Probe exhaustion raises the overflow flag and
    rides the same capacity-escalation retry as a full frontier.
    Verdicts, counterexample localization, max-frontier and iteration
    counts are identical to the sort path (frontier ROW ORDER differs;
    tests pin everything order-independent).

    `sparse_pallas` ("off"/"on"/"interpret", resolved by
    _resolve_sparse_pallas) fuses the whole per-event hash closure into
    ONE pallas_call (parallel.sparse_kernels): candidate rows, the
    visited-set table, and the event's slot tables stay VMEM-resident
    for every closure iteration, so the N*(C+1) candidate arrays never
    round-trip HBM and the probe/claim while_loops cost no
    per-iteration dispatch. The kernel body IS _hash_event_closure, so
    results are identical by construction.

    Both strategies accumulate a configs-stepped counter (sort: the
    whole live frontier per iteration; hash: the delta) — the counter
    that makes the delta win measurable even on CPU advisory runs.

    `search_stats` (static; JEPSEN_TPU_SEARCH_STATS) switches the
    scan's per-event output from the bare overflow flag to a dict of
    per-event device-computed stats: post-filter frontier width (-1 on
    events that did not run — pads, post-failure), closure peak (the
    pre-filter frontier, which under hash equals the visited-table
    occupancy), iterations, per-event configs-stepped, the
    sort-equivalent work (delta-split denominator), and the bucketed
    probe-length histogram (zeros under sort). Verdict-carrying
    outputs are untouched — stats-on/off parity is pinned.

    `pack` (static; JEPSEN_TPU_CONFIG_PACK via pack_spec_for) selects
    the configuration-row layout: () is the historical (state,
    mask_lo, mask_hi) triple; (state_bits, state_lo) the packed word
    carried as 1-2 uint32 lanes. The scan carry is
    (*row_lanes, live, ok, fail_r, r_idx, maxf, steps_n, stepped) —
    every path below is lane-generic, so verdicts, counterexample
    localization, max-frontier, and configs-stepped are identical
    across layouts (parity-pinned)."""
    step = STEPS[step_name]
    rep = _rep(pack, C)
    if probe_limit <= 0:
        # host entry points resolve eagerly; this is the safety net for
        # internal callers (e.g. _frontier_at's default-arg path)
        probe_limit = _resolve_probe_limit(0)
    T = _next_pow2(2 * N)

    # model step vmapped over configs x slots
    step_cc = jax.vmap(
        jax.vmap(step, in_axes=(None, 0, 0, 0, 0)),  # over slots
        in_axes=(0, None, None, None, None),         # over configs
    )

    def closure_cond(c):
        return c["changed"] & ~c["ovf"]

    def make_closure_body(ev):
        def body(c):
            rows, live = c["rows"], c["live"]
            cand_st, cand_ok = step_cc(
                rep.state(rows), ev["slot_f"], ev["slot_a0"],
                ev["slot_a1"], ev["slot_wild"]
            )
            already = rep.mask_test(rows)
            legal = (live[:, None] & ev["slot_occ"][None, :]
                     & ~already & cand_ok)
            all_rows = _rows_concat(rows, rep.candidates(rows, cand_st))
            all_live = jnp.concatenate([live, legal.reshape(-1)])
            old_count = jnp.sum(live)
            rows2, live2, count, ovf = _dedupe_compact(
                all_rows, all_live, N, rep)
            return {"rows": rows2, "live": live2,
                    "changed": count > old_count, "ovf": ovf,
                    "iters": c["iters"] + 1,
                    "stepped": c["stepped"] + old_count}
        return body

    zero_hist = jnp.zeros(N_PROBE_BUCKETS, jnp.int32)
    tiled_mode = sparse_pallas in ("tiled", "tiled-interpret")
    if tiled_mode:
        from jepsen_tpu.parallel import sparse_kernels as sk
        tiled_plan = sk.tiled_plan(N, C, rep.lanes)

    def make_tiled_insert(interpret: bool):
        """The `insert` hook that streams the visited-set transaction
        through the tiled kernel (probe/claim in VMEM tiles) while the
        append stays XLA-side — the closure around it is byte-for-byte
        _hash_event_closure."""
        from jepsen_tpu.parallel import sparse_kernels as sk

        def insert(c_rows, c_live, f_rows, count, table):
            table2, fresh, off, p_ovf = sk.tiled_insert_call(
                c_rows, c_live, table, probe_limit, tiled_plan, pack,
                C, interpret=interpret)
            rows2, count2, n_fresh, a_ovf = _append_fresh(
                c_rows, fresh, f_rows, count, N)
            out = (rows2, table2, count2, n_fresh, p_ovf | a_ovf)
            if search_stats:
                return out + (_probe_hist(off, c_live),)
            return out
        return insert

    def run_closure(ev, rows, live, run, stepped):
        """-> (rows2, live2, ovf, iters, stepped2, extras) where
        extras is (swork_delta, probe_hist) under search_stats (sort:
        swork == the stepped delta, hist zeros) and None otherwise."""
        if dedupe == "sort":
            out = lax.while_loop(
                closure_cond, make_closure_body(ev),
                {"rows": rows, "live": live, "changed": run,
                 "ovf": jnp.array(False), "iters": jnp.int32(0),
                 "stepped": stepped})
            stepped2 = out["stepped"]
            extras = ((stepped2 - stepped, zero_hist)
                      if search_stats else None)
            return (out["rows"], out["live"], out["ovf"], out["iters"],
                    stepped2, extras)
        if sparse_pallas in ("on", "interpret"):
            # the fused kernel: the whole per-event closure inside one
            # pallas_call, frontier + table + slot tables VMEM-resident
            from jepsen_tpu.parallel import sparse_kernels as sk
            out = sk.frontier_closure_call(
                step_name, ev, rows, live, run, N, C,
                probe_limit, pack,
                interpret=(sparse_pallas == "interpret"),
                stats=search_stats)
        elif tiled_mode:
            out = _hash_event_closure(
                rep, step_cc, ev, rows, live, run, N, T, probe_limit,
                stats=search_stats,
                insert=make_tiled_insert(
                    sparse_pallas == "tiled-interpret"))
        else:
            out = _hash_event_closure(
                rep, step_cc, ev, rows, live, run, N, T,
                probe_limit, stats=search_stats)
        rows2, count, ovf, iters, d = out[:5]
        extras = (out[5], out[6]) if search_stats else None
        live2 = jnp.arange(N) < count
        return rows2, live2, ovf, iters, stepped + d, extras

    L = rep.lanes

    def scan_step(carry, ev):
        rows = carry[:L]
        live, ok, fail_r, r_idx, maxf, steps_n, stepped = carry[L:]
        is_pad = ev["ev_slot"] < 0
        run = ok & ~is_pad

        # closure: expand until no new configs (skipped when run=False:
        # the initial `changed` flag is `run`)
        rows2, live2, ovf, iters, stepped2, extras = run_closure(
            ev, rows, live, run, stepped)
        # the hash prologue runs unconditionally (lax.scan cannot skip
        # an event) — a pad/settled event's probe flag must not leak
        # into the host's capacity-escalation decision
        ovf = run & ovf

        # filter: returning call must have linearized; then free its slot
        s = jnp.maximum(ev["ev_slot"], 0).astype(jnp.uint32)
        bits = rep.event_bits(s)
        has = rep.has_event_bit(rows2, bits)
        live3 = live2 & has
        rows3 = rep.clear_event_bit(rows2, bits, live3)
        n_live = jnp.sum(live3)
        failed_here = run & (n_live == 0)

        new_ok = jnp.where(run, ~failed_here & ~ovf, ok)
        new_fail = jnp.where(failed_here & (fail_r < 0), r_idx, fail_r)
        rows_o = _rows_where(run, rows3, rows)
        live_o = jnp.where(run, live3, live)
        maxf = jnp.maximum(maxf, jnp.where(run, jnp.sum(live2), 0))
        # count closure iterations only; the host multiplies by N*C in
        # Python (int32 would overflow at large capacities). The
        # configs-stepped counter is the TRUE work: configs actually
        # expanded (sort: whole frontier per iteration; hash: the
        # delta) — both strategies record it so the reduction is
        # visible in the same units.
        steps_n = steps_n + jnp.where(run, iters, 0)
        stepped_o = jnp.where(run, stepped2, stepped)
        # pad events do not advance the return-event index: a resumed
        # carry's r_idx must equal the number of REAL events processed
        # so a checkpoint taken after a quantum-padded chunk (the
        # streaming extension pads chunks to few jit shapes, and the
        # batched form interleaves per-key pads) resumes at the right
        # event. Identical for the historical paths — their pads only
        # ever trail the last real event.
        carry_o = rows_o + (live_o, new_ok, new_fail,
                            r_idx + jnp.where(is_pad, 0, 1), maxf,
                            steps_n, stepped_o)
        if not search_stats:
            return carry_o, ovf
        # per-event device stats: width -1 marks "did not run" (pad or
        # post-failure) — the host filters on it, so padded chunks and
        # batch interleaving need no extra bookkeeping
        y = {
            "ovf": ovf,
            "width": jnp.where(run, n_live, -1).astype(jnp.int32),
            "peak": jnp.where(run, jnp.sum(live2), 0).astype(jnp.int32),
            "iters": jnp.where(run, iters, 0).astype(jnp.int32),
            "stepped": jnp.where(run, stepped2 - stepped,
                                 0).astype(jnp.int32),
            "swork": jnp.where(run, extras[0], 0).astype(jnp.int32),
            "phist": jnp.where(run, extras[1], 0).astype(jnp.int32),
        }
        return carry_o, y

    return scan_step


def _check_impl(xs, state0, step_name: str, N: int,
                dedupe: str = "sort", probe_limit: int = 0,
                sparse_pallas: str = "off",
                search_stats: bool = False, pack: tuple = ()):
    """Scan over all return events from scratch. xs: dict of [R, ...]
    arrays. Returns (valid, fail_event, overflow, max_frontier,
    steps_evaluated, configs_stepped) — plus, under `search_stats`,
    the per-event stats dict of [R]-stacked arrays."""
    C = xs["slot_f"].shape[1]
    rep = _rep(pack, C)
    carry0 = _initial_carry(state0, N, rep)
    carry, ys = lax.scan(
        _scan_step_factory(step_name, N, C, dedupe, probe_limit,
                           sparse_pallas, search_stats, pack),
        carry0, xs)
    live, ok, fail_r, _, maxf, steps_n, stepped = carry[rep.lanes:]
    ovfs = ys["ovf"] if search_stats else ys
    overflow = jnp.any(ovfs)
    valid = ok & (jnp.sum(live) > 0) & ~overflow
    base = (valid, fail_r, overflow, maxf, steps_n, stepped)
    if search_stats:
        return base + (ys,)
    return base


# donation decision (recompile-donate-argnums), DECIDED: the resumable
# jits DONATE their frontier carry — it is rebuilt per call from the
# host-side FrontierCheckpoint (cp.carry / extend's _stack_carries
# place fresh device arrays every dispatch, including the
# overflow-retry and _frontier_at paths), the output carry aliases it
# exactly (same shapes/dtypes), and at the top capacity tiers the
# carry IS the peak-HBM buffer — donation halves it. xs/state0 are NOT
# donated anywhere: the one-shot escalation loop re-dispatches the
# SAME xs arrays at doubled N after an overflow, and no output aliases
# the event tables (donating them would only trade the retry inputs
# for an unusable-donation warning).
@functools.partial(jax.jit,
                   donate_argnames=("carry0",),
                   static_argnames=("step_name", "N", "dedupe",
                                    "probe_limit", "sparse_pallas",
                                    "search_stats", "pack"))
def _check_device_resumable(xs, carry0, step_name: str, N: int,
                            dedupe: str = "sort", probe_limit: int = 0,
                            sparse_pallas: str = "off",
                            search_stats: bool = False,
                            pack: tuple = ()):
    """One chunk of events from an explicit carry; returns the final
    carry plus the overflow flag so the host can checkpoint between
    chunks. Under `search_stats` a third output: the chunk's
    per-event stats dict (pad rows carry width=-1; the stats arrays
    are per-call scan outputs, NOT part of the carry, so checkpoints
    and their format are untouched)."""
    C = xs["slot_f"].shape[1]
    carry, ys = lax.scan(
        _scan_step_factory(step_name, N, C, dedupe, probe_limit,
                           sparse_pallas, search_stats, pack),
        carry0, xs)
    if search_stats:
        return carry, jnp.any(ys["ovf"]), ys
    return carry, jnp.any(ys)


# donation decision, DECIDED: nothing donatable — see the block
# comment above _check_device_resumable (xs is reused across the
# capacity-escalation retries; every output is a scalar)
_check_device = jax.jit(_check_impl,
                        donate_argnums=(),
                        static_argnames=("step_name", "N", "dedupe",
                                         "probe_limit", "sparse_pallas",
                                         "search_stats", "pack"))


# donation decision, DECIDED: nothing donatable — the batch tier loop
# re-dispatches pending keys from freshly placed arrays, but every
# output is a per-key scalar, so no input buffer can alias an output
@functools.partial(jax.jit,
                   donate_argnums=(),
                   static_argnames=("step_name", "N", "dedupe",
                                    "probe_limit", "sparse_pallas",
                                    "search_stats", "pack"))
def _check_device_batch(xs, state0, step_name: str, N: int,
                        dedupe: str = "sort", probe_limit: int = 0,
                        sparse_pallas: str = "off",
                        search_stats: bool = False, pack: tuple = ()):
    return jax.vmap(
        lambda x, s0: _check_impl(x, s0, step_name, N, dedupe,
                                  probe_limit, sparse_pallas,
                                  search_stats, pack)
    )(xs, state0)


# donation decision, DECIDED: the stacked per-key carry donates — same
# rationale as _check_device_resumable (extend builds it fresh per
# dispatch; overflowed members fall back to their solo path from the
# host-side checkpoint, never from these device arrays)
@functools.partial(jax.jit,
                   donate_argnames=("carry0",),
                   static_argnames=("step_name", "N", "dedupe",
                                    "probe_limit", "sparse_pallas",
                                    "search_stats", "pack"))
def _check_device_batch_resumable(xs, carry0, step_name: str, N: int,
                                  dedupe: str = "sort",
                                  probe_limit: int = 0,
                                  sparse_pallas: str = "off",
                                  search_stats: bool = False,
                                  pack: tuple = ()):
    """The streaming extension's batched scan: one chunk of events per
    key from an explicit per-key carry — jepsen_tpu.parallel.extend
    stacks shape-compatible sessions' frontiers and advances them in
    ONE device program (the cross-key delta batching the serve layer
    dispatches). Pad events (ev_slot < 0) leave a key's carry
    untouched, event index included, so per-key chunks of different
    real lengths share the padded shape. Returns (carry_batch,
    overflow[K]) — plus the per-key per-event stats dict under
    `search_stats` (width=-1 rows are that key's pads)."""
    C = xs["slot_f"].shape[2]
    step = _scan_step_factory(step_name, N, C, dedupe, probe_limit,
                              sparse_pallas, search_stats, pack)

    if search_stats:
        def one_s(x, c):
            carry, ys = lax.scan(step, c, x)
            return carry, jnp.any(ys["ovf"]), ys
        return jax.vmap(one_s)(xs, carry0)

    def one(x, c):
        carry, ovfs = lax.scan(step, c, x)
        return carry, jnp.any(ovfs)

    return jax.vmap(one)(xs, carry0)


# ------------------------------------------- compile-economics seam

# The AOT-managed engine entries (jepsen_tpu.parallel.programs): name
# -> (entry attr, traced-arg count, static names in the positional
# order every call site uses). Attrs resolve through globals() at
# call time so a test that monkeypatches an entry keeps its patch —
# and a patched entry without .lower() falls back to the plain call.
_PROGRAM_STATICS = ("step_name", "N", "dedupe", "probe_limit",
                    "sparse_pallas", "search_stats", "pack")
_PROGRAM_ENTRIES = {
    "engine.check": ("_check_device", 2, _PROGRAM_STATICS),
    "engine.check_resumable": ("_check_device_resumable", 2,
                               _PROGRAM_STATICS),
    "engine.check_batch": ("_check_device_batch", 2,
                           _PROGRAM_STATICS),
    "engine.check_batch_resumable": ("_check_device_batch_resumable",
                                     2, _PROGRAM_STATICS),
}


def program_entries() -> dict:
    """name -> (jitted entry, n_traced, static_names): what
    programs.ProgramRegistry.warm_manifest pre-warms from (the serve
    adopter's rehome path)."""
    return {name: (globals()[attr], n, statics)
            for name, (attr, n, statics) in _PROGRAM_ENTRIES.items()}


def _run_program(name: str, *args):
    """Dispatch one engine jit entry through the program registry when
    JEPSEN_TPU_COMPILE_CACHE arms it — AOT lower().compile(), the
    hit/miss/compile ledger, disk persistence, ladder precompile —
    else the plain jit call. Flag off is byte-identical: same entry,
    same args, no registry, no new metrics."""
    attr, n_traced, static_names = _PROGRAM_ENTRIES[name]
    entry = globals()[attr]
    reg = programs.registry()
    if reg is None or not hasattr(entry, "lower"):
        return entry(*args)
    return reg.call(name, entry, args, n_traced, static_names)


# ------------------------------------------------------------- host API


def _place(tree, device=None):
    """Host arrays -> device arrays. With `device` (a Device or
    Sharding) every array is *explicitly* placed there — never on the
    default backend, which may be a broken TPU runtime while the caller
    is deliberately running on a CPU mesh (the MULTICHIP_r01 failure
    mode: jnp.asarray landing on the poisoned default backend). Every
    engine entry point that accepts `device` routes through here."""
    if device is not None:
        return jax.device_put(tree, device)
    return jax.tree.map(jnp.asarray, tree)


def _place_owned(tree, device=None):
    """_place for buffers that will be DONATED: guarantees device-
    OWNED allocations. jnp.asarray / device_put can be ZERO-COPY on
    the CPU backend — the ArrayImpl then merely windows host numpy
    memory — and donating such a view is unsound: XLA aliases its
    output into memory it does not own (observed as
    nondeterministically corrupt counters on resumed searches). The
    post-placement jnp.copy runs on the placed array's OWN
    device/sharding, so the never-the-default-backend invariant of
    _place(device=...) is preserved."""
    return jax.tree.map(jnp.copy, _place(tree, device))


def _xs_from_encoded(e: EncodedHistory, device=None,
                     canon: bool = False) -> dict:
    """Event arrays as device arrays, placed via _place. ``canon``
    quantizes the event-row count onto the EVENT_QUANTUM ladder when
    JEPSEN_TPU_CANON_SHAPES arms it (pad rows are scan no-ops —
    parity-safe; docs/performance.md "Compile economics"); only the
    one-shot sparse path opts in — the sharded tier's xs feed
    shard_map layouts that size to the exact R."""
    xs = {
        "slot_f": e.slot_f,
        "slot_a0": e.slot_a0,
        "slot_a1": e.slot_a1,
        "slot_wild": e.slot_wild,
        "slot_occ": e.slot_occ,
        "ev_slot": e.ev_slot,
    }
    if canon:
        xs = programs.maybe_canon_rows(xs)
    return _place(xs, device)


class FrontierCheckpoint:
    """A resumable snapshot of the search frontier — the checker-side
    checkpoint/resume capability (SURVEY.md §5.4: the reference's
    resume is re-analysis of a stored history; long device searches
    additionally checkpoint mid-search so a crash or preemption loses
    at most one chunk of events).

    Saved as .npz; history identity is guarded by a digest of the
    encoded event arrays — resuming against a different history is an
    error, not silent corruption. Format versioning rides the meta
    array's LENGTH: v1 checkpoints carried 6 scalars, v2 appends the
    configs-stepped counter — v1 files load with stepped=0 (the
    counter is advisory; the search state is complete without it)."""

    def __init__(self, event_index: int, capacity: int, step_name: str,
                 history_digest: str, st, ml, mh, live, ok, fail_r,
                 maxf, steps_n, stepped: int = 0):
        self.event_index = int(event_index)
        self.capacity = int(capacity)
        self.step_name = step_name
        self.history_digest = history_digest
        self.st = np.asarray(st)
        self.ml = np.asarray(ml)
        self.mh = np.asarray(mh)
        self.live = np.asarray(live)
        self.ok = bool(ok)
        self.fail_r = int(fail_r)
        self.maxf = int(maxf)
        self.steps_n = int(steps_n)
        self.stepped = int(stepped)

    @classmethod
    def fresh(cls, e, capacity: int,
              digest: Optional[str] = None) -> "FrontierCheckpoint":
        """The event-0 checkpoint for an encoded history: one live
        config (the initial model state, nothing linearized) — shared
        by the resumable entry point and the streaming extension
        (parallel.extend) so the two cannot diverge."""
        N = max(64, capacity)
        cp = cls(0, N, e.step_name,
                 digest if digest is not None else history_digest(e),
                 np.zeros(N, np.int32), np.zeros(N, np.uint32),
                 np.zeros(N, np.uint32), np.arange(N) < 1,
                 True, -1, 1, 0)
        cp.st[0] = e.state0
        return cp

    def carry(self, device=None, pack=(), C: int = 0):
        """The device scan carry this checkpoint resumes from. With
        `device` every array is explicitly placed there (same
        invariant as _xs_from_encoded: never the default backend).

        Checkpoints store the CANONICAL (st, ml, mh) triple whatever
        layout the engine runs — the representation-independent
        interchange format (v1/v2 files, serve freeze/thaw, host
        resume seeds, and resuming a packed search unpacked or vice
        versa all just work, even when a delta grows the slot window
        and shifts the packed bit positions). With `pack` (and the
        traced program's slot width `C`) the rows pack at this
        boundary — cheap host numpy over N rows, once per chunk."""
        if pack:
            rows = pack_rows_np(pack, C, self.st, self.ml, self.mh)
        else:
            rows = (self.st, self.ml, self.mh)
        # _place_owned, not _place: the resumable jits DONATE this
        # carry, and a zero-copy placement would hand XLA a window
        # onto memory this live checkpoint still owns
        return _place_owned(tuple(rows) + (self.live,
                            np.bool_(self.ok), np.int32(self.fail_r),
                            np.int32(self.event_index),
                            np.int32(self.maxf),
                            np.int32(self.steps_n),
                            np.int32(self.stepped)),
                            device)

    def grown(self, new_capacity: int) -> "FrontierCheckpoint":
        """Re-embed the frontier into a larger capacity (overflow
        doubling across a resume)."""
        pad = new_capacity - self.capacity
        assert pad >= 0
        return FrontierCheckpoint(
            self.event_index, new_capacity, self.step_name,
            self.history_digest,
            np.concatenate([self.st, np.zeros(pad, np.int32)]),
            np.concatenate([self.ml, np.zeros(pad, np.uint32)]),
            np.concatenate([self.mh, np.zeros(pad, np.uint32)]),
            np.concatenate([self.live, np.zeros(pad, bool)]),
            self.ok, self.fail_r, self.maxf, self.steps_n,
            self.stepped)

    def save(self, path: str) -> str:
        # np.savez appends .npz to suffix-less paths; normalize so
        # load(save(p)) always works.
        if not path.endswith(".npz"):
            path = path + ".npz"
        np.savez_compressed(
            path, st=self.st, ml=self.ml, mh=self.mh, live=self.live,
            meta=np.array([self.event_index, self.capacity,
                           int(self.ok), self.fail_r, self.maxf,
                           self.steps_n, self.stepped], np.int64),
            step_name=np.array(self.step_name),
            history_digest=np.array(self.history_digest))
        return path

    @classmethod
    def load(cls, path: str) -> "FrontierCheckpoint":
        if not path.endswith(".npz"):
            path = path + ".npz"
        z = np.load(path, allow_pickle=False)
        meta = z["meta"].tolist()
        # v1 checkpoints predate the configs-stepped counter: 6 meta
        # scalars instead of 7 — load with stepped=0 rather than
        # rejecting a resumable search state over an advisory counter
        ev, cap, ok, fail_r, maxf, steps_n = meta[:6]
        stepped = meta[6] if len(meta) > 6 else 0
        return cls(ev, cap, str(z["step_name"]), str(z["history_digest"]),
                   z["st"], z["ml"], z["mh"], z["live"], bool(ok),
                   fail_r, maxf, steps_n, stepped)


def carry_fields_np(carry, pack=(), C: int = 0):
    """A returned device scan carry -> the canonical numpy 10-tuple
    (st, ml, mh, live, ok, fail_r, r_idx, maxf, steps_n, stepped) —
    the inverse of FrontierCheckpoint.carry's packing boundary, shared
    by the resumable entry point and the streaming extension."""
    lanes = pack_lanes(pack, C) if pack else 3
    rows = [np.asarray(x) for x in carry[:lanes]]
    rest = tuple(np.asarray(x) for x in carry[lanes:])
    if pack:
        st, ml, mh = unpack_rows_np(pack, C, rows)
    else:
        st, ml, mh = rows
    return (st, ml, mh) + rest


def history_digest(e: EncodedHistory) -> str:
    """Stable identity of an encoded history, for checkpoint safety."""
    import hashlib
    h = hashlib.sha256()
    for a in (e.slot_f, e.slot_a0, e.slot_a1, e.slot_wild, e.slot_occ,
              e.ev_slot):
        h.update(np.ascontiguousarray(a).tobytes())
    h.update(str(e.state0).encode())
    return h.hexdigest()[:16]


def check_encoded_resumable(e: EncodedHistory, capacity: int = 1024,
                            max_capacity: int = 1 << 20,
                            checkpoint_every: int = 256,
                            checkpoint_cb=None,
                            resume: Optional[FrontierCheckpoint] = None,
                            device=None,
                            dedupe: Optional[str] = None,
                            probe_limit: int = 0,
                            sparse_pallas: Optional[bool] = None,
                            model=None,
                            search_stats: Optional[bool] = None,
                            config_pack: Optional[bool] = None) -> dict:
    """check_encoded with mid-search checkpointing: events are processed
    in chunks of `checkpoint_every`; after each chunk the frontier is
    pulled to host and handed to checkpoint_cb(FrontierCheckpoint) (e.g.
    cp.save(path)). Pass `resume` to continue a prior search. Overflow
    inside a chunk re-runs that chunk at doubled capacity — the
    checkpoint taken before the chunk stays valid. With `device`, every
    chunk and resumed carry is explicitly placed there — same invariant
    as check_encoded(device=...): never the default backend.

    Degradation contract (docs/resilience.md): every chunk dispatch
    runs through the supervised seam. A dispatch failure mid-search
    never loses work or flips a verdict — the checkpoint taken before
    the failing chunk is the recovery point: first ONE device retry
    (the breaker's half-open probe gets to readmit a recovered
    runtime), then, with `model` given, the remaining events resume on
    the host from the checkpoint (resilience.recovery.host_resume);
    without a model the failure re-raises with ``.checkpoint``
    attached so the caller can resume later."""
    if e.n_returns == 0:
        return {"valid?": True, "max-frontier": 0, "capacity": 0}
    dedupe = _resolve_dedupe(dedupe)
    probe_limit = _resolve_probe_limit(probe_limit)
    ss = _resolve_search_stats(search_stats)
    pack_req = _resolve_config_pack(config_pack)
    C_enc = e.slot_f.shape[1]
    pack = pack_spec_for(e) if pack_req else ()
    platform = getattr(device, "platform", None) or jax.default_backend()
    digest = history_digest(e)
    if resume is not None:
        if resume.history_digest != digest:
            raise ValueError(
                f"checkpoint is for a different history "
                f"(digest {resume.history_digest} != {digest})")
        if resume.step_name != e.step_name:
            raise ValueError("checkpoint is for a different model")
        cp = resume
        N = cp.capacity
    else:
        cp = FrontierCheckpoint.fresh(e, capacity, digest)
        N = cp.capacity
    xs_np = {
        "slot_f": e.slot_f, "slot_a0": e.slot_a0, "slot_a1": e.slot_a1,
        "slot_wild": e.slot_wild, "slot_occ": e.slot_occ,
        "ev_slot": e.ev_slot,
    }
    R = e.n_returns
    mode, note = "off", None
    recovered = None
    acc = SearchStats(dedupe) if ss else None
    from time import perf_counter as _pc
    t0 = _pc()
    while cp.event_index < R and cp.ok:
        lo = cp.event_index
        hi = min(R, lo + checkpoint_every)
        # re-resolve per chunk: capacity may have grown past the
        # kernel's VMEM gate mid-search (same degrade-with-note
        # contract as check_encoded's tier loop)
        mode, note = _resolve_sparse_pallas(
            sparse_pallas, cp.capacity, e.slot_f.shape[1], platform,
            dedupe, pack)

        def _chunk(lo=lo, hi=hi, cp=cp, mode=mode):
            chunk = _place(programs.maybe_canon_rows(
                {k: v[lo:hi] for k, v in xs_np.items()}), device)
            out = _run_program(
                "engine.check_resumable",
                chunk, cp.carry(device, pack, C_enc), e.step_name,
                cp.capacity, dedupe, probe_limit, mode, ss, pack)
            # materialize inside the supervised window: async dispatch
            # must fail (or hang) here, not at a later host read
            if ss:
                carry, overflow, ys = out
                return ([np.asarray(x) for x in carry], bool(overflow),
                        jax.tree.map(np.asarray, ys))
            carry, overflow = out
            return ([np.asarray(x) for x in carry], bool(overflow))

        try:
            res = sup.dispatch("search", _chunk, backend=platform)
        except sup.DISPATCH_FAILURES as err:
            # the checkpoint taken before this chunk is the recovery
            # point: one device retry first (a recovered runtime —
            # half-open probe passed, transient cleared — resumes
            # right where it stopped, zero work lost) ...
            try:
                obs.counter("resilience.retries").inc()
                with obs.span("resilience.device_resume",
                              event=cp.event_index):
                    res = sup.dispatch("search", _chunk,
                                       backend=platform)
                recovered = {
                    "degraded": "device-resume",
                    "site": getattr(err, "site", "search"),
                    "reason": f"{type(err).__name__}: {err}",
                    "resumed-from-event": cp.event_index}
            except sup.DISPATCH_FAILURES as err2:
                # ... then the host: with a model the remaining events
                # resume from the checkpoint on the WGL path — verdict
                # preserved, device progress kept
                if model is not None:
                    from jepsen_tpu.resilience import recovery
                    return recovery.host_resume(
                        model, e, cp, getattr(err2, "site", "search"),
                        f"{type(err2).__name__}: {err2}",
                        backend=platform)
                err2.checkpoint = cp
                raise
        carry, overflow = res[0], res[1]
        if bool(overflow):
            if cp.capacity * 2 > max_capacity:
                return _tag_sparse_closure(
                    {"valid?": "unknown",
                     "error": f"frontier overflow at capacity "
                              f"{cp.capacity}",
                     "capacity": cp.capacity,
                     "checkpoint": cp}, mode, note)
            cp = cp.grown(cp.capacity * 2)
            if acc is not None:
                acc.escalations += 1
            continue  # re-run the same chunk at doubled capacity
        if acc is not None:
            # only successful chunks count: a retried chunk's partial
            # stats would double its events
            acc.add_chunk(res[2], cp.capacity)
        st, ml, mh, live, ok, fail_r, r_idx, maxf, steps_n, stepped = \
            carry_fields_np(carry, pack, C_enc)
        cp = FrontierCheckpoint(int(r_idx), cp.capacity, e.step_name,
                                digest, st, ml, mh, live, bool(ok),
                                int(fail_r), int(maxf), int(steps_n),
                                int(stepped))
        if checkpoint_cb is not None:
            checkpoint_cb(cp)
    out = {"valid?": cp.ok and bool(cp.live.any()),
           "max-frontier": cp.maxf,
           "capacity": cp.capacity,
           "dedupe": dedupe,
           "configs-stepped": cp.stepped,
           # approximate when capacity grew mid-search: iterations from
           # earlier chunks ran at smaller capacities
           "explored": cp.steps_n * cp.capacity * len(e.slot_f[0])}
    if recovered is not None:
        out["resilience"] = recovered
    if acc is not None:
        out["stats"] = _finish_search_stats(acc, t0, _pc())
    _tag_sparse_closure(out, mode, note)
    _tag_config_pack(out, pack, pack_req, C_enc)
    if not out["valid?"]:
        out.update(_fail_op(e, cp.fail_r))
    return out


_fail_op = enc_mod.fail_op_fields


def _tag_sparse_closure(out: dict, mode: str, note) -> dict:
    """Stamp which hash-closure implementation ran — bitdense's
    "closure"/"closure-note" vocabulary. Only when the kernel was
    REQUESTED (mode on, or a downgrade note): the flag-off result dict
    stays byte-identical to the pre-kernel schema. "pallas-tiled" =
    the per-iteration insert streamed the table through VMEM tiles
    (sparse_kernels.tiled_insert_call) because the whole-event fusion
    was past the width-aware gate."""
    if mode in ("tiled", "tiled-interpret"):
        out["closure"] = "pallas-tiled"
    elif mode != "off":
        out["closure"] = "pallas"
    elif note is not None:
        out["closure"] = "xla-hash"
        out["closure-note"] = note
    return out


def _tag_config_pack(out: dict, pack, requested: bool, C: int) -> dict:
    """Stamp the configuration-row layout that actually ran — only
    when packing was REQUESTED (argument or JEPSEN_TPU_CONFIG_PACK),
    so the flag-off result dict stays byte-identical. "unpacked" on a
    requested run is the overflow-to-unpacked path: the event family's
    state_bits + C exceeded 64 bits (or its state space is unknown),
    so the engine ran the historical triple."""
    if not requested:
        return out
    if pack:
        out["config-pack"] = f"packed:{pack[0] + C}b/" \
                             f"{pack_lanes(pack, C)}-lane"
    else:
        out["config-pack"] = "unpacked"
    return out


# metric-name-safe spellings of PROBE_HIST_LABELS (dots/dashes would
# sanitize ambiguously in the Prometheus mapping)
PROBE_METRIC_LABELS = ("0", "1", "le3", "le7", "le15", "le31", "over")

# counter-track sample cap per search: a 50k-event trajectory must not
# bloat the trace file — past this the exporter strides
STATS_TRACK_MAX_SAMPLES = 512


# Per-key trajectory bound: a streamed key's lifetime stats (and a
# giant one-shot search's) stop growing past this many events — the
# serve mode's every other per-key structure is deliberately bounded,
# and its telemetry must not be the one exception. Aggregates freeze
# with the lists; the block says "truncated": True.
SEARCH_STATS_MAX_EVENTS = 32768


class SearchStats:
    """Host-side accumulator over the device's per-event stats arrays
    (JEPSEN_TPU_SEARCH_STATS) — one per searched key, fed one chunk at
    a time (the one-shot paths feed a single chunk; the resumable /
    streaming paths feed every successful chunk, so streamed keys
    report LIFETIME stats). Rows with width < 0 (pads, post-failure)
    are dropped here, so callers never re-derive pad bookkeeping.
    Trajectories cap at SEARCH_STATS_MAX_EVENTS (bounded host memory
    per live key; the block is then marked truncated)."""

    def __init__(self, dedupe: str):
        self.dedupe = dedupe
        self.width: list = []
        self.peak: list = []
        self.iters: list = []
        self.stepped: list = []
        self.swork: list = []     # per-event sort-equivalent work
        self.phist: list = []     # per-event [N_PROBE_BUCKETS] rows
        self.capacity = 0
        self.escalations = 0
        self.truncated = False

    def splice(self, resume_ev: int, leg: "SearchStats") -> None:
        """Replace events [resume_ev, ...) with a re-scanned leg's —
        the streaming session's accumulation: a delta re-opens the
        tail at the stable boundary and re-scans from `resume_ev`, so
        the stale tail rows are superseded, not appended. Events below
        `resume_ev` are bit-identical under extension (the settled
        certificate), which is what makes the spliced lifetime stats
        EXACTLY a one-shot check's of the current prefix."""
        for attr in ("width", "peak", "iters", "stepped", "swork",
                     "phist"):
            rows = getattr(self, attr)
            del rows[resume_ev:]
            keep = SEARCH_STATS_MAX_EVENTS - len(rows)
            rows.extend(getattr(leg, attr)[:max(0, keep)])
        if leg.truncated or len(self.width) >= SEARCH_STATS_MAX_EVENTS:
            self.truncated = True
        self.capacity = max(self.capacity, leg.capacity)
        self.escalations += leg.escalations

    def add_chunk(self, ys, N: int) -> None:
        """One chunk's stats dict (np arrays, [R] / [R, B])."""
        room = SEARCH_STATS_MAX_EVENTS - len(self.width)
        w = np.asarray(ys["width"]).reshape(-1)
        real = w >= 0
        if int(real.sum()) > max(0, room):
            # freeze at the cap: aggregates and lists stay mutually
            # consistent (both describe the first MAX events)
            self.truncated = True
            keep = np.flatnonzero(real)[:max(0, room)]
            real = np.zeros_like(real)
            real[keep] = True
        self.width.extend(int(x) for x in w[real])
        self.peak.extend(int(x) for x in
                         np.asarray(ys["peak"]).reshape(-1)[real])
        self.iters.extend(int(x) for x in
                          np.asarray(ys["iters"]).reshape(-1)[real])
        self.stepped.extend(int(x) for x in
                            np.asarray(ys["stepped"]).reshape(-1)[real])
        self.swork.extend(int(x) for x in
                          np.asarray(ys["swork"]).reshape(-1)[real])
        self.phist.extend(
            [int(v) for v in row] for row in
            np.asarray(ys["phist"]).reshape(-1, N_PROBE_BUCKETS)[real])
        self.capacity = max(self.capacity, int(N))

    def block(self) -> dict:
        """The result-dict ``"stats"`` block — the schema every sink
        (result dicts, /metrics, counter tracks, `jepsen report
        --search`) reads; pinned by tests/test_search_stats.py."""
        peak = max(self.peak, default=0)
        stepped = sum(self.stepped)
        swork = sum(self.swork)
        phist = np.asarray(self.phist, np.int64).reshape(
            -1, N_PROBE_BUCKETS).sum(axis=0)
        out = {
            "events": len(self.width),
            "frontier-width": list(self.width),
            "closure-iters": list(self.iters),
            "configs-stepped-per-event": list(self.stepped),
            "closure-peak": list(self.peak),
            "frontier-peak": peak,
            "capacity": self.capacity,
            "capacity-tier": self.escalations,
            "peak-occupancy": (round(peak / self.capacity, 6)
                               if self.capacity else None),
            "dedupe": self.dedupe,
            "delta-split-ratio": (round(stepped / swork, 6)
                                  if swork else None),
            "table-capacity": None,
            "load-factor-peak": None,
            "load-factor-final": None,
            "probe-hist": None,
            "probes": None,
        }
        if self.truncated:
            # no silent caps: the block covers the FIRST
            # SEARCH_STATS_MAX_EVENTS events only
            out["truncated"] = True
        if self.dedupe == "hash" and self.capacity:
            # under hash the closure peak IS the visited-table
            # occupancy (every config inserted exactly once per event)
            T = _next_pow2(2 * self.capacity)
            out["table-capacity"] = T
            out["load-factor-peak"] = round(peak / T, 6)
            out["load-factor-final"] = (round(self.peak[-1] / T, 6)
                                        if self.peak else None)
            out["probe-hist"] = {lab: int(n) for lab, n in
                                 zip(PROBE_HIST_LABELS, phist)}
            out["probes"] = int(phist.sum())
        return out


def _publish_search_stats(block: dict,
                          prefix: str = "engine.search") -> None:
    """Thread one search's stats block into the obs registry under
    ``engine.search.*`` — the names /metrics serves as
    ``jepsen_engine_search_*`` (docs/observability.md)."""
    reg = obs.registry()
    reg.counter(f"{prefix}.events").inc(block["events"])
    reg.gauge(f"{prefix}.frontier_peak").set(block["frontier-peak"])
    if block.get("peak-occupancy") is not None:
        reg.gauge(f"{prefix}.peak_occupancy").set(
            block["peak-occupancy"])
    if block.get("delta-split-ratio") is not None:
        reg.gauge(f"{prefix}.delta_split_ratio").set(
            block["delta-split-ratio"])
    if block.get("load-factor-peak") is not None:
        reg.gauge(f"{prefix}.load_factor_peak").set(
            block["load-factor-peak"])
    if block.get("capacity-tier"):
        reg.counter(f"{prefix}.escalations").inc(block["capacity-tier"])
    if block.get("pad-waste") is not None:
        reg.gauge(f"{prefix}.pad_waste").set(block["pad-waste"])
    hist = block.get("probe-hist")
    if hist:
        for raw, lab in zip(PROBE_HIST_LABELS, PROBE_METRIC_LABELS):
            if hist.get(raw):
                reg.counter(f"{prefix}.probe_len.{lab}").inc(hist[raw])


def _emit_stats_tracks(block: dict, t0: float, t1: float) -> None:
    """The search's per-event trajectories as Perfetto counter tracks:
    event index mapped linearly onto the search's wall window (the
    device scan yields no per-event host timestamps — the x axis is
    the EVENT axis rendered in time units, aligned with the search
    span). No-op when tracing is off."""
    if not obs.enabled():
        return
    widths = block["frontier-width"]
    R = len(widths)
    if not R or t1 <= t0:
        return
    stride = max(1, -(-R // STATS_TRACK_MAX_SAMPLES))
    dt = (t1 - t0) / R
    T = block.get("table-capacity")
    for i in range(0, R, stride):
        t = t0 + (i + 1) * dt
        obs.counter_sample("engine.search.frontier_width", widths[i],
                           t=t)
        if T:
            obs.counter_sample(
                "engine.search.load_factor",
                round(block["closure-peak"][i] / T, 4), t=t)


def finish_stats_block(block: dict, t0: float, t1: float,
                       key=None) -> dict:
    """Fan a ready stats block into the always-on sinks (obs registry,
    run-dir search-stats record) plus the counter tracks when tracing
    is on — shared by the sparse, bitdense, and sharded engines so
    every path feeds the same four sinks."""
    _publish_search_stats(block)
    _emit_stats_tracks(block, t0, t1)
    rec = dict(block)
    if key is not None:
        rec["key"] = key
    obs.record_search_stats(rec)
    return block


def _finish_search_stats(acc: "SearchStats", t0: float, t1: float,
                         key=None, engine: str = "sparse",
                         extra: Optional[dict] = None) -> dict:
    """Build the stats block and fan it into the three always-on sinks
    (result dict via the return value, obs registry, run-dir
    search-stats record) plus the counter tracks when tracing is on."""
    block = acc.block()
    if extra:
        block.update(extra)
    block["engine"] = engine
    return finish_stats_block(block, t0, t1, key=key)


def check_encoded(e: EncodedHistory, capacity: int = 1024,
                  max_capacity: int = 1 << 20, device=None,
                  dedupe: Optional[str] = None,
                  probe_limit: int = 0,
                  sparse_pallas: Optional[bool] = None,
                  search_stats: Optional[bool] = None,
                  config_pack: Optional[bool] = None) -> dict:
    """Check one encoded history, doubling frontier capacity on overflow
    (re-jit per capacity tier; tiers are cached by jax.jit). With
    `device` every input is explicitly placed there and the search runs
    on it — never on the default backend, which may be a broken TPU
    runtime while the caller deliberately runs on a CPU mesh.

    `dedupe` picks the frontier dedupe strategy (_resolve_dedupe:
    "sort"/"hash"/None = the JEPSEN_TPU_DEDUPE flag). Verdicts and
    counterexample fields are identical either way; "configs-stepped"
    records the closure work actually paid — strictly less under
    "hash" whenever a closure runs more than one iteration (the delta
    stops re-stepping the settled majority). `probe_limit` bounds the
    hash path's linear probes (0 = the JEPSEN_TPU_PROBE_LIMIT flag,
    default 32; a test seam — probe exhaustion escalates capacity
    exactly like a full frontier).

    `sparse_pallas` routes the hash closure through the fused VMEM
    frontier kernel (parallel.sparse_kernels; None = the
    JEPSEN_TPU_SPARSE_PALLAS flag, default off until the chip A/B).
    Results are identical by construction — the kernel body is the
    same _hash_event_closure trace; the gate re-resolves per capacity
    tier, so an escalation past the kernel's VMEM budget degrades to
    the XLA hash closure with a "closure-note" rather than erroring.

    `search_stats` (None = the JEPSEN_TPU_SEARCH_STATS flag) adds a
    device-computed per-event ``"stats"`` block to the result — the
    frontier-width trajectory, closure iterations, hash-table load,
    probe-length histogram, capacity tier (docs/observability.md
    "Search telemetry"). Off: the result dict is byte-identical to the
    pre-stats schema.

    `config_pack` (None = the JEPSEN_TPU_CONFIG_PACK flag) packs each
    configuration row into the minimal word the event family needs
    (docs/performance.md "VMEM economics") — verdicts,
    counterexamples, max-frontier, and configs-stepped are identical
    either way (parity-pinned); a family whose word exceeds 64 bits
    runs unpacked, tagged "config-pack": "unpacked"."""
    if e.n_returns == 0:
        return {"valid?": True, "max-frontier": 0, "capacity": 0}
    C = e.slot_f.shape[1]
    pl = _planner.active()
    plan_prov = None
    if pl is not None:
        # JEPSEN_TPU_AUTO: axes the caller left unresolved are picked
        # from the per-shape decision table — explicit arguments are
        # never overridden, and every arm is parity-pinned, so a plan
        # can only change wall-clock, never the verdict
        dec = pl.decide("sparse", e.step_name, C,
                        {"dedupe": dedupe, "pallas": sparse_pallas,
                         "pack": config_pack}, keys=1)
        if dec is not None:
            chosen = dec["strategy"]
            dedupe = chosen.get("dedupe", dedupe)
            sparse_pallas = chosen.get("pallas", sparse_pallas)
            config_pack = chosen.get("pack", config_pack)
            plan_prov = dec["plan"]
    dedupe = _resolve_dedupe(dedupe)
    probe_limit = _resolve_probe_limit(probe_limit)
    ss = _resolve_search_stats(search_stats)
    pack_req = _resolve_config_pack(config_pack)
    pack = pack_spec_for(e) if pack_req else ()
    platform = getattr(device, "platform", None) or jax.default_backend()
    # H2D placement and the search both run through the supervised
    # dispatch seam (resilience.supervisor): faults are injectable,
    # the watchdog bounds the wait, and the backend's breaker records
    # the outcome. The search thunk MATERIALIZES its results so async
    # dispatch surfaces failures (and hangs) inside the supervised
    # window, not at a later host read.
    xs, state0 = sup.dispatch(
        "transfer",
        lambda: (_xs_from_encoded(e, device, canon=True),
                 _place(np.int32(e.state0), device)),
        backend=platform)
    N = max(64, capacity)
    n_esc = 0
    from time import perf_counter as _pc
    t0 = _pc()
    with obs.span("engine.search", returns=e.n_returns,
                  dedupe=dedupe) as sp:
        while True:
            mode, note = _resolve_sparse_pallas(sparse_pallas, N, C,
                                                platform, dedupe, pack)

            def _search(N=N, mode=mode):
                out = _run_program(
                    "engine.check", xs, state0, e.step_name, N,
                    dedupe, probe_limit, mode, ss, pack)
                # tree map (not a list comp): the stats output is a
                # dict of arrays riding along under search_stats
                return jax.tree.map(np.asarray, out)

            res = sup.dispatch("search", _search, backend=platform)
            valid, fail_r, overflow, maxf, steps_n, stepped = res[:6]
            if not bool(overflow):
                break
            if N * 2 > max_capacity:
                out = _tag_sparse_closure(
                    {"valid?": "unknown",
                     "error": f"frontier overflow at capacity {N}",
                     "capacity": N, "dedupe": dedupe}, mode, note)
                if plan_prov is not None:
                    out["plan"] = dict(plan_prov)
                return out
            N *= 2
            n_esc += 1
            obs.counter("engine.capacity_escalations").inc()
        sp.set(capacity=N)
        if mode != "off":
            # only when the kernel was requested: the flag-off trace
            # schema stays identical, like the result dict
            sp.set(closure="pallas-tiled"
                   if mode in ("tiled", "tiled-interpret")
                   else "pallas")
    obs.counter("engine.configs_stepped").inc(int(stepped))
    out = {
        "valid?": bool(valid),
        "max-frontier": int(maxf),
        "capacity": N,
        "dedupe": dedupe,
        "configs-stepped": int(stepped),
        # the historical trajectory metric (iters x N x C), preserved
        # under its old key for cross-round comparability; the true
        # work lives in configs-stepped
        "explored": int(steps_n) * N * len(e.slot_f[0]),
    }
    _tag_sparse_closure(out, mode, note)
    _tag_config_pack(out, pack, pack_req, C)
    if pl is not None:
        # every dispatch contributes evidence, planned or not (the
        # below-floor contract); the cell is keyed by the REQUESTED
        # arm so decisions and observations land in the same cell
        pallas_req = (bool(sparse_pallas) if sparse_pallas is not None
                      else envflags.env_bool("JEPSEN_TPU_SPARSE_PALLAS",
                                             default=False))
        pl.observe("sparse", e.step_name, C,
                   {"dedupe": dedupe, "pallas": pallas_req,
                    "pack": pack_req}, _pc() - t0)
    if plan_prov is not None:
        out["plan"] = dict(plan_prov)
    if ss:
        acc = SearchStats(dedupe)
        acc.escalations = n_esc
        acc.add_chunk(res[6], N)
        out["stats"] = _finish_search_stats(acc, t0, _pc())
    if not out["valid?"]:
        out.update(_fail_op(e, int(fail_r)))
    return out


def analysis(model, history, capacity: int = 1024,
             max_capacity: int = 1 << 20, encode_cache=None,
             dedupe: Optional[str] = None,
             sparse_pallas: Optional[bool] = None,
             search_stats: Optional[bool] = None,
             config_pack: Optional[bool] = None) -> dict:
    """knossos-style (model, history) -> result on the device engine.

    Falls back to the host WGL engine when the model can't pack or the
    open-call window exceeds the device limit. On failure, counter-example
    paths are reconstructed host-side on the failing prefix (SURVEY.md
    §7.3 hard part #3: breadcrumbs stay implicit; a host re-search of the
    short failing prefix supplies :final-paths). `max_capacity` caps the
    frontier's double-on-overflow growth; past it the result is
    `{"valid?": "unknown"}` — histories that never prune (e.g. invalid
    queue histories, where every enqueue-order hypothesis stays live)
    otherwise escalate through every tier before deciding.

    `encode_cache` (an EncodeCache, or True for the process default)
    memoizes the host encode across re-analyses of the same history —
    content-keyed, so a mutated history never hits stale (see
    parallel.pipeline). Default: no caching, the historical behavior.

    `dedupe` picks the sparse engine's frontier dedupe strategy
    (check_encoded; None defers to JEPSEN_TPU_DEDUPE) — verdict- and
    counterexample-identical either way; `sparse_pallas` its fused
    VMEM kernel (None defers to JEPSEN_TPU_SPARSE_PALLAS).
    """
    from jepsen_tpu.history import History
    h = history if isinstance(history, History) else History.wrap(history)
    try:
        with obs.span("engine.encode"):
            if encode_cache is not None and encode_cache is not False:
                from jepsen_tpu.parallel import pipeline as pipe_mod
                e = pipe_mod.encode_cached(
                    model, h,
                    cache=None if encode_cache is True else encode_cache)
            else:
                e = enc_mod.encode(model, h)
    except EncodeError as err:
        from jepsen_tpu.checker import wgl
        obs.counter("engine.host_fallbacks").inc()
        _log.warning(
            "history not device-checkable (%s) — using the host WGL "
            "engine; expect it to be orders of magnitude slower", err)
        r = wgl.analysis(model, h)
        r["fallback"] = str(err)
        return r
    from jepsen_tpu.parallel import bitdense
    try:
        if bitdense.fits_bitdense(bitdense.n_states(e), e.n_slots):
            # the dense bitmap IS a complete visited set — the sparse
            # dedupe strategy has nothing to select there (its result
            # says dedupe="dense"); the flag governs the sparse
            # dispatch below
            r = bitdense.check_encoded_bitdense(
                e, search_stats=search_stats)
        else:
            r = check_encoded(e, capacity=capacity,
                              max_capacity=max_capacity, dedupe=dedupe,
                              sparse_pallas=sparse_pallas,
                              search_stats=search_stats,
                              config_pack=config_pack)
    except sup.DISPATCH_FAILURES as err:
        # the degradation contract (docs/resilience.md): a dead device
        # dispatch — wedged, crashed, or breaker-refused — degrades to
        # the host WGL engine with the verdict preserved and a
        # structured note saying so, instead of crashing the check
        from jepsen_tpu.resilience import recovery
        return recovery.host_check_encoded(
            model, e, getattr(err, "site", "dispatch"),
            f"{type(err).__name__}: {err}")
    if r["valid?"] is False:
        apply_final_paths(r, model, e)
    return r


# --------------------------------------- counterexample extraction

# Host re-search window for long histories: events before the failure
# covered by the seeded re-search (the reference emits full paths but
# truncates to 10 — checker.clj:203-213; for histories the host could
# never search whole, a window ending at the failure is the useful part)
PATHS_WINDOW_EVENTS = 64
PATHS_MAX_SEEDS = 8

# Bounds for the full-host recheck run when the host path re-search
# CONTRADICTS a device-invalid (below): big enough to decide any key a
# per-key batch realistically carries, small enough that a pathological
# key cannot stall the checker.
DISAGREEMENT_RECHECK_MAX_STATES = 5_000_000
DISAGREEMENT_RECHECK_SECS = 30.0


def _disagreement_recheck(model, e: EncodedHistory, note: str) -> dict:
    """The host re-search contradicted a device-invalid. Before shipping
    "invalid, no paths", re-check the WHOLE key host-side under a
    bounded budget: a device false-invalid must not become the verdict
    when the host can decide the key. Decisive host verdicts win (WGL
    searches exhaustively; the device engine's approximations — padded
    slots, packed states — are the suspect side of a disagreement). An
    over-budget recheck keeps the device verdict, tagged."""
    import time as _time

    from jepsen_tpu.checker import wgl
    n_history = max(c.complete_index for c in e.calls) + 1
    host = wgl.check_calls(
        model, list(e.calls), n_history,
        max_states=DISAGREEMENT_RECHECK_MAX_STATES,
        deadline=_time.monotonic() + DISAGREEMENT_RECHECK_SECS)
    if host.get("valid?") is False:
        # the key IS invalid — the disagreement was about the failure
        # site; take the host's whole failure report so op/paths/configs
        # describe one consistent stuck point
        out = {"final-paths": host.get("final-paths", []),
               "configs": host.get("configs", []),
               "engine-disagreement": note + "; full-host recheck "
                                             "confirms invalid"}
        if host.get("op"):
            out["op"] = host["op"]
        return out
    if host.get("valid?") is True:
        # counted, not just logged: a false-invalid override is the
        # loudest possible device-engine signal, and the registry makes
        # it greppable in telemetry exports across a whole run
        obs.counter("engine.false_invalid").inc()
        _log.error("device engine false-invalid: %s, and the bounded "
                   "full-host recheck says VALID — overriding the device "
                   "verdict (this may hide a device-engine bug; please "
                   "report the history)", note)
        return {"valid?": True, "final-paths": [], "configs": [],
                "engine-disagreement": note + "; full-host recheck says "
                                              "valid — device verdict "
                                              "overridden"}
    _log.warning("final-paths: %s; the bounded full-host recheck was "
                 "indecisive (%s) — keeping the device verdict",
                 note, host.get("error", "?"))
    return {"final-paths": [], "configs": [],
            "final-paths-note": note + "; bounded full-host recheck "
                                       "indecisive — device verdict "
                                       "kept"}


def apply_final_paths(r: dict, model, e: EncodedHistory) -> dict:
    """Merge extract_final_paths into a device-invalid result `r`, in
    place. When the disagreement recheck OVERRIDES the verdict to
    valid, the device's stale counterexample fields are dropped — a
    valid result must not carry a phantom failing op.

    A supervised-dispatch failure DURING extraction (the seed-frontier
    re-scan is a device dispatch too) must not crash a verdict that is
    already decided: the result keeps its verdict with an empty-paths
    note instead (the same loud-but-not-fatal policy as _empty)."""
    try:
        fp = extract_final_paths(model, e, int(r["fail-event"]))
    except sup.DISPATCH_FAILURES as err:
        obs.counter("engine.final_paths_missing").inc()
        _log.warning("final-paths extraction lost its device dispatch "
                     "(%s) — verdict kept, paths empty", err)
        r.setdefault("final-paths", [])
        r.setdefault("configs", [])
        r["final-paths-note"] = (f"extraction dispatch failed: "
                                 f"{type(err).__name__}: {err}")
        return r
    if fp.get("valid?") is True:
        for k in ("op", "fail-event"):
            r.pop(k, None)
    r.update(fp)
    return r


def extract_final_paths(model, e: EncodedHistory, fail_r: int,
                        window: int = PATHS_WINDOW_EVENTS,
                        max_seeds: int = PATHS_MAX_SEEDS) -> dict:
    """knossos-style :final-paths / :configs for a failing return event.

    Short histories (<= 500 calls) re-search the whole failing prefix on
    the host. Longer ones re-run the device scan up to a checkpoint
    `window` return-events before the failure, decode the frontier into
    (model state, linearized-open-calls) seeds, and host-search only the
    window from each seed — exact counterexamples at any history length,
    with the device doing the long prefix."""
    from jepsen_tpu.checker import wgl
    fail_idx = e.calls[int(e.ret_call[fail_r])].complete_index
    if e.n_calls <= 500:
        host = wgl.check_calls(model, _prefix_calls(e.calls, fail_idx),
                               fail_idx + 1)
        if host.get("valid?") is False:
            return {"final-paths": host.get("final-paths", []),
                    "configs": host.get("configs", [])}
        # the host can linearize the prefix the device failed on:
        # escalate to a bounded full-host recheck of the key rather
        # than shipping "invalid, no paths" on a possible device
        # false-invalid
        return _disagreement_recheck(
            model, e, "host re-search of the failing prefix came back "
                      "valid while the device said invalid")

    def _empty(note: str) -> dict:
        # an invalid history with no paths is a loud event, same policy
        # as the device-fallback tagging in independent.py — silence
        # here would look like "no counterexample available" by design;
        # the counter makes it visible in the run's telemetry too
        obs.counter("engine.final_paths_missing").inc()
        _log.warning("final-paths extraction returned nothing for an "
                     "invalid history: %s", note)
        return {"final-paths": [], "configs": [], "final-paths-note": note}

    from jepsen_tpu import models as model_ns
    # the encoded history carries its *prepared* spec — for models with
    # history-dependent packing (gset lanes, queue widths) a fresh
    # pack_spec could not unpack device states
    spec = e.spec or model_ns.pack_spec(model, e.intern)
    if spec is None or spec.unpack_state is None:
        return _empty("model has no unpack_state; cannot seed a window "
                      "re-search")
    start_ev = max(0, fail_r - window)
    if start_ev == 0:
        seeds = [(e.state0, frozenset())]
        occupants: dict = {}
    else:
        from jepsen_tpu.parallel import bitdense
        if bitdense.fits_bitdense(bitdense.n_states(e), e.n_slots):
            # the engine that decided this history: its dense tensor is
            # the complete frontier, with no capacity to escalate
            rows = bitdense.frontier_rows(e, start_ev)
        else:
            rows = _frontier_at(e, start_ev)
        if rows is None:
            return _empty("seed-frontier re-scan overflowed max capacity")
        occupants = _slot_occupants_before(e, start_ev)
        seeds = []
        for stc, ml, mh in rows[:max_seeds]:
            mask = ml | (mh << 32)
            seeds.append((stc, frozenset(
                cid for s, cid in occupants.items() if (mask >> s) & 1)))

    boundary = (e.calls[int(e.ret_call[start_ev])].complete_index
                if start_ev > 0 else -1)
    paths: list = []
    configs: list = []
    # Every sampled seed runs BEFORE any paths are trusted: a failing
    # seed may just be a dead-end config (reachable but unextendable —
    # normal in a valid history), while a seed that linearizes through
    # the failure proves a valid linearization of the whole prefix
    # EXISTS — a direct contradiction of the device's
    # empty-frontier-at-fail_r. Only an all-seeds-fail outcome
    # corroborates the device verdict.
    for seed_i, (stc, linearized) in enumerate(seeds):
        seed_model = spec.unpack_state(stc, e.intern)
        cs = _window_calls(e.calls, boundary, fail_idx, linearized)
        host = wgl.check_calls(seed_model, cs, fail_idx + 1)
        if host.get("valid?") is True:
            return _disagreement_recheck(
                model, e, "window re-search from device seed %d "
                          "linearized through the failure "
                          "(window [%d, %d])"
                          % (seed_i, start_ev, fail_r))
        if host.get("valid?") is False:
            paths.extend(host.get("final-paths", []))
            configs.extend(host.get("configs", []))
    if not paths:
        # no seed failed and none decisively linearized either (all
        # indecisive): the window/seed machinery itself may be the
        # wrong side, so the recheck covers the whole key
        return _disagreement_recheck(
            model, e, "none of the %d window re-searches from device "
                      "seeds produced a verdict (window [%d, %d])"
                      % (len(seeds), start_ev, fail_r))
    out = {"final-paths": paths[:10], "configs": configs[:10]}
    if start_ev > 0:
        # paths cover the failure window only; the device verified the
        # prefix and supplied the seed states
        out["final-paths-window"] = [start_ev, fail_r]
    return out


def _frontier_at(e: EncodedHistory, start_ev: int):
    """Re-run the device scan over return events [0, start_ev) and pull
    the live frontier rows to host as (state, mask_lo, mask_hi)."""
    xs_np = {
        "slot_f": e.slot_f[:start_ev], "slot_a0": e.slot_a0[:start_ev],
        "slot_a1": e.slot_a1[:start_ev], "slot_wild": e.slot_wild[:start_ev],
        "slot_occ": e.slot_occ[:start_ev], "ev_slot": e.ev_slot[:start_ev],
    }
    chunk = {k: jnp.asarray(v) for k, v in xs_np.items()}
    N = 1024
    while True:
        def _rescan(N=N):
            # always the unpacked layout: this re-scan feeds host-side
            # seed decoding (the canonical triple), and extraction
            # correctness must never depend on a perf flag
            carry0 = _initial_carry(jnp.int32(e.state0), N,
                                    _rep((), e.slot_f.shape[1]))
            carry, overflow = _check_device_resumable(
                chunk, carry0, e.step_name, N)
            return ([np.asarray(x) for x in carry], bool(overflow))

        # supervised like every dispatch, but with no breaker backend:
        # this re-scan runs INSIDE recovery/extraction paths, and its
        # failure must not double-count against the breaker that is
        # already handling the original one
        carry, overflow = sup.dispatch("search", _rescan)
        if not bool(overflow):
            break
        if N * 2 > (1 << 20):
            return None
        N *= 2
    st, ml, mh, live = [np.asarray(x) for x in carry[:4]]
    idx = np.nonzero(live)[0]
    return [(int(st[i]), int(ml[i]), int(mh[i])) for i in idx]


def _slot_occupants_before(e: EncodedHistory, r_target: int) -> dict:
    """slot -> call id of the snapshot taken just before return event
    r_target — the same walk encode() performs (same heap discipline,
    so slot numbers match the device masks)."""
    import heapq
    events = []
    for c in e.calls:
        events.append((c.invoke_index, 0, c.index))
        if not c.crashed:
            events.append((c.complete_index, 1, c.index))
    events.sort()
    free: list = []
    n_slots = 0
    slot_of: dict = {}
    occ: dict = {}
    r = 0
    for _, kind, cid in events:
        if kind == 0:
            s = heapq.heappop(free) if free else n_slots
            if s == n_slots:
                n_slots += 1
            slot_of[cid] = s
            occ[s] = cid
        else:
            if r == r_target:
                return dict(occ)
            s = slot_of[cid]
            del occ[s]
            heapq.heappush(free, s)
            r += 1
    return dict(occ)


def _window_calls(cs, boundary: int, fail_idx: int, linearized):
    """Calls active in the window (boundary, fail_idx]: drops calls
    fully completed before the boundary and calls the seed already
    linearized; clamps completions past fail_idx to still-open."""
    from jepsen_tpu.history import Call
    out = []
    for c in cs:
        if c.invoke_index > fail_idx:
            continue
        if (not c.crashed) and c.complete_index < boundary:
            continue  # returned before the window: effect is in the seed
        if c.index in linearized:
            continue  # already applied in the seed state
        if c.complete_index > fail_idx:
            c2 = Call(c.index, c.process, c.f, c.value, None,
                      c.invoke_index, fail_idx + 1, True)
        else:
            c2 = Call(c.index, c.process, c.f, c.value, c.result,
                      c.invoke_index, c.complete_index, c.crashed)
        out.append(c2)
    for j, c in enumerate(out):
        c.index = j
    return out


def _prefix_calls(cs, fail_idx):
    """Calls restricted to the failing prefix: everything invoked up to
    fail_idx, with completions after it treated as still-open (crashed)."""
    return _window_calls(cs, -1, fail_idx, frozenset())


# ----------------------------------------------------- batched (per-key)


def encode_batch(model, histories, pad_slots: Optional[int] = None,
                 encs: Optional[list] = None, mesh=None):
    """Encode many per-key histories to one padded batch (the reference's
    per-key data parallelism, jepsen.independent — SURVEY.md §2.20 P5:
    'one key's history per TPU program instance'). With `mesh`, the
    arrays are explicitly device_put onto the mesh (key axis sharded
    when divisible, replicated otherwise) so the default backend is
    never touched."""
    if encs is None:
        encs = [enc_mod.encode(model, h, pad_slots=pad_slots)
                for h in histories]
    elif pad_slots is not None:
        # a pre-encoded history's slot tables are already allocated at
        # their final width — silently ignoring pad_slots here (the old
        # behavior) would hand back a batch narrower than the caller
        # asked for, which only surfaces later as a shape mismatch in
        # whatever program the caller compiled for the requested width.
        # The one legal case: every enc was already padded to exactly
        # the requested width (the streaming extension pre-allocates
        # its group tier's width — parallel.extend), in which case the
        # request is a no-op rather than a conflict.
        if any(e.slot_f.shape[1] != pad_slots for e in encs):
            raise ValueError(
                "encode_batch: pad_slots conflicts with pre-encoded "
                "encs whose slot tables are at a different width (their "
                "tables are already final) — re-encode with pad_slots, "
                "or grow them through the extension API "
                "(jepsen_tpu.parallel.extend.extend_encoded / "
                "HistorySession), which pre-allocates matching widths")
    xs, state0, _, _, _ = enc_mod.pad_batch(encs, mesh=mesh)
    return encs, xs, state0


def check_batch(model, histories, capacity: int = 512,
                max_capacity: int = 1 << 18, mesh=None,
                bucket: Optional[str] = None,
                pipeline: Optional[bool] = None, cache=None,
                pipeline_stats: Optional[dict] = None,
                dedupe: Optional[str] = None,
                sparse_pallas: Optional[bool] = None,
                search_stats: Optional[bool] = None,
                config_pack: Optional[bool] = None,
                reshard: Optional[bool] = None) -> list:
    """Check many per-key histories in one device program per
    slot-window bucket: vmap over the key axis; with a mesh (and K
    divisible by its size) the key axis is sharded across devices —
    data parallelism over ICI.

    `bucket` picks the grouping strategy before padding (default: the
    JEPSEN_TPU_BUCKET env var, else "tier"):

    - "tier" (default): power-of-two slot-window tiers — one wide key
      (say C=20) must not force every narrow key through a 2^20-mask
      program (measured on v5e: a 336-key batch with a C=20 straggler
      ran ~6x slower un-bucketed).
    - "exact": one bucket per exact slot count. Tiers are coarse at
      the top of a tier: the reference workload's 84 keys span slots
      11..15 — one tier — so all pad to W=1024 while most need 256 or
      less (~2.9x the word-work). Exact buckets trade that against one
      compile + dispatch per distinct C. tools/perf_ab.py measures the
      trade ("batch ... exact-C bucketed" line); stays opt-in until an
      on-chip win is recorded there — flags do not get to claim
      speedups.

    Each bucket independently dispatches to the bit-packed dense
    engine (parallel.bitdense) when its combined padded dims fit,
    sparse frontier mode otherwise.

    `pipeline` routes the batch through the pipelined executor
    (parallel.pipeline): host encode, H2D transfer, and device search
    overlap instead of running as three serial phases, and encodings
    come from the digest-keyed encode cache (`cache`; pass False to
    disable, None for the process default). Default: the
    JEPSEN_TPU_PIPELINE env flag, else off — opt-in until bench
    records a win (flags do not get to claim speedups). Results are
    bit-identical to the serial path either way (docs/performance.md).
    `pipeline_stats`, when a dict, receives the per-bucket
    encode/transfer/device split the bench reports.

    `sparse_pallas` routes the sparse buckets' hash closure through the
    fused VMEM frontier kernel (check_encoded's docstring; None = the
    JEPSEN_TPU_SPARSE_PALLAS flag).

    `reshard` (None = JEPSEN_TPU_RESHARD) makes capacity escalation
    recruit mesh devices (sharded elastic ladder) instead of only
    growing tables."""
    bucket = _resolve_bucket(bucket)   # fail-fast: before the encode
    pl = _planner.active()
    from time import perf_counter as _pc
    if pl is None:
        dedupe = _resolve_dedupe(dedupe)   # likewise fail-fast
    else:
        _resolve_dedupe(dedupe)   # fail-fast validation only — with
        # the planner armed the dedupe REQUEST stays raw so each
        # sparse bucket plans its own arm per shape
        # (_check_batch_sparse); the batch-level axis (executor
        # choice) is planned here, where it routes
        dec = pl.decide("batch", type(model).__name__, None,
                        {"pipeline": pipeline}, keys=len(histories))
        if dec is not None:
            pipeline = dec["strategy"].get("pipeline", pipeline)
        t0_plan = _pc()
    run_pipeline = _resolve_pipeline(pipeline)
    if run_pipeline:
        from jepsen_tpu.parallel import pipeline as pipe_mod
        res = pipe_mod.check_batch_pipelined(
            model, histories, capacity=capacity,
            max_capacity=max_capacity, mesh=mesh, bucket=bucket,
            cache=cache, stats=pipeline_stats, dedupe=dedupe,
            sparse_pallas=sparse_pallas, search_stats=search_stats,
            config_pack=config_pack, reshard=reshard)
    else:
        if (cache is not None and cache is not False) \
                or pipeline_stats is not None:
            # the serial path consults no cache and fills no stats —
            # silently ignoring these arguments would be the same trap
            # this PR closed in encode_batch(pad_slots, encs): the
            # caller clearly wanted the pipelined executor, so say so.
            # cache=False ("no caching") is exempt: the serial path
            # already satisfies it by doing nothing, so it must not
            # crash env-flag-dependently
            raise ValueError(
                "check_batch: cache/pipeline_stats are "
                "pipelined-executor arguments — pass pipeline=True "
                "(or set JEPSEN_TPU_PIPELINE=1) to use them")
        with obs.span("engine.check_batch", keys=len(histories),
                      bucket=bucket), obs.maybe_jax_profile():
            with obs.span("engine.encode_batch",
                          keys=len(histories)):
                pre = [enc_mod.encode(model, h) for h in histories]
            res = check_batch_encoded(model, pre, capacity=capacity,
                                      max_capacity=max_capacity,
                                      mesh=mesh,
                                      bucket=bucket, dedupe=dedupe,
                                      sparse_pallas=sparse_pallas,
                                      search_stats=search_stats,
                                      config_pack=config_pack,
                                      reshard=reshard)
    if pl is not None:
        pl.observe("batch", type(model).__name__, None,
                   {"pipeline": run_pipeline},
                   _pc() - t0_plan)
    return res


def _resolve_bucket(bucket: Optional[str]) -> str:
    if bucket is None:
        # JEPSEN_TPU_BUCKET gives deployments the lever without a code
        # change, same opt-in philosophy as the other perf flags; the
        # validated accessor raises on values outside the contract
        bucket = envflags.env_choice("JEPSEN_TPU_BUCKET",
                                     ("tier", "exact"), default="tier",
                                     what="bucket strategy")
    if bucket not in ("tier", "exact"):
        raise ValueError(f"unknown bucket strategy {bucket!r}")
    return bucket


def _resolve_pipeline(pipeline: Optional[bool]) -> bool:
    if pipeline is None:
        pipeline = envflags.env_bool("JEPSEN_TPU_PIPELINE",
                                     default=False)
    return bool(pipeline)


def _resolve_reshard(reshard: Optional[bool]) -> bool:
    """JEPSEN_TPU_RESHARD: capacity escalation recruits devices
    (parallel.sharded.check_encoded_sharded_elastic) instead of only
    growing per-device tables. Opt-in until a recorded A/B flips it —
    flags do not get to claim speedups."""
    if reshard is None:
        reshard = envflags.env_bool("JEPSEN_TPU_RESHARD",
                                    default=False)
    return bool(reshard)


def bucket_key(n_slots: int, bucket: str) -> int:
    """The bucket a key with `n_slots` open-call slots lands in under
    the given strategy — shared by the serial (check_batch_encoded)
    and pipelined (parallel.pipeline) executors so their grouping, and
    therefore their padded programs and per-key result dicts, match
    exactly."""
    if bucket == "exact":
        # floor at bitdense's min_slots=5: narrower keys pad to
        # the same C=5 program anyway, so splitting them would be
        # pure dispatch overhead (and perf_ab's measured grouping
        # uses the same floor)
        return max(5, n_slots)
    return 1 << max(2, (max(1, n_slots) - 1).bit_length())


def check_batch_encoded(model, pre, capacity: int = 512,
                        max_capacity: int = 1 << 18, mesh=None,
                        bucket: Optional[str] = None,
                        dedupe: Optional[str] = None,
                        sparse_pallas: Optional[bool] = None,
                        search_stats: Optional[bool] = None,
                        config_pack: Optional[bool] = None,
                        reshard: Optional[bool] = None) -> list:
    """check_batch on ALREADY-ENCODED keys (the bucketing + dispatch
    half without the encode half). Public so callers that time or
    cache the encode separately — bench.sec_multikey's encode/device
    split, re-analysis over a stored columnar history — drive the
    same bucketing policy as check_batch. Results keep `pre`'s
    order. `dedupe` governs the sparse buckets (bitdense buckets are
    a complete visited set by construction; their results say
    dedupe="dense")."""
    if not pre:
        _resolve_bucket(bucket)
        _resolve_dedupe(dedupe)
        return []
    bucket = _resolve_bucket(bucket)
    if _planner.active() is None:
        dedupe = _resolve_dedupe(dedupe)
    else:
        # fail-fast validation only: with the planner armed the dedupe
        # REQUEST stays raw (None = plannable) so each sparse bucket
        # picks its own arm per padded shape in _check_batch_sparse;
        # bitdense buckets never consult dedupe either way
        _resolve_dedupe(dedupe)
    from jepsen_tpu.parallel import bitdense
    out: list = [None] * len(pre)
    buckets: dict = {}
    for i, e in enumerate(pre):
        buckets.setdefault(bucket_key(e.n_slots, bucket), []).append(i)
    for tier in sorted(buckets):
        idxs = buckets[tier]
        sub = [pre[i] for i in idxs]
        S_max = max(bitdense.n_states(e) for e in sub)
        C_max = max(e.n_slots for e in sub)
        if bitdense.fits_bitdense(S_max, C_max):
            try:
                rs = bitdense.check_batch_bitdense(
                    sub, mesh=mesh, search_stats=search_stats)
            except sup.DISPATCH_FAILURES as err:
                # degradation contract: a dead bitdense dispatch costs
                # this bucket the device path, not the batch the
                # verdict — each key re-checks on the host WGL engine
                # with a structured resilience note
                from jepsen_tpu.resilience import recovery
                reason = f"{type(err).__name__}: {err}"
                rs = [recovery.host_check_encoded(
                          model, e, getattr(err, "site", "dispatch"),
                          reason) for e in sub]
        else:
            rs = _check_batch_sparse(model, sub, capacity, max_capacity,
                                     mesh, dedupe=dedupe,
                                     sparse_pallas=sparse_pallas,
                                     search_stats=search_stats,
                                     config_pack=config_pack,
                                     reshard=reshard)
        for i, r in zip(idxs, rs):
            out[i] = r
    return out


def _check_batch_sparse(model, pre, capacity: int, max_capacity: int,
                        mesh=None, dedupe: Optional[str] = None,
                        probe_limit: int = 0,
                        sparse_pallas: Optional[bool] = None,
                        search_stats: Optional[bool] = None,
                        config_pack: Optional[bool] = None,
                        reshard: Optional[bool] = None) -> list:
    """Sparse-frontier batch path with per-key capacity-tier retry."""
    step_name = pre[0].step_name
    K = len(pre)
    out: list = [None] * K
    probe_limit = _resolve_probe_limit(probe_limit)
    ss = _resolve_search_stats(search_stats)
    C = max(e.slot_f.shape[1] for e in pre)
    pl = _planner.active()
    plan_prov = None
    if pl is not None:
        # the plan routes this padded shape between parity-pinned
        # strategy arms; axes the caller fixed (explicit arg or env)
        # are never overridden — decide() only fills the None ones
        dec = pl.decide("sparse", step_name, C,
                        {"dedupe": dedupe, "pallas": sparse_pallas,
                         "pack": config_pack}, keys=K)
        if dec is not None:
            chosen = dec["strategy"]
            dedupe = chosen.get("dedupe", dedupe)
            sparse_pallas = chosen.get("pallas", sparse_pallas)
            config_pack = chosen.get("pack", config_pack)
            plan_prov = dec["plan"]
    dedupe = _resolve_dedupe(dedupe)
    pack_req = _resolve_config_pack(config_pack)
    led = _ledger.active()
    from time import perf_counter as _pc
    # the padded batch runs one program: gate the kernel on where the
    # batch actually lives (the mesh when given), like bitdense does
    platform = (np.asarray(mesh.devices).flat[0].platform
                if mesh is not None else jax.default_backend())
    # one COMMON layout for the whole padded program: the state field
    # must cover every member's domain (pack_spec_for unions them)
    pack = pack_spec_for(pre, C) if pack_req else ()
    # Per-key capacity retry: keys are bucketed by the capacity tier
    # they need — only keys that overflowed re-run (at doubled
    # capacity), so one hot key never drags the whole batch through
    # re-padding and re-search at 2-512x capacity.
    pending = list(range(K))
    N = max(64, capacity)
    n_tier = 0
    while pending:
        encs_t = [pre[i] for i in pending]
        mode, note = _resolve_sparse_pallas(sparse_pallas, N, C,
                                            platform, dedupe, pack)
        t0 = _pc()
        try:
            with obs.span("engine.sparse_batch", keys=len(pending),
                          capacity=N, dedupe=dedupe):
                _, xs, state0 = sup.dispatch(
                    "transfer",
                    lambda encs_t=encs_t: encode_batch(
                        model, [], encs=encs_t, mesh=mesh),
                    backend=platform)

                def _search(xs=xs, state0=state0, N=N, mode=mode):
                    out = _run_program(
                        "engine.check_batch", xs, state0, step_name,
                        N, dedupe, probe_limit, mode, ss, pack)
                    # materialize inside the supervised window
                    return jax.tree.map(np.asarray, out)

                res = sup.dispatch("search", _search, backend=platform)
                valid, fail_r, overflow, maxf, steps_n, stepped = \
                    res[:6]
        except sup.DISPATCH_FAILURES as err:
            # degradation contract: the keys still pending at the
            # failure degrade to the host WGL path, each with a
            # structured resilience note — keys already decided on
            # the device keep their device results
            from jepsen_tpu.resilience import recovery
            reason = f"{type(err).__name__}: {err}"
            for i in pending:
                out[i] = recovery.host_check_encoded(
                    model, pre[i], getattr(err, "site", "search"),
                    reason, backend=platform)
            break
        t1 = _pc()
        if pl is not None:
            # evidence lands on the REQUESTED arm (what decide() would
            # hand out again), not the resolved closure mode — the
            # platform fallback inside _resolve_sparse_pallas is the
            # same for every arm, so the comparison stays fair
            pallas_req = (bool(sparse_pallas)
                          if sparse_pallas is not None
                          else envflags.env_bool(
                              "JEPSEN_TPU_SPARSE_PALLAS",
                              default=False))
            pl.observe("sparse", step_name, C,
                       {"dedupe": dedupe, "pallas": pallas_req,
                        "pack": pack_req}, t1 - t0)
        if ss or led is not None:
            # padded program dims for this tier: the pad-waste the
            # stats block reports is measured against what actually
            # shipped to the device
            R_pad = max(e.n_returns for e in encs_t)
            C_pad = max(e.slot_f.shape[1] for e in encs_t)
        retry = []
        n_valid = n_invalid = 0
        tier_stats: list = []
        for j, i in enumerate(pending):
            if bool(overflow[j]):
                retry.append(i)
                continue
            e = pre[i]
            r = {"valid?": bool(valid[j]), "max-frontier": int(maxf[j]),
                 "capacity": N, "dedupe": dedupe,
                 "configs-stepped": int(stepped[j])}
            _tag_sparse_closure(r, mode, note)
            _tag_config_pack(r, pack, pack_req, C)
            if plan_prov is not None:
                r["plan"] = dict(plan_prov)
            obs.counter("engine.configs_stepped").inc(int(stepped[j]))
            if r["valid?"]:
                n_valid += 1
            else:
                n_invalid += 1
            if ss:
                acc = SearchStats(dedupe)
                acc.escalations = n_tier
                acc.add_chunk(
                    jax.tree.map(lambda a, j=j: a[j], res[6]), N)
                waste = 1.0 - ((e.n_returns * e.slot_f.shape[1])
                               / max(1, R_pad * C_pad))
                r["stats"] = _finish_search_stats(
                    acc, t0, t1, key=i,
                    extra={"pad-waste": round(waste, 6),
                           "pad-events": int(R_pad - e.n_returns),
                           "pad-slots": int(C_pad - e.slot_f.shape[1])})
                tier_stats.append(r["stats"])
            if not r["valid?"]:
                r.update(enc_mod.fail_op_fields(e, int(fail_r[j])))
            out[i] = r
        if led is not None:
            # one evidence record per device dispatch (not per key):
            # the padded program's shape fingerprint + the strategy
            # vector that ran it, with the SAME perf_counter reads
            # the span/bench splits use
            led.record(
                "dispatch", engine="sparse",
                shape={"family": step_name, "N": N, "R": int(R_pad),
                       "C": int(C_pad), "tier": n_tier,
                       "pack": bool(pack)},
                strategy={"dedupe": dedupe, "closure": mode,
                          "pack": pack_req,
                          "probe_limit": probe_limit},
                secs=round(t1 - t0, 6), keys=len(pending),
                stats=_ledger.stats_digest(tier_stats),
                outcome={"valid": n_valid, "invalid": n_invalid,
                         "overflow": len(retry)})
        if not retry:
            break
        if N * 2 > max_capacity:
            for i in retry:
                out[i] = _escalate_overflow(pre[i], N, mesh,
                                            dedupe=dedupe,
                                            sparse_pallas=sparse_pallas,
                                            search_stats=ss,
                                            config_pack=pack_req,
                                            reshard=reshard)
            break
        # keys that overflowed re-dispatch at the doubled tier — the
        # counter the capacity-retry ladder's cost is visible through
        obs.counter("engine.overflow_redispatch").inc(len(retry))
        pending = retry
        N *= 2
        n_tier += 1
    return out


def _escalate_overflow(e: EncodedHistory, batch_cap: int, mesh,
                       dedupe: str = "sort",
                       sparse_pallas: Optional[bool] = None,
                       search_stats: Optional[bool] = None,
                       config_pack: Optional[bool] = None,
                       reshard: Optional[bool] = None) -> dict:
    """Ledger-instrumented wrapper around the escalation ladder: when
    the decision ledger is armed, each escalation lands one evidence
    record — which tier decided (single/sharded/none), under what
    strategy vector, and how long the whole ladder took. Semantics
    are exactly ``_escalate_overflow_impl``'s (its docstring is the
    contract)."""
    led = _ledger.active()
    if led is None:
        return _escalate_overflow_impl(
            e, batch_cap, mesh, dedupe=dedupe,
            sparse_pallas=sparse_pallas, search_stats=search_stats,
            config_pack=config_pack, reshard=reshard)
    from time import perf_counter as _pc
    t0 = _pc()
    r = _escalate_overflow_impl(
        e, batch_cap, mesh, dedupe=dedupe,
        sparse_pallas=sparse_pallas, search_stats=search_stats,
        config_pack=config_pack, reshard=reshard)
    t1 = _pc()
    led.record(
        "escalation", engine="sparse",
        shape={"family": e.step_name, "R": int(e.n_returns),
               "C": int(e.slot_f.shape[1])},
        strategy={"dedupe": dedupe, "reshard": bool(reshard)
                  if reshard is not None else _resolve_reshard(None)},
        secs=round(t1 - t0, 6), batch_cap=batch_cap,
        outcome={"escalated": r.get("escalated"),
                 "verdict": _ledger.verdict_class(r),
                 "error": bool(r.get("error")
                               or r.get("escalation-error"))})
    return r


def _escalate_overflow_impl(e: EncodedHistory, batch_cap: int, mesh,
                            dedupe: str = "sort",
                            sparse_pallas: Optional[bool] = None,
                            search_stats: Optional[bool] = None,
                            config_pack: Optional[bool] = None,
                            reshard: Optional[bool] = None) -> dict:
    """A key too wide for the batch program escalates instead of dying
    as "unknown": first the single-key sparse engine at 4x the batch
    ceiling, then — with a mesh — the frontier-sharded engine, whose
    aggregate capacity scales with the device count (the dp -> sp
    escalation SURVEY.md §5.7 frames as the long-history story:
    per-key batching until a key outgrows a chip, frontier sharding
    beyond). Ceilings scale from the caller's batch bound — batch_cap
    x4 on one device, a further xD across the mesh — so a tight bound
    set for latency/memory reasons stays meaningful. Reports which
    tier decided via "escalated". The first batch run already proved
    batch_cap overflows, so every tier starts at 2x.

    Under `reshard` (None = JEPSEN_TPU_RESHARD) the sharded tier runs
    the elastic device ladder (sharded.check_encoded_sharded_elastic
    via check_encoded_sharded's delegation): the retry recruits a
    widening slice of the mesh at flat per-device capacity — idle
    devices, not bigger tables, absorb the overflow — with the same
    ceilings and the same overflow->unknown semantics."""
    obs.counter("engine.capacity_escalations").inc()
    ceil_single = min(batch_cap * 4, 1 << 21)
    # pin the single tier to the caller's mesh: check_encoded on the
    # default backend would break the invariant the batch and sharded
    # paths maintain (nothing on the default backend — it can be a
    # wedged TPU runtime while we deliberately run on a CPU mesh), and
    # a batch-overflow key would hang in escalation
    dev = None if mesh is None else np.asarray(mesh.devices).flat[0]
    r = check_encoded(e, capacity=min(batch_cap * 2, ceil_single),
                      max_capacity=ceil_single, device=dev,
                      dedupe=dedupe, sparse_pallas=sparse_pallas,
                      search_stats=search_stats,
                      config_pack=config_pack)
    if r["valid?"] != "unknown":
        r["escalated"] = "single"
        return r
    if mesh is not None \
            and min(batch_cap * 4 * np.asarray(mesh.devices).size,
                    1 << 24) > ceil_single:
        # the tier only runs when its aggregate ceiling can actually
        # exceed what the single tier just proved overflows — on a
        # 1-device mesh the two ceilings coincide and a re-run would
        # be pure waste
        try:
            from jepsen_tpu.parallel import sharded
            n_dev = np.asarray(mesh.devices).size
            # pass the caller's mesh through untouched: the sharded
            # engine picks the hierarchical exchange on 2-D (multi-
            # slice) meshes and flattens anything else itself. Start
            # past the single tier's proven-overflowing 4x ceiling —
            # frontier occupancy is a property of the history, so
            # re-running smaller global capacities is pure waste.
            ceil_sharded = min(batch_cap * 4 * n_dev, 1 << 24)
            rs = sharded.check_encoded_sharded(
                e, mesh, capacity=min(batch_cap * 8, ceil_sharded),
                max_capacity=ceil_sharded, dedupe=dedupe,
                sparse_pallas=sparse_pallas,
                search_stats=search_stats,
                config_pack=config_pack, reshard=reshard)
            if rs["valid?"] != "unknown":
                rs["escalated"] = "sharded"
                return rs
            r = rs
        except Exception as err:  # noqa: BLE001 — escalation must not
            # turn a decidable batch into a crash; but a broken sharded
            # engine must be LOUD (the same rule as independent.py's
            # device-fallback), not a buried result key
            obs.counter("engine.escalation_errors").inc()
            _log.warning(
                "sharded escalation tier crashed (%r) — key left "
                "unknown; this may hide a sharded-engine regression",
                err)
            r = dict(r)
            r["escalation-error"] = repr(err)
    r["error"] = (f"frontier overflow: batch capacity {batch_cap}, "
                  f"escalation tiers exhausted ({r.get('error')})")
    return r
