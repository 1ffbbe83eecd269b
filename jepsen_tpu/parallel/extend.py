"""Incremental frontier extension: append ops to a checked history and
resume the device search from its settled prefix.

The batch engine assumes a complete history — every return event's
slot tables are fixed at encode time, which is exactly why
``engine.encode_batch`` refuses pre-encoded encs at a different width.
Streaming (ROADMAP item 1: check histories while the test is still
running) needs the opposite: per-key history *deltas* arrive over
time, and each delta's verdict must be **bit-identical to a one-shot
check of the current prefix** without re-searching what is already
settled.

Three facts make that possible:

  1. The scan carry after return event r depends only on rows
     ``[0, r]`` of the encoded event tables. If those rows are
     bit-identical between the old and the extended encode, a
     :class:`~jepsen_tpu.parallel.engine.FrontierCheckpoint` taken at
     r resumes the extended search exactly (``settled_events`` is the
     ground-truth array diff that certifies this).
  2. Appending ops can only change rows at or after the first return
     event that an as-yet-open call participates in: a completion can
     tighten an open observed-f op from wildcard to a concrete
     constraint, un-prune an open crashed-wildcard call (shifting slot
     assignment), or re-open the tail event with a new return.
     ``stable_events`` computes that immutable boundary from the raw
     op stream, so each scan leaves a checkpoint that the NEXT delta
     is guaranteed to be able to resume from.
  3. Linearizability is prefix-closed: an invalid prefix stays invalid
     under any extension, so early counterexamples are final verdicts.

The re-encode itself is host work (``prepare_encode``/``finish_encode``
— the same split the pipelined executor streams through, and
``EncodeCache`` makes repeats cheap); what extension saves is the
expensive part, the device search over the settled prefix.

:class:`HistorySession` is the per-key stateful wrapper;
:func:`extend_encoded` the functional core; :func:`advance_sessions`
batches shape-compatible sessions' pending scans into one device
program (``engine._check_device_batch_resumable``) — the cross-key
delta batching ``jepsen_tpu.serve`` dispatches.

Import-safe: importing this module must not touch a JAX backend (the
same contract as the other engine modules).
"""

from __future__ import annotations

import bisect
import logging
from time import perf_counter
from typing import Optional

import numpy as np

import jax

from jepsen_tpu import envflags, obs
from jepsen_tpu.history import TYPES, History
from jepsen_tpu.obs import ledger as _ledger
from jepsen_tpu.parallel import encode as enc_mod
from jepsen_tpu.parallel import engine
from jepsen_tpu.parallel import planner as _planner
from jepsen_tpu.parallel.encode import EncodedHistory, EncodeError
from jepsen_tpu.resilience import supervisor as sup

_log = logging.getLogger(__name__)

# Chunk scan lengths are padded up to a multiple of this quantum so a
# stream of arbitrary-sized deltas compiles a handful of jit shapes
# instead of one per delta length (pad events skip: run=False, and the
# event index does not advance on them — see engine._scan_step_factory).
# The quantum now lives in parallel.programs — the compile-economics
# layer generalized this ladder to the one-shot paths
# (JEPSEN_TPU_CANON_SHAPES) — and is re-exported here for its
# historical importers.
from jepsen_tpu.parallel.programs import EVENT_QUANTUM  # noqa: E402


class FrontierOverflowError(RuntimeError):
    """The frontier outgrew max_capacity mid-extension; carries the
    last checkpoint so callers can report the same structured
    ``{"valid?": "unknown"}`` the one-shot ladder does."""

    def __init__(self, checkpoint):
        super().__init__(f"frontier overflow at capacity "
                         f"{checkpoint.capacity}")
        self.checkpoint = checkpoint


# ------------------------------------------------------------ settling


def _pad_cols(a, C: int, fill):
    if a.shape[1] == C:
        return a
    out = np.full((a.shape[0], C), fill, a.dtype)
    out[:, : a.shape[1]] = a
    return out


def settled_events(old: Optional[EncodedHistory],
                   new: EncodedHistory) -> int:
    """Number of leading return events whose encoded rows are
    bit-identical between ``old`` and ``new`` — the ground truth for
    how far a checkpoint taken against ``old`` may resume a search
    over ``new``. Width growth is fine (extra columns are unoccupied);
    a changed model/state0 settles nothing."""
    if old is None or old.step_name != new.step_name \
            or old.state0 != new.state0:
        return 0
    R = min(old.n_returns, new.n_returns)
    if R == 0:
        return 0
    C = max(old.slot_f.shape[1], new.slot_f.shape[1])
    same = np.ones(R, bool)
    for attr, fill in (("slot_f", -1), ("slot_a0", -1), ("slot_a1", -1),
                       ("slot_wild", False), ("slot_occ", False)):
        a = _pad_cols(getattr(old, attr)[:R], C, fill)
        b = _pad_cols(getattr(new, attr)[:R], C, fill)
        same &= (a == b).all(axis=1)
    same &= old.ev_slot[:R] == new.ev_slot[:R]
    if same.all():
        return R
    return int(np.argmin(same))


def stable_events(ops, e: Optional[EncodedHistory]) -> int:
    """The immutable row boundary: the largest r such that rows
    ``[0, r)`` of the current encode can NEVER change under future
    appends. Future ops only complete currently-open invocations (or
    add new calls, whose rows are all past the current tail), and a
    completion can only perturb rows from the first return event after
    that invocation — so the boundary is the earliest such row over
    all still-open invocations. Checkpoints retained at or below it
    are guaranteed resumable by the next delta."""
    if e is None:
        return 0
    open_at: dict = {}
    for i, o in enumerate(ops):
        p = o.get("process")
        if not isinstance(p, int):
            continue
        t = o.get("type")
        if t == "invoke":
            open_at[p] = i
        elif t in ("ok", "fail", "info"):
            # every completion kind is final: ok/fail fix the packing,
            # info pins the call crashed forever
            open_at.pop(p, None)
    if not open_at:
        return e.n_returns
    completes = sorted(c.complete_index for c in e.calls if not c.crashed)
    return bisect.bisect_left(completes, min(open_at.values()))


def _restamp(cp, digest: str):
    """A checkpoint re-bound to an extended history whose settled
    prefix it certifiably covers (settled_events is the caller's
    proof) — same frontier, new identity."""
    return engine.FrontierCheckpoint(
        cp.event_index, cp.capacity, cp.step_name, digest,
        cp.st, cp.ml, cp.mh, cp.live, cp.ok, cp.fail_r, cp.maxf,
        cp.steps_n, cp.stepped)


def extend_encoded(model, old_e: Optional[EncodedHistory], ops,
                   new_ops, pad_slots: Optional[int] = None):
    """Functional core of extension: re-encode ``ops + new_ops``
    through the prepare/finish split and report how much of ``old_e``
    the new encode settles. Returns ``(new_e, n_settled)`` where
    rows ``[0, n_settled)`` are bit-identical to ``old_e``'s — a
    FrontierCheckpoint at or below ``n_settled`` (restamped to the new
    digest) resumes the extended search exactly. Raises EncodeError
    where ``encode`` would (host fallback)."""
    full = list(ops) + list(new_ops)
    prep = enc_mod.prepare_encode(model, History.wrap(full))
    new_e = enc_mod.finish_encode(prep, pad_slots)
    return new_e, settled_events(old_e, new_e)


# ------------------------------------------------------------ scanning


def _quantize(n: int) -> int:
    return max(EVENT_QUANTUM, -(-n // EVENT_QUANTUM) * EVENT_QUANTUM)


def _xs_slice(e: EncodedHistory, lo: int, hi: int, R_pad: int,
              C_pad: int) -> dict:
    """Event rows [lo, hi) as a (R_pad, C_pad) chunk; pad rows carry
    ev_slot=-1 / unoccupied slots, which the scan skips without
    advancing its event index."""
    n = hi - lo
    out = {}
    for attr, fill in (("slot_f", -1), ("slot_a0", -1), ("slot_a1", -1),
                       ("slot_wild", False), ("slot_occ", False)):
        a = getattr(e, attr)
        buf = np.full((R_pad, C_pad), fill, a.dtype)
        buf[:n, : a.shape[1]] = a[lo:hi]
        out[attr] = buf
    ev = np.full(R_pad, -1, np.int32)
    ev[:n] = e.ev_slot[lo:hi]
    out["ev_slot"] = ev
    return out


def _cp_from_carry(carry, cp, step_name: str, pack=(), C: int = 0):
    st, ml, mh, live, ok, fail_r, r_idx, maxf, steps_n, stepped = \
        engine.carry_fields_np(carry, pack, C)
    return engine.FrontierCheckpoint(
        int(r_idx), cp.capacity, step_name, cp.history_digest,
        st, ml, mh, live, bool(ok), int(fail_r), int(maxf),
        int(steps_n), int(stepped))


def _advance_cp(e: EncodedHistory, cp, target: int, *, dedupe: str,
                probe_limit: int, sparse_pallas, device, platform: str,
                max_capacity: int, C_pad: Optional[int] = None,
                stats_acc=None, config_pack: bool = False):
    """Advance ``cp`` over return events [cp.event_index, target) of
    ``e``, doubling capacity on overflow. Supervised like every device
    dispatch, with the resumable path's degradation ladder: one device
    retry (a recovered runtime resumes exactly where it stopped), then
    the failure re-raises with ``.checkpoint`` attached so the caller
    can degrade to the host from the same recovery point. Returns
    (cp2, mode, note, recovered_note).

    CONTRACT TWIN of engine.check_encoded_resumable's chunk loop —
    same retry/overflow/degradation semantics, differing only in the
    target-bounded quantum-padded chunks (vs checkpoint_every slices)
    and in degrading at the caller (HistorySession keeps the
    checkpoint live across deltas) instead of inline. A change to the
    retry or overflow contract must land in BOTH (test_checkpoint and
    test_serve pin each side)."""
    C = C_pad or e.slot_f.shape[1]
    ss = stats_acc is not None
    # the pack layout rides the CURRENT encode (a delta that grows the
    # slot window shifts the packed bit positions — safe, because the
    # checkpoints in hand are canonical-unpacked and re-pack here)
    pack = engine.pack_spec_for(e, C) if config_pack else ()
    mode, note = "off", None
    recovered = None
    while cp.event_index < target and cp.ok:
        lo = cp.event_index
        R_pad = _quantize(target - lo)
        mode, note = engine._resolve_sparse_pallas(
            sparse_pallas, cp.capacity, C, platform, dedupe, pack)

        def _chunk(lo=lo, cp=cp, mode=mode, R_pad=R_pad):
            import jax as _jax
            xs = engine._place(_xs_slice(e, lo, target, R_pad, C),
                               device)
            out = engine._run_program(
                "engine.check_resumable",
                xs, cp.carry(device, pack, C), e.step_name,
                cp.capacity, dedupe, probe_limit, mode, ss, pack)
            # materialize inside the supervised window (async dispatch
            # must fail or hang here, not at a later host read)
            if ss:
                carry, overflow, ys = out
                return ([np.asarray(x) for x in carry], bool(overflow),
                        _jax.tree.map(np.asarray, ys))
            carry, overflow = out
            return ([np.asarray(x) for x in carry], bool(overflow))

        try:
            res = sup.dispatch("search", _chunk, backend=platform)
        except sup.DISPATCH_FAILURES as err:
            # the checkpoint in hand is the recovery point: one device
            # retry first (a half-open breaker probe may have
            # readmitted a recovered runtime) ...
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
                # ... then hand the checkpoint to the caller's
                # degradation contract (host resume keeps the verdict)
                err2.checkpoint = cp
                raise
        carry, overflow = res[0], res[1]
        if overflow:
            if cp.capacity * 2 > max_capacity:
                raise FrontierOverflowError(cp)
            obs.counter("engine.capacity_escalations").inc()
            cp = cp.grown(cp.capacity * 2)
            if ss:
                stats_acc.escalations += 1
            continue
        if ss:
            # only successful chunks: a re-run chunk's discarded
            # attempt must not double its events
            stats_acc.add_chunk(res[2], cp.capacity)
        cp = _cp_from_carry(carry, cp, e.step_name, pack, C)
    return cp, mode, note, recovered


# ------------------------------------------------------------- session


class HistorySession:
    """One key's streaming check state: the accumulated op stream, its
    current encode, and the frontier checkpoints that let each delta's
    verdict resume from the settled prefix.

    Contract (pinned by tests/test_serve.py): after any sequence of
    :meth:`extend` calls, :meth:`check` returns a result whose
    verdict, counterexample fields, max-frontier, and configs-stepped
    are identical to ``engine.check_encoded(encode(model, ops))`` over
    the same prefix with the same dedupe strategy — delta feeding is
    an optimization, never a semantics change. Invalid verdicts are
    early counterexamples and final (prefix closure).

    Not thread-safe; the serve layer serializes access per key.
    """

    def __init__(self, model, *, capacity: int = 1024,
                 max_capacity: int = 1 << 20,
                 dedupe: Optional[str] = None, probe_limit: int = 0,
                 sparse_pallas: Optional[bool] = None, device=None,
                 key=None, search_stats: Optional[bool] = None,
                 config_pack: Optional[bool] = None):
        self.model = model
        self.key = key
        self.ops: list = []
        self.enc: Optional[EncodedHistory] = None
        # with the planner armed (JEPSEN_TPU_AUTO), axes the caller
        # left None are plannable: the decision waits for the first
        # scan that has an encode (the plan is per padded shape) and
        # then pins for the session's lifetime — every delta and the
        # advance_sessions group key see ONE stable vector
        self._auto_axes: tuple = ()
        self._plan = None
        if _planner.active() is not None:
            self._auto_axes = tuple(
                ax for ax, v in (("dedupe", dedupe),
                                 ("pallas", sparse_pallas),
                                 ("pack", config_pack)) if v is None)
        self.dedupe = engine._resolve_dedupe(dedupe)
        self.probe_limit = engine._resolve_probe_limit(probe_limit)
        self.sparse_pallas = sparse_pallas
        self.search_stats = engine._resolve_search_stats(search_stats)
        # the packed-row REQUEST (JEPSEN_TPU_CONFIG_PACK); the layout
        # itself is re-derived per scan from the current encode, since
        # deltas can grow the slot window (checkpoints stay canonical)
        self.config_pack = engine._resolve_config_pack(config_pack)
        # lifetime device-search stats across every delta's legs
        # (JEPSEN_TPU_SEARCH_STATS); _leg_acc is the in-flight check's
        # accumulator, merged in at _finish. NOT persisted by
        # freeze/thaw — an evicted key's stats restart at thaw.
        self._stats_acc = (engine.SearchStats(self.dedupe)
                           if self.search_stats else None)
        self._leg_acc = None
        self._leg_t0 = None
        self.device = device
        self.capacity = max(64, capacity)
        self.max_capacity = max_capacity
        self.host_only: Optional[str] = None  # EncodeError text
        self.finalized = False
        self._cp = None          # the next scan's resume point
        self._cp_stable = None   # retained at the immutable boundary
        self._cp_tail = None     # retained at the last scanned event
        self._scan_cp = None     # in-flight cursor (advance_sessions)
        self._stable_ev = 0
        self._digest = None
        self._dirty = False
        self._last_result = None

    # -- introspection

    @property
    def n_ops(self) -> int:
        return len(self.ops)

    @property
    def n_returns(self) -> int:
        return 0 if self.enc is None else self.enc.n_returns

    @property
    def resume_event(self) -> int:
        """Where the next scan will resume (0 = from scratch)."""
        return self._cp.event_index if self._cp is not None else 0

    # -- extension

    def extend(self, new_ops) -> None:
        """Append a delta of invoke/ok/fail/info ops and re-encode.
        Host work only — the device scan runs at the next
        :meth:`check`/:func:`advance_sessions`. Raises ValueError on a
        malformed delta (the op stream must stay a well-formed
        history) BEFORE mutating any state."""
        if self.finalized:
            raise RuntimeError("session is finalized; no more deltas")
        new_ops = list(new_ops)
        for o in new_ops:
            t = o.get("type") if hasattr(o, "get") else None
            if t not in TYPES:
                raise ValueError(
                    f"delta op {o!r}: type must be one of {TYPES}")
        self.ops.extend(new_ops)
        self._dirty = True
        if self.host_only is not None:
            return  # once unpackable, always host-checked
        old_e = self.enc
        try:
            with obs.span("stream.encode", key=self.key,
                          ops=len(self.ops)):
                self.enc = enc_mod.encode(self.model,
                                          History.wrap(self.ops))
        except EncodeError as err:
            # same contract as engine.analysis: not device-checkable
            # degrades to the host WGL engine — and stays there (the
            # open-call window that overflowed is a historical fact)
            self.host_only = str(err)
            self.enc = None
            self._cp = self._cp_stable = self._cp_tail = None
            obs.counter("stream.host_only_keys").inc()
            return
        if self.enc.n_returns == 0:
            self._cp = self._cp_stable = self._cp_tail = None
            self._stable_ev = 0
            return
        settled = settled_events(old_e, self.enc)
        self._digest = engine.history_digest(self.enc)
        self._stable_ev = stable_events(self.ops, self.enc)
        best = None
        for cp in (self._cp_tail, self._cp_stable, self._cp):
            if cp is not None and cp.event_index <= settled \
                    and (best is None
                         or cp.event_index > best.event_index):
                best = cp
        if best is None:
            if self._cp_tail is not None or self._cp_stable is not None:
                # the delta perturbed rows below every retained
                # checkpoint (packing shifted wholesale — e.g. a
                # model whose prepared widths grew): rescan from
                # scratch, loudly countable, never wrong
                obs.counter("stream.rescans").inc()
            self._cp = None
        else:
            self._cp = _restamp(best, self._digest)
            obs.counter("stream.resumed_events").inc(best.event_index)
        self._cp_stable = self._cp_tail = None

    # -- checking

    def _fresh_cp(self):
        return engine.FrontierCheckpoint.fresh(self.enc, self.capacity,
                                               self._digest)

    def _host_check(self) -> dict:
        from jepsen_tpu.checker import wgl
        with obs.span("stream.host_check", key=self.key):
            r = wgl.analysis(self.model, History.wrap(self.ops))
        r["fallback"] = self.host_only
        self._last_result = dict(r)
        self._dirty = False
        return r

    def _result_from(self, cp, mode, note, resume_ev: int,
                     pack=None, pack_C: Optional[int] = None) -> dict:
        e = self.enc
        out = {"valid?": cp.ok and bool(np.asarray(cp.live).any()),
               "max-frontier": cp.maxf,
               "capacity": cp.capacity,
               "dedupe": self.dedupe,
               "configs-stepped": cp.stepped,
               "explored": cp.steps_n * cp.capacity * e.slot_f.shape[1],
               "stream": {"resumed-from-event": resume_ev,
                          "events": e.n_returns}}
        engine._tag_sparse_closure(out, mode, note)
        # tag the layout that actually RAN: the batched path passes its
        # group's union layout (over the group's padded width), which
        # can differ from this session's solo layout — a group with an
        # unpackable member runs unpacked, and the evidence trail must
        # say so. Solo scans (pack=None) re-derive their own.
        if pack is None:
            pack_C = e.slot_f.shape[1]
            pack = (engine.pack_spec_for(e, pack_C)
                    if self.config_pack else ())
        engine._tag_config_pack(out, pack, self.config_pack, pack_C)
        if self._plan is not None:
            out["plan"] = dict(self._plan)
        if not out["valid?"]:
            out.update(engine._fail_op(e, cp.fail_r))
        return out

    def _leg_stats(self):
        """The in-flight check's stats accumulator (created on first
        use so a batched advance's earlier legs and the solo fallback
        share one), or None with stats off."""
        if not self.search_stats:
            return None
        if self._leg_acc is None:
            from time import perf_counter
            self._leg_acc = engine.SearchStats(self.dedupe)
            self._leg_t0 = perf_counter()
        return self._leg_acc

    def _apply_plan(self) -> None:
        """One-shot strategy planning for this session
        (JEPSEN_TPU_AUTO): fill the axes the caller left None from
        the planner's decision table, keyed on the first encode's
        padded shape. Runs before the first scan — the stats
        accumulator and the advance_sessions group key both see the
        planned vector, and it stays pinned for the session's
        lifetime (a thawed key re-plans against the ADOPTING fleet's
        table, since thaw rebuilds the session from ops)."""
        if not self._auto_axes or self.enc is None:
            return
        pl = _planner.active()
        if pl is None:
            self._auto_axes = ()
            return
        req = {"dedupe": self.dedupe, "pallas": self.sparse_pallas,
               "pack": self.config_pack}
        for ax in self._auto_axes:
            req[ax] = None
        dec = pl.decide("stream", self.enc.step_name,
                        self.enc.slot_f.shape[1], req, keys=1)
        self._auto_axes = ()
        if dec is None:
            return
        chosen = dec["strategy"]
        if "dedupe" in chosen:
            self.dedupe = chosen["dedupe"]
            if self._stats_acc is not None:
                # no chunks accumulated yet — this runs before the
                # first scan, so swapping the strategy label is safe
                self._stats_acc = engine.SearchStats(self.dedupe)
        if "pallas" in chosen:
            self.sparse_pallas = chosen["pallas"]
        if "pack" in chosen:
            self.config_pack = chosen["pack"]
        self._plan = dec["plan"]

    def _finish(self, tcp, mode, note, resume_ev: int,
                recovered, pack=None,
                pack_C: Optional[int] = None) -> dict:
        """Bookkeeping shared by check() and advance_sessions() once
        the tail leg's carry is in hand."""
        resume_stepped = self._cp.stepped if self._cp is not None else 0
        obs.counter("engine.configs_stepped").inc(
            max(0, tcp.stepped - resume_stepped))
        self.capacity = max(self.capacity, tcp.capacity)
        self._cp = self._cp_stable or tcp
        r = self._result_from(tcp, mode, note, resume_ev, pack, pack_C)
        if recovered is not None:
            r["resilience"] = recovered
        if self._stats_acc is not None and self._leg_acc is not None:
            # registry + counter tracks get THIS check's leg only (a
            # stream republishing its lifetime totals every delta
            # would inflate every counter); the result block and the
            # run-dir record carry the LIFETIME stats — the leg is
            # SPLICED in at its resume event, superseding the stale
            # re-opened tail, so lifetime == a one-shot check of the
            # current prefix (parity-pinned). `jepsen report --search`
            # dedupes by key, newest record wins.
            leg_block = self._leg_acc.block()
            leg_block["engine"] = "stream"
            engine._publish_search_stats(leg_block)
            engine._emit_stats_tracks(leg_block, self._leg_t0,
                                      perf_counter())
            self._stats_acc.splice(resume_ev, self._leg_acc)
            block = self._stats_acc.block()
            block["engine"] = "stream"
            block["resumed-from-event"] = resume_ev
            rec = dict(block)
            if self.key is not None:
                rec["key"] = self.key
            obs.record_search_stats(rec)
            r["stats"] = block
            self._leg_acc = None
        led = _ledger.active()
        if led is not None:
            # CONTRACT TWIN of the one-shot engines' dispatch records:
            # engine="stream" is the serve fleet's device executor, so
            # the advisor can weigh the incremental scan against a
            # one-shot re-check on the same shape axis
            t0 = getattr(self, "_scan_t0", None)
            e = self.enc
            led.record(
                "dispatch", engine="stream",
                shape={"family": e.step_name, "N": tcp.capacity,
                       "R": e.n_returns, "C": e.slot_f.shape[1]},
                strategy={"dedupe": self.dedupe, "closure": mode,
                          "pack": self.config_pack,
                          "probe_limit": self.probe_limit,
                          "batched": pack is not None},
                secs=(round(perf_counter() - t0, 6)
                      if t0 is not None else None),
                keys=1,
                key=(str(self.key) if self.key is not None else None),
                resume=resume_ev,
                stats=(_ledger.stats_digest([r["stats"]])
                       if "stats" in r else None),
                outcome={"verdict": _ledger.verdict_class(r),
                         "degraded": recovered is not None})
        pl = _planner.active()
        scan_t0 = getattr(self, "_scan_t0", None)
        if pl is not None and scan_t0 is not None:
            # evidence on the REQUESTED arm, same convention as the
            # batch engines — the platform fallback inside the
            # closure resolution is identical for every arm
            e = self.enc
            pallas_req = (bool(self.sparse_pallas)
                          if self.sparse_pallas is not None
                          else envflags.env_bool(
                              "JEPSEN_TPU_SPARSE_PALLAS",
                              default=False))
            pl.observe("stream", e.step_name, e.slot_f.shape[1],
                       {"dedupe": self.dedupe, "pallas": pallas_req,
                        "pack": self.config_pack},
                       perf_counter() - scan_t0)
        self._last_result = dict(r)
        self._dirty = False
        return r

    def _overflow_result(self, err: FrontierOverflowError) -> dict:
        self._leg_acc = None   # no stats on an undecided check
        r = {"valid?": "unknown",
             "error": f"frontier overflow at capacity "
                      f"{err.checkpoint.capacity}",
             "capacity": err.checkpoint.capacity,
             "dedupe": self.dedupe,
             "checkpoint": err.checkpoint}
        self._last_result = dict(r)
        self._dirty = False
        return r

    def _degraded_result(self, err, cp, platform: str) -> dict:
        """The PR-6 degradation contract for a dead streamed dispatch:
        resume the remaining suffix on the host WGL engine from the
        checkpoint in hand — verdict preserved, device progress kept,
        structured ``resilience`` note attached."""
        from jepsen_tpu.resilience import recovery
        cp_at = getattr(err, "checkpoint", None) or cp
        self._leg_acc = None   # device stats end where the device died
        obs.counter("stream.degraded_checks").inc()
        r = recovery.host_resume(
            self.model, self.enc, cp_at, getattr(err, "site", "search"),
            f"{type(err).__name__}: {err}", backend=platform)
        # keep the device-side progress: the next delta retries the
        # device from this same checkpoint (the breaker's half-open
        # probe decides when that is allowed again)
        self._cp = cp_at
        self._last_result = dict(r)
        self._dirty = False
        return r

    def check(self, degrade: bool = True) -> dict:
        """The current prefix's verdict — bit-identical (verdict,
        op/fail-event, max-frontier, configs-stepped) to a one-shot
        ``engine.check_encoded`` of the same prefix. Scans only
        [resume_event, R); retains checkpoints at the immutable
        boundary and the tail so the next delta resumes as far forward
        as its content allows. ``degrade=False`` re-raises dispatch
        failures (with ``.checkpoint``) instead of host-resuming."""
        if self.host_only is not None:
            if not self._dirty and self._last_result is not None:
                return dict(self._last_result)
            return self._host_check()
        if self.enc is None or self.enc.n_returns == 0:
            r = {"valid?": True, "max-frontier": 0, "capacity": 0}
            self._last_result = dict(r)
            self._dirty = False
            return r
        if not self._dirty and self._last_result is not None:
            return dict(self._last_result)
        self._apply_plan()
        e = self.enc
        platform = getattr(self.device, "platform", None) \
            or jax.default_backend()
        cp = self._cp if self._cp is not None else self._fresh_cp()
        resume_ev = cp.event_index
        R = e.n_returns
        stable = max(self._stable_ev, cp.event_index)
        kw = dict(dedupe=self.dedupe, probe_limit=self.probe_limit,
                  sparse_pallas=self.sparse_pallas, device=self.device,
                  platform=platform, max_capacity=self.max_capacity,
                  stats_acc=self._leg_stats(),
                  config_pack=self.config_pack)
        recovered = None
        mode, note = "off", None
        self._scan_t0 = perf_counter()
        with obs.span("stream.check", key=self.key, returns=R,
                      resume=resume_ev):
            try:
                if cp.ok and cp.event_index < stable:
                    cp, mode, note, rec = _advance_cp(e, cp, stable,
                                                      **kw)
                    recovered = recovered or rec
                self._cp_stable = cp
                tcp = cp
                if tcp.ok and tcp.event_index < R:
                    tcp, mode, note, rec = _advance_cp(e, tcp, R, **kw)
                    recovered = recovered or rec
                self._cp_tail = tcp
            except FrontierOverflowError as err:
                return self._overflow_result(err)
            except sup.DISPATCH_FAILURES as err:
                if not degrade:
                    raise
                return self._degraded_result(err, cp, platform)
        return self._finish(tcp, mode, note, resume_ev, recovered)

    def finalize(self, final_paths: bool = True) -> dict:
        """Mark the stream complete and return the final verdict —
        identical to the one-shot check of the whole history. With
        ``final_paths``, an invalid verdict additionally gets the
        knossos-style counterexample extraction (the same
        ``apply_final_paths`` the analysis entry point runs)."""
        r = self.check()
        if final_paths and r.get("valid?") is False \
                and self.enc is not None and "final-paths" not in r:
            engine.apply_final_paths(r, self.model, self.enc)
            self._last_result = dict(r)
        self.finalized = True
        return r

    # -- eviction support (the serve layer's checkpoint store)

    def freeze(self, path: str) -> dict:
        """Persist the best resume checkpoint to ``path`` (.npz) and
        return the metadata the thaw needs. The op stream is NOT
        persisted here — the caller owns it (the serve layer's WAL is
        the durable op record)."""
        best = None
        for cp in (self._cp_tail, self._cp_stable, self._cp):
            if cp is not None and (best is None
                                   or cp.event_index > best.event_index):
                best = cp
        meta = {"n_ops": len(self.ops),
                "capacity": self.capacity,
                "host_only": self.host_only,
                "finalized": self.finalized,
                "checkpoint": None}
        if best is not None:
            meta["checkpoint"] = best.save(path)
            meta["event_index"] = best.event_index
            meta["digest"] = best.history_digest
        return meta

    def thaw(self, ops, cp) -> None:
        """Restore an evicted session: the full op stream (replayed
        from the WAL) plus the frozen checkpoint. The re-encode is
        deterministic, so the checkpoint's digest must match the
        re-encoded history's — a mismatch degrades to a from-scratch
        rescan (counted), never a stale frontier."""
        if self.ops:
            raise RuntimeError("thaw into a fresh session only")
        self.extend(ops)
        if cp is None or self.host_only is not None or self.enc is None:
            return
        if cp.history_digest == self._digest \
                and cp.step_name == self.enc.step_name \
                and cp.event_index <= self.enc.n_returns:
            self._cp = cp
            self.capacity = max(self.capacity, cp.capacity)
        else:
            obs.counter("stream.thaw_rescans").inc()
            _log.warning(
                "thawed checkpoint does not match the replayed "
                "history (digest/model drift) — rescanning key %r "
                "from scratch", self.key)

    # -- device migration (serve.service.steal_key)

    def migrate(self, device) -> None:
        """Re-place the session's device search onto ``device``
        (serve.service.steal_key). The canonical host-side
        FrontierCheckpoint IS the migration primitive: every retained
        checkpoint stores unsharded numpy rows, so moving a streamed
        key between devices is pure re-placement — the next scan's
        ``cp.carry(device)`` lands on the new device and resumes
        bit-identically, exactly as the freeze/thaw eviction path
        already proves. Keys are independent; no device state
        moves."""
        if device is self.device:
            return
        self.device = device
        obs.counter("stream.migrated_keys").inc()


# ----------------------------------------------- cross-key batching


def _next_pow2(n: int) -> int:
    return 1 << max(0, (int(n) - 1).bit_length())


def _stack_carries(cps, K_pad: int, pack=(), C: int = 0):
    members = list(cps) + [cps[-1]] * (K_pad - len(cps))
    if pack:
        lanes = [engine.pack_rows_np(pack, C, c.st, c.ml, c.mh)
                 for c in members]
        row_stacks = tuple(
            np.stack([ln[i] for ln in lanes])
            for i in range(len(lanes[0])))
    else:
        row_stacks = (np.stack([c.st for c in members]),
                      np.stack([c.ml for c in members]),
                      np.stack([c.mh for c in members]))
    return row_stacks + (
        np.stack([c.live for c in members]),
        np.array([c.ok for c in members], bool),
        np.array([c.fail_r for c in members], np.int32),
        np.array([c.event_index for c in members], np.int32),
        np.array([c.maxf for c in members], np.int32),
        np.array([c.steps_n for c in members], np.int32),
        np.array([c.stepped for c in members], np.int32))


def _batch_leg(pairs, N: int, C_pad: int, dedupe: str,
               probe_limit: int, sparse_pallas, device,
               platform: str, search_stats: bool = False,
               pack: tuple = ()):
    """One batched scan leg: advance each (session, target) pair's
    in-flight cursor over its own rows in ONE device program. Returns
    (mode, note, overflowed_sessions); overflowed members keep their
    pre-leg cursor (their capacity retry runs individually). Under
    `search_stats`, each successful member's per-key stats rows feed
    its session's leg accumulator — batched legs report the same
    per-event telemetry solo scans do. `pack` is the GROUP's common
    layout (advance_sessions computes it once over every member, so
    all legs trace one layout and the result tag says exactly what
    ran); a member set that cannot share a 64-bit word runs the leg
    unpacked — representation never changes results, so
    solo-vs-batched parity holds either way."""
    R_pad = _quantize(max(t - s._scan_cp.event_index
                          for s, t in pairs))
    K = len(pairs)
    K_pad = _next_pow2(K)
    mode, note = engine._resolve_sparse_pallas(
        sparse_pallas, N, C_pad, platform, dedupe, pack)
    step_name = pairs[0][0].enc.step_name

    def _thunk():
        chunks = [_xs_slice(s.enc, s._scan_cp.event_index, t, R_pad,
                            C_pad) for s, t in pairs]
        chunks += [chunks[-1]] * (K_pad - K)   # shape filler, discarded
        xs = {k: np.stack([c[k] for c in chunks])
              for k in chunks[0]}
        carry0 = _stack_carries([s._scan_cp for s, _ in pairs], K_pad,
                                pack, C_pad)
        xs = engine._place(xs, device)
        # owned placement: the batched-resumable jit donates carry0
        carry0 = engine._place_owned(carry0, device)
        out = engine._run_program(
            "engine.check_batch_resumable",
            xs, carry0, step_name, N, dedupe, probe_limit, mode,
            search_stats, pack)
        if search_stats:
            carry, ovf, ys = out
            return ([np.asarray(x) for x in carry], np.asarray(ovf),
                    jax.tree.map(np.asarray, ys))
        carry, ovf = out
        return ([np.asarray(x) for x in carry], np.asarray(ovf))

    with obs.span("stream.batch_scan", keys=K, events=R_pad,
                  capacity=N):
        res = sup.dispatch("search", _thunk, backend=platform)
    carry, ovf = res[0], res[1]
    overflowed = []
    for k, (s, _t) in enumerate(pairs):
        if bool(ovf[k]):
            overflowed.append(s)
            continue
        if search_stats:
            s._leg_stats().add_chunk(
                jax.tree.map(lambda a, k=k: a[k], res[2]), N)
        st, ml, mh, live, ok, fail_r, r_idx, maxf, steps_n, stepped = \
            engine.carry_fields_np(
                tuple(a[k] for a in carry), pack, C_pad)
        s._scan_cp = engine.FrontierCheckpoint(
            int(r_idx), N, step_name,
            s._scan_cp.history_digest, st, ml, mh, live, bool(ok),
            int(fail_r), int(maxf), int(steps_n), int(stepped))
    return mode, note, overflowed


def advance_sessions(sessions, bucket: Optional[str] = None) -> list:
    """Run every session's pending scan, batching shape-compatible
    keys (same model step, capacity tier, slot-window bucket, and
    dedupe knobs) into one device program per leg — the serve layer's
    cross-key/tenant delta batching. Results are identical to calling
    ``session.check()`` one by one (the batched scan runs the same
    per-key rows from the same carries; padding is skipped work).
    Any per-key overflow or dispatch failure falls back to that
    session's individual path, which owns the capacity ladder and the
    degradation contract. Returns results in ``sessions`` order."""
    bucket = engine._resolve_bucket(bucket)
    results: dict = {}
    groups: dict = {}
    for s in sessions:
        if id(s) in results:
            continue
        if (s.host_only is not None or s.enc is None
                or s.enc.n_returns == 0
                or (not s._dirty and s._last_result is not None)):
            results[id(s)] = s.check()
            continue
        # the plan must land BEFORE the group key is computed: planned
        # sessions join batches on the vector that will actually run
        s._apply_plan()
        cp = s._cp if s._cp is not None else s._fresh_cp()
        s._scan_cp = cp
        s._scan_t0 = perf_counter()
        gk = (s.enc.step_name, cp.capacity,
              engine.bucket_key(s.enc.n_slots, bucket), s.dedupe,
              s.probe_limit, s.sparse_pallas, s.search_stats,
              s.config_pack, id(s.device))
        groups.setdefault(gk, []).append(s)

    for (step_name, N, tier, dedupe, probe_limit, sparse_pallas,
         search_stats, config_pack, _dev), members in groups.items():
        if len(members) == 1:
            s = members[0]
            results[id(s)] = s.check()
            continue
        device = members[0].device
        platform = getattr(device, "platform", None) \
            or jax.default_backend()
        C_pad = min(enc_mod.MAX_SLOTS,
                    max(tier, max(m.enc.slot_f.shape[1]
                                  for m in members)))
        # ONE union layout for the whole group, computed before any
        # leg: every leg traces the same representation and the
        # per-session result tag reports exactly what ran
        pack = (engine.pack_spec_for([m.enc for m in members], C_pad)
                if config_pack else ())
        obs.counter("stream.batched_keys").inc(len(members))
        live = list(members)

        def _fallback(ss):
            for s in ss:
                # resume from wherever the batched legs got it to
                s._cp = s._scan_cp
                results[id(s)] = s.check()

        try:
            for targets in ("stable", "tail"):
                pairs = []
                for s in live:
                    t = (max(s._stable_ev, s._scan_cp.event_index)
                         if targets == "stable" else s.enc.n_returns)
                    if s._scan_cp.ok and s._scan_cp.event_index < t:
                        pairs.append((s, t))
                if pairs:
                    mode, note, overflowed = _batch_leg(
                        pairs, N, C_pad, dedupe, probe_limit,
                        sparse_pallas, device, platform,
                        search_stats=search_stats, pack=pack)
                    if overflowed:
                        # the capacity ladder is per key: overflowed
                        # members leave the group and re-run solo
                        _fallback(overflowed)
                        live = [s for s in live
                                if id(s) not in results]
                if targets == "stable":
                    for s in live:
                        s._cp_stable = s._scan_cp
            for s in live:
                s._cp_tail = s._scan_cp
                resume_ev = (s._cp.event_index
                             if s._cp is not None else 0)
                mode_s, note_s = engine._resolve_sparse_pallas(
                    s.sparse_pallas, s._scan_cp.capacity,
                    s.enc.slot_f.shape[1], platform, s.dedupe, pack)
                results[id(s)] = s._finish(s._scan_cp, mode_s, note_s,
                                           resume_ev, None,
                                           pack=pack, pack_C=C_pad)
        except sup.DISPATCH_FAILURES:
            # a dead batched dispatch costs the batch nothing but the
            # batching: each member degrades through its own
            # contract (retry, then host resume from its checkpoint)
            _fallback([s for s in live if id(s) not in results])
    return [results[id(s)] for s in sessions]
