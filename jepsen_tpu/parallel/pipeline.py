"""Pipelined multi-key checking: overlap host encode, H2D transfer,
and device search.

The round-5 on-chip numbers (bench_results/bench_r05_onchip.jsonl)
showed the batched checker is no longer
search-bound: device-only throughput beat end-to-end by ~7%, and the
whole gap is the HOST phase — `check_batch` encoded every key serially
in Python before the first device dispatch, so the TPU sat idle
through the entire encode. This module restructures that into a
stream:

  1. **Bucket first.** Stage 1 of the encode (`encode.prepare_encode`:
     call packing + slot assignment — cheap, and where the bulk
     `spec.encode_calls` hook lives) runs for every key on a host
     worker pool. Its `n_slots`/`n_states` are exactly what the serial
     path's bucketing consumes, so the grouping (`engine.bucket_key`,
     tier or exact policy) matches `check_batch_encoded` bit for bit.
  2. **Stream buckets through a bounded double buffer.** Each bucket
     is split into near-equal chunks; a chunk's stage-2 encode
     (`encode.finish_encode`, the allocation-heavy snapshot fill) runs
     on the pool and its padded batch is placed + issued via
     `bitdense.dispatch_batch_bitdense` — JAX async dispatch returns
     immediately, so chunk k+1 encodes and transfers while chunk k's
     program runs on the device. At most `depth` programs are in
     flight; results are consumed (`finalize()`) oldest-first. Chunks
     pad to the BUCKET's (S, C, R) dims so the closure gating (pallas
     included) resolves as the whole bucket would and all chunks of a
     size share one jit shape (the near-equal split keeps a bucket to
     at most two chunk sizes). Sparse
     buckets (dims past the bitdense budget) run whole and
     synchronously through `engine._check_batch_sparse` — same ladder,
     same results; they are the rare tail, not the bench path.
  3. **Encode cache.** Encodings are memoized in a digest-keyed LRU
     (`EncodeCache`) so re-analysis of a stored history, bench
     warm/steady phases, and repeated checker passes stop re-paying
     the encode. The key is a content digest of (model, op stream) —
     mutate a history in place and the digest moves, so a stale hit is
     structurally impossible; the entry carries the ENCODED digest
     (`engine.history_digest`) as a cross-check for tests. Optional
     `store_dir` persistence spills entries to disk (pickle — load
     only from store dirs you wrote; the prepared spec's closures are
     rebuilt from the model on load, not persisted).

Results are bit-identical to serial `check_batch` — verdicts,
counterexample fields, engine/closure tags, and ordering — which the
parity suite (tests/test_pipeline.py) pins across every packable model
family. Opt-in via `check_batch(pipeline=True)` or
JEPSEN_TPU_PIPELINE=1 (validated accessor; flags do not get to claim
speedups until bench records the win — see docs/performance.md).
"""

from __future__ import annotations

import hashlib
import logging
import os
import threading
from collections import OrderedDict, deque
from concurrent.futures import ThreadPoolExecutor
from dataclasses import dataclass
from time import perf_counter
from typing import Optional

from jepsen_tpu import envflags
from jepsen_tpu import models as model_ns
from jepsen_tpu import obs
from jepsen_tpu.obs import ledger as _ledger
from jepsen_tpu.parallel import encode as enc_mod
from jepsen_tpu.parallel import engine
from jepsen_tpu.parallel import planner as _planner
from jepsen_tpu.parallel.encode import EncodedHistory
from jepsen_tpu.resilience import supervisor as sup

_log = logging.getLogger(__name__)

DEFAULT_CACHE_ENTRIES = 256
DEFAULT_CHUNK_KEYS = 32


# ------------------------------------------------------------ cache key


def encode_cache_key(model, history, pad_slots: Optional[int] = None) -> str:
    """Content digest of (model, op stream, pad_slots) — the encode
    cache key. Hashes exactly what the encoder consumes: the model's
    identity and state (repr — stable for the dataclass model
    families) and every op's (process, type, f, value) in stream order
    (invoke/complete pairing is positional, so order IS part of the
    content). In-place mutation of a history therefore yields a new
    key: a stale hit after mutation is structurally impossible, which
    is the cache's invalidation contract (docs/performance.md).

    The contract rides on repr being content-complete, which holds for
    the EDN plain data op values are by framework contract (numbers,
    strings, lists, KV tuples, sets/maps). A custom value object with
    the default address-based repr would weaken it two ways: the key
    changes across processes (persisted entries degrade to misses —
    the safe direction) and an in-place mutation of the object's
    internals does NOT move the key (a stale hit — the unsafe one).
    Don't put such objects in op values; the encoder's Intern table
    would mis-handle them anyway."""
    h = hashlib.sha256()
    h.update(repr((type(model).__module__, type(model).__qualname__,
                   model, pad_slots)).encode())
    for o in history:
        h.update(repr((o.get("process"), o.get("type"), o.get("f"),
                       o.get("value"))).encode())
    return h.hexdigest()[:32]


# ---------------------------------------------------------- EncodeCache


_PERSIST_FIELDS = ("slot_f", "slot_a0", "slot_a1", "slot_wild",
                   "slot_occ", "ev_slot", "ret_call", "state0",
                   "step_name", "n_calls", "n_slots", "calls", "intern",
                   "state_lo", "n_states", "model_pruned")
_PERSIST_VERSION = 2
DEFAULT_CACHE_BYTES = 512 << 20   # in-memory array-byte budget


class EncodeCache:
    """Digest-keyed LRU of EncodedHistory, with optional store-dir
    persistence.

    Thread-safe (the pipeline's worker pool reads and writes it
    concurrently). `max_entries` bounds the in-memory LRU (default:
    JEPSEN_TPU_ENCODE_CACHE via the validated accessor, else
    DEFAULT_CACHE_ENTRIES; 0 disables the cache entirely) and
    `max_bytes` bounds its summed array payload (a 10k-op adversarial
    entry is tens of MB — 256 entries of those must not silently pin
    gigabytes; whichever bound trips first evicts). With `store_dir`,
    entries spill to pickle files and survive the process —
    re-analysis of a stored run re-pays zero encodes. Disk growth is
    deliberate and unbounded, the same posture as the run store: the
    directory is an artifact the operator owns and prunes. The
    prepared spec (history-dependent closures: gset lanes, queue
    widths) is NOT persisted; `get()` rebuilds it from the model +
    stored calls. That rebuild is only deterministic when the stored
    calls equal the list `prepare` originally saw — entries whose
    model-specific wildcard prune dropped calls AFTER prepare
    (EncodedHistory.model_pruned) are therefore kept in memory but
    never persisted, and loads are cross-checked against the stored
    state0/n_states. Pickles are only as trustworthy as whoever wrote
    them: point `store_dir` at directories this framework owns."""

    def __init__(self, max_entries: Optional[int] = None,
                 store_dir: Optional[str] = None,
                 max_bytes: int = DEFAULT_CACHE_BYTES):
        if max_entries is None:
            max_entries = envflags.env_int(
                "JEPSEN_TPU_ENCODE_CACHE",
                default=DEFAULT_CACHE_ENTRIES, min_value=0,
                what="encode-cache capacity")
        self.max_entries = int(max_entries)
        self.max_bytes = int(max_bytes)
        self.store_dir = store_dir
        if store_dir is not None:
            os.makedirs(store_dir, exist_ok=True)
        self._lock = threading.Lock()
        self._entries: OrderedDict = OrderedDict()
        self._bytes = 0
        self.hits = 0
        self.disk_hits = 0
        self.misses = 0
        self.encodes = 0

    # -- accounting

    def counters(self) -> dict:
        with self._lock:
            return {"hits": self.hits, "disk_hits": self.disk_hits,
                    "misses": self.misses, "encodes": self.encodes,
                    "entries": len(self._entries),
                    "bytes": self._bytes}

    def note_encode(self):
        """An encode was actually paid (cache miss path) — the counter
        the zero-re-encode assertions watch."""
        with self._lock:
            self.encodes += 1

    # -- core

    def get(self, key: str, model=None) -> Optional[EncodedHistory]:
        if self.max_entries == 0:
            with self._lock:
                self.misses += 1
            return None
        with self._lock:
            e = self._entries.get(key)
            if e is not None:
                self._entries.move_to_end(key)
                self.hits += 1
                return e
        e = self._load(key, model)
        if e is not None:
            with self._lock:
                self.disk_hits += 1
            self.put(key, e, persist=False)
            return e
        with self._lock:
            self.misses += 1
        return None

    @staticmethod
    def _entry_bytes(e: EncodedHistory) -> int:
        return sum(getattr(e, f).nbytes for f in
                   ("slot_f", "slot_a0", "slot_a1", "slot_wild",
                    "slot_occ", "ev_slot", "ret_call"))

    def put(self, key: str, e: EncodedHistory, persist: bool = True):
        if self.max_entries == 0:
            return
        nb = self._entry_bytes(e)
        with self._lock:
            old = self._entries.pop(key, None)
            if old is not None:
                self._bytes -= self._entry_bytes(old)
            self._entries[key] = e
            self._bytes += nb
            while self._entries and (len(self._entries) > self.max_entries
                                     or self._bytes > self.max_bytes):
                if len(self._entries) == 1:
                    break  # always keep the newest entry, however big
                _, ev = self._entries.popitem(last=False)
                self._bytes -= self._entry_bytes(ev)
        if persist and self.store_dir is not None:
            self._save(key, e)

    # -- persistence

    def _path(self, key: str) -> str:
        return os.path.join(self.store_dir, f"enc_{key}.pkl")

    def _save(self, key: str, e: EncodedHistory):
        import pickle
        if e.model_pruned and e.spec is not None \
                and getattr(e.spec, "prepare", None) is not None:
            # the stored calls no longer equal the list prepare built
            # its lane tables from (the model-specific wildcard prune
            # ran AFTER prepare) — a disk reload's rebuilt spec could
            # assign different lanes and unpack device states wrongly.
            # Keep such entries in memory (they carry the original
            # spec object) but never on disk.
            return
        payload = {"version": _PERSIST_VERSION,
                   "fields": {f: getattr(e, f) for f in _PERSIST_FIELDS}}
        path = self._path(key)
        tmp = path + ".tmp"
        try:
            with open(tmp, "wb") as f:
                pickle.dump(payload, f)
            os.replace(tmp, path)
        except Exception as err:  # noqa: BLE001 — persistence is an
            # optimization; a value that won't pickle (exotic op
            # payloads) must not fail the check. But say so: silence
            # would look like the store dir works when it doesn't.
            _log.warning(
                "encode cache: could not persist entry %s (%r) — "
                "in-memory cache unaffected", key, err)

    def _load(self, key: str, model) -> Optional[EncodedHistory]:
        if self.store_dir is None:
            return None
        import pickle
        path = self._path(key)
        if not os.path.exists(path):
            return None
        try:
            with open(path, "rb") as f:
                payload = pickle.load(f)
            if payload.get("version") != _PERSIST_VERSION:
                return None
            e = EncodedHistory(spec=None, **payload["fields"])
        except Exception as err:  # noqa: BLE001 — a corrupt/stale
            # entry degrades to a miss, loudly
            _log.warning(
                "encode cache: unreadable persisted entry %s (%r) — "
                "treating as a miss", key, err)
            return None
        # rebuild the prepared spec: its closures (gset lanes, queue
        # widths) are not persistable. prepare() is a deterministic
        # function of (model, calls), and _save refused any entry whose
        # stored calls differ from what prepare originally saw
        # (model_pruned), so the rebuild is faithful — the
        # state0/n_states cross-check below is defense in depth against
        # stale files written before that rule (or by other builds).
        if model is not None:
            try:
                spec = model_ns.pack_spec(model, e.intern)
                if spec is not None and spec.prepare is not None:
                    spec.prepare(e.calls, e.intern)
                if spec is not None:
                    rebuilt_n = (spec.n_states(e.intern) if spec.n_states
                                 else len(e.intern) + 1)
                    if spec.state0 != e.state0 or rebuilt_n != e.n_states:
                        return None   # lane/width drift: miss, re-encode
                e.spec = spec
            except Exception:  # noqa: BLE001 — a model that no longer
                # prepares against the stored calls means the entry is
                # for something else: miss, re-encode
                return None
        return e


_default_cache = None
_default_cache_lock = threading.Lock()


def default_cache() -> EncodeCache:
    """The process-wide encode cache the pipelined executor uses when
    the caller passes none (sized by JEPSEN_TPU_ENCODE_CACHE)."""
    global _default_cache
    if _default_cache is None:
        with _default_cache_lock:
            if _default_cache is None:
                _default_cache = EncodeCache()
    return _default_cache


# ------------------------------------------------------- worker stages


@dataclass
class _KeyInfo:
    """Per-key phase-1 outcome: a cache hit (enc) or a stage-1 encode
    (prep) awaiting its fill."""

    ckey: Optional[str]
    enc: Optional[EncodedHistory]
    prep: object
    secs: float
    hit: bool

    @property
    def n_slots(self) -> int:
        return (self.enc or self.prep).n_slots

    @property
    def n_states(self) -> int:
        return (self.enc or self.prep).n_states

    @property
    def n_returns(self) -> int:
        return (self.enc or self.prep).n_returns


def encode_cached(model, history, cache: Optional[EncodeCache] = None,
                  pad_slots: Optional[int] = None) -> EncodedHistory:
    """encode() through the cache: the single-key entry point for
    re-analysis paths (engine.analysis(encode_cache=...), stored-run
    re-checks) that want to stop re-paying the encode without going
    through the batch executor. None -> the process default cache."""
    if cache is None:
        cache = default_cache()
    if cache.max_entries == 0:
        # disabled (JEPSEN_TPU_ENCODE_CACHE=0) must cost nothing:
        # no O(history) digest, no lock, just the encode
        return enc_mod.encode(model, history, pad_slots=pad_slots)
    key = encode_cache_key(model, history, pad_slots)
    e = cache.get(key, model)
    if e is None:
        e = enc_mod.encode(model, history, pad_slots=pad_slots)
        cache.note_encode()
        cache.put(key, e)
    return e


def _lookup_or_prepare(model, h, cache: Optional[EncodeCache],
                       key: Optional[int] = None) -> _KeyInfo:
    # the timer runs on a pool thread; ctx_runner propagation in the
    # executor makes it nest under the pipeline.run root span. timer,
    # not span: the recorded span IS the prep_secs fed to
    # pipeline_stats, so the two can never disagree.
    e = prep = ckey = None
    with obs.timer("pipeline.prepare", key=key) as sp:
        if cache is not None:
            ckey = encode_cache_key(model, h)
            e = cache.get(ckey, model)
        if e is not None:
            sp.set(hit=True)
        else:
            prep = enc_mod.prepare_encode(model, h)
    return _KeyInfo(ckey, e, prep, sp.wall, e is not None)


def _fill(prep, cache: Optional[EncodeCache], ckey: Optional[str],
          key: Optional[int] = None):
    with obs.timer("pipeline.encode", key=key) as sp:
        e = enc_mod.finish_encode(prep)
    if cache is not None:
        cache.note_encode()
        cache.put(ckey, e)
    return e, sp.wall


def _chunks(idxs: list, chunk_keys: int, align: int = 1) -> list:
    """Split a bucket into chunks of <= ~chunk_keys keys.

    Meshless (align=1): near-equal sizes rather than greedy, because
    jit caches by shape — a greedy split of 84 keys at 32 compiles
    K=32 AND K=20 programs, the near-equal split compiles K=28 once.

    With a mesh (align = device count): every full chunk is a MULTIPLE
    of align, because place_batch only shards the key axis when K
    divides the mesh — un-aligned chunks would silently replicate
    every key to every device, ~device-count times the work on the
    executor whose whole point is speed. Only the final remainder
    chunk may be un-aligned (it replicates, exactly as a serial
    whole-bucket dispatch of that K would)."""
    n = len(idxs)
    if align > 1:
        ck = max(align, (max(1, chunk_keys) // align) * align)
        out = [idxs[p:p + ck] for p in range(0, n - n % ck, ck)]
        rem = idxs[n - n % ck:]
        r_aligned = len(rem) - len(rem) % align
        if r_aligned:
            out.append(rem[:r_aligned])   # still shards
        if len(rem) % align:
            out.append(rem[r_aligned:])   # tail replicates, as serial
            # dispatch of the same K would
        return out
    k = max(1, -(-n // max(1, chunk_keys)))  # ceil(n / chunk_keys)
    base, rem = divmod(n, k)
    out = []
    pos = 0
    for j in range(k):
        size = base + (1 if j < rem else 0)
        out.append(idxs[pos:pos + size])
        pos += size
    return out


# ------------------------------------------------------------ executor


def check_batch_pipelined(model, histories, capacity: int = 512,
                          max_capacity: int = 1 << 18, mesh=None,
                          bucket: Optional[str] = None, cache=None,
                          workers: Optional[int] = None,
                          chunk_keys: int = DEFAULT_CHUNK_KEYS,
                          depth: int = 2,
                          stats: Optional[dict] = None,
                          dedupe: Optional[str] = None,
                          sparse_pallas: Optional[bool] = None,
                          search_stats: Optional[bool] = None,
                          config_pack: Optional[bool] = None,
                          reshard: Optional[bool] = None) -> list:
    """engine.check_batch with the three host/device phases overlapped
    (module docstring). Same arguments and bit-identical results;
    extras:

    cache       EncodeCache to consult/fill (None -> the process
                default; False -> no caching this call)
    workers     host pool width for the encode stages
    chunk_keys  target keys per dispatched chunk (the double buffer's
                granularity)
    depth       max device programs in flight before the oldest is
                consumed
    stats       optional dict, filled with the per-bucket
                encode/transfer/device split and cache counters —
                the numbers bench.py's multikey section reports
    dedupe      frontier dedupe strategy for sparse buckets
                (engine._resolve_dedupe; None = JEPSEN_TPU_DEDUPE) —
                recorded in stats so the bench lines can say which
                strategy was active
    sparse_pallas  route the sparse buckets' hash closure through the
                fused VMEM frontier kernel (engine.check_encoded's
                docstring; None = JEPSEN_TPU_SPARSE_PALLAS)
    search_stats  per-key device-computed search telemetry in the
                result "stats" dicts (engine._resolve_search_stats;
                None = JEPSEN_TPU_SEARCH_STATS)
    config_pack  packed configuration rows for the sparse buckets
                (engine.check_encoded's docstring; None =
                JEPSEN_TPU_CONFIG_PACK) — bitdense buckets are
                untouched (the dense bitmap has no row triple to pack)
    reshard     device-recruiting escalation for overflow keys (None
                = JEPSEN_TPU_RESHARD; engine._escalate_overflow)
    """
    bucket = engine._resolve_bucket(bucket)
    if _planner.active() is None:
        dedupe = engine._resolve_dedupe(dedupe)
        dedupe_label = dedupe
    else:
        # fail-fast validation only: with the planner armed a raw
        # dedupe request flows through to the sparse tail so each
        # bucket plans its own arm per shape (_check_batch_sparse);
        # stats say "auto" rather than pretending the static default
        # ran — per-key results carry the actual chosen vector in
        # their "plan" block
        engine._resolve_dedupe(dedupe)
        dedupe_label = dedupe if dedupe is not None else "auto"
    search_stats = engine._resolve_search_stats(search_stats)
    if stats is None:
        stats = {}
    K = len(histories)
    stats.update({"n_keys": K, "bucket": bucket,
                  "dedupe": dedupe_label, "buckets": []})
    if K == 0:
        return []
    if cache is None:
        cache = default_cache()
    elif cache is False:
        cache = None
    if cache is not None and cache.max_entries == 0:
        # JEPSEN_TPU_ENCODE_CACHE=0: a disabled cache must cost
        # nothing — without this, every key would still pay the
        # content digest (O(history) in the exact host hot path this
        # executor exists to shrink) just to hit a guaranteed miss
        cache = None
    c0 = cache.counters() if cache is not None else None

    from jepsen_tpu.parallel import bitdense

    root = obs.span("pipeline.run", keys=K, bucket=bucket,
                    dedupe=dedupe_label)
    with root, obs.maybe_jax_profile():
        out = _stream(model, histories, capacity, max_capacity, mesh,
                      bucket, cache, workers, chunk_keys, depth, stats,
                      dedupe, bitdense, sparse_pallas, search_stats,
                      config_pack, reshard)
    if c0 is not None:
        c1 = cache.counters()
        stats["cache"] = {k: c1[k] - c0[k] for k in
                          ("hits", "disk_hits", "misses", "encodes")}
        stats["cache"]["entries"] = c1["entries"]
        # the SAME deltas feed the registry: the bench line's cache
        # block and the telemetry export read one measurement
        reg = obs.registry()
        for k in ("hits", "disk_hits", "misses", "encodes"):
            if stats["cache"][k]:
                reg.counter(f"pipeline.cache.{k}").inc(stats["cache"][k])
    return out


def _stream(model, histories, capacity, max_capacity, mesh, bucket,
            cache, workers, chunk_keys, depth, stats, dedupe,
            bitdense, sparse_pallas=None,
            search_stats: bool = False, config_pack=None,
            reshard=None) -> list:
    """The executor body (check_batch_pipelined's docstring), under the
    pipeline.run root span. Telemetry it feeds: pipeline.prepare /
    pipeline.encode spans on the pool threads (nested via ctx_runner),
    pipeline.dispatch / pipeline.finalize spans per chunk on the main
    thread, one synthetic device-track span per chunk's in-flight
    window (the "one track per device bucket" rows in the Chrome
    trace), the pipeline.inflight depth gauge, and the
    pipeline.keys/chunks counters — all from the same clock reads that
    fill the caller-visible `stats` dict."""
    K = len(histories)
    reg = obs.registry()
    reg.counter("pipeline.keys").inc(K)
    inflight = reg.gauge("pipeline.inflight")

    def _depth(n: int):
        """Gauge + counter-track sample from the SAME level read: the
        Perfetto inflight area chart and the registry gauge cannot
        disagree (counter_sample is a no-op with tracing off)."""
        inflight.set(n)
        obs.counter_sample("pipeline.inflight", n)

    wrap = obs.ctx_runner()

    t_wall = perf_counter()
    out: list = [None] * K
    n_workers = workers or min(8, max(2, os.cpu_count() or 2))
    with ThreadPoolExecutor(max_workers=min(n_workers, K)) as pool:
        # ---- phase 1: cache lookups + stage-1 encodes, in parallel.
        # n_slots/n_states land here, so the bucketing below consumes
        # exactly what the serial path's would.
        infos = list(pool.map(
            wrap(lambda ih: _lookup_or_prepare(model, ih[1], cache,
                                               key=ih[0])),
            enumerate(histories)))
        stats["prepare_secs"] = round(perf_counter() - t_wall, 4)

        buckets: dict = {}
        for i, info in enumerate(infos):
            buckets.setdefault(engine.bucket_key(info.n_slots, bucket),
                               []).append(i)

        # ---- phase 2: submit the stage-2 fills in processing order;
        # the pool chews through them while the main thread pads,
        # places, and dispatches earlier chunks — the overlap.
        order = [i for tier in sorted(buckets) for i in buckets[tier]]
        fills = {}
        for i in order:
            if infos[i].enc is None:
                fills[i] = pool.submit(wrap(_fill), infos[i].prep,
                                       cache, infos[i].ckey, i)

        def enc_of(i):
            info = infos[i]
            if info.enc is None:
                e, dt = fills[i].result()
                info.enc = e
                info.secs += dt
            return info.enc

        # ---- phase 3: stream buckets through the double buffer
        pending: deque = deque()
        bstats: list = []

        def degrade_chunk(chunk_idxs, err, bstat):
            """A failed chunk degrades ONLY ITS KEYS to the host WGL
            path with a structured resilience note (the degradation
            contract, docs/resilience.md) — the rest of the batch
            keeps its device results instead of dying with the chunk."""
            from jepsen_tpu.resilience import recovery
            reason = f"{type(err).__name__}: {err}"
            reg.counter("pipeline.chunks_degraded").inc()
            site = getattr(err, "site", "pipeline")
            for i in chunk_idxs:
                out[i] = recovery.host_check_encoded(
                    model, enc_of(i), site, reason)
            bstat["degraded"] = bstat.get("degraded", 0) + len(chunk_idxs)

        def drain_one():
            chunk_idxs, pb, bstat, chunk_no, t_issue = pending.popleft()
            try:
                with obs.span("pipeline.finalize", tier=bstat["tier"],
                              chunk=chunk_no, keys=len(chunk_idxs)):
                    rs = sup.dispatch("pipeline", pb.finalize)
            except sup.DISPATCH_FAILURES as err:
                degrade_chunk(chunk_idxs, err, bstat)
                _depth(len(pending))
                return
            _depth(len(pending))
            t_done = perf_counter()
            tr = obs.tracer()
            if tr is not None:
                # the chunk's whole in-flight window on a per-bucket
                # device track: issue -> results materialized. An
                # approximation of device occupancy (JAX async dispatch
                # hides the exact kernel window; the jax.profiler
                # capture has ground truth), but the right shape for
                # seeing overlap in Perfetto.
                tr.add_span("device.search", t_issue, t_done,
                            track=f"bucket-{bstat['tier']}",
                            chunk=chunk_no, keys=len(chunk_idxs),
                            engine=bstat["engine"])
            led = _ledger.active()
            if led is not None:
                # one record per drained chunk: the in-flight window
                # (same clock reads as the device.search span) plus
                # the pipeline-level strategy the bitdense record
                # cannot see (depth, chunk sizing)
                led.record(
                    "dispatch", engine="pipeline",
                    shape={"family": enc_of(chunk_idxs[0]).step_name,
                           "tier": bstat["tier"]},
                    strategy={"engine": bstat["engine"],
                              "depth": depth,
                              "chunk_keys": chunk_keys},
                    secs=round(t_done - t_issue, 6),
                    keys=len(chunk_idxs), chunk=chunk_no,
                    outcome={"valid": sum(1 for r in rs
                                          if r["valid?"] is True),
                             "invalid": sum(1 for r in rs
                                            if r["valid?"] is False)})
            bstat["transfer_secs"] += pb.transfer_secs
            bstat["device_wait_secs"] += pb.device_wait_secs
            for i, r in zip(chunk_idxs, rs):
                out[i] = r

        for tier in sorted(buckets):
            idxs = buckets[tier]
            S_max = max(infos[i].n_states for i in idxs)
            C_max = max(infos[i].n_slots for i in idxs)
            R_max = max(infos[i].n_returns for i in idxs)
            bstat = {"tier": tier, "keys": len(idxs), "chunks": 0,
                     "encode_secs": 0.0, "transfer_secs": 0.0,
                     "device_wait_secs": 0.0}
            bstats.append(bstat)
            if bitdense.fits_bitdense(S_max, C_max):
                bstat["engine"] = "bitdense"
                align = (1 if mesh is None
                         else int(mesh.shape[mesh.axis_names[0]]))
                for chunk in _chunks(idxs, chunk_keys, align=align):
                    sub = [enc_of(i) for i in chunk]
                    # pad every chunk to the BUCKET's (S, C, R): the
                    # closure gating resolves as the whole bucket
                    # would (the parity tests rely on this) and every
                    # chunk shares one jit shape per chunk size — the
                    # R floor matters most, since per-chunk local
                    # maxima would otherwise make every chunk its own
                    # compile
                    t_issue = perf_counter()
                    try:
                        with obs.span("pipeline.dispatch", tier=tier,
                                      chunk=bstat["chunks"],
                                      keys=len(chunk)):
                            # site "pipeline" wraps the (itself
                            # supervised) bitdense dispatch so the
                            # fault matrix can target chunk dispatch
                            # specifically; the inner sites own the
                            # breaker bookkeeping
                            pb = sup.dispatch(
                                "pipeline",
                                lambda sub=sub: bitdense.
                                dispatch_batch_bitdense(
                                    sub, mesh=mesh, min_states=S_max,
                                    min_slots=max(5, C_max),
                                    min_returns=R_max,
                                    search_stats=search_stats))
                    except sup.DISPATCH_FAILURES as err:
                        degrade_chunk(chunk, err, bstat)
                        bstat["chunks"] += 1
                        reg.counter("pipeline.chunks").inc()
                        continue
                    pending.append((chunk, pb, bstat, bstat["chunks"],
                                    t_issue))
                    bstat["chunks"] += 1
                    reg.counter("pipeline.chunks").inc()
                    _depth(len(pending))
                    while len(pending) >= depth:
                        drain_one()
            else:
                # sparse tail: the per-key capacity-retry ladder is
                # host-interactive, so it runs whole and synchronous —
                # identical results, no double buffering (it still
                # overlaps any earlier chunks left in flight)
                bstat["engine"] = "sparse"
                bstat["chunks"] = 1
                reg.counter("pipeline.chunks").inc()
                sub = [enc_of(i) for i in idxs]
                with obs.span("pipeline.sparse", tier=tier,
                              keys=len(idxs)):
                    rs = engine._check_batch_sparse(
                        model, sub, capacity, max_capacity, mesh,
                        dedupe=dedupe, sparse_pallas=sparse_pallas,
                        search_stats=search_stats,
                        config_pack=config_pack, reshard=reshard)
                for i, r in zip(idxs, rs):
                    out[i] = r
        while pending:
            drain_one()

        for bstat in bstats:
            bstat["encode_secs"] = round(sum(
                infos[i].secs for i in buckets[bstat["tier"]]), 4)
            bstat["transfer_secs"] = round(bstat["transfer_secs"], 4)
            bstat["device_wait_secs"] = round(
                bstat["device_wait_secs"], 4)
            # per-bucket split -> registry histograms: the telemetry
            # export reports the same numbers the stats dict carries
            for key in ("encode_secs", "transfer_secs",
                        "device_wait_secs"):
                reg.histogram(f"pipeline.{key}").observe(bstat[key])

    stats["buckets"] = bstats
    stats["e2e_secs"] = round(perf_counter() - t_wall, 4)
    return out
