"""The decision ledger: durable, bounded, append-only evidence
records for every device-dispatch and strategy decision the engines
make (``JEPSEN_TPU_LEDGER``).

Why it exists (ROADMAP item 2): the engine has five orthogonal
strategy axes (dedupe sort|hash, fused|tiled|xla closure,
packed|unpacked, pipeline depth, reshard rung) and all the evidence
for choosing between them per shape is ephemeral — ``search_stats``
blocks die with the result dict, registry snapshots die with the
process, and the ``bench_results/`` perf_ab verdicts are never joined
against live traffic. The ledger makes that evidence durable and
queryable: one compact JSONL record per dispatch (and per escalation
/ reshard / publish decision), carrying

    shape       the padded-program fingerprint — event family, N
                (capacity), R (padded events), C (padded slots),
                capacity tier, pack layout
    strategy    the vector that actually ran — dedupe, closure kernel,
                pack, pipeline depth, reshard rung, probe_limit
    secs        wall time between the SAME ``perf_counter`` reads the
                dispatch spans use (bench splits and ledger rows
                cannot disagree)
    stats       a summarized search_stats digest when
                JEPSEN_TPU_SEARCH_STATS is armed (load-factor peak,
                delta-split ratio, pad waste, probe p99)
    outcome     verdict class counts, overflow/escalation trail,
                fallback notes

Format (the ``DeltaWAL`` precedent, simplified for evidence):
append-only JSONL segments ``ledger.<nnnnnnnn>.jsonl`` under the
ledger dir, active segment = highest index. Rotation starts a NEW
higher-indexed file once the active one crosses
``JEPSEN_TPU_LEDGER_SEGMENT_BYTES`` — no renames, so a crash can
never corrupt a sealed segment — and retention unlinks the
lowest-indexed segments past ``JEPSEN_TPU_LEDGER_SEGMENTS`` (counted
``obs.ledger.drops``): the ledger's disk footprint is bounded by
construction, which is what ``tools/soak.py --smoke`` asserts.

Durability posture — evidence-grade, not ack-grade: every append is
flushed (a crash loses at most the OS write-back tail), fsync happens
at rotation and close. Unlike the WAL, NOTHING acknowledged depends
on a ledger record, so a torn or undecodable line anywhere — not
just the tail — is skipped and counted (``obs.ledger.corrupt_lines``)
instead of raising: a ledger hole costs evidence, never correctness.
The torn active tail is truncated before the first append of a
process (the ``_repair_tail`` contract) so restart appends never
concatenate onto partial bytes.

Default off: with ``JEPSEN_TPU_LEDGER`` unset, :func:`active` answers
None, no ``obs.ledger.*`` metric is ever minted, no file is touched,
and results / bench lines / /metrics / /status / trace files are
byte-identical to the pre-ledger tree (parity-pinned by
tests/test_ledger.py).

Consumers: the ``/ledger`` ops endpoint (``obs.httpd``) renders
:func:`ledger_doc` — newest-wins per shape×strategy cell;
``obs.export_run`` copies the records into the store run dir as
``ledger.jsonl``; ``jepsen report --plan`` (``obs.advisor``) joins
them with perf_ab JSONL + ``gate_coverage`` into the recommended-
strategy table the future ``JEPSEN_TPU_AUTO=1`` planner loads.

Import-safe: no JAX, no engine imports — same contract as the rest
of ``obs``. Never call :func:`record` inside jit-traced code
(``purity-obs-in-trace``).
"""

from __future__ import annotations

import json
import logging
import os
import re
import threading
import time
from typing import Dict, List, Optional, Tuple

from jepsen_tpu import envflags
from jepsen_tpu.obs import metrics as _metrics

_log = logging.getLogger(__name__)

LEDGER_VERSION = 1

#: default destination for ``JEPSEN_TPU_LEDGER=1`` — next to the
#: serve WAL's ``store/serve_wal`` convention
DEFAULT_DIR = os.path.join("store", "ledger")
DEFAULT_SEGMENT_BYTES = 1 << 20
DEFAULT_SEGMENTS = 8
DEFAULT_FLOOR = 3

_SEG_RE = re.compile(r"^ledger\.(\d{8})\.jsonl$")


def resolve_ledger_dir() -> Optional[str]:
    """The ledger directory from ``JEPSEN_TPU_LEDGER``: unset/"0" ->
    None (off), "1" -> :data:`DEFAULT_DIR`, anything else -> that
    path. Validation (whitespace-only raises) is ``env_path``'s."""
    dest = envflags.env_path("JEPSEN_TPU_LEDGER",
                             what="ledger directory")
    if dest is None:
        return None
    return dest or DEFAULT_DIR


def plan_table_path(root: str) -> str:
    """Where the ``JEPSEN_TPU_AUTO`` planner persists its decision
    table — beside the ledger segments, since the table is derived
    evidence over them (``parallel.planner``). The dir-layout
    knowledge lives here with the segments' own."""
    return os.path.join(root, "plan_table.json")


def resolve_segment_bytes(v: Optional[int] = None) -> int:
    if v is not None:
        return int(v)
    return envflags.env_int("JEPSEN_TPU_LEDGER_SEGMENT_BYTES",
                            default=DEFAULT_SEGMENT_BYTES,
                            min_value=4096,
                            what="ledger segment size (bytes)")


def resolve_max_segments(v: Optional[int] = None) -> int:
    if v is not None:
        return int(v)
    return envflags.env_int("JEPSEN_TPU_LEDGER_SEGMENTS",
                            default=DEFAULT_SEGMENTS, min_value=2,
                            what="retained ledger segment count")


def sample_floor(v: Optional[int] = None) -> int:
    """The advisor's per-cell evidence floor
    (``JEPSEN_TPU_LEDGER_FLOOR``): a shape cell with fewer ledger
    records than this says "insufficient evidence" instead of
    guessing."""
    if v is not None:
        return int(v)
    return envflags.env_int("JEPSEN_TPU_LEDGER_FLOOR",
                            default=DEFAULT_FLOOR, min_value=1,
                            what="advisor sample floor")


# ------------------------------------------------------------ writer


class DecisionLedger:
    """One process's append handle on a ledger directory (module
    docstring for the format/durability contract). Thread-safe: the
    engines append from dispatch threads, serve from its worker."""

    def __init__(self, root: str,
                 segment_bytes: Optional[int] = None,
                 max_segments: Optional[int] = None):
        self.root = root
        self.segment_bytes = resolve_segment_bytes(segment_bytes)
        self.max_segments = resolve_max_segments(max_segments)
        os.makedirs(root, exist_ok=True)
        self._lock = threading.Lock()
        self._fh = None
        self._n = 0
        existing = _segment_indices(root)
        self._idx = existing[-1] if existing else 1
        path = self._path(self._idx)
        if os.path.exists(path):
            self._repair_tail(path)

    def _path(self, idx: int) -> str:
        return os.path.join(self.root, f"ledger.{idx:08d}.jsonl")

    @staticmethod
    def _repair_tail(path: str) -> None:
        """Truncate a torn (newline-less) trailing line before the
        first append of this process — appending after partial bytes
        would corrupt the NEXT record too (the WAL ``_repair_tail``
        contract). The lost line was never read by anything: ledger
        records are evidence, not acknowledgements."""
        try:
            with open(path, "rb+") as fh:
                fh.seek(0, os.SEEK_END)
                if fh.tell() == 0:
                    return
                fh.seek(-1, os.SEEK_END)
                if fh.read(1) == b"\n":
                    return
                fh.seek(0)
                data = fh.read()
                cut = data.rfind(b"\n")
                fh.truncate(cut + 1 if cut >= 0 else 0)
            _metrics.counter("obs.ledger.corrupt_lines").inc()
            _log.warning("ledger %s: truncated a torn trailing line "
                         "before appending", path)
        except OSError as err:
            _log.warning("ledger %s: could not repair tail (%r)",
                         path, err)

    # AUDITED I/O-under-lock: the buffered write + flush under the
    # ledger lock is what keeps two dispatch threads' records from
    # interleaving bytes; fsync only happens at rotation/close, so
    # the hot-path cost under the lock is one buffered write.
    # jepsen-lint: disable=concurrency-blocking-under-lock
    def record(self, kind: str, **fields) -> None:
        """Append one evidence record. Never raises: an I/O failure
        costs this record (counted ``obs.ledger.drops``), never the
        dispatch that was minting it."""
        try:
            with self._lock:
                self._n += 1
                rec = {"v": LEDGER_VERSION,
                       "t": round(time.time(), 6), "n": self._n,
                       "kind": kind}
                # records stay compact: an absent field is absent,
                # not null (the export "absent, not empty" posture)
                rec.update({k: v for k, v in fields.items()
                            if v is not None})
                line = json.dumps(rec, sort_keys=True, default=str)
                if self._fh is None:
                    self._fh = open(self._path(self._idx), "a")
                self._fh.write(line + "\n")
                self._fh.flush()
                if self._fh.tell() >= self.segment_bytes:
                    self._rotate_locked()
            _metrics.counter("obs.ledger.records").inc()
        except (OSError, ValueError) as err:
            _metrics.counter("obs.ledger.drops").inc()
            _log.warning("ledger: dropped a %s record (%r)", kind,
                         err)

    # AUDITED I/O-under-lock: rotation (seal-fsync + retention unlink)
    # runs under the ledger lock from `record` BY DESIGN — it is rare
    # (once per segment_bytes of evidence) and racing it against
    # appends would tear the segment boundary.
    # jepsen-lint: disable=concurrency-blocking-under-lock
    def _rotate_locked(self) -> None:
        """Seal the active segment (fsync — a sealed segment is never
        written again) and start the next index; then enforce the
        retained-segment bound by unlinking the oldest."""
        fh, self._fh = self._fh, None
        if fh is not None:
            try:
                fh.flush()
                os.fsync(fh.fileno())
            finally:
                fh.close()
        self._idx += 1
        _metrics.counter("obs.ledger.rotations").inc()
        for idx in _segment_indices(self.root)[:-(self.max_segments)]:
            try:
                os.unlink(self._path(idx))
                _metrics.counter("obs.ledger.drops").inc()
            except OSError:
                pass

    # AUDITED I/O-under-lock: the export/shutdown fsync serializes
    # against appends on purpose — syncing a handle mid-append would
    # observe a torn line.
    # jepsen-lint: disable=concurrency-blocking-under-lock
    def sync(self) -> None:
        """fsync the active segment (export / shutdown path)."""
        with self._lock:
            if self._fh is not None:
                self._fh.flush()
                os.fsync(self._fh.fileno())

    def close(self) -> None:
        with self._lock:
            fh, self._fh = self._fh, None
        if fh is not None:
            try:
                fh.flush()
                os.fsync(fh.fileno())
            finally:
                fh.close()


# ------------------------------------------------- process singleton

_active: Optional[DecisionLedger] = None
_resolved = False
_singleton_lock = threading.Lock()


def active() -> Optional[DecisionLedger]:
    """The process ledger, or None when ``JEPSEN_TPU_LEDGER`` is off.
    Resolved once per process (``reset()`` re-resolves — tests). A
    malformed flag value raises :class:`envflags.EnvFlagError` loudly
    at the first dispatch (the envflags contract); an unwritable
    destination logs and disables — evidence must never take down the
    engine."""
    global _active, _resolved
    if _resolved:
        return _active
    with _singleton_lock:
        if _resolved:
            return _active
        root = resolve_ledger_dir()
        if root is not None:
            try:
                _active = DecisionLedger(root)
            except OSError as err:
                _log.warning("ledger: cannot open %s (%r) — ledger "
                             "disabled for this process", root, err)
                _active = None
        _resolved = True
    return _active


def record(kind: str, **fields) -> None:
    """Module-level convenience: append to the active ledger, no-op
    when off. Hook sites that build non-trivial field dicts should
    guard on :func:`active` first so the off path stays one call +
    None check."""
    led = active()
    if led is not None:
        led.record(kind, **fields)


def reset() -> None:
    """Close and forget the process ledger so the next
    :func:`active` re-reads the environment (tests)."""
    global _active, _resolved
    with _singleton_lock:
        if _active is not None:
            _active.close()
        _active = None
        _resolved = False


# ------------------------------------------------------------ reader


def _segment_indices(root: str) -> List[int]:
    out = []
    try:
        names = os.listdir(root)
    except OSError:
        return out
    for name in names:
        m = _SEG_RE.match(name)
        if m:
            out.append(int(m.group(1)))
    return sorted(out)


def segment_paths(root: str) -> List[str]:
    """Segment files in append order (ascending index)."""
    return [os.path.join(root, f"ledger.{i:08d}.jsonl")
            for i in _segment_indices(root)]


def read_records(root: str) -> Tuple[List[dict], int]:
    """Every decodable record in the ledger dir, in append order,
    plus the count of lines skipped as torn/undecodable. Skipping is
    the whole posture (module docstring): a hole costs evidence, so
    it is counted, never raised."""
    records: List[dict] = []
    corrupt = 0
    for path in segment_paths(root):
        try:
            with open(path) as fh:
                for ln in fh:
                    ln = ln.strip()
                    if not ln:
                        continue
                    try:
                        rec = json.loads(ln)
                    except ValueError:
                        corrupt += 1
                        continue
                    if isinstance(rec, dict):
                        records.append(rec)
                    else:
                        corrupt += 1
        except OSError:
            continue
    return records, corrupt


def size_bytes(root: str) -> int:
    total = 0
    for path in segment_paths(root):
        try:
            total += os.path.getsize(path)
        except OSError:
            pass
    return total


# ------------------------------------------------ digests and cells


def probe_p99(hist: Optional[dict]) -> Optional[str]:
    """The bucket label covering p99 of a search-stats probe-length
    histogram ({label: count}) — the hash-table health number the
    advisor ranks probe_limit evidence by."""
    if not hist:
        return None
    items = [(lab, int(n)) for lab, n in hist.items() if n]
    total = sum(n for _, n in items)
    if not total:
        return None
    running = 0
    for lab, n in items:
        running += n
        if running >= 0.99 * total:
            return lab
    return items[-1][0]


def stats_digest(stats_blocks: List[dict]) -> Optional[dict]:
    """Summarize the per-key search_stats blocks of one dispatch into
    the compact digest the ledger record carries: worst load factor,
    mean delta-split ratio, mean pad waste, aggregate probe p99.
    Reads the block fields defensively — an absent field is absent in
    the digest, never a guess."""
    if not stats_blocks:
        return None
    digest: dict = {}
    lf = [b.get("load-factor-peak") for b in stats_blocks
          if b.get("load-factor-peak") is not None]
    if lf:
        digest["load_factor_peak"] = round(max(float(v) for v in lf), 6)
    ds = [b.get("delta-split") for b in stats_blocks
          if b.get("delta-split") is not None]
    if ds:
        digest["delta_split"] = round(
            sum(float(v) for v in ds) / len(ds), 6)
    pw = [b.get("pad-waste") for b in stats_blocks
          if b.get("pad-waste") is not None]
    if pw:
        digest["pad_waste"] = round(
            sum(float(v) for v in pw) / len(pw), 6)
    agg: dict = {}
    for b in stats_blocks:
        for lab, n in (b.get("probe-hist") or {}).items():
            agg[lab] = agg.get(lab, 0) + int(n)
    p99 = probe_p99(agg)
    if p99 is not None:
        digest["probe_p99"] = p99
    return digest or None


def verdict_class(r: Optional[dict]) -> str:
    """A result dict's verdict as the ledger's small vocabulary:
    valid / invalid / unknown."""
    if r is None:
        return "unknown"
    v = r.get("valid?")
    if v is True:
        return "valid"
    if v is False:
        return "invalid"
    return "unknown"


def shape_sig(shape: Optional[dict]) -> str:
    """A shape fingerprint dict as the stable cell-key half: sorted
    ``k=v`` pairs, so two processes (or two PRs) render the same
    shape identically."""
    if not shape:
        return "-"
    return ",".join(f"{k}={shape[k]}" for k in sorted(shape))


def strategy_sig(strategy: Optional[dict]) -> str:
    if not strategy:
        return "-"
    return ",".join(f"{k}={strategy[k]}" for k in sorted(strategy))


def cell_key(rec: dict) -> str:
    """The shape×strategy aggregation cell a record lands in —
    ``<engine>/<kind> shape|strategy``."""
    return (f"{rec.get('engine', '?')}/{rec.get('kind', '?')} "
            f"{shape_sig(rec.get('shape'))}"
            f"|{strategy_sig(rec.get('strategy'))}")


def aggregate(records: List[dict]) -> Dict[str, dict]:
    """Newest-wins per shape×strategy cell: each cell keeps its
    newest record (by append time, then sequence) plus evidence count
    and total/mean secs — the /ledger document's body and the
    advisor's per-cell input."""
    cells: Dict[str, dict] = {}
    for rec in records:
        key = cell_key(rec)
        cell = cells.get(key)
        if cell is None:
            cell = cells[key] = {"count": 0, "total_secs": 0.0,
                                 "newest": rec}
        cell["count"] += 1
        secs = rec.get("secs")
        if isinstance(secs, (int, float)):
            cell["total_secs"] += float(secs)
        newest = cell["newest"]
        if ((rec.get("t") or 0, rec.get("n") or 0)
                >= (newest.get("t") or 0, newest.get("n") or 0)):
            cell["newest"] = rec
    for cell in cells.values():
        cell["total_secs"] = round(cell["total_secs"], 6)
        cell["mean_secs"] = round(cell["total_secs"]
                                  / max(1, cell["count"]), 6)
    return cells


def ledger_doc(root: Optional[str] = None) -> dict:
    """The ``/ledger`` endpoint document: header (dir, record/
    segment/corrupt counts, bytes) + the newest-wins cell table.
    Ledger off answers ``{"ledger": {"enabled": False}, "cells": {}}``
    — a valid, empty document, the /trace posture."""
    if root is None:
        led = active()
        if led is not None:
            led.sync()
            root = led.root
        else:
            root = resolve_ledger_dir()
    if root is None:
        return {"ledger": {"enabled": False}, "cells": {}}
    records, corrupt = read_records(root)
    return {"ledger": {"enabled": True, "dir": root,
                       "records": len(records),
                       "segments": len(segment_paths(root)),
                       "corrupt": corrupt,
                       "bytes": size_bytes(root)},
            "cells": aggregate(records)}
