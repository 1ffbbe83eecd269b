"""The self-tuning strategy planner (``JEPSEN_TPU_AUTO``).

ROADMAP item 2's ONLINE half: the engine has four orthogonal strategy
axes (dedupe sort|hash, pallas closure, config pack, pipeline)
and peak speed used to require an operator who knows the whole flag
table. With ``JEPSEN_TPU_AUTO=1`` the engines ask this module, per
slot-window bucket, for the strategy vector to run — chosen from a
small per-shape decision table that is

  seeded    offline from ``bench_results/`` perf_ab JSONL joined with
            the decision ledger by the ``jepsen report --plan``
            advisor (``obs.advisor.build_plan`` — the advisor IS the
            seed loader),
  updated   online from the per-dispatch secs/shape/strategy evidence
            the engines already measure (EWMA per shape×strategy
            cell, via :func:`ewma_update`),
  explored  occasionally (every ``JEPSEN_TPU_AUTO_EXPLORE``-th
            decision per shape, default 8, 0 = off): the
            least-sampled non-chosen arm runs instead, so a table
            seeded on stale evidence self-corrects.

A cell below the sample floor (``JEPSEN_TPU_LEDGER_FLOOR``) never
decides: the static defaults run (source ``floor-default``) and the
dispatch merely contributes evidence. Wrong-plan recovery is free by
construction — a plan only routes between already-parity-pinned
paths (verdict/op/fail-event/max-frontier/configs-stepped identical
across every arm), so the planner can never produce a wrong verdict,
only a slower one, and the overflow/fallback/escalation machinery is
untouched.

Provenance: every planned result carries a ``"plan"`` block
({chosen vector, table cell evidence count, source:
seeded|online|floor-default, explored: bool}) which the serve
``/status`` rows surface; every decision mints a ``kind=plan``
decision-ledger record and an ``engine.plan.decisions`` counter.

Durability: the table persists as ``plan_table.json`` beside the
ledger segments (``obs.ledger.plan_table_path``), written atomically
(tmp + ``os.replace``). A truncated/garbage/stale-schema file
degrades to a re-seed (counted ``engine.plan.reseeds``) — never a
crash, never a wrong program. With the ledger off the table is
process-local memory only.

Flag off (unset/"0"): :func:`active` answers None, no file is
touched, no ``engine.plan.*`` metric is minted, and results / bench
lines / ``/status`` / WAL bytes are identical to the pre-planner
tree (parity-pinned by tests/test_planner.py).

Import-safe: no JAX, no engine imports — the ``/plan`` ops endpoint
and ``jepsen report --plan`` read this module on boxes whose device
runtime may be wedged.
"""

from __future__ import annotations

import json
import logging
import os
import threading
from typing import Dict, List, Optional

from jepsen_tpu import envflags
from jepsen_tpu.obs import ledger as _ledger
from jepsen_tpu.obs import metrics as _metrics

_log = logging.getLogger(__name__)

TABLE_VERSION = 1
DEFAULT_EXPLORE_EVERY = 8
#: the EWMA smoothing of the planner's per-shape cost cells
#: (:func:`ewma_update`): each new observation weighs half
EWMA_ALPHA = 0.5

#: the strategy axes a plan may set, and the env flag each falls back
#: to below the floor (the axis vocabulary of the dispatch records)
AXES = ("dedupe", "pallas", "pack", "pipeline")

#: default perf_ab evidence dir for seeding — the same default
#: ``jepsen report --plan`` resolves
DEFAULT_BENCH_DIR = "bench_results"


def ewma_update(prev: Optional[float], cost: float,
                alpha: float = EWMA_ALPHA) -> float:
    """One exponentially-weighted update: ``alpha`` weights the NEW
    observation. First observation (prev None) adopts the cost
    outright."""
    if prev is None:
        return float(cost)
    return alpha * float(cost) + (1.0 - alpha) * float(prev)


def auto_enabled() -> bool:
    """``JEPSEN_TPU_AUTO`` — strict tri-state (the envflags contract:
    unset/"0" off, "1" on, anything else raises loudly)."""
    return envflags.env_bool("JEPSEN_TPU_AUTO", default=False)


def resolve_explore_every(v: Optional[int] = None) -> int:
    """``JEPSEN_TPU_AUTO_EXPLORE``: run the least-sampled non-chosen
    arm every Nth decision per shape group (default 8); 0 disables
    exploration — the table then only ever sharpens what it has."""
    if v is not None:
        return int(v)
    return envflags.env_int("JEPSEN_TPU_AUTO_EXPLORE",
                            default=DEFAULT_EXPLORE_EVERY, min_value=0,
                            what="planner exploration period")


def group_key(engine: str, family: str, C: Optional[int] = None) -> str:
    """The decision-table row for a dispatch — the SAME key the
    advisor's ``_shape_group`` derives from ledger records, so seeded
    rows and live decisions land in one table."""
    parts = [f"engine={engine}", f"family={family}"]
    if C is not None:
        parts.append(f"C={int(C)}")
    return ",".join(parts)


def _static_default(axis: str):
    """The value an unplanned dispatch would run: the axis's env flag,
    else its measured-off default (the resolver precedent in
    ``engine._resolve_*`` — same flags, evaluated import-safely)."""
    if axis == "dedupe":
        return envflags.env_choice("JEPSEN_TPU_DEDUPE",
                                   ("sort", "hash"), default="sort",
                                   what="dedupe strategy")
    flag = {"pallas": "JEPSEN_TPU_SPARSE_PALLAS",
            "pack": "JEPSEN_TPU_CONFIG_PACK",
            "pipeline": "JEPSEN_TPU_PIPELINE"}[axis]
    return envflags.env_bool(flag, default=False)


def _sanitize(arm: dict) -> dict:
    """Never a wrong program: the fused kernel requires the hash
    dedupe (``engine._resolve_sparse_pallas`` raises on the
    contradiction), so a plan may not combine pallas with sort."""
    if arm.get("pallas") and arm.get("dedupe", "hash") != "hash":
        arm = dict(arm)
        arm["pallas"] = False
    return arm


def _arm_from_detail(detail: dict) -> dict:
    """Map a ledger dispatch record's strategy dict (dedupe, closure
    mode, pack, probe_limit, depth ...) onto the planner's arm
    vocabulary; unknown axes are dropped, an unmappable record
    contributes nothing."""
    arm: dict = {}
    if isinstance(detail.get("dedupe"), str):
        arm["dedupe"] = detail["dedupe"]
    if "closure" in detail:
        arm["pallas"] = detail["closure"] not in (None, "off")
    if "pack" in detail:
        arm["pack"] = bool(detail["pack"])
    if "depth" in detail:
        arm["pipeline"] = True
    return arm


def _fresh_cell(arm: dict) -> dict:
    return {"arm": dict(arm), "ewma": None, "n": 0, "n_live": 0,
            "seeded": False}


class Planner:
    """One process's decision table (module docstring for the
    lifecycle). Thread-safe: engine dispatch threads and the serve
    worker decide/observe concurrently."""

    def __init__(self, root: Optional[str],
                 explore_every: Optional[int] = None,
                 floor: Optional[int] = None,
                 bench_dir: Optional[str] = None):
        self.root = root
        self.explore_every = resolve_explore_every(explore_every)
        self.floor = _ledger.sample_floor(floor)
        self.bench_dir = (bench_dir if bench_dir is not None
                          else DEFAULT_BENCH_DIR)
        self._lock = threading.Lock()
        #: group -> {"decisions": int, "cells": {sig: cell}}
        self.table: Dict[str, dict] = {}
        self.seeded_groups = 0
        self._load_or_seed()

    # ------------------------------------------------- load and seed

    def _table_path(self) -> Optional[str]:
        if self.root is None:
            return None
        return _ledger.plan_table_path(self.root)

    def _load_or_seed(self) -> None:
        path = self._table_path()
        if path is not None and os.path.exists(path):
            try:
                with open(path) as fh:
                    doc = json.load(fh)
                if (isinstance(doc, dict)
                        and doc.get("version") == TABLE_VERSION
                        and isinstance(doc.get("groups"), dict)):
                    self.table = {
                        g: {"decisions": int(row.get("decisions", 0)),
                            "cells": {sig: dict(c) for sig, c
                                      in (row.get("cells")
                                          or {}).items()}}
                        for g, row in doc["groups"].items()}
                    return
                raise ValueError(
                    f"stale schema (version={doc.get('version')!r})"
                    if isinstance(doc, dict) else "not a table")
            except (OSError, ValueError) as err:
                # corrupt-file contract: degrade to a re-seed,
                # counted, never a crash — the table is derived
                # evidence, the ledger segments are the record
                _metrics.counter("engine.plan.reseeds").inc()
                _log.warning("planner: %s unreadable (%r) — "
                             "re-seeding", path, err)
        self._seed()
        payload = self._snapshot_locked()
        if payload is not None:
            self._write_table(payload)

    def _seed(self) -> None:
        """Seed the table from the advisor join of the ledger
        segments (when durable) and the perf_ab bench JSONL — the
        exact table ``jepsen report --plan`` renders, converted to
        live EWMA cells (source ``seeded``)."""
        from jepsen_tpu.obs import advisor
        led_records: List[dict] = []
        if self.root is not None:
            led_records, _corrupt = _ledger.read_records(self.root)
        bench = (advisor.load_bench_dir(self.bench_dir)
                 if self.bench_dir else [])
        if not led_records and not bench:
            return
        plan = advisor.build_plan(led_records, bench, floor=self.floor)
        for entry in plan.get("shapes") or []:
            cells: Dict[str, dict] = {}
            for row in entry.get("cells") or []:
                arm = _sanitize(_arm_from_detail(row.get("detail")
                                                 or {}))
                if not arm:
                    continue
                sig = _ledger.strategy_sig(arm)
                cell = cells.setdefault(sig, _fresh_cell(arm))
                # two ledger strategies can fold onto one arm (e.g.
                # differing probe_limit): merge their evidence
                cell["ewma"] = ewma_update(
                    cell["ewma"], row.get("mean_secs") or 0.0)
                cell["n"] += int(row.get("count") or 0)
                cell["seeded"] = True
            if cells:
                self.table[entry["shape"]] = {"decisions": 0,
                                              "cells": cells}
                self.seeded_groups += 1

    def _snapshot_locked(self) -> Optional[str]:
        """Serialize the table. Caller holds ``_lock`` (or is the
        single-threaded constructor); the bytes are written OUTSIDE
        the lock so file I/O never stalls other dispatchers."""
        if self._table_path() is None:
            return None
        return json.dumps({"version": TABLE_VERSION,
                           "floor": self.floor,
                           "groups": self.table}, sort_keys=True)

    def _write_table(self, payload: str) -> None:
        """Atomic durable write (tmp + ``os.replace``, the EncodeCache
        idiom). Failure costs durability, never the dispatch."""
        path = self._table_path()
        try:
            os.makedirs(self.root, exist_ok=True)
            tmp = path + ".tmp"
            with open(tmp, "w") as fh:
                fh.write(payload + "\n")
            os.replace(tmp, path)
        except (OSError, ValueError) as err:
            _log.warning("planner: could not persist %s (%r)", path,
                         err)

    # ------------------------------------------------------ deciding

    def _compatible(self, cell: dict, fixed: dict) -> bool:
        arm = cell.get("arm") or {}
        return all(arm.get(k, v) == v for k, v in fixed.items())

    def decide(self, engine: str, family: str, C: Optional[int],
               requested: dict, keys: Optional[int] = None
               ) -> Optional[dict]:
        """Pick the strategy vector for one dispatch. ``requested``
        maps axis -> the caller's value (None = plannable; an
        explicit argument or pre-resolved value is never overridden).
        Returns ``{"strategy": {axis: value ...}, "plan": provenance}``
        for the plannable axes, or None when nothing was plannable.
        Mints the ``kind=plan`` ledger record + planner metrics."""
        plannable = sorted(k for k, v in requested.items() if v is None)
        if not plannable:
            return None
        fixed = {k: v for k, v in requested.items() if v is not None}
        group = group_key(engine, family, C)
        with self._lock:
            row = self.table.setdefault(group,
                                        {"decisions": 0, "cells": {}})
            row["decisions"] += 1
            cells = row["cells"]
            candidates = sorted(
                (sig for sig, c in cells.items()
                 if self._compatible(c, fixed)))
            eligible = [sig for sig in candidates
                        if cells[sig]["n"] >= self.floor
                        and cells[sig]["ewma"] is not None]
            static = dict(fixed)
            for axis in plannable:
                static[axis] = _static_default(axis)
            static = _sanitize(static)
            explored = False
            if not eligible:
                chosen_arm = static
                source = "floor-default"
            else:
                best = min(eligible,
                           key=lambda s: (cells[s]["ewma"], s))
                chosen_arm = dict(static)
                chosen_arm.update(cells[best]["arm"])
                chosen_arm = _sanitize(chosen_arm)
                source = ("online"
                          if cells[best]["n_live"] >= self.floor
                          else "seeded")
                if (self.explore_every
                        and row["decisions"] % self.explore_every == 0):
                    alt = self._explore_arm(cells, candidates, best,
                                            static, plannable, fixed)
                    if alt is not None:
                        chosen_arm = alt
                        explored = True
            chosen_sig = _ledger.strategy_sig(
                {k: chosen_arm[k] for k in chosen_arm
                 if k in AXES})
            cell_n = (cells[chosen_sig]["n"]
                      if chosen_sig in cells else 0)
            vector = {k: chosen_arm[k]
                      for k in sorted(set(plannable) | set(fixed))
                      if k in chosen_arm}
        prov = {"vector": vector, "cell_n": cell_n,
                "source": source, "explored": explored}
        _metrics.counter("engine.plan.decisions").inc()
        if explored:
            _metrics.counter("engine.plan.explorations").inc()
        shape = {"family": family}
        if C is not None:
            shape["C"] = int(C)
        _ledger.record("plan", engine=engine, shape=shape,
                       strategy=vector, source=source,
                       explored=explored, cell_n=cell_n, keys=keys)
        return {"strategy": {k: chosen_arm[k] for k in plannable
                             if k in chosen_arm},
                "plan": prov}

    def _explore_arm(self, cells: dict, candidates: List[str],
                     best: str, static: dict, plannable: List[str],
                     fixed: dict) -> Optional[dict]:
        """The exploration arm: among every known compatible arm, the
        static default, and the best arm with its dedupe flipped
        (when dedupe is plannable — the headline axis), pick the
        least-live-sampled one that is NOT the current best.
        Deterministic (count then sig order): tests can pin the
        cadence."""
        alts: Dict[str, dict] = {}
        for sig in candidates:
            if sig != best:
                alts[sig] = dict(static, **cells[sig]["arm"])
        alts.setdefault(_ledger.strategy_sig(static), dict(static))
        if "dedupe" in plannable:
            flipped = dict(static, **cells[best]["arm"])
            flipped["dedupe"] = ("sort"
                                 if flipped.get("dedupe") == "hash"
                                 else "hash")
            flipped = _sanitize(flipped)
            alts.setdefault(_ledger.strategy_sig(flipped), flipped)
        alts.pop(best, None)
        alts = {sig: _sanitize(arm) for sig, arm in alts.items()
                if all(_sanitize(arm).get(k, v) == v
                       for k, v in fixed.items())}
        if not alts:
            return None
        sig = min(alts, key=lambda s: (
            cells[s]["n_live"] if s in cells else 0, s))
        return alts[sig]

    # ----------------------------------------------------- observing

    def observe(self, engine: str, family: str, C: Optional[int],
                arm: dict, secs: float) -> None:
        """Fold one dispatch's measured wall secs into its
        shape×strategy cell — every dispatch contributes evidence,
        planned or not (the below-floor contract)."""
        if not isinstance(secs, (int, float)):
            return
        arm = _sanitize({k: v for k, v in arm.items() if k in AXES})
        if not arm:
            return
        group = group_key(engine, family, C)
        sig = _ledger.strategy_sig(arm)
        with self._lock:
            row = self.table.setdefault(group,
                                        {"decisions": 0, "cells": {}})
            cell = row["cells"].setdefault(sig, _fresh_cell(arm))
            cell["ewma"] = round(ewma_update(cell["ewma"], secs), 6)
            cell["n"] += 1
            cell["n_live"] += 1
            _metrics.gauge("engine.plan.table_cells").set(
                sum(len(r["cells"]) for r in self.table.values()))
            payload = self._snapshot_locked()
        if payload is not None:
            self._write_table(payload)

    # ----------------------------------------------------- rendering

    def table_doc(self) -> dict:
        """The ``/plan`` endpoint / ``report --plan`` live-table
        document — deterministic (sorted, rounded, no timestamps)."""
        with self._lock:
            groups = {}
            for g in sorted(self.table):
                row = self.table[g]
                groups[g] = {
                    "decisions": row["decisions"],
                    "cells": {
                        sig: {"ewma_secs": c["ewma"], "n": c["n"],
                              "n_live": c["n_live"],
                              "seeded": bool(c.get("seeded")),
                              "arm": c["arm"]}
                        for sig, c in sorted(row["cells"].items())}}
        return {"auto": {"enabled": True,
                         "dir": self.root,
                         "floor": self.floor,
                         "explore_every": self.explore_every,
                         "seeded_groups": self.seeded_groups},
                "groups": groups}


# ------------------------------------------------- process singleton

_active: Optional[Planner] = None
_resolved = False
_singleton_lock = threading.Lock()


def active() -> Optional[Planner]:
    """The process planner, or None when ``JEPSEN_TPU_AUTO`` is off.
    Resolved once per process (:func:`reset` re-resolves — tests). A
    malformed flag raises loudly at the first dispatch (the envflags
    contract); everything else degrades (module docstring)."""
    global _active, _resolved
    if _resolved:
        return _active
    with _singleton_lock:
        if _resolved:
            return _active
        if auto_enabled():
            _active = Planner(_ledger.resolve_ledger_dir())
        _resolved = True
    return _active


def reset() -> None:
    """Forget the process planner so the next :func:`active` re-reads
    the environment (tests)."""
    global _active, _resolved
    with _singleton_lock:
        _active = None
        _resolved = False


def load_table(root: str) -> Optional[dict]:
    """Read a durable ``plan_table.json`` without constructing a
    planner (the ``report --plan`` live-table view). None when
    absent/corrupt/stale — the reader shows nothing rather than
    guessing."""
    path = _ledger.plan_table_path(root)
    try:
        with open(path) as fh:
            doc = json.load(fh)
    except (OSError, ValueError):
        return None
    if not (isinstance(doc, dict)
            and doc.get("version") == TABLE_VERSION
            and isinstance(doc.get("groups"), dict)):
        return None
    return doc


def plan_doc() -> dict:
    """The ``/plan`` ops document. Planner off answers
    ``{"auto": {"enabled": False}, "groups": {}}`` — a valid, empty
    document (the /ledger posture)."""
    pl = active()
    if pl is None:
        return {"auto": {"enabled": False}, "groups": {}}
    return pl.table_doc()
