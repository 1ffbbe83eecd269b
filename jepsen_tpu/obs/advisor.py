"""``jepsen report --plan`` — the offline strategy advisor.

Joins three evidence sources into ONE per-shape recommended-strategy
table — the artifact the ``JEPSEN_TPU_AUTO=1`` planner
(``parallel.planner``) seeds its live decision table from, built here
as read-only provenance:

  ledger   the decision ledger's dispatch/escalation/reshard
           records (``obs.ledger``) — live traffic's shape×strategy
           cells with measured wall secs
  bench    ``bench_results/`` perf_ab JSONL — the recorded A/B
           verdicts per axis: closure (``xla/pallas/fori_secs``),
           dedupe (``sort/hash/hash-pallas/hash-packed_secs``),
           elastic (``static_secs`` vs ``reshard_secs``), plus the
           flip-rule verdict records
  gates    ``sparse_kernels.gate_coverage`` records riding the same
           bench JSONL — which kernel would run per layout, chip-free

The join is deliberately conservative: a recommendation only comes
from a ledger cell with at least ``JEPSEN_TPU_LEDGER_FLOOR`` records
— a shape below the floor says **insufficient evidence**, never a
guess (wrong-plan recovery is free, but an unevidenced plan is still
noise). Bench evidence upgrades or contests confidence; it never
substitutes for live samples, because the bench shapes are synthetic
adversarial histories, not the operator's traffic.

Determinism: every iteration is sorted, floats are rounded, nothing
timestamps the output — the same inputs render byte-identical tables
(pinned by tests/test_ledger.py on a committed fixture).

Import-safe: no JAX — ``jepsen report`` runs on a box whose device
runtime may be wedged.
"""

from __future__ import annotations

import json
import os
from typing import Dict, List, Optional, Tuple

from jepsen_tpu.obs import ledger as _ledger

#: strategy-axis vocabulary of the perf_ab ``{variant}_secs`` keys
CLOSURE_VARIANTS = ("xla", "pallas", "fori")
DEDUPE_VARIANTS = ("sort", "hash", "hash-pallas", "hash-packed")
ELASTIC_ARMS = ("reshard",)

PLAN_VERSION = 1


# --------------------------------------------------- bench evidence


def load_bench_dir(path: str) -> List[dict]:
    """Every decodable JSONL record under ``path`` (files sorted,
    torn lines skipped — the ``load_records`` posture)."""
    out: List[dict] = []
    if not path or not os.path.isdir(path):
        return out
    for name in sorted(os.listdir(path)):
        if not name.endswith(".jsonl"):
            continue
        try:
            with open(os.path.join(path, name)) as fh:
                for ln in fh:
                    ln = ln.strip()
                    if not ln:
                        continue
                    try:
                        rec = json.loads(ln)
                    except ValueError:
                        continue
                    if isinstance(rec, dict):
                        out.append(rec)
        except OSError:
            continue
    return out


def _axis_wins(records: List[dict],
               variants: Tuple[str, ...]) -> Dict[str, dict]:
    """Per-variant win tally for one strategy axis over the bench
    lines that measured it: ``{variant: {"wins": n, "shapes": [...]}}``
    where a win is the strictly smallest ``{variant}_secs`` on a
    shape that measured >= 2 variants of the axis."""
    tally: Dict[str, dict] = {}
    for rec in records:
        shape = rec.get("shape")
        if not isinstance(shape, str):
            continue
        timed = [(v, rec[f"{v}_secs"]) for v in variants
                 if isinstance(rec.get(f"{v}_secs"), (int, float))]
        if len(timed) < 2:
            continue
        winner = min(timed, key=lambda t: (t[1], t[0]))[0]
        cell = tally.setdefault(winner, {"wins": 0, "shapes": []})
        cell["wins"] += 1
        cell["shapes"].append(shape)
    for cell in tally.values():
        cell["shapes"] = sorted(set(cell["shapes"]))
    return tally


def _elastic_verdicts(records: List[dict]) -> Dict[str, dict]:
    """reshard vs the static placement: per arm, on how many
    shapes the arm beat ``static_secs``."""
    out: Dict[str, dict] = {}
    for arm in ELASTIC_ARMS:
        measured = wins = 0
        for rec in records:
            a, s = rec.get(f"{arm}_secs"), rec.get("static_secs")
            if isinstance(a, (int, float)) \
                    and isinstance(s, (int, float)):
                measured += 1
                if a < s:
                    wins += 1
        if measured:
            out[arm] = {"measured": measured, "wins": wins}
    return out


def bench_evidence(records: List[dict]) -> dict:
    """The bench half of the join: per-axis win tallies, elastic arm
    verdicts, the recorded flip-rule verdict lines, and the
    gate_coverage records."""
    return {
        "closure": _axis_wins(records, CLOSURE_VARIANTS),
        "dedupe": _axis_wins(records, DEDUPE_VARIANTS),
        "elastic": _elastic_verdicts(records),
        "verdicts": sorted(
            (r for r in records if "verdict" in r and "backend" in r),
            key=lambda r: json.dumps(r, sort_keys=True)),
        "gates": sorted(
            (r for r in records if "gate_coverage" in r),
            key=lambda r: str(r.get("shape"))),
    }


def _axis_best(tally: Dict[str, dict]) -> Optional[str]:
    """The axis winner by total bench wins (ties break to the
    lexicographically-first variant — deterministic, and the tie
    says the evidence doesn't separate them anyway)."""
    if not tally:
        return None
    return max(sorted(tally), key=lambda v: tally[v]["wins"])


# -------------------------------------------------- the plan table


def _shape_group(rec: dict) -> Optional[str]:
    """The plan-table row a ledger record belongs to: engine + event
    family + slot width. Capacity tier N is folded INTO the strategy
    comparison (a strategy that avoids escalation shows up as fewer
    high-tier cells), not the row key — the planner picks per
    (family, C) bucket, which is what ``bucket_key`` quantizes."""
    shape = rec.get("shape")
    if not isinstance(shape, dict):
        return None
    parts = [f"engine={rec.get('engine', '?')}"]
    for k in ("family", "C"):
        if shape.get(k) is not None:
            parts.append(f"{k}={shape[k]}")
    return ",".join(parts)


def build_plan(ledger_records: List[dict], bench_records: List[dict],
               floor: Optional[int] = None,
               auto_table: Optional[dict] = None) -> dict:
    """The joined plan document (machine-readable; ``render_plan``
    makes it human-readable). Per shape group, the recommended
    strategy is the strategy vector whose ledger cell has the lowest
    mean secs AMONG cells meeting the sample floor; a group with no
    cell at the floor recommends nothing ("insufficient evidence").

    ``kind=plan`` records (the live planner's own decisions,
    ``parallel.planner``) feed the FOURTH confidence tier: when the
    newest online decision for a group picked the vector this join
    recommends, confidence says ``auto-online`` — the fleet's live
    table already converged there, which outranks what the synthetic
    bench shapes prefer. ``auto_table`` (a durable ``plan_table.json``
    document, ``planner.load_table``) rides along verbatim under
    ``"auto"`` so one report shows the offline join AND the live
    table."""
    floor = _ledger.sample_floor(floor)
    bench = bench_evidence(bench_records)
    groups: Dict[str, Dict[str, dict]] = {}
    auto_latest: Dict[str, dict] = {}
    for rec in ledger_records:
        if rec.get("kind") == "plan":
            g = _shape_group(rec)
            if g is not None:
                auto_latest[g] = rec   # newest wins (segment order)
            continue
        if rec.get("kind") not in ("dispatch", "escalation"):
            continue
        g = _shape_group(rec)
        if g is None:
            continue
        sig = _ledger.strategy_sig(rec.get("strategy"))
        cell = groups.setdefault(g, {}).setdefault(
            sig, {"count": 0, "total_secs": 0.0, "keys": 0,
                  "strategy": rec.get("strategy") or {}})
        cell["count"] += 1
        if isinstance(rec.get("secs"), (int, float)):
            cell["total_secs"] += float(rec["secs"])
        if isinstance(rec.get("keys"), int):
            cell["keys"] += rec["keys"]
    bench_dedupe = _axis_best(bench["dedupe"])
    bench_closure = _axis_best(bench["closure"])
    shapes: List[dict] = []
    for g in sorted(groups):
        cells = groups[g]
        rows = []
        for sig in sorted(cells):
            c = cells[sig]
            rows.append({"strategy": sig, "count": c["count"],
                         "keys": c["keys"],
                         "mean_secs": round(
                             c["total_secs"] / max(1, c["count"]), 6),
                         "detail": c["strategy"]})
        evidence = sum(r["count"] for r in rows)
        eligible = [r for r in rows if r["count"] >= floor]
        entry = {"shape": g, "evidence": evidence, "cells": rows}
        if not eligible:
            best = max(rows, key=lambda r: r["count"])
            entry["recommend"] = None
            entry["confidence"] = (
                f"insufficient evidence (best cell n={best['count']} "
                f"< floor {floor})")
        else:
            win = min(eligible,
                      key=lambda r: (r["mean_secs"], r["strategy"]))
            entry["recommend"] = win["strategy"]
            entry["mean_secs"] = win["mean_secs"]
            detail = win["detail"] or {}
            conf = "ledger-only"
            led_dedupe = detail.get("dedupe")
            if bench_dedupe is not None and led_dedupe is not None:
                # bench dedupe variants fold the kernel in
                # (hash-pallas/hash-packed); compare on the base axis
                conf = ("bench-agrees"
                        if str(bench_dedupe).startswith(
                            str(led_dedupe))
                        else f"bench-prefers-{bench_dedupe}")
            pr = auto_latest.get(g)
            if pr is not None and pr.get("source") == "online":
                # lazy + import-safe: parallel.planner holds no JAX;
                # its arm mapping is the one vocabulary both tables
                # speak, so agreement is checked in it
                from jepsen_tpu.parallel import planner as _planner_mod
                led_arm = _planner_mod._arm_from_detail(detail)
                vec = pr.get("strategy") or {}
                if vec and all(led_arm.get(k) == v
                               for k, v in vec.items()):
                    conf = "auto-online"
            entry["confidence"] = conf
        shapes.append(entry)
    doc = {"version": PLAN_VERSION, "floor": floor,
           "shapes": shapes,
           "bench": {"closure": bench["closure"],
                     "dedupe": bench["dedupe"],
                     "elastic": bench["elastic"],
                     "closure_best": bench_closure,
                     "dedupe_best": bench_dedupe,
                     "verdicts": bench["verdicts"]},
           "gates": bench["gates"],
           "ledger_records": len(ledger_records)}
    if auto_table is not None:
        doc["auto"] = auto_table
    return doc


def _fmt_secs(v) -> str:
    return "-" if v is None else f"{float(v):.6g}"


def render_plan(plan: dict) -> str:
    """The plan document as the operator table ``jepsen report
    --plan`` prints."""
    lines = ["# Strategy plan (decision ledger + perf_ab + "
             "gate_coverage)", ""]
    lines.append(f"ledger records: {plan.get('ledger_records', 0)}   "
                 f"shape groups: {len(plan.get('shapes') or [])}   "
                 f"sample floor: {plan.get('floor')}")
    lines.append("")
    lines.append("## Per-shape recommendations")
    lines.append("")
    shapes = plan.get("shapes") or []
    if not shapes:
        lines.append("(no dispatch evidence in the ledger — run with "
                     "JEPSEN_TPU_LEDGER=1 to record some)")
    for s in shapes:
        lines.append(f"shape {s['shape']}  (n={s['evidence']})")
        if s.get("recommend") is None:
            lines.append(f"    {s['confidence']}")
        else:
            lines.append(f"    recommend: {s['recommend']}")
            lines.append(f"    mean_secs: "
                         f"{_fmt_secs(s.get('mean_secs'))}   "
                         f"confidence: {s['confidence']}")
        for c in s.get("cells") or []:
            lines.append(f"      cell n={c['count']:<4} "
                         f"mean={_fmt_secs(c['mean_secs']):<10} "
                         f"{c['strategy']}")
        lines.append("")
    bench = plan.get("bench") or {}
    lines.append("## Bench axis verdicts (perf_ab)")
    lines.append("")
    any_bench = False
    for axis in ("closure", "dedupe"):
        tally = bench.get(axis) or {}
        if tally:
            any_bench = True
            best = bench.get(f"{axis}_best")
            parts = [f"{v}:{tally[v]['wins']}" for v in sorted(tally)]
            lines.append(f"{axis}: best={best}  wins " +
                         "  ".join(parts))
    for arm, v in sorted((bench.get("elastic") or {}).items()):
        any_bench = True
        lines.append(f"{arm}: wins {v['wins']}/{v['measured']} "
                     f"measured shapes vs static")
    for v in bench.get("verdicts") or []:
        any_bench = True
        lines.append(f"recorded verdict [{v.get('backend')}]: "
                     f"{v.get('verdict')} ratios={v.get('ratios')}")
    if not any_bench:
        lines.append("(no perf_ab evidence — point --bench-dir at a "
                     "bench_results/ directory)")
    lines.append("")
    gates = plan.get("gates") or []
    if gates:
        lines.append("## Kernel gates (gate_coverage)")
        lines.append("")
        for g in gates:
            gc = g.get("gate_coverage") or {}
            wr = gc.get("would_run") or {}
            lines.append(f"{g.get('shape')}: C={gc.get('C')} "
                         f"N={gc.get('capacity')} "
                         f"packable={gc.get('packable')} "
                         f"unpacked->{wr.get('unpacked')} "
                         f"packed->{wr.get('packed')}")
        lines.append("")
    auto = plan.get("auto")
    if auto is not None:
        lines.append("## Auto planner live table (JEPSEN_TPU_AUTO)")
        lines.append("")
        agroups = auto.get("groups") or {}
        if not agroups:
            lines.append("(plan_table.json present but empty)")
        for g in sorted(agroups):
            row = agroups[g]
            lines.append(f"group {g}  "
                         f"(decisions={row.get('decisions', 0)})")
            cells = row.get("cells") or {}
            for sig in sorted(cells):
                c = cells[sig]
                lines.append(
                    f"      cell n={c.get('n', 0):<4} "
                    f"live={c.get('n_live', 0):<4} "
                    f"ewma={_fmt_secs(c.get('ewma', c.get('ewma_secs')))}"
                    f"{' seeded' if c.get('seeded') else '':<8} "
                    f"{sig}")
        lines.append("")
    return "\n".join(lines) + "\n"
