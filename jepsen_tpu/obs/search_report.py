"""``jepsen report --search`` — render a run's device-search telemetry
(JEPSEN_TPU_SEARCH_STATS) into the operator table that makes ROADMAP
items 2 and 5 executable: which keys run the visited table hottest
(load factor -> table sizing for the tiled-VMEM work), which escalate
capacity (re-shard candidates), and which waste the most padded rows
(bucket-policy evidence).

Input: ``search_stats.jsonl`` in a store run dir — one stats block per
line, written by ``Store.save_telemetry`` / ``obs.export_run`` from the
records the engines emit as each search finishes. Streamed keys emit a
record per delta with lifetime stats; the report keeps the newest
(most-events) record per key.

Output: ``search_report.txt`` next to the input (and stdout) — a
summary header plus worst-keys tables. Pre-parse forwarded from
``cli.py`` exactly like lint/probe/status; exit 0 report written,
1 no stats found, 254 usage. Import-safe: no JAX.

``--plan`` rides the same entry point: the strategy advisor
(``obs.advisor``) joins the decision ledger (``<run_dir>/ledger.jsonl``
or ``--ledger-dir``) with perf_ab bench JSONL (``--bench-dir``,
default ``bench_results/``) into ``plan_report.txt`` + ``plan.json``
— the per-shape recommended-strategy table, sample-floored so thin
evidence says so instead of guessing.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
from typing import List, Optional, Sequence


def load_records(path: str) -> List[dict]:
    out = []
    with open(path) as fh:
        for ln in fh:
            ln = ln.strip()
            if not ln:
                continue
            try:
                out.append(json.loads(ln))
            except ValueError:
                continue   # a torn line loses one record, not the report
    return out


def dedupe_records(records: List[dict]) -> List[dict]:
    """One record per key, newest (most events — a streamed key's
    lifetime grows monotonically) wins; keyless records are kept
    as-is under synthetic indices."""
    by_key = {}
    anon = []
    for i, r in enumerate(records):
        k = r.get("key")
        if k is None:
            anon.append(r)
            continue
        kk = json.dumps(k, sort_keys=True, default=str)
        prev = by_key.get(kk)
        if prev is None or (r.get("events") or 0) >= \
                (prev.get("events") or 0):
            by_key[kk] = r
    return list(by_key.values()) + anon


def _fmt(v, nd: int = 4) -> str:
    if v is None:
        return "-"
    if isinstance(v, float):
        return f"{v:.{nd}g}"
    return str(v)


def _key_of(r: dict) -> str:
    k = r.get("key")
    return "-" if k is None else str(k)


def device_skew(r: dict):
    """max/mean of the per-device load-factor peaks across the mesh
    (sharded records; falls back to the per-device frontier-width
    peaks) — 1.0 means perfectly balanced, higher means part of the
    mesh idles while one device's table runs hot. None for
    single-device records."""
    pd = r.get("per-device") or {}
    vals = pd.get("load-factor-peak") or pd.get("width-peak")
    if not vals or len(vals) < 2:
        return None
    vals = [float(v) for v in vals if v is not None]
    if not vals:
        return None
    mean = sum(vals) / len(vals)
    return round(max(vals) / mean, 4) if mean else None


def _worst_table(rows: List[dict], field: str, title: str,
                 limit: int = 10) -> List[str]:
    ranked = [r for r in rows if r.get(field) is not None
              and r.get(field)]
    ranked.sort(key=lambda r: r[field], reverse=True)
    if not ranked:
        return []
    lines = [f"## {title}", ""]
    lines.append(f"{'key':<20} {'engine':<9} {'events':>7} "
                 f"{'peak':>8} {'dev-skew':>9} {field:>18}")
    for r in ranked[:limit]:
        lines.append(
            f"{_key_of(r)[:20]:<20} {str(r.get('engine', '-')):<9} "
            f"{_fmt(r.get('events')):>7} "
            f"{_fmt(r.get('frontier-peak')):>8} "
            f"{_fmt(r.get('device-skew')):>9} "
            f"{_fmt(r.get(field)):>18}")
    lines.append("")
    return lines


def render_search_report(records: List[dict]) -> str:
    rows = [dict(r) for r in dedupe_records(records)]
    for r in rows:
        r["device-skew"] = device_skew(r)
    lines = ["# Search telemetry report (JEPSEN_TPU_SEARCH_STATS)", ""]
    n_events = sum(r.get("events") or 0 for r in rows)
    engines = {}
    for r in rows:
        engines[r.get("engine", "?")] = \
            engines.get(r.get("engine", "?"), 0) + 1
    lines.append(f"keys: {len(rows)}   events: {n_events}   "
                 f"engines: " + ", ".join(
                     f"{k}={v}" for k, v in sorted(engines.items())))
    peaks = [r.get("frontier-peak") or 0 for r in rows]
    lines.append(f"frontier peak: max={max(peaks, default=0)}   "
                 f"escalated keys: "
                 f"{sum(1 for r in rows if r.get('capacity-tier'))}")
    # aggregate probe histogram over every hash-dedupe key
    agg: dict = {}
    for r in rows:
        for lab, n in (r.get("probe-hist") or {}).items():
            agg[lab] = agg.get(lab, 0) + int(n)
    if agg:
        total = sum(agg.values()) or 1
        lines.append("probe lengths: " + "  ".join(
            f"{lab}:{n} ({100.0 * n / total:.1f}%)"
            for lab, n in agg.items() if n))
    lines.append("")
    lines.extend(_worst_table(rows, "load-factor-peak",
                              "Worst keys by visited-table load "
                              "factor"))
    lines.extend(_worst_table(rows, "capacity-tier",
                              "Worst keys by capacity escalations"))
    lines.extend(_worst_table(rows, "pad-waste",
                              "Worst keys by pad-row waste"))
    lines.extend(_worst_table(rows, "device-skew",
                              "Worst keys by per-device skew"))
    if len(lines) == 5 and not agg:   # header only: nothing ranked
        lines.append("(no key exceeded any threshold — no hash load, "
                     "no escalations, no pad waste)")
        lines.append("")
    return "\n".join(lines) + "\n"


# --------------------------------------------- slow-delta forensics


def _fmt_secs(v) -> str:
    if v is None:
        return "-"
    return f"{float(v):.4g}"


def render_slow_report(records: List[dict]) -> str:
    """The ``jepsen report --slow`` table
    (JEPSEN_TPU_SLOW_DELTA_SECS): every retained slow-delta record,
    worst first — which delta, on which key, how long, and WHERE the
    time went (the stage-by-stage breakdown) — plus the worst
    offender's full context (resilience notes, search-stats block).
    One read replaces the PR-12-style manual diagnosis of a wedged
    worker."""
    rows = sorted(records,
                  key=lambda r: r.get("total_secs") or 0.0,
                  reverse=True)
    lines = ["# Slow-delta forensics (JEPSEN_TPU_SLOW_DELTA_SECS)",
             ""]
    lines.append(f"records: {len(rows)}   worst: "
                 f"{_fmt_secs(rows[0].get('total_secs')) if rows else '-'}s")
    by_stage: dict = {}
    for r in rows:
        s = r.get("slowest_stage") or "?"
        by_stage[s] = by_stage.get(s, 0) + 1
    if by_stage:
        lines.append("dominant stages: " + "  ".join(
            f"{k}:{v}" for k, v in sorted(by_stage.items())))
    lines.append("")
    lines.append(f"{'delta_id':<18} {'key':<16} {'tenant':<10} "
                 f"{'seq':>5} {'total_s':>9} {'slowest':<12} "
                 f"bp/wal/queue/device/pub")
    for r in rows:
        st = r.get("stages") or {}
        breakdown = "/".join(
            _fmt_secs(st.get(k)) for k in
            ("backpressure", "wal", "queue", "device", "publish"))
        lines.append(
            f"{str(r.get('delta_id', '-'))[:18]:<18} "
            f"{str(r.get('key', '-'))[:16]:<16} "
            f"{str(r.get('tenant') or '-')[:10]:<10} "
            f"{r.get('seq', 0) or 0:>5} "
            f"{_fmt_secs(r.get('total_secs')):>9} "
            f"{str(r.get('slowest_stage', '-')):<12} {breakdown}")
    if rows:
        worst = rows[0]
        lines.append("")
        lines.append(f"## Worst offender: {worst.get('delta_id')} "
                     f"(key {worst.get('key')})")
        for field in ("verdict", "error", "resilience", "stats"):
            if worst.get(field) is not None:
                lines.append(f"{field}: {worst[field]}")
    lines.append("")
    return "\n".join(lines) + "\n"


def _load_report_input(run_dir: str, fname: str,
                       hint: str) -> Optional[List[dict]]:
    """Read ``fname``'s records from the run dir (``report_main``
    resolved it already) and report the usual failure modes (shared
    by --search and --slow)."""
    path = os.path.join(run_dir, fname)
    if not os.path.exists(path):
        print(f"jepsen report: {path} not found — {hint}",
              file=sys.stderr)
        return None
    records = load_records(path)
    if not records:
        print(f"jepsen report: {path} holds no records",
              file=sys.stderr)
        return None
    return records


def report_main(argv: Optional[Sequence[str]] = None) -> int:
    p = argparse.ArgumentParser(
        prog="jepsen report",
        description="render a stored run's telemetry reports: "
                    "--search renders search_stats.jsonl "
                    "(JEPSEN_TPU_SEARCH_STATS) into search_report.txt "
                    "— worst keys by visited-table load factor, "
                    "capacity escalations, and pad-row waste; --slow "
                    "renders slow_deltas.jsonl "
                    "(JEPSEN_TPU_SLOW_DELTA_SECS) into "
                    "slow_report.txt — every slow delta's stage "
                    "breakdown, worst first; --plan joins the "
                    "decision ledger (JEPSEN_TPU_LEDGER) with "
                    "perf_ab bench JSONL into plan_report.txt — the "
                    "per-shape recommended-strategy table")
    p.add_argument("--search", action="store_true",
                   help="render the device-search telemetry report")
    p.add_argument("--slow", action="store_true",
                   help="render the slow-delta forensics report")
    p.add_argument("--plan", action="store_true",
                   help="render the strategy-advisor plan table "
                        "(decision ledger + perf_ab + gate_coverage)")
    p.add_argument("--run-dir", default=None,
                   help="store run dir holding the report input "
                        "(default: the latest stored run)")
    p.add_argument("--ledger-dir", default=None,
                   help="read --plan's ledger evidence straight from "
                        "a JEPSEN_TPU_LEDGER segment dir instead of "
                        "the run dir's ledger.jsonl snapshot")
    p.add_argument("--bench-dir", default=None,
                   help="perf_ab JSONL dir for --plan's bench "
                        "evidence (default: bench_results/ when "
                        "present)")
    p.add_argument("--floor", type=int, default=None,
                   help="--plan's per-cell sample floor (default: "
                        "JEPSEN_TPU_LEDGER_FLOOR)")
    p.add_argument("--json", action="store_true",
                   help="with --plan: print the machine-readable "
                        "plan document (sorted keys — the same "
                        "schema plan.json stores) instead of the "
                        "operator table; exit codes unchanged")
    p.add_argument("--stdout-only", action="store_true",
                   help="print the report without writing the "
                        ".txt artifact")
    try:
        args = p.parse_args(list(argv) if argv is not None else None)
    except SystemExit as e:
        return 0 if e.code in (0, None) else 254
    if not (args.search or args.slow or args.plan):
        print("jepsen report: nothing to render — pass --search, "
              "--slow, and/or --plan", file=sys.stderr)
        return 254
    # resolve the run dir ONCE so --search + --slow in one call read
    # the same run even if a new run lands mid-render. --plan with an
    # explicit --ledger-dir is the one mode that can run without a
    # stored run at all (the fleet-debug posture: point it anywhere).
    run_dir = args.run_dir
    need_run_dir = args.search or args.slow \
        or (args.plan and args.ledger_dir is None)
    if run_dir is None and need_run_dir:
        from jepsen_tpu import store as jstore
        run_dir = jstore.latest()
        if run_dir is None:
            print("jepsen report: no stored runs and no --run-dir",
                  file=sys.stderr)
            return 1
    rc = 0
    if args.search:
        records = _load_report_input(
            run_dir, "search_stats.jsonl",
            "run with JEPSEN_TPU_SEARCH_STATS=1 so the engines "
            "record per-key search stats (docs/observability.md)")
        if records is None:
            rc = 1
        else:
            text = render_search_report(records)
            sys.stdout.write(text)
            if not args.stdout_only:
                out = os.path.join(run_dir, "search_report.txt")
                with open(out, "w") as fh:
                    fh.write(text)
                print(f"report written to {out}", file=sys.stderr)
    if args.slow:
        records = _load_report_input(
            run_dir, "slow_deltas.jsonl",
            "run with JEPSEN_TPU_SLOW_DELTA_SECS=<secs> so the serve "
            "worker records slow-delta forensics "
            "(docs/observability.md)")
        if records is None:
            rc = 1
        else:
            text = render_slow_report(records)
            sys.stdout.write(text)
            if not args.stdout_only:
                out = os.path.join(run_dir, "slow_report.txt")
                with open(out, "w") as fh:
                    fh.write(text)
                print(f"report written to {out}", file=sys.stderr)
    if args.plan:
        from jepsen_tpu.obs import advisor, ledger as _ledger
        if args.ledger_dir is not None:
            records, corrupt = _ledger.read_records(args.ledger_dir)
            if not records:
                print(f"jepsen report: no ledger records under "
                      f"{args.ledger_dir} — run with "
                      f"JEPSEN_TPU_LEDGER=1 so the engines record "
                      f"dispatch evidence (docs/observability.md)",
                      file=sys.stderr)
                records = None
            elif corrupt:
                print(f"jepsen report: skipped {corrupt} corrupt "
                      f"ledger line(s)", file=sys.stderr)
        else:
            records = _load_report_input(
                run_dir, "ledger.jsonl",
                "run with JEPSEN_TPU_LEDGER=1 so the run dir "
                "snapshots dispatch evidence, or pass --ledger-dir "
                "(docs/observability.md)")
        if records is None:
            rc = 1
        else:
            bench_dir = args.bench_dir
            if bench_dir is None and os.path.isdir("bench_results"):
                bench_dir = "bench_results"
            bench = (advisor.load_bench_dir(bench_dir)
                     if bench_dir else [])
            # the live auto-planner table (JEPSEN_TPU_AUTO) rides the
            # report when its durable file sits beside the ledger
            # segments being read — one view over both evidence tiers
            auto_table = None
            table_dir = args.ledger_dir or _ledger.resolve_ledger_dir()
            if table_dir:
                from jepsen_tpu.parallel import planner as _planner_mod
                auto_table = _planner_mod.load_table(table_dir)
            plan = advisor.build_plan(records, bench,
                                      floor=args.floor,
                                      auto_table=auto_table)
            text = advisor.render_plan(plan)
            if args.json:
                sys.stdout.write(json.dumps(plan, sort_keys=True,
                                            indent=1) + "\n")
            else:
                sys.stdout.write(text)
            if not args.stdout_only:
                dest = run_dir if run_dir is not None \
                    else args.ledger_dir
                out = os.path.join(dest, "plan_report.txt")
                with open(out, "w") as fh:
                    fh.write(text)
                with open(os.path.join(dest, "plan.json"), "w") as fh:
                    json.dump(plan, fh, sort_keys=True, indent=1)
                    fh.write("\n")
                print(f"report written to {out}", file=sys.stderr)
    return rc


if __name__ == "__main__":
    sys.exit(report_main())
