"""Record a traced run of one cell, for reading by hand and for the
reduction's test.

    python benchmark/record_trace.py --workload <cell> --seed <n> --out <dir>

Runs the cell once with `--trace 1` semantics, keeps the profiler's
files, and writes two files into `<dir>`:

- `<cell>.structure.json`: the run's result line, the trace file's
  size and read time, every plane and line of the trace with its event
  count and the eight names that took most time, and the full text of
  a few custom-call ops (how the closure kernel is found);
- `trace_<cell>.json`: a few milliseconds of the traced window around
  the first device op of the second check, cut down to at most 3,000
  device events, with the numbers the reduction gave on it
  (`expected`). Copied to `tests/data/`, it pins the reduction.

Needs the chip, like the benchmark itself.
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import shutil
import sys
import time

import harness
import tracereduce


def structure(trace_dir: str) -> dict:
    from jax.profiler import ProfileData
    out = {"planes": [], "custom_calls": {}}
    for path in glob.glob(os.path.join(trace_dir, "plugins", "profile",
                                       "*", "*.xplane.pb")):
        out["xplane_bytes"] = os.path.getsize(path)
        t = time.perf_counter()
        pd = ProfileData.from_file(path)
        for plane in pd.planes:
            lines = []
            for line in plane.lines:
                tot: dict = {}
                n = 0
                for ev in line.events:
                    n += 1
                    name = ev.name[:90]
                    tot[name] = tot.get(name, 0) + ev.duration_ns
                    if ("custom-call" in ev.name[:200]
                            and len(out["custom_calls"]) < 6):
                        out["custom_calls"].setdefault(
                            ev.name.split(" = ")[0], ev.name[:3000])
                lines.append({"line": line.name, "events": n,
                              "top": sorted(tot.items(),
                                            key=lambda x: -x[1])[:8]})
            out["planes"].append({"plane": plane.name, "lines": lines})
        out["read_secs"] = time.perf_counter() - t
    return out


def cut_down(run) -> dict:
    """A window of a few milliseconds with device ops and an idle gap
    in it, and what the reduction reads there."""
    checks = [c for c in run.checks if c[2] <= run.traced[1]]
    after = checks[1][1] if len(checks) > 2 else run.traced[0]
    first = min(e[1] for e in run.device if e[1] >= after)
    for width in (0.016, 0.008, 0.004, 0.002, 0.001, 0.0005):
        d = tracereduce.cut(run.device, run.spans, run.checks, run.traced,
                            first - width / 2, width)
        if len(d["device"]) <= 3000:
            break
    part = harness.Run()
    part.traced = tuple(d["traced"])
    part.device = [tuple(e) for e in d["device"]]
    part.spans = d["spans"]
    part.checks = [tuple(c) for c in d["checks"]]
    root = os.path.dirname(harness.HERE)
    d["expected"] = {
        name: harness.load_reader(root, name)(part)
        for name in ("device_idle_share", "closure_kernel_share")}
    d["expected"].update(tracereduce.breakdown(
        part.device, part.traced, part.spans, part.checks))
    return d


def main(argv=None) -> int:
    t_start = time.perf_counter()
    p = argparse.ArgumentParser(description="Record a traced run.")
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, default=30.0)
    p.add_argument("--out", required=True)
    args = p.parse_args(argv)
    root = os.path.dirname(harness.HERE)
    box: dict = {}
    out = harness.run_cell(root, args.workload, args.seed, args.seconds,
                           True, t_start, keep_trace=True,
                           observe=lambda r: box.update(run=r))
    trace_dir = os.path.join(root, "benchmark", ".profile", args.workload)
    rec = structure(trace_dir)
    shutil.rmtree(trace_dir, ignore_errors=True)
    rec["result"] = out
    d = cut_down(box["run"])
    d["recorded"] = {"cell": args.workload, "seed": args.seed,
                     "device": out["device"]}
    os.makedirs(args.out, exist_ok=True)
    with open(os.path.join(args.out,
                           f"{args.workload}.structure.json"), "w") as f:
        json.dump(rec, f, indent=1)
    with open(os.path.join(args.out, f"trace_{args.workload}.json"),
              "w") as f:
        json.dump(d, f)
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
