"""The control: the reference with one stated guarantee broken, put in
the program's place, must come out as not correct.

The broken guarantee is the crash rule: every crashed (:info) call is
treated as one that never happened, the shortcut that would shrink the
open-call window a search carries. Each cell's traffic holds histories
that are valid only under the crash rule, so the control answers them
wrongly.

    python benchmark/control.py --workload <cell> --checks <n> \
        --seed <n> [--seed <n> ...]

prints, for each seed, the compared numbers of `--checks` checks that
cycle through the pool as a run's window does, each answered by the
control, and exits 0 when every seed's control comes out not correct. It runs on the host alone; the benchmark's own
runs never run it.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

import compare
import harness
import named
import traffic


def control_numbers(root: str, cell: str, seed: int, checks: int = 0,
                    shrink=None) -> dict:
    spec = harness.load_spec(root)
    entry = next(w for w in spec["workloads"] if w["name"] == cell)
    cfg = traffic.load(root, "configs", entry["config"])
    mix = traffic.load(root, "traffic", entry["traffic"])
    if shrink is not None:
        cfg, mix = shrink(dict(cfg), dict(mix))
    pool = traffic.pool(root, cfg, mix, seed)
    ref = named.load(root, "references", cfg["model"])
    refs = [compare.reference_answers(ref, e) for e in pool]
    got = [compare.reference_answers(ref, e, crashed_dropped=True)
           for e in pool]
    n = checks or len(pool)
    return compare.compare([(i % len(pool), got[i % len(pool)])
                            for i in range(n)], refs)


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description="Run the control of a cell.")
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, action="append", required=True)
    p.add_argument("--checks", type=int, default=0,
                   help="checks a run's window completes (default: "
                        "one of each pool entry)")
    args = p.parse_args(argv)
    root = os.path.dirname(harness.HERE)
    failing = 0
    for seed in args.seed:
        numbers = control_numbers(root, args.workload, seed, args.checks)
        bad = not compare.correct(numbers)
        failing += bad
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "control_correct": not bad,
                          "compared": numbers}))
    return 0 if failing == len(args.seed) else 1


if __name__ == "__main__":
    sys.exit(main())
