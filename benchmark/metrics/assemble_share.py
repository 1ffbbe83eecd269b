"""Share of the traced window in building results after the device has
answered: `bitdense.results` (per-key result dicts, counterexample
fields, stats, ledger record) and `independent.assemble` (keyed
results, failures, the merged verdict, persistence)."""

import tracereduce

NAMES = ("independent.assemble", "bitdense.results")


def read(run):
    if run.traced is None or not any(s["name"] in NAMES for s in run.spans):
        return None
    secs = tracereduce.span_seconds(run.spans, NAMES, run.traced)
    return secs / (run.traced[1] - run.traced[0])
