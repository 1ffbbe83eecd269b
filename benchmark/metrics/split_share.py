"""Share of the traced window in splitting a keyed history into per-key
histories: `independent.split` spans (the KV scan and
`independent.split_history`)."""

import tracereduce

NAMES = ("independent.split",)


def read(run):
    if run.traced is None or not any(s["name"] in NAMES for s in run.spans):
        return None
    secs = tracereduce.span_seconds(run.spans, NAMES, run.traced)
    return secs / (run.traced[1] - run.traced[0])
