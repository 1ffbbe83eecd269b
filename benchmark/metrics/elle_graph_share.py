"""Share of the traced window in list-append's host scan and graph
building: `elle.scan` (:ok transactions, failed and intermediate
appends, internal, G1a and G1b) and `elle.graph` (ww, wr, rw and
realtime edges)."""

import tracereduce

NAMES = ("elle.scan", "elle.graph")


def read(run):
    if run.traced is None or not any(s["name"] in NAMES for s in run.spans):
        return None
    secs = tracereduce.span_seconds(run.spans, NAMES, run.traced)
    return secs / (run.traced[1] - run.traced[0])
