"""Share of the traced window in the device SCC closure, from its
dispatch until its labels are ready: `elle.sccs.device` spans."""

import tracereduce

NAMES = ("elle.sccs.device",)


def read(run):
    if run.traced is None or not any(s["name"] in NAMES for s in run.spans):
        return None
    secs = tracereduce.span_seconds(run.spans, NAMES, run.traced)
    return secs / (run.traced[1] - run.traced[0])
