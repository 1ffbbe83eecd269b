"""Share of the traced window in the jit call that enqueues a bitdense
program on the device: `bitdense.issue` spans."""

import tracereduce

NAMES = ("bitdense.issue",)


def read(run):
    if run.traced is None or not any(s["name"] in NAMES for s in run.spans):
        return None
    secs = tracereduce.span_seconds(run.spans, NAMES, run.traced)
    return secs / (run.traced[1] - run.traced[0])
