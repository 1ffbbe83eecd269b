"""Share of the traced window in pauses of the Python collector:
`python.gc` spans, one per collection, on their own track."""

import tracereduce

NAMES = ("python.gc",)


def read(run):
    if run.traced is None or not any(s["name"] in NAMES for s in run.spans):
        return None
    secs = tracereduce.span_seconds(run.spans, NAMES, run.traced)
    return secs / (run.traced[1] - run.traced[0])
