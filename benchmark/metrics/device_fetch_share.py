"""Share of the traced window in copying a bitdense program's results
to the host once the device has finished: `bitdense.fetch` spans
(inside `bitdense.finalize` and `bitdense.check`, after the wait)."""

import tracereduce

NAMES = ("bitdense.fetch",)


def read(run):
    if run.traced is None or not any(s["name"] in NAMES for s in run.spans):
        return None
    secs = tracereduce.span_seconds(run.spans, NAMES, run.traced)
    return secs / (run.traced[1] - run.traced[0])
