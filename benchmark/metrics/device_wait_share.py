"""Share of the traced window the host spends blocked on the device:
`bitdense.finalize` (a keyed batch's wait for its results) and
`bitdense.check` (a single key's dispatch and wait)."""

import tracereduce

NAMES = ("bitdense.finalize", "bitdense.check")


def read(run):
    if run.traced is None or not any(s["name"] in NAMES for s in run.spans):
        return None
    secs = tracereduce.span_seconds(run.spans, NAMES, run.traced)
    return secs / (run.traced[1] - run.traced[0])
