"""Share of the traced window in classifying the SCCs: the cycle
searches and their explanations, `elle.cycles` spans."""

import tracereduce

NAMES = ("elle.cycles",)


def read(run):
    if run.traced is None or not any(s["name"] in NAMES for s in run.spans):
        return None
    secs = tracereduce.span_seconds(run.spans, NAMES, run.traced)
    return secs / (run.traced[1] - run.traced[0])
