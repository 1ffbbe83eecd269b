"""Share of the traced window in the host-to-device copies of a padded
keyed batch: `transfer.place` spans (inside `bitdense.pad_place`)."""

import tracereduce

NAMES = ("transfer.place",)


def read(run):
    if run.traced is None or not any(s["name"] in NAMES for s in run.spans):
        return None
    secs = tracereduce.span_seconds(run.spans, NAMES, run.traced)
    return secs / (run.traced[1] - run.traced[0])
