"""Share of the traced window in padding a keyed batch and placing it
on the device: `bitdense.pad_place` spans."""

import tracereduce


def read(run):
    if run.traced is None or not any(
            s["name"] == "bitdense.pad_place" for s in run.spans):
        return None
    secs = tracereduce.span_seconds(run.spans, ("bitdense.pad_place",),
                                    run.traced)
    return secs / (run.traced[1] - run.traced[0])
