"""Share of the traced window in the program's host encode: the self
time of `engine.encode_batch` (keyed batches) and `engine.encode`
(single key) spans."""

import tracereduce


def read(run):
    if run.traced is None:
        return None
    names = ("engine.encode_batch", "engine.encode")
    if not any(s["name"] in names for s in run.spans):
        return None
    secs = tracereduce.span_seconds(run.spans, names, run.traced,
                                    self_time=True)
    return secs / (run.traced[1] - run.traced[0])
