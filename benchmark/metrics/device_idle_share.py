"""Share of the traced window in which no op ran on the device:
1 - (union of the device ops' intervals) / window, from the profiler."""

import tracereduce


def read(run):
    if run.traced is None or run.device is None:
        return None
    busy = tracereduce.busy_seconds(run.device)
    return 1.0 - busy / (run.traced[1] - run.traced[0])
