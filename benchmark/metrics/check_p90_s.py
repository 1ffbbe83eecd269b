"""90th percentile of one check's latency over all checks of the
window: from the history handed to `check` until the result returns."""

import statistics


def read(run):
    lat = [end - start for _, start, end, _ in run.checks]
    if len(lat) < 2:
        return None
    return statistics.quantiles(lat, n=10)[8]
