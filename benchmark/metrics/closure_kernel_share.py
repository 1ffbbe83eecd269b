"""Share of the traced window in the bitset closure's Pallas kernel:
the summed device time of its ops (`tracereduce.is_closure`). Cells
whose batches the program runs on the XLA while closure have no such
op, and report nothing."""

import tracereduce


def read(run):
    if run.traced is None or not run.device:
        return None
    secs = sum(e - s for name, s, e in run.device
               if tracereduce.is_closure(name))
    if secs <= 0:
        return None
    return secs / (run.traced[1] - run.traced[0])
