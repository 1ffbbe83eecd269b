"""Share of the traced window inside checks where no program span
below a check's root is open: the innermost open span
(`tracereduce.host_segments`) is the harness's `harness.check` or a
root, `independent.check` (a keyed check) or `linearizable.check` (a
single key). What the program's spans leave unexplained."""

import tracereduce

ROOTS = ("harness.check", "independent.check", "linearizable.check")


def read(run):
    if run.traced is None or not run.checks:
        return None
    secs = sum(b - a for a, b, name in tracereduce.host_segments(
        run.spans, run.checks, run.traced) if name in ROOTS)
    return secs / (run.traced[1] - run.traced[0])
