"""History operations checked per second: invocations, over all keys,
of every check completed in the window, over the window's wall
seconds (from its start to the end of its last check)."""


def read(run):
    t0, t1 = run.window
    if not run.checks or t1 <= t0:
        return None
    return sum(c[3] for c in run.checks) / (t1 - t0)
