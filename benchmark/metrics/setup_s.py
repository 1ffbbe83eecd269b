"""Seconds from the harness's first statement until the window opens:
importing, finding the device, making the pool, building the checker,
and the warm-up checks that compile or load every program."""


def read(run):
    return run.setup_s
