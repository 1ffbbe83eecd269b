"""Seconds of tracing, lowering, compiling and loading programs from
the persistent cache during set-up, from a `jax.monitoring` listener."""


def read(run):
    return run.setup_compile_s
