"""Programs compiled or loaded from JAX's persistent cache during the
window, counted by a `jax.monitoring` listener. Should read 0."""


def read(run):
    return run.window_compiles
