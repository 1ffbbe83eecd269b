"""Where JAX keeps its persistent compilation cache.

One rule for every entry point (the CLI, the bench child, the chip
smoke script, the test suite): ``JAX_COMPILATION_CACHE_DIR`` wins, and
JAX reads that variable itself, so nothing is set in code; otherwise
the cache lives at ``<repo root>/.jax_cache``. The path is fixed
because it is part of the cache's key: a directory that moves never
hits.
"""

import os

REPO_CACHE = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), ".jax_cache")


def enable_compile_cache() -> str:
    """Place JAX's persistent compilation cache and return its
    directory. Call before the first compile: JAX fixes the directory
    when it first uses the cache."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", REPO_CACHE)
    return REPO_CACHE
