"""Where JAX's persistent compilation cache lives (compile_cache.py):
JAX_COMPILATION_CACHE_DIR when set, with nothing set in code;
otherwise the fixed <repo>/.jax_cache."""

import os

import jax

from jepsen_tpu import compile_cache

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_unset_places_the_cache_at_the_repo_root(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    prev = jax.config.jax_compilation_cache_dir
    try:
        jax.config.update("jax_compilation_cache_dir", None)
        assert compile_cache.enable_compile_cache() \
            == os.path.join(REPO, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir \
            == os.path.join(REPO, ".jax_cache")
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)


def test_the_variable_wins_and_nothing_is_set_in_code(monkeypatch,
                                                      tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    prev = jax.config.jax_compilation_cache_dir
    try:
        jax.config.update("jax_compilation_cache_dir", None)
        assert compile_cache.enable_compile_cache() == str(tmp_path)
        # JAX reads the variable itself; the helper left the config be
        assert jax.config.jax_compilation_cache_dir is None
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)


def test_jax_reads_the_variable_itself(tmp_path):
    import subprocess
    import sys
    env = dict(os.environ, JAX_COMPILATION_CACHE_DIR=str(tmp_path),
               JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, "-c",
         "import jax; print(jax.config.jax_compilation_cache_dir)"],
        capture_output=True, text=True, env=env, timeout=120)
    assert out.stdout.strip() == str(tmp_path), out.stderr[-500:]
