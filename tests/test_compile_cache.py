"""``utils.use_compile_cache``: the one place the persistent compilation
cache is placed. An operator's ``JAX_COMPILATION_CACHE_DIR`` wins
untouched (JAX reads it itself); otherwise the path is fixed inside the
checkout — never a tempdir, a pid or a time, because a cache whose
directory moves never hits."""

import os

import jax

from chainermn_tpu.utils import use_compile_cache

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_env_var_wins_and_nothing_is_set(monkeypatch):
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/somewhere/else")
    assert use_compile_cache() == "/somewhere/else"
    assert jax.config.jax_compilation_cache_dir == before


def test_default_is_a_fixed_path_in_the_checkout(monkeypatch):
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    try:
        path = use_compile_cache()
        assert path == os.path.join(REPO_ROOT, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
        assert use_compile_cache() == path        # same place every call
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
