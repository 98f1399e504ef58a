"""Placement of JAX's persistent compilation cache (repro.compile_cache)."""

import jax
import pytest
from jax.experimental.compilation_cache import compilation_cache

from repro import compile_cache


@pytest.fixture
def restore_cache_dir():
    before = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", before)
    compilation_cache.reset_cache()


@pytest.mark.parametrize("env_dir", [None, "/elsewhere/jax-cache"],
                         ids=["fixed_path", "from_env"])
def test_cache_dir_placement(monkeypatch, restore_cache_dir, env_dir):
    """Unset: the fixed ``.jax_cache/`` at the repo root, the same path in
    every process.  Set: JAX's own reading of the variable is left alone
    and no other path is configured."""
    before = jax.config.jax_compilation_cache_dir
    if env_dir is None:
        monkeypatch.delenv(compile_cache.CACHE_ENV, raising=False)
        got = compile_cache.enable_compile_cache()
        assert got == compile_cache.DEFAULT_CACHE_DIR
        assert got.endswith(".jax_cache")
        assert jax.config.jax_compilation_cache_dir == got
        assert compile_cache.enable_compile_cache() == got
    else:
        monkeypatch.setenv(compile_cache.CACHE_ENV, env_dir)
        assert compile_cache.enable_compile_cache() == env_dir
        assert jax.config.jax_compilation_cache_dir == before
