"""The entry points' compile cache: where ``JAX_COMPILATION_CACHE_DIR``
says, otherwise a fixed directory in the checkout."""

import jax
import pytest

from repro import compile_cache


@pytest.fixture
def cache_dir_config():
    before = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", before)


def test_env_var_wins_and_nothing_is_set(monkeypatch, cache_dir_config):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/somewhere/else")
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.place_compile_cache() == "/somewhere/else"
    assert jax.config.jax_compilation_cache_dir == before


def test_default_is_fixed_path_in_checkout(monkeypatch, cache_dir_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    path = compile_cache.place_compile_cache()
    assert path == str(compile_cache.CHECKOUT / ".jax_cache")
    assert (compile_cache.CHECKOUT / "src" / "repro").is_dir()
    assert jax.config.jax_compilation_cache_dir == path
    # the same on every call: no pid, time or temp name in it
    assert compile_cache.place_compile_cache() == path
