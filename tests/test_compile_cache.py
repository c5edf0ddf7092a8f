"""Where the entry points put JAX's persistent compilation cache."""
import jax
import pytest

from repro.launch import compile_cache


@pytest.fixture
def restore_cache_dir():
    prev = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", prev)


@pytest.mark.parametrize("env", [None, "/elsewhere/cache"])
def test_enable_compile_cache(monkeypatch, restore_cache_dir, env):
    """An outside JAX_COMPILATION_CACHE_DIR wins and nothing is set in
    code; otherwise the cache goes to the fixed checkout directory."""
    before = jax.config.jax_compilation_cache_dir
    if env is None:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    else:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env)
    got = compile_cache.enable_compile_cache()
    if env is None:
        assert got == str(compile_cache.DEFAULT_DIR)
        assert jax.config.jax_compilation_cache_dir == got
        assert compile_cache.DEFAULT_DIR.parent.joinpath("src").is_dir()
    else:
        assert got == env
        assert jax.config.jax_compilation_cache_dir == before
