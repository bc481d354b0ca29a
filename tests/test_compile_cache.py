"""The entry points' persistent compile cache: the environment's directory
when ``JAX_COMPILATION_CACHE_DIR`` is set, else a fixed one in the
checkout."""
import jax
import pytest

from repro.utils import compile_cache


@pytest.fixture()
def restore_cache_dir():
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)


@pytest.mark.parametrize("env_dir", [None, "/srv/jax-cache"])
def test_cache_dir_env_wins_else_checkout(monkeypatch, restore_cache_dir,
                                          env_dir):
    jax.config.update("jax_compilation_cache_dir", None)
    if env_dir is None:
        monkeypatch.delenv(compile_cache.ENV, raising=False)
        want = str(compile_cache.CHECKOUT / ".jax_cache")
        assert (compile_cache.CHECKOUT / "src" / "repro").is_dir()
        assert compile_cache.enable_compile_cache() == want
        assert jax.config.jax_compilation_cache_dir == want
    else:
        # JAX reads the variable itself; the helper must not override it
        monkeypatch.setenv(compile_cache.ENV, env_dir)
        assert compile_cache.enable_compile_cache() == env_dir
        assert jax.config.jax_compilation_cache_dir is None
