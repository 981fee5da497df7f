"""Where ``enable_compile_cache`` puts JAX's persistent compile cache."""
import jax
import pytest

from repro import compile_cache


@pytest.fixture
def restore_cache_config():
    saved = {name: getattr(jax.config, name) for name in (
        "jax_compilation_cache_dir",
        "jax_persistent_cache_min_compile_time_secs")}
    yield
    for name, value in saved.items():
        jax.config.update(name, value)


@pytest.mark.parametrize("env_dir", [None, "/elsewhere/jax-cache"])
def test_cache_dir_is_fixed_or_placed_from_outside(env_dir, monkeypatch,
                                                   restore_cache_config):
    if env_dir is None:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        want = str(compile_cache.DEFAULT_DIR)
    else:
        # JAX reads the variable itself; the helper must not override it
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env_dir)
        jax.config.update("jax_compilation_cache_dir", env_dir)
        want = env_dir
    assert compile_cache.enable_compile_cache() == want
    assert jax.config.jax_compilation_cache_dir == want
    assert jax.config.jax_persistent_cache_min_compile_time_secs == 0
    assert compile_cache.DEFAULT_DIR.parent.joinpath("src", "repro").is_dir()
