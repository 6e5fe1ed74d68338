"""Where entry points put JAX's persistent compilation cache."""

from pathlib import Path

import jax
import pytest
from jax.experimental.compilation_cache import compilation_cache

from repro.launch.compile_cache import ENV_VAR, use_compile_cache

CHECKOUT = Path(__file__).resolve().parents[1]


@pytest.fixture
def cache_dir_config():
    """Restore the process's cache directory after the test."""
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)
    compilation_cache.reset_cache()


def test_env_var_is_left_to_jax(cache_dir_config, monkeypatch, tmp_path):
    was = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv(ENV_VAR, str(tmp_path))
    assert use_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == was


def test_default_is_the_fixed_path_in_the_checkout(cache_dir_config,
                                                   monkeypatch):
    monkeypatch.delenv(ENV_VAR, raising=False)
    want = str(CHECKOUT / ".jax_cache")
    assert use_compile_cache() == want
    assert jax.config.jax_compilation_cache_dir == want
    # The same path on every call: a moved directory would never hit.
    assert use_compile_cache() == want
