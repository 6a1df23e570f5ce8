"""The entry points' compile cache: JAX's own variable wins; otherwise a
fixed directory in the checkout."""

import jax
import pytest

from repro.compile_cache import DEFAULT_DIR, enable_compile_cache


@pytest.fixture()
def cache_dir_config():
    before = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", before)


def test_env_var_is_left_to_jax(monkeypatch, cache_dir_config, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_unset_env_uses_the_checkout_dir(monkeypatch, cache_dir_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    assert enable_compile_cache() == str(DEFAULT_DIR)
    assert jax.config.jax_compilation_cache_dir == str(DEFAULT_DIR)
    assert DEFAULT_DIR.parent.joinpath("pyproject.toml").is_file()
