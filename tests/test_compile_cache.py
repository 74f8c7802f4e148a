"""The one compile-cache helper every launcher calls
(lodestar_tpu/utils/compile_cache.py)."""

import pathlib

import jax
import jax.numpy as jnp
import pytest

from lodestar_tpu.utils import compile_cache

CHECKOUT = pathlib.Path(__file__).resolve().parents[1]


@pytest.fixture
def restore_cache_config():
    from jax.experimental.compilation_cache import compilation_cache as cc

    keys = (
        "jax_compilation_cache_dir",
        "jax_persistent_cache_min_compile_time_secs",
        "jax_persistent_cache_min_entry_size_bytes",
    )
    prev = {k: getattr(jax.config, k) for k in keys}
    yield cc
    for k, v in prev.items():
        jax.config.update(k, v)
    cc.reset_cache()


def test_default_is_the_checkout(monkeypatch, restore_cache_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    want = str(CHECKOUT / ".jax_cache")
    assert compile_cache.configure() == want
    assert jax.config.jax_compilation_cache_dir == want


def test_environment_places_the_cache(monkeypatch, tmp_path, restore_cache_config):
    """With JAX_COMPILATION_CACHE_DIR set, compiled programs land there."""
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert compile_cache.configure() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == str(tmp_path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    restore_cache_config.reset_cache()
    jax.jit(lambda x: x * 3 + 17).lower(jnp.ones(11)).compile()
    assert any(tmp_path.iterdir())
