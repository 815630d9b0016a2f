"""Entry-point device set-up: where the persistent compile cache goes."""
import pathlib

import jax
import pytest

import repro.device as device


@pytest.fixture
def cache_dir_restored():
    before = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", before)


def test_import_sets_nothing():
    # Importing repro.device (done above) leaves JAX's cache setting alone.
    import repro  # noqa: F401
    assert jax.config.jax_compilation_cache_dir != str(device.CACHE_DIR)


def test_env_dir_stands(monkeypatch, cache_dir_restored):
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/cache")
    assert device.use_compile_cache() == "/elsewhere/cache"
    assert jax.config.jax_compilation_cache_dir == before   # JAX's own reading


def test_default_dir_is_fixed_inside_checkout(monkeypatch, cache_dir_restored):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    path = device.use_compile_cache()
    assert path == str(device.CACHE_DIR)
    assert jax.config.jax_compilation_cache_dir == path
    root = pathlib.Path(__file__).resolve().parents[1]
    assert device.CACHE_DIR == root / ".jax_cache"
    assert device.use_compile_cache() == path                # same every call
