"""The compile-cache helper: the environment's directory when it names
one, else a fixed directory inside the checkout."""
import jax
import pytest
from jax.experimental.compilation_cache import compilation_cache as cc

from repro.launch import cache


@pytest.fixture
def restore_cache_dir():
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)
    cc.reset_cache()


def test_env_directory_wins(monkeypatch, tmp_path, restore_cache_dir):
    monkeypatch.setenv(cache.ENV, str(tmp_path / "jc"))
    assert cache.enable_compile_cache() == str(tmp_path / "jc")
    assert jax.config.jax_compilation_cache_dir == str(tmp_path / "jc")


def test_default_is_fixed_inside_the_checkout(monkeypatch, restore_cache_dir):
    monkeypatch.delenv(cache.ENV, raising=False)
    path = cache.enable_compile_cache()
    assert path == str(cache.REPO_ROOT / ".jax_cache")
    assert (cache.REPO_ROOT / "src" / "repro" / "launch" / "cache.py").exists()
    assert cache.enable_compile_cache() == path      # same path every call
    assert jax.config.jax_compilation_cache_dir == path
