"""The entry points' persistent compile cache placement."""

import jax
import pytest

from repro.launch import compile_cache


@pytest.fixture
def restore_cache_dir():
    prev = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", prev)


def test_cache_dir_from_environment_is_left_alone(monkeypatch,
                                                  restore_cache_dir):
    jax.config.update("jax_compilation_cache_dir", "/elsewhere")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/from/env")
    assert compile_cache.enable_compile_cache() == "/from/env"
    assert jax.config.jax_compilation_cache_dir == "/elsewhere"


def test_default_cache_dir_is_fixed_inside_the_checkout(monkeypatch,
                                                        restore_cache_dir):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    path = compile_cache.enable_compile_cache()
    assert path == str(compile_cache.DEFAULT_DIR)
    assert jax.config.jax_compilation_cache_dir == path
    root = compile_cache.DEFAULT_DIR.parent
    assert (root / "chip_smoke.py").is_file()
    ignored = (root / ".gitignore").read_text().split()
    assert compile_cache.DEFAULT_DIR.name + "/" in ignored
