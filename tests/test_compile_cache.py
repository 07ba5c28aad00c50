"""The persistent compilation cache has one fixed home per checkout."""
import os
import tempfile
from pathlib import Path

import jax
import pytest

from repro.launch import compile_cache

ROOT = Path(__file__).resolve().parents[1]


@pytest.fixture
def restore_cache_dir():
    from jax.experimental.compilation_cache import compilation_cache

    before = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", before)
    compilation_cache.reset_cache()


def test_environment_variable_wins(monkeypatch, tmp_path, restore_cache_dir):
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert compile_cache.configure_compile_cache() == str(tmp_path)
    # JAX reads the variable itself; nothing is set in code
    assert jax.config.jax_compilation_cache_dir == before


def test_default_is_the_fixed_in_checkout_path(monkeypatch, restore_cache_dir):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    first = compile_cache.configure_compile_cache()
    assert first == str(ROOT / ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == first
    # nothing in it comes from a temporary name, the process or the clock
    assert not first.startswith(tempfile.gettempdir())
    assert str(os.getpid()) not in first
    assert compile_cache.configure_compile_cache() == first
