"""The persistent compilation cache is placed from outside the program:
``JAX_COMPILATION_CACHE_DIR`` when set, else one fixed gitignored directory
inside the checkout."""
from __future__ import annotations

from pathlib import Path

import jax
import pytest

from repro import jax_cache

ROOT = Path(__file__).resolve().parents[1]


@pytest.fixture
def restore_cache_dir():
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)


def test_env_dir_wins_and_nothing_else_is_set(monkeypatch, tmp_path,
                                              restore_cache_dir):
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv(jax_cache.ENV_VAR, str(tmp_path))
    assert jax_cache.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_default_dir_is_fixed_inside_the_checkout(monkeypatch,
                                                  restore_cache_dir):
    monkeypatch.delenv(jax_cache.ENV_VAR, raising=False)
    first = jax_cache.enable_compile_cache()
    assert jax_cache.enable_compile_cache() == first
    assert jax.config.jax_compilation_cache_dir == first
    path = Path(first)
    assert path == ROOT / ".jax_cache"
    ignored = (ROOT / ".gitignore").read_text().split()
    assert f"{path.name}/" in ignored
