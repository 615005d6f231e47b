"""The persistent compile cache helper: placed from outside when
JAX_COMPILATION_CACHE_DIR is set, otherwise one fixed path in the checkout."""
import pathlib

import jax
import pytest

from repro.launch import compile_cache


@pytest.fixture
def restore_cache_dir():
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)


def test_env_dir_wins_and_sets_nothing(monkeypatch, tmp_path,
                                       restore_cache_dir):
    monkeypatch.setenv(compile_cache.CACHE_ENV, str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_default_dir_is_fixed_and_in_checkout(monkeypatch, restore_cache_dir):
    monkeypatch.delenv(compile_cache.CACHE_ENV, raising=False)
    first = compile_cache.enable_compile_cache()
    assert first == compile_cache.enable_compile_cache()
    assert jax.config.jax_compilation_cache_dir == first
    repo = pathlib.Path(__file__).resolve().parents[1]
    assert pathlib.Path(first) == repo / ".jax_cache"
    assert ".jax_cache/" in (repo / ".gitignore").read_text().split()
