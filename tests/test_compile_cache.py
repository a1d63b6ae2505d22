"""repro.compile_cache: the persistent compilation cache sits at a fixed
place — JAX_COMPILATION_CACHE_DIR when set (JAX reads it itself), else
<repo>/.jax_cache."""
from pathlib import Path

import jax
import pytest

from repro import compile_cache


@pytest.fixture
def restore_cache_dir():
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)


def test_default_is_the_repo_cache_dir(monkeypatch, restore_cache_dir):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    path = compile_cache.enable_compile_cache()
    repo = Path(__file__).resolve().parents[1]
    assert Path(path) == repo / ".jax_cache"
    assert jax.config.jax_compilation_cache_dir == path
    assert ".jax_cache/" in (repo / ".gitignore").read_text().split()


def test_env_var_wins_and_nothing_is_set(monkeypatch, tmp_path,
                                         restore_cache_dir):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before
