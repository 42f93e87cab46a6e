"""Where the entry points keep JAX's persistent compilation cache."""
from pathlib import Path

import jax
import numpy as np
import pytest
from jax.experimental.compilation_cache import compilation_cache

from repro.launch.compile_cache import CHECKOUT_CACHE_DIR, enable_compile_cache

REPO = Path(__file__).resolve().parents[1]


@pytest.fixture
def cache_config():
    """Restore the process's cache settings after the test."""
    names = ("jax_compilation_cache_dir",
             "jax_persistent_cache_min_compile_time_secs",
             "jax_enable_compilation_cache")
    was = {n: getattr(jax.config, n) for n in names}
    yield
    for n, v in was.items():
        jax.config.update(n, v)
    compilation_cache.reset_cache()


def _listing(path: Path):
    return sorted(p.name for p in path.iterdir()) if path.is_dir() else []


def test_env_dir_takes_every_entry(tmp_path, monkeypatch, cache_config):
    target = tmp_path / "cache"
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(target))
    before = _listing(CHECKOUT_CACHE_DIR)
    assert enable_compile_cache() == str(target)
    assert jax.config.jax_compilation_cache_dir == str(target)

    jax.config.update("jax_enable_compilation_cache", True)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    compilation_cache.reset_cache()
    jax.jit(lambda x: x * 3 + 1)(np.arange(7.0)).block_until_ready()
    assert _listing(target), "nothing was written to the named directory"
    assert _listing(CHECKOUT_CACHE_DIR) == before


def test_unset_env_uses_the_ignored_checkout_dir(monkeypatch, cache_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    assert enable_compile_cache() == str(REPO / ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == str(REPO / ".jax_cache")
    ignored = (REPO / ".gitignore").read_text().split()
    assert ".jax_cache/" in ignored
