"""Where the launchers put JAX's persistent compilation cache.

The cache itself is never turned on here: ``jax.config.update`` is
replaced by a recorder, so the suite compiles as it always does.
"""

from pathlib import Path

import jax
import pytest

from repro.launch import compile_cache

REPO = Path(__file__).resolve().parents[1]


@pytest.fixture
def config_updates(monkeypatch):
    calls = []
    monkeypatch.setattr(jax.config, "update",
                        lambda name, value: calls.append((name, value)))
    return calls


def test_cache_in_checkout_when_env_unset(monkeypatch, config_updates):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    want = REPO / ".jax_cache"
    assert compile_cache.enable_compile_cache() == want
    assert config_updates == [("jax_compilation_cache_dir", str(want))]
    # a fixed path: the same on every call, so a later run hits
    assert compile_cache.enable_compile_cache() == want
    assert ".jax_cache/" in (REPO / ".gitignore").read_text().split()


def test_cache_env_dir_stands(monkeypatch, config_updates, tmp_path):
    """A set ``JAX_COMPILATION_CACHE_DIR`` is JAX's own to read: the
    helper reports it and sets no directory of its own."""
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert compile_cache.enable_compile_cache() == tmp_path
    assert config_updates == []
