"""The persistent compilation cache helper picks one stable directory."""

from pathlib import Path

import jax
import pytest
from jax.experimental.compilation_cache import compilation_cache

from repro import compile_cache

REPO = Path(__file__).resolve().parents[1]


@pytest.fixture
def restore_cache_config():
    saved = (jax.config.jax_compilation_cache_dir,
             jax.config.jax_enable_compilation_cache)
    yield
    jax.config.update("jax_compilation_cache_dir", saved[0])
    jax.config.update("jax_enable_compilation_cache", saved[1])
    compilation_cache.reset_cache()


@pytest.mark.parametrize("env_set", [True, False], ids=["env", "default"])
def test_cache_dir(env_set, tmp_path, monkeypatch, restore_cache_config):
    if env_set:
        monkeypatch.setenv(compile_cache.ENV_VAR, str(tmp_path))
        expect = str(tmp_path)
    else:
        monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
        expect = str(REPO / ".jax_cache")
    assert compile_cache.enable() == expect
    assert compile_cache.enable() == expect        # stable across calls
    assert jax.config.jax_compilation_cache_dir == expect
    assert jax.config.jax_enable_compilation_cache
