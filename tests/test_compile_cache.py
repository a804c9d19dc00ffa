"""Persistent XLA compilation cache: where it lives.

The reference's cold query path is milliseconds because the JVM stays
warm (ref: src/tsd/QueryRpc.java:128). Our analogue: compiled XLA
programs must survive process restarts via the persistent compilation
cache. The directory is part of the cache key, so it must never move:
``JAX_COMPILATION_CACHE_DIR`` wins when set, otherwise one fixed path
inside the checkout (utils/compile_cache.py).
"""

from __future__ import annotations

import glob
import os

import jax
import jax.numpy as jnp
import pytest

from opentsdb_tpu.utils import compile_cache as cc
from opentsdb_tpu.utils.config import Config


@pytest.fixture(autouse=True)
def _restore_cache_config():
    """These tests repoint the process-global jax compilation cache;
    restore it so later test files keep the suite's directory."""
    prev_dir = jax.config.jax_compilation_cache_dir
    prev_on = jax.config.jax_enable_compilation_cache
    yield
    from jax._src import compilation_cache as jax_cc
    jax_cc.reset_cache()
    jax.config.update("jax_compilation_cache_dir", prev_dir)
    jax.config.update("jax_enable_compilation_cache", prev_on)


def test_unset_resolves_to_the_fixed_path_in_the_checkout(monkeypatch):
    monkeypatch.delenv(cc.ENV_VAR, raising=False)
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert cc.DEFAULT_CACHE_DIR == os.path.join(repo, ".jax_cache")
    assert cc.resolve_cache_dir(Config()) == cc.DEFAULT_CACHE_DIR


def test_data_dir_no_longer_moves_the_cache(tmp_path, monkeypatch):
    from opentsdb_tpu import TSDB

    monkeypatch.delenv(cc.ENV_VAR, raising=False)
    data = str(tmp_path / "server")
    t = TSDB(Config(**{"tsd.storage.data_dir": data,
                       "tsd.core.auto_create_metrics": "true"}))
    try:
        assert not os.path.exists(os.path.join(data, "xla_cache"))
        assert cc.active_cache_dir() == cc.DEFAULT_CACHE_DIR
        assert t.device_info()["compile_cache_dir"] == \
            cc.DEFAULT_CACHE_DIR
    finally:
        t.shutdown()


def test_env_wins_and_nothing_is_appended(tmp_path, monkeypatch):
    env_dir = str(tmp_path / "from-env")
    monkeypatch.setenv(cc.ENV_VAR, env_dir)
    cfg = Config(**{"tsd.query.compile_cache_dir":
                    str(tmp_path / "from-config")})
    assert cc.resolve_cache_dir(cfg) == env_dir
    # jax reads the variable itself at import; stand in for that here
    # and check that enabling sets no directory in code
    jax.config.update("jax_compilation_cache_dir", env_dir)
    seen = []
    real_update = jax.config.update

    def spy(name, value):
        seen.append(name)
        real_update(name, value)

    monkeypatch.setattr(jax.config, "update", spy)
    assert cc.enable_from_config(cfg)
    assert "jax_compilation_cache_dir" not in seen
    assert cc.active_cache_dir() == env_dir
    assert not os.path.exists(str(tmp_path / "from-config"))


def test_config_dir_used_as_is_and_entries_land_in_it(tmp_path,
                                                      monkeypatch):
    monkeypatch.delenv(cc.ENV_VAR, raising=False)
    d = str(tmp_path / "explicit")
    assert cc.enable_from_config(
        Config(**{"tsd.query.compile_cache_dir": d}))
    f = jax.jit(lambda x: (x * 3.0 + 1.0).sum())
    f(jnp.ones((64, 64))).block_until_ready()
    # entries directly in the directory: no per-platform subdirectory
    assert glob.glob(os.path.join(d, "*-cache"))


def test_repointing_cache_dir_takes_effect(tmp_path, monkeypatch):
    """jax opens its cache lazily and ignores later dir updates: a
    SECOND enable must not keep writing into the FIRST directory."""
    monkeypatch.delenv(cc.ENV_VAR, raising=False)
    d1, d2 = str(tmp_path / "one"), str(tmp_path / "two")
    assert cc.enable_from_config(
        Config(**{"tsd.query.compile_cache_dir": d1}))
    jax.jit(lambda x: (x * 5.0 - 2.0).sum())(
        jnp.ones((32, 32))).block_until_ready()
    assert glob.glob(os.path.join(d1, "*-cache"))
    assert cc.enable_from_config(
        Config(**{"tsd.query.compile_cache_dir": d2}))
    jax.jit(lambda x: (x * 7.0 + 3.0).sum())(
        jnp.ones((32, 32))).block_until_ready()
    assert glob.glob(os.path.join(d2, "*-cache")), \
        "entries kept landing in the first-configured dir"


@pytest.mark.parametrize("env_set", [False, True])
def test_off_still_off(tmp_path, monkeypatch, env_set):
    if env_set:
        monkeypatch.setenv(cc.ENV_VAR, str(tmp_path / "env"))
    else:
        monkeypatch.delenv(cc.ENV_VAR, raising=False)
    cfg = Config(**{"tsd.query.compile_cache_dir": "off"})
    assert cc.resolve_cache_dir(cfg) is None
    assert not cc.enable_from_config(cfg)
    assert cc.active_cache_dir() is None
