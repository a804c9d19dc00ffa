"""Persistent XLA compilation cache.

The reference serves a cold query from the warm JVM in tens of ms
(ref: src/tsd/QueryRpc.java:128 dispatches straight into TsdbQuery; its
only "warmup" is a gnuplot pool pre-spawn, GraphHandler.java:85-99).
Here every jitted query program is an XLA compile. Without a persistent
cache a *restarted* server pays every compile again — the whole warm-up
set plus a cold first query per shape class.

JAX's persistent compilation cache makes each compile a
once-per-code-version cost instead of once-per-process: the serialized
executable is keyed by (HLO, compile options, backend version) and
reloaded from disk on the next boot.

Where the cache lives, in order:

1. ``JAX_COMPILATION_CACHE_DIR`` when the environment sets it. JAX
   reads the variable itself; this module sets no directory in code
   and no config key overrides it.
2. ``tsd.query.compile_cache_dir`` when it names a directory.
3. :data:`DEFAULT_CACHE_DIR`, one fixed git-ignored directory inside
   the checkout, shared by the server, the CLI tools and
   ``chip_smoke.py``.

The directory is part of the cache key (JAX embeds it in the compile
options), so nothing that moves — a data dir, a temp dir, a pid —
may appear in it. ``tsd.query.compile_cache_dir=off`` disables the
cache.
"""

from __future__ import annotations

import logging
import os

_log = logging.getLogger("tsdb.compile_cache")

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")
_OFF = ("off", "none", "disabled")


def resolve_cache_dir(config) -> str | None:
    """The directory this process caches compiled programs in, or
    None when ``tsd.query.compile_cache_dir`` switches the cache off."""
    explicit = config.get_string("tsd.query.compile_cache_dir", "")
    if explicit.lower() in _OFF:
        return None
    return os.environ.get(ENV_VAR) or explicit or DEFAULT_CACHE_DIR


def active_cache_dir() -> str | None:
    """What JAX is using right now (reported by ``/api/health``)."""
    import jax
    if not jax.config.jax_enable_compilation_cache:
        return None
    return jax.config.jax_compilation_cache_dir


def enable_from_config(config) -> bool:
    """Switch the persistent cache on at :func:`resolve_cache_dir`.

    Safe to call before or after the backend initializes (JAX consults
    the config at compile time). Returns True if the cache is active."""
    import jax
    cache_dir = resolve_cache_dir(config)
    if cache_dir is None:
        jax.config.update("jax_enable_compilation_cache", False)
        return False
    cache_dir = os.path.abspath(cache_dir)
    try:
        os.makedirs(cache_dir, exist_ok=True)
    except OSError as exc:
        _log.warning("compile cache disabled: %s", exc)
        jax.config.update("jax_enable_compilation_cache", False)
        return False
    if not os.environ.get(ENV_VAR) and \
            jax.config.jax_compilation_cache_dir != cache_dir:
        # jax opens its cache lazily on first use and then ignores
        # later jax_compilation_cache_dir updates: without the reset,
        # entries keep landing in the first directory this process
        # ever configured
        from jax._src import compilation_cache as _jax_cc
        _jax_cc.reset_cache()
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    jax.config.update("jax_enable_compilation_cache", True)
    # cache every program: the warm-up set is dominated by programs
    # that compile in under JAX's default one-second threshold, and a
    # restarted server should reload all of them
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    _log.info("persistent compilation cache at %s", cache_dir)
    return True
