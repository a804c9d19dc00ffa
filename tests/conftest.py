"""Test harness configuration.

Tests run on CPU with 8 virtual XLA devices so the multi-chip sharding
paths (shard_map over the series/salt axis) execute without TPU hardware —
the TPU analogue of the reference's Salted/unsalted test-matrix trick
(SURVEY.md §4: every TestTsdbQuery has a *Salted twin exercising the
20-way parallel merge without a cluster).

Must set env vars before jax is imported anywhere.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"  # force: the shell may point at TPU
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()
os.environ["JAX_ENABLE_X64"] = "1"

# A plugin or an earlier conftest may have imported jax already, with
# the env vars above latched at their old values — override through the
# config API as well.
import jax

jax.config.update("jax_platforms", "cpu")
# Tests compare against float64 golden values computed with numpy.
jax.config.update("jax_enable_x64", True)

import numpy as np
import pytest

# Modules that compile shard_map / multi-process mesh programs. They are
# marked `slow` because tier-1 (`-m 'not slow'` on six xdist workers) has
# never timed them, not because they are wrong to run: PR 29 took every
# single-device battery (the oracle conformance matrices, the dense /
# padded / blocked pipelines, shapes, WAL, import, tools) off this list
# after timing them. Everything else gets `quick`.
HEAVY_MODULES = {
    "test_sharded", "test_multihost", "test_oracle_conformance_mesh",
    "test_distributed",
}


def pytest_collection_modifyitems(config, items):
    for item in items:
        mod = item.module.__name__.rsplit(".", 1)[-1] \
            if item.module else ""
        # mesh-mode twins of the query-integration matrix compile
        # shard_map programs — the expensive class on a 1-CPU host
        mesh_param = getattr(getattr(item, "callspec", None),
                             "params", {}).get("engine_mode") == "mesh"
        if mod in HEAVY_MODULES or "slow" in item.keywords \
                or mesh_param:
            item.add_marker(pytest.mark.slow)
        else:
            item.add_marker(pytest.mark.quick)


@pytest.fixture(autouse=True, scope="module")
def _jax_cache_hygiene():
    """Drop JAX's compiled-executable and tracing caches after every
    test module. The full 950+-item suite accumulates hundreds of
    shard_map executables across dozens of synthetic meshes; on this
    host that state reliably segfaulted XLA CPU compilation ~780 items
    in (order-dependent, VERDICT r03 weak #4). Per-module clearing
    bounds the live-executable population at what one module creates.
    """
    yield
    import jax
    jax.clear_caches()


@pytest.fixture(scope="module")
def lock_witness():
    """Runtime lock-order witness (tools/tsdlint/witness.py): every
    ``threading.Lock``/``RLock`` created while a battery module runs
    records per-thread acquisition-order pairs; teardown fails the
    module on any cycle, with both stacks. Opted into by the
    concurrency and cluster batteries via a module-level autouse
    fixture — the object graphs under test are built inside tests, so
    installing at test setup catches every lock that matters."""
    from opentsdb_tpu.tools.tsdlint import witness as witness_mod
    handle = witness_mod.install()
    try:
        yield handle.witness
    finally:
        handle.uninstall()
        # raises AssertionError with the full two-stack cycle report
        handle.witness.assert_clean()


@pytest.fixture(scope="module")
def leak_witness():
    """Thread/fd leak witness (tools/tsdlint/witness.py LeakWitness):
    snapshots live threads + open fds at module setup and asserts
    both CONVERGE back after the module's servers/clusters tear down,
    naming the allocation site of any thread that survives. The
    concurrency and cluster batteries opt in via a module-level
    autouse fixture — they build and tear down whole TSDServer
    topologies, exactly where an unjoined loop or unclosed socket
    would hide."""
    import jax

    from opentsdb_tpu.tools.tsdlint import witness as witness_mod

    # force backend init BEFORE the baseline: jax opens fds/threads
    # lazily on first use, and a module that happens to trigger that
    # first use would otherwise "leak" process-wide backend state
    jax.devices()
    handle = witness_mod.install_leak()
    try:
        yield handle.witness
    finally:
        handle.uninstall()
        # raises AssertionError naming each leaked thread (with the
        # stack that started it) and each surviving fd
        handle.witness.assert_converged()


@pytest.fixture
def tsdb():
    """A TSDB with auto-create enabled — the BaseTsdbTest analogue
    (ref: test/core/BaseTsdbTest.java:72)."""
    from opentsdb_tpu import TSDB, Config
    return TSDB(Config(**{
        "tsd.core.auto_create_metrics": "true",
        "tsd.rollups.enable": "true",
        # tests construct many TSDServers; their background warmup
        # threads would otherwise still be JIT-compiling at interpreter
        # exit, racing XLA teardown (observed exit-time segfaults)
        "tsd.tpu.warmup": "false",
    }))


@pytest.fixture
def seeded_tsdb(tsdb):
    """TSDB pre-loaded with the canonical two-series fixture used across
    the reference query tests (sys.cpu.user on web01/web02)."""
    base = 1356998400  # 2013-01-01 00:00:00 UTC, the reference's fixture time
    for i in range(300):
        tsdb.add_point("sys.cpu.user", base + i * 10, i,
                       {"host": "web01"})
        tsdb.add_point("sys.cpu.user", base + i * 10, 300 - i,
                       {"host": "web02"})
    return tsdb


def make_regular_series(n_series: int, n_points: int, start_ms: int = 0,
                        step_ms: int = 1000, seed: int = 42):
    """Synthetic regular-cadence data: (ts[n_points], vals[n_series, n_points])."""
    rng = np.random.default_rng(seed)
    ts = start_ms + np.arange(n_points, dtype=np.int64) * step_ms
    vals = rng.normal(100.0, 10.0, size=(n_series, n_points))
    return ts, vals
