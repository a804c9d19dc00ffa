"""Multi-chip sharded pipeline tests on the virtual 8-device CPU mesh —
the TPU analogue of the reference's *Salted test twins
(TestTsdbQuerySalted.java, TestSaltScannerSalted.java): every result
must be identical to the single-chip pipeline."""

import numpy as np
import pytest

from opentsdb_tpu.ops.pipeline import PipelineSpec, execute
from opentsdb_tpu.ops.rate import RateOptions
from opentsdb_tpu.parallel.mesh import make_mesh
from opentsdb_tpu.parallel.sharded_pipeline import (prepare_sharded_batch,
                                                    run_sharded)


def random_batch(num_series=24, num_buckets=40, points_per=30, seed=0):
    rng = np.random.default_rng(seed)
    rows = []
    for s in range(num_series):
        buckets = rng.choice(num_buckets, size=min(points_per, num_buckets),
                             replace=False)
        for b in sorted(buckets):
            rows.append((s, b, rng.normal(100, 20)))
    arr = np.asarray(rows)
    order = np.lexsort((arr[:, 1], arr[:, 0]))
    arr = arr[order]
    values = arr[:, 2].astype(np.float64)
    series_idx = arr[:, 0].astype(np.int32)
    bucket_idx = arr[:, 1].astype(np.int32)
    bucket_ts = np.arange(num_buckets, dtype=np.int64) * 60_000
    return values, series_idx, bucket_idx, bucket_ts


def compare(mesh_shape, spec, num_series, seed=0, points_per=30,
            rate_options=None, num_groups=None, group_mod=3):
    values, sidx, bidx, bts = random_batch(num_series, spec.num_buckets,
                                           points_per, seed)
    g = spec.num_groups
    group_ids = (np.arange(num_series) % g).astype(np.int32)
    ref, ref_emit = execute(values, sidx, bidx, bts, group_ids, spec,
                            rate_options)
    mesh = make_mesh(*mesh_shape)
    batch = prepare_sharded_batch(values, sidx, bidx, bts, group_ids,
                                  num_series, g, mesh_shape[0],
                                  mesh_shape[1])
    got, got_emit = run_sharded(mesh, spec, batch, rate_options)
    np.testing.assert_allclose(got, ref, rtol=1e-9, equal_nan=True)
    np.testing.assert_array_equal(got_emit, ref_emit)


MESHES = [(8, 1), (4, 2), (2, 4), (1, 8)]


@pytest.mark.parametrize("mesh_shape", MESHES)
@pytest.mark.parametrize("agg", ["sum", "avg", "max", "count", "dev"])
def test_reducible_aggs_match_single_chip(mesh_shape, agg):
    spec = PipelineSpec(num_series=24, num_buckets=40, num_groups=3,
                        ds_function="avg", agg_name=agg)
    compare(mesh_shape, spec, 24, seed=sum(map(ord, agg)) % 1000)


@pytest.mark.parametrize("mesh_shape", [(8, 1), (2, 4)])
@pytest.mark.parametrize("agg", ["first", "last", "multiply", "diff"])
def test_gathered_aggs_match_single_chip(mesh_shape, agg):
    # first/last: distributed edge-candidate merge (exact);
    # multiply/diff: the remaining all_gather fallbacks (exact)
    spec = PipelineSpec(num_series=16, num_buckets=24, num_groups=2,
                        ds_function="sum", agg_name=agg)
    compare(mesh_shape, spec, 16, seed=sum(map(ord, agg)) % 1000, points_per=20)


@pytest.mark.parametrize("mesh_shape", [(8, 1), (2, 4)])
@pytest.mark.parametrize("agg", ["p95", "p50", "median", "ep99r7"])
def test_distributed_percentiles_within_estimator_error(mesh_shape,
                                                        agg):
    """Percentiles on the mesh use bucketed-histogram psum partials
    (VERDICT r02 #5) — per-device memory O(S_loc x B) instead of an
    all_gather of the series axis. Conformance bar: within the
    documented estimator error (group value range / PERCENTILE_BINS)
    of the exact single-device answer."""
    from opentsdb_tpu.parallel.sharded_pipeline import PERCENTILE_BINS
    num_series, g = 32, 2
    spec = PipelineSpec(num_series=num_series, num_buckets=24,
                        num_groups=g, ds_function="sum", agg_name=agg)
    values, sidx, bidx, bts = random_batch(num_series, 24, 20,
                                           seed=sum(map(ord, agg)))
    group_ids = (np.arange(num_series) % g).astype(np.int32)
    ref, ref_emit = execute(values, sidx, bidx, bts, group_ids, spec)
    mesh = make_mesh(*mesh_shape)
    batch = prepare_sharded_batch(values, sidx, bidx, bts, group_ids,
                                  num_series, g, mesh_shape[0],
                                  mesh_shape[1])
    got, got_emit = run_sharded(mesh, spec, batch)
    np.testing.assert_array_equal(got_emit, ref_emit)
    # same NaN pattern; values within the documented bin error
    assert np.array_equal(np.isnan(got), np.isnan(ref))
    # the per-(g,b) INPUT value range bounds the bin width; the global
    # input range bounds every cell's
    rng_ = values.max() - values.min() + 1e-9
    tol = 2.0 * rng_ / PERCENTILE_BINS
    m = ~np.isnan(ref)
    assert np.max(np.abs(got[m] - ref[m])) <= tol, \
        f"estimator error {np.max(np.abs(got[m] - ref[m]))} > {tol}"


@pytest.mark.parametrize("mesh_shape", [(4, 2), (2, 4)])
def test_blocked_sharded_gap_spans_whole_block(mesh_shape):
    """A series with points in blocks 0 and 2 but NONE in block 1 must
    still LERP across the empty middle block: next-carries accumulate
    over ALL later blocks, not just the adjacent one."""
    from opentsdb_tpu.parallel.sharded_pipeline import \
        execute_blocked_sharded
    num_series, g, b = 8, 2, 48
    spec = PipelineSpec(num_series=num_series, num_buckets=b,
                        num_groups=g, ds_function="avg",
                        agg_name="sum")
    rows = []
    for s in range(num_series):
        if s == 3:
            # block 0 (buckets 0-15) and block 2 (32-47) only
            rows += [(s, 2, 10.0), (s, 40, 90.0)]
        else:
            rows += [(s, bb, float(100 + s + bb)) for bb in range(48)]
    arr = np.asarray(rows)
    values = arr[:, 2].astype(np.float64)
    sidx = arr[:, 0].astype(np.int32)
    bidx = arr[:, 1].astype(np.int32)
    bts = np.arange(b, dtype=np.int64) * 60_000
    group_ids = (np.arange(num_series) % g).astype(np.int32)
    ref, ref_emit = execute(values, sidx, bidx, bts, group_ids, spec)
    mesh = make_mesh(*mesh_shape)
    got, got_emit = execute_blocked_sharded(
        mesh, values, sidx, bidx, bts, group_ids, spec,
        block_buckets=16)  # 3 blocks; series 3 empty in block 1
    np.testing.assert_array_equal(got_emit, ref_emit)
    np.testing.assert_allclose(got, ref, rtol=1e-9, equal_nan=True)


@pytest.mark.parametrize("mesh_shape", [(8, 1), (4, 2), (2, 4)])
@pytest.mark.parametrize("agg,rate", [("sum", False), ("avg", True),
                                      ("p95", False)])
def test_blocked_sharded_matches_single_chip(mesh_shape, agg, rate):
    """Over-budget long ranges stream time blocks while KEEPING the
    mesh (VERDICT r02 #4): the carry-chained block scan as a shard_map
    program must match the unblocked single-device pipeline."""
    from opentsdb_tpu.parallel.sharded_pipeline import (
        PERCENTILE_BINS, execute_blocked_sharded)
    num_series, g, b = 24, 3, 48
    spec = PipelineSpec(num_series=num_series, num_buckets=b,
                        num_groups=g, ds_function="avg", agg_name=agg,
                        rate=rate)
    values, sidx, bidx, bts = random_batch(num_series, b, 30, seed=11)
    group_ids = (np.arange(num_series) % g).astype(np.int32)
    ro = RateOptions() if rate else None
    ref, ref_emit = execute(values, sidx, bidx, bts, group_ids, spec,
                            ro)
    mesh = make_mesh(*mesh_shape)
    got, got_emit = execute_blocked_sharded(
        mesh, values, sidx, bidx, bts, group_ids, spec, ro,
        block_buckets=16)  # forces 3 blocks
    np.testing.assert_array_equal(got_emit, ref_emit)
    if agg.startswith("p"):
        assert np.array_equal(np.isnan(got), np.isnan(ref))
        rng_ = values.max() - values.min() + 1e-9
        m = ~np.isnan(ref)
        assert np.max(np.abs(got[m] - ref[m])) <= 2 * rng_ / \
            PERCENTILE_BINS
    else:
        np.testing.assert_allclose(got, ref, rtol=1e-9,
                                   equal_nan=True)


@pytest.mark.parametrize("mesh_shape", MESHES)
def test_rate_across_time_blocks(mesh_shape):
    """Rate carries must cross time-shard boundaries exactly."""
    spec = PipelineSpec(num_series=12, num_buckets=32, num_groups=2,
                        ds_function="avg", agg_name="sum", rate=True)
    compare(mesh_shape, spec, 12, seed=7, points_per=10,
            rate_options=RateOptions())


@pytest.mark.parametrize("mesh_shape", [(1, 8), (2, 4)])
def test_lerp_across_time_blocks(mesh_shape):
    """Sparse series whose gaps span several time shards must lerp
    identically to single-chip."""
    spec = PipelineSpec(num_series=6, num_buckets=64, num_groups=1,
                        ds_function="sum", agg_name="sum")
    # very sparse: 4 points per series over 64 buckets -> long gaps
    compare(mesh_shape, spec, 6, seed=11, points_per=4)


@pytest.mark.parametrize("agg, rate", [("sum", False), ("pfsum", False),
                                       ("mimmax", False), ("sum", True)])
def test_hole_straddles_a_time_shard_edge(agg, rate):
    """Series 0 has values at buckets 6 and 9 alone on four time
    shards of 4 buckets: the hole 7-8 lies on both sides of the edge
    7|8 (PR 49: the shard's own sweep ends inside it, the boundary
    carry finishes it); the other series are complete."""
    num_series, b, mesh_shape = 4, 16, (2, 4)
    rows = [(0, 6, 30.0), (0, 9, 90.0)]
    for s in range(1, num_series):
        rows += [(s, bb, float(100 * s + 3 * bb)) for bb in range(b)]
    arr = np.asarray(rows)
    values = arr[:, 2].astype(np.float64)
    sidx, bidx = arr[:, 0].astype(np.int32), arr[:, 1].astype(np.int32)
    bts = np.arange(b, dtype=np.int64) * 60_000
    gids = np.zeros(num_series, np.int32)
    spec = PipelineSpec(num_series=num_series, num_buckets=b,
                        num_groups=1, ds_function="sum", agg_name=agg,
                        rate=rate)
    ro = RateOptions() if rate else None
    ref, ref_emit = execute(values, sidx, bidx, bts, gids, spec, ro)
    batch = prepare_sharded_batch(values, sidx, bidx, bts, gids,
                                  num_series, 1, *mesh_shape)
    got, got_emit = run_sharded(make_mesh(*mesh_shape), spec, batch, ro)
    np.testing.assert_allclose(got, ref, rtol=1e-9, equal_nan=True)
    np.testing.assert_array_equal(got_emit, ref_emit)


@pytest.mark.parametrize("mesh_shape", [(4, 2)])
def test_counter_rate_sharded(mesh_shape):
    spec = PipelineSpec(num_series=8, num_buckets=16, num_groups=1,
                        ds_function="last", agg_name="sum", rate=True,
                        rate_counter=True)
    compare(mesh_shape, spec, 8, seed=3, points_per=12,
            rate_options=RateOptions(counter=True, counter_max=1e9))


def test_zero_fill_sharded():
    from opentsdb_tpu.ops.downsample import FillPolicy
    spec = PipelineSpec(num_series=8, num_buckets=24, num_groups=2,
                        ds_function="sum", agg_name="sum",
                        fill_policy=FillPolicy.ZERO)
    compare((2, 4), spec, 8, seed=5, points_per=6)


def test_uneven_series_count():
    """Series count not divisible by shard count exercises padding."""
    spec = PipelineSpec(num_series=13, num_buckets=17, num_groups=4,
                        ds_function="avg", agg_name="avg")
    compare((8, 1), spec, 13, seed=13, points_per=9)


@pytest.mark.parametrize("agg,expected", [("first", 101.0),
                                          ("last", 108.0),
                                          ("diff", 7.0)])
def test_series_order_preserved_across_shards(agg, expected):
    """Regression: first/last/diff pick by *global* series index.

    With a group of series {1, 8} on an (8,1) mesh, a shard-major
    gather would put series 8 before series 1 and invert first/last.
    Constant per-series values 100+s make the selection observable.
    """
    num_series, b = 16, 4
    values, sidx, bidx = [], [], []
    for s in range(num_series):
        for bk in range(b):
            values.append(100.0 + s)
            sidx.append(s)
            bidx.append(bk)
    values = np.asarray(values)
    sidx = np.asarray(sidx, dtype=np.int32)
    bidx = np.asarray(bidx, dtype=np.int32)
    bts = np.arange(b, dtype=np.int64) * 1000
    group_ids = np.zeros(num_series, dtype=np.int32)
    group_ids[1] = group_ids[8] = 1
    spec = PipelineSpec(num_series=num_series, num_buckets=b,
                        num_groups=2, ds_function="sum", agg_name=agg)
    mesh = make_mesh(8, 1)
    batch = prepare_sharded_batch(values, sidx, bidx, bts, group_ids,
                                  num_series, 2, 8, 1)
    got, _ = run_sharded(mesh, spec, batch)
    np.testing.assert_allclose(got[1], expected)
