"""Time-blocked streaming executor golden tests: block-by-block
execution must be bit-identical to the materialize-everything pipeline
for every interpolation mode, including carries across block edges
(the single-chip twin of the sharded time-axis tests)."""

import numpy as np
import pytest

from opentsdb_tpu.ops.blocked import execute_blocked, pick_block_buckets
from opentsdb_tpu.ops.downsample import FillPolicy
from opentsdb_tpu.ops.pipeline import PipelineSpec, execute
from opentsdb_tpu.ops.rate import RateOptions


def sparse_batch(s=6, b=24, seed=0, density=0.5):
    """Irregular data with real holes so interpolation carries must
    cross block edges."""
    rng = np.random.default_rng(seed)
    values, sidx, bidx = [], [], []
    for i in range(s):
        present = rng.random(b) < density
        present[rng.integers(0, b)] = True  # at least one point
        for j in np.nonzero(present)[0]:
            values.append(rng.normal(100.0, 20.0))
            sidx.append(i)
            bidx.append(j)
    bts = np.arange(b, dtype=np.int64) * 60_000 + 1_356_998_400_000
    return (np.asarray(values), np.asarray(sidx, np.int32),
            np.asarray(bidx, np.int32), bts)


def _compare(spec, rate_options=None, block_buckets=5, seed=0,
             density=0.5):
    values, sidx, bidx, bts = sparse_batch(
        s=spec.num_series, b=spec.num_buckets, seed=seed,
        density=density)
    gids = (np.arange(spec.num_series) % spec.num_groups) \
        .astype(np.int32)
    ref, ref_emit = execute(values, sidx, bidx, bts, gids, spec,
                            rate_options)
    got, got_emit = execute_blocked(values, sidx, bidx, bts, gids, spec,
                                    rate_options,
                                    block_buckets=block_buckets)
    np.testing.assert_allclose(got, ref, rtol=1e-9, equal_nan=True)
    np.testing.assert_array_equal(got_emit, ref_emit)


@pytest.mark.parametrize("agg", ["sum", "avg", "zimsum", "pfsum",
                                 "mimmin", "mimmax", "dev", "p95",
                                 "median"])
def test_blocked_matches_full_over_aggs(agg):
    spec = PipelineSpec(num_series=6, num_buckets=24, num_groups=2,
                        ds_function="avg", agg_name=agg)
    _compare(spec, seed=3)


@pytest.mark.parametrize("counter", [False, True])
def test_blocked_rate_carries(counter):
    spec = PipelineSpec(num_series=5, num_buckets=21, num_groups=2,
                        ds_function="sum", agg_name="sum", rate=True,
                        rate_counter=counter)
    _compare(spec, rate_options=RateOptions(counter=counter),
             block_buckets=4, seed=7)


def test_blocked_fill_policies():
    for policy, fv in ((FillPolicy.ZERO, 0.0),
                       (FillPolicy.SCALAR, 42.0),
                       (FillPolicy.NOT_A_NUMBER, float("nan"))):
        spec = PipelineSpec(num_series=4, num_buckets=18, num_groups=2,
                            ds_function="avg", agg_name="sum",
                            fill_policy=policy, fill_value=fv)
        _compare(spec, block_buckets=7, seed=11)


def test_blocked_very_sparse_cross_block_lerp():
    """A series with single points many blocks apart: LERP must bridge
    several empty blocks in both directions."""
    spec = PipelineSpec(num_series=3, num_buckets=30, num_groups=1,
                        ds_function="sum", agg_name="sum")
    _compare(spec, block_buckets=3, seed=5, density=0.08)


@pytest.mark.parametrize("agg, rate", [("sum", False), ("pfsum", False),
                                       ("mimmax", False), ("sum", True)])
def test_hole_straddles_a_block_edge(agg, rate):
    """Series 0 has values at buckets 3 and 8 alone and blocks are 5
    buckets: the hole 4-7 lies on both sides of the edge 4|5, so the
    block's own sweep ends inside it and the boundary carry has to
    finish it (lerp, prev, the extremes' range, the rate's previous
    point); series 1 is complete."""
    b = 10
    values = np.asarray([30.0, 80.0] + [100.0 + 7 * j for j in range(b)])
    sidx = np.asarray([0, 0] + [1] * b, np.int32)
    bidx = np.asarray([3, 8] + list(range(b)), np.int32)
    bts = np.arange(b, dtype=np.int64) * 60_000 + 1_356_998_400_000
    gids = np.zeros(2, np.int32)
    spec = PipelineSpec(num_series=2, num_buckets=b, num_groups=1,
                        ds_function="sum", agg_name=agg, rate=rate)
    ro = RateOptions() if rate else None
    ref, ref_emit = execute(values, sidx, bidx, bts, gids, spec, ro)
    got, got_emit = execute_blocked(values, sidx, bidx, bts, gids, spec,
                                    ro, block_buckets=5)
    np.testing.assert_array_equal(got, ref)
    np.testing.assert_array_equal(got_emit, ref_emit)
    if agg == "sum" and not rate:
        # 30 + (80 - 30) * 2/5 at bucket 5, beside series 1's 135
        assert got[0, 5] == 50.0 + 135.0


def test_block_size_one():
    spec = PipelineSpec(num_series=4, num_buckets=10, num_groups=2,
                        ds_function="avg", agg_name="avg", rate=True)
    _compare(spec, rate_options=RateOptions(), block_buckets=1, seed=9)


def test_pick_block_buckets():
    assert pick_block_buckets(1_000_000, 10_000, 1 << 26) == 67
    assert pick_block_buckets(10, 100) == 100  # fits entirely
    assert pick_block_buckets(1 << 30, 100) == 1  # floor at 1
