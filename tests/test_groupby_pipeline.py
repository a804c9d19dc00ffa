"""Group-by + fused pipeline tests
(ref: test/core/TestSpanGroup.java, TestTsdbQueryAggregators.java)."""

import jax.numpy as jnp
import numpy as np
import pytest

from opentsdb_tpu.ops import aggregators as aggs
from opentsdb_tpu.ops import groupby as gb
from opentsdb_tpu.ops.groupby import group_aggregate
from opentsdb_tpu.ops.pipeline import PipelineSpec, execute
from opentsdb_tpu.ops.downsample import FillPolicy
from opentsdb_tpu.ops.rate import RateOptions


def grid_of(*rows):
    return np.asarray(rows, dtype=np.float64)


class TestGroupAggregate:
    TS = np.arange(3) * 1000

    def test_sum_two_groups(self):
        g = grid_of([1.0, 2.0, 3.0], [10.0, 20.0, 30.0],
                    [100.0, 200.0, 300.0])
        gids = np.array([0, 0, 1], dtype=np.int32)
        out = np.asarray(group_aggregate(g, self.TS, gids, 2,
                                         aggs.get("sum")))
        np.testing.assert_allclose(out[0], [11.0, 22.0, 33.0])
        np.testing.assert_allclose(out[1], [100.0, 200.0, 300.0])

    def test_sum_lerp_interpolates(self):
        # series 1 missing the middle bucket: lerp fills 15
        g = grid_of([1.0, 2.0, 3.0], [10.0, np.nan, 20.0])
        gids = np.zeros(2, dtype=np.int32)
        out = np.asarray(group_aggregate(g, self.TS, gids, 1,
                                         aggs.get("sum")))
        np.testing.assert_allclose(out[0], [11.0, 17.0, 23.0])

    def test_zimsum_zero_fills(self):
        g = grid_of([1.0, 2.0, 3.0], [10.0, np.nan, 20.0])
        gids = np.zeros(2, dtype=np.int32)
        out = np.asarray(group_aggregate(g, self.TS, gids, 1,
                                         aggs.get("zimsum")))
        np.testing.assert_allclose(out[0], [11.0, 2.0, 23.0])

    def test_sum_edge_gaps_excluded(self):
        # series 1 starts late: before its first point it contributes 0
        g = grid_of([1.0, 2.0, 3.0], [np.nan, 5.0, 6.0])
        gids = np.zeros(2, dtype=np.int32)
        out = np.asarray(group_aggregate(g, self.TS, gids, 1,
                                         aggs.get("sum")))
        np.testing.assert_allclose(out[0], [1.0, 7.0, 9.0])

    def test_avg_divides_by_contributors(self):
        g = grid_of([10.0, 10.0, 10.0], [np.nan, 20.0, np.nan])
        gids = np.zeros(2, dtype=np.int32)
        out = np.asarray(group_aggregate(g, self.TS, gids, 1,
                                         aggs.get("avg")))
        # bucket 0: only s0 (10); bucket 1: (10+20)/2; bucket 2: only s0
        np.testing.assert_allclose(out[0], [10.0, 15.0, 10.0])

    def test_mimmin_ignores_missing(self):
        g = grid_of([5.0, 5.0, 5.0], [1.0, np.nan, 9.0])
        gids = np.zeros(2, dtype=np.int32)
        out = np.asarray(group_aggregate(g, self.TS, gids, 1,
                                         aggs.get("mimmin")))
        np.testing.assert_allclose(out[0], [1.0, 5.0, 5.0])

    def test_min_lerps_missing(self):
        g = grid_of([5.0, 5.0, 5.0], [1.0, np.nan, 9.0])
        gids = np.zeros(2, dtype=np.int32)
        out = np.asarray(group_aggregate(g, self.TS, gids, 1,
                                         aggs.get("min")))
        np.testing.assert_allclose(out[0], [1.0, 5.0, 5.0])

    def test_dev_group(self):
        g = grid_of([2.0], [4.0], [6.0], [8.0])
        gids = np.zeros(4, dtype=np.int32)
        out = np.asarray(group_aggregate(g, self.TS[:1], gids, 1,
                                         aggs.get("dev")))
        np.testing.assert_allclose(out[0, 0],
                                   np.std([2, 4, 6, 8]), rtol=1e-10)

    def test_percentile_group(self):
        vals = np.arange(1.0, 101.0)
        g = vals.reshape(100, 1)
        gids = np.zeros(100, dtype=np.int32)
        out = np.asarray(group_aggregate(g, self.TS[:1], gids, 1,
                                         aggs.get("p95")))
        np.testing.assert_allclose(out[0, 0], 95.95, rtol=1e-10)

    def test_percentile_two_groups(self):
        g = np.concatenate([np.arange(1.0, 11.0),
                            np.arange(100.0, 1100.0, 100.0)]).reshape(20, 1)
        gids = np.array([0] * 10 + [1] * 10, dtype=np.int32)
        out = np.asarray(group_aggregate(g, self.TS[:1], gids, 2,
                                         aggs.get("p50")))
        # LEGACY n=10: pos=5.5 -> 5 + 0.5*(6-5) = 5.5 / 550
        np.testing.assert_allclose(out[:, 0], [5.5, 550.0], rtol=1e-10)

    def test_median_group(self):
        g = grid_of([1.0], [9.0], [5.0], [7.0])
        gids = np.zeros(4, dtype=np.int32)
        out = np.asarray(group_aggregate(g, self.TS[:1], gids, 1,
                                         aggs.get("median")))
        assert out[0, 0] == 7.0  # upper median of 1,5,7,9

    def test_first_last_group(self):
        g = grid_of([np.nan, 2.0], [10.0, 20.0], [100.0, np.nan])
        gids = np.zeros(3, dtype=np.int32)
        first = np.asarray(group_aggregate(g, self.TS[:2], gids, 1,
                                           aggs.get("first")))
        last = np.asarray(group_aggregate(g, self.TS[:2], gids, 1,
                                          aggs.get("last")))
        # ZIM interpolation: holes become 0 before selection
        np.testing.assert_allclose(first[0], [0.0, 2.0])
        np.testing.assert_allclose(last[0], [100.0, 0.0])


class TestFusedPipeline:
    def make_batch(self):
        """2 series x 6 points @10s, bucketed to 30s (2 buckets)."""
        values = np.array([1, 2, 3, 4, 5, 6,
                           10, 20, 30, 40, 50, 60], dtype=np.float64)
        series_idx = np.array([0] * 6 + [1] * 6, dtype=np.int32)
        bucket_idx = np.array([0, 0, 0, 1, 1, 1] * 2, dtype=np.int32)
        bucket_ts = np.array([0, 30_000], dtype=np.int64)
        return values, series_idx, bucket_idx, bucket_ts

    def test_downsample_groupby_sum(self):
        values, sidx, bidx, bts = self.make_batch()
        spec = PipelineSpec(num_series=2, num_buckets=2, num_groups=1,
                            ds_function="avg", agg_name="sum")
        result, emit = execute(values, sidx, bidx, bts,
                               np.zeros(2, dtype=np.int32), spec)
        # s0 avg: [2, 5]; s1 avg: [20, 50] -> sum [22, 55]
        np.testing.assert_allclose(result[0], [22.0, 55.0])
        assert emit.all()

    def test_two_groups(self):
        values, sidx, bidx, bts = self.make_batch()
        spec = PipelineSpec(num_series=2, num_buckets=2, num_groups=2,
                            ds_function="sum", agg_name="max")
        result, _ = execute(values, sidx, bidx, bts,
                            np.array([0, 1], dtype=np.int32), spec)
        np.testing.assert_allclose(result[0], [6.0, 15.0])
        np.testing.assert_allclose(result[1], [60.0, 150.0])

    def test_rate_after_downsample(self):
        values, sidx, bidx, bts = self.make_batch()
        spec = PipelineSpec(num_series=2, num_buckets=2, num_groups=1,
                            ds_function="avg", agg_name="sum", rate=True)
        result, emit = execute(values, sidx, bidx, bts,
                               np.zeros(2, dtype=np.int32), spec,
                               RateOptions())
        # s0: (5-2)/30 = .1; s1: (50-20)/30 = 1 -> sum = 1.1
        assert not emit[0, 0]  # first bucket has no rate anywhere
        np.testing.assert_allclose(result[0, 1], 1.1)

    def test_emit_mask_union(self):
        values = np.array([1.0, 2.0])
        sidx = np.array([0, 1], dtype=np.int32)
        bidx = np.array([0, 2], dtype=np.int32)
        bts = np.array([0, 1000, 2000], dtype=np.int64)
        spec = PipelineSpec(num_series=2, num_buckets=3, num_groups=1,
                            ds_function="sum", agg_name="zimsum")
        result, emit = execute(values, sidx, bidx, bts,
                               np.zeros(2, dtype=np.int32), spec)
        np.testing.assert_array_equal(emit[0], [True, False, True])

    def test_zero_fill_emits_everything(self):
        values = np.array([1.0])
        sidx = np.array([0], dtype=np.int32)
        bidx = np.array([0], dtype=np.int32)
        bts = np.array([0, 1000], dtype=np.int64)
        spec = PipelineSpec(num_series=1, num_buckets=2, num_groups=1,
                            ds_function="sum", agg_name="sum",
                            fill_policy=FillPolicy.ZERO)
        result, emit = execute(values, sidx, bidx, bts,
                               np.zeros(1, dtype=np.int32), spec)
        np.testing.assert_allclose(result[0], [1.0, 0.0])
        assert emit.all()

    def test_emit_raw_series(self):
        values, sidx, bidx, bts = self.make_batch()
        spec = PipelineSpec(num_series=2, num_buckets=2, num_groups=2,
                            ds_function="avg", agg_name="none",
                            emit_raw=True)
        result, _ = execute(values, sidx, bidx, bts,
                            np.arange(2, dtype=np.int32), spec)
        np.testing.assert_allclose(result, [[2.0, 5.0], [20.0, 50.0]])


RANK_AGGS = ("median", "p50", "p95", "p99", "p999", "ep95r3", "ep99r7")


def _rank_grid(case: str):
    """(float32 grid [S, B], labels [S], groups) for one case of the
    selection against the sort. The last label is the dummy trailing
    group of padded and excluded rows, as the engine pads."""
    rng = np.random.default_rng(sum(map(ord, case)))
    s, b, g = 1003, 5, 7          # S no multiple of a tile or chunk
    vals = rng.normal(0.0, 50.0, (s, b)).astype(np.float32)
    gids = rng.integers(0, g - 1, s).astype(np.int32)
    if case == "mixed":
        special = np.array([-np.inf, np.inf, -0.0, 0.0, 1.5, 1.5, 1e-40,
                            -1e-40, 3e38, -3e38], np.float32)
        pick = rng.random((s, b)) < 0.4
        vals[pick] = rng.choice(special, int(pick.sum()))
        vals[rng.random((s, b)) < 0.1] = np.nan
    elif case == "ties":
        # every member of a group holds the same value in a bucket
        vals = (gids[:, None] * 10 + np.arange(b)[None, :]) \
            .astype(np.float32)
    elif case == "zeros":
        # both zeros in no order of rows: -0 < +0 in the sort's order
        vals = rng.choice(np.array([-0.0, 0.0, -1.0, 1.0], np.float32),
                          (s, b))
    elif case == "infs":
        vals = rng.choice(np.array([-np.inf, np.inf, 2.0], np.float32),
                          (s, b), p=[0.3, 0.3, 0.4])
        vals[gids == 2] = np.inf
    elif case == "missing":
        vals[rng.random((s, b)) < 0.1] = np.nan
        vals[gids == 1, 2] = np.nan     # a bucket a group has nothing in
        vals[gids == 4] = np.nan        # a group with no value at all
    elif case == "one_row":
        gids[gids == 3] = 2
        gids[17] = 3                    # a group of one row
        vals[17, 1] = np.nan            # and nothing of it in a bucket
    elif case == "emptied":
        gids[gids == 0] = g - 1         # the filter dropped a whole group
        gids[gids == 5] = g - 1
    elif case == "tiny":
        s = 3
        vals, gids = vals[:s], np.array([0, 0, g - 1], np.int32)
    else:
        raise AssertionError(case)
    return vals, gids, g


class TestRankBySelection:
    """The device's lowering of the rank group stage (a radix
    selection over one-hot counts) against the ``lax.sort`` body it
    stands in for: the same cells, bit for bit."""

    @pytest.mark.parametrize("agg", RANK_AGGS)
    @pytest.mark.parametrize("case", [
        "mixed", "ties", "zeros", "infs", "missing", "one_row",
        "emptied", "tiny"])
    def test_the_selection_is_the_sorts_bit_for_bit(self, case, agg):
        vals, gids, g = _rank_grid(case)
        assert gb.rank_lowering(len(vals), g, vals.dtype) == "select"
        picked = np.asarray(gb._group_reduce(
            jnp.asarray(vals), gids, g, agg))
        # a host-placed tail keeps the sort (and counts by scatter)
        sorted_ = np.asarray(gb._group_reduce(
            jnp.asarray(vals), gids, g, agg, prefer_segment=True))
        assert picked.dtype == sorted_.dtype == np.float32
        np.testing.assert_array_equal(picked.view(np.int32),
                                      sorted_.view(np.int32))
        if agg == "median" and case not in ("mixed", "infs"):
            # and both are NumPy's upper median of the valid cells
            for k in range(g):
                for j in range(vals.shape[1]):
                    cell = vals[gids == k, j]
                    cell = np.sort(cell[~np.isnan(cell)])
                    if len(cell):
                        assert picked[k, j] == cell[len(cell) // 2]
                    else:
                        assert np.isnan(picked[k, j])

    @pytest.mark.parametrize("refusal", ["prefer_segment", "float64",
                                         "over_budget", "groups",
                                         "rows"])
    def test_what_the_selection_cannot_count_is_sorted(
            self, refusal, monkeypatch):
        vals, gids, g = _rank_grid("missing")
        rows = {"prefer_segment": 1003, "float64": 1002,
                "over_budget": 1001, "groups": 998,
                "rows": 1000}[refusal]
        vals, gids = vals[:rows], gids[:rows]     # a trace of its own
        dtype = np.float64 if refusal == "float64" else np.float32
        if refusal == "over_budget":
            monkeypatch.setattr(gb, "_MATMUL_GROUP_MAX_ELEMS",
                                rows * g - 1)
        if refusal == "groups":
            g = gb._SELECT_MAX_GROUPS + 1     # most of them empty
            assert gb.rank_lowering(rows, g - 1, dtype) == "select"
        assert gb.rank_lowering(
            1 << 24 if refusal == "rows" else rows, g, dtype,
            refusal == "prefer_segment") == "sort"
        assert gb.rank_lowering(rows, g, np.float32) == (
            "sort" if refusal in ("over_budget", "groups") else "select")
        if refusal == "rows":
            return     # 2**24 rows: the predicate alone, no grid
        lowered = gb._group_reduce.lower(
            jnp.asarray(vals.astype(dtype)), gids, g, "p95",
            prefer_segment=refusal == "prefer_segment").as_text()
        assert "sort" in lowered and "while" not in lowered
        monkeypatch.undo()
        taken = gb._group_reduce.lower(     # the same grid, unrefused
            jnp.asarray(vals[:999]), gids[:999], 7, "p95").as_text()
        assert "while" in taken and "sort" not in taken
