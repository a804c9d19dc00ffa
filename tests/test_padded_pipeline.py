"""Padded (scatter-free) pipeline tests.

The row-padded layout (PaddedBatch) is the TPU-preferred materialization
for irregular data: bucketization contracts the point axis on the MXU
instead of scattering. These tests pin the padded kernel to the flat
scatter kernel (golden equivalence) and the engine's path selection.
"""

import numpy as np
import pytest

from opentsdb_tpu.ops import downsample as ds_mod
from opentsdb_tpu.ops.pipeline import (PipelineSpec, detect_regular_padded,
                                       flatten_padded, prepare_auto,
                                       run_prepared)
from opentsdb_tpu.query.model import TSQuery


def make_padded(seed=0, s=13, pmax=17, b=5, frac_pad=0.4):
    """Irregular padded batch + its flat equivalent."""
    rng = np.random.default_rng(seed)
    counts = rng.integers(1, pmax + 1, size=s).astype(np.int64)
    values2d = np.full((s, pmax), np.nan)
    bidx2d = np.full((s, pmax), -1, dtype=np.int32)
    for i in range(s):
        n = counts[i]
        values2d[i, :n] = rng.normal(100, 10, n)
        bidx2d[i, :n] = np.sort(rng.integers(0, b, n)).astype(np.int32)
    return values2d, bidx2d, counts


ALL_PADDED_FNS = sorted(ds_mod.PADDED_FNS)


class TestBucketizePadded:
    @pytest.mark.parametrize("fn", ALL_PADDED_FNS)
    def test_matches_flat_bucketize(self, fn):
        s, b = 13, 5
        values2d, bidx2d, counts = make_padded(s=s, b=b)
        vals, sidx, bidx = flatten_padded(values2d, bidx2d, counts)
        import jax.numpy as jnp
        gold, gold_cnt = ds_mod.bucketize(
            jnp.asarray(vals), jnp.asarray(sidx), jnp.asarray(bidx),
            s, b, fn)
        got, got_cnt = ds_mod.bucketize_padded(
            jnp.asarray(values2d), jnp.asarray(bidx2d), b, fn)
        np.testing.assert_allclose(np.asarray(got), np.asarray(gold),
                                   rtol=1e-9, atol=1e-9, equal_nan=True)
        np.testing.assert_allclose(np.asarray(got_cnt),
                                   np.asarray(gold_cnt))

    def test_stored_nan_values_are_skipped(self):
        import jax.numpy as jnp
        values2d = np.array([[1.0, np.nan, 3.0]])
        bidx2d = np.array([[0, 0, 1]], dtype=np.int32)
        grid, cnt = ds_mod.bucketize_padded(
            jnp.asarray(values2d), jnp.asarray(bidx2d), 2, "sum")
        assert np.asarray(grid)[0, 0] == 1.0
        assert np.asarray(cnt)[0, 0] == 1

    def test_padded_supported_matrix(self):
        assert ds_mod.padded_supported("sum", 10_000)
        assert ds_mod.padded_supported("min", 10_000)
        assert not ds_mod.padded_supported("p99", 4)
        assert not ds_mod.padded_supported("median", 4)


class TestDetectRegularPadded:
    def test_regular(self):
        counts = np.full(3, 6, dtype=np.int64)
        bidx = np.tile(np.repeat(np.arange(3, dtype=np.int32), 2), (3, 1))
        assert detect_regular_padded(counts, bidx, 3) == 2

    def test_ragged_counts(self):
        counts = np.asarray([6, 5, 6], dtype=np.int64)
        bidx = np.tile(np.repeat(np.arange(3, dtype=np.int32), 2), (3, 1))
        assert detect_regular_padded(counts, bidx, 3) is None

    def test_mismatched_pattern(self):
        counts = np.full(2, 4, dtype=np.int64)
        bidx = np.asarray([[0, 0, 1, 1], [0, 1, 1, 1]], dtype=np.int32)
        assert detect_regular_padded(counts, bidx, 2) is None


class TestPrepareAutoEquivalence:
    @pytest.mark.parametrize("agg,fn,rate", [
        ("sum", "avg", False), ("max", "sum", True),
        ("avg", "min", False), ("dev", "count", False),
    ])
    def test_padded_vs_flat(self, agg, fn, rate):
        from opentsdb_tpu.core.store import PaddedBatch
        from opentsdb_tpu.ops.pipeline import execute
        s, b, g = 11, 6, 3
        values2d, bidx2d, counts = make_padded(s=s, b=b, pmax=12)
        bucket_ts = np.arange(b, dtype=np.int64) * 60_000
        gids = (np.arange(s) % g).astype(np.int32)
        spec = PipelineSpec(num_series=s, num_buckets=b, num_groups=g,
                            ds_function=fn, agg_name=agg, rate=rate)
        padded = PaddedBatch(np.arange(s, dtype=np.int64), values2d,
                             np.zeros_like(values2d, dtype=np.int64),
                             counts)
        prep = prepare_auto(padded, bidx2d, spec)
        assert prep.kind == "padded"
        got, got_emit = run_prepared(prep, bucket_ts, gids, spec)
        vals, sidx, bidx = flatten_padded(values2d, bidx2d, counts)
        gold, gold_emit = execute(vals, sidx, bidx, bucket_ts, gids,
                                  spec)
        np.testing.assert_allclose(got, gold, rtol=1e-9, atol=1e-12,
                                   equal_nan=True)
        np.testing.assert_array_equal(got_emit, gold_emit)


class TestSkewGuard:
    def test_count_range(self, seeded_tsdb):
        mid = seeded_tsdb.uids.metrics.get_id("sys.cpu.user")
        sids = seeded_tsdb.store.series_ids_for_metric(mid)
        counts = seeded_tsdb.store.count_range(
            sids, 1356998400_000, 1356998400_000 + 3_000_000)
        assert list(counts) == [300, 300]

    def test_skewed_batch_stays_flat(self, monkeypatch):
        """One dense series among many sparse ones must not trigger the
        quadratic padded materialization. (Runs with the storage-side
        grid pre-reduction off — the skew guard belongs to the
        point-batch paths.)"""
        from opentsdb_tpu import TSDB, Config
        tsdb = TSDB(Config(**{"tsd.core.auto_create_metrics": "true",
                              "tsd.query.grid_reduce": "false",
                              # materialize must run on every query for
                              # the call-counting below: keep no batch
                              # resident and answer none from the
                              # result cache
                              "tsd.query.device_cache_mb": "0",
                              "tsd.query.cache.enable": "false"}))
        base = 1356998400
        for i in range(2000):
            tsdb.add_point("m", base + i, float(i), {"host": "big"})
        for h in range(40):
            tsdb.add_point("m", base, 1.0, {"host": f"s{h:02d}"})
        calls = {"padded": 0, "flat": 0}
        orig_p = tsdb.store.materialize_padded
        orig_f = tsdb.store.materialize
        monkeypatch.setattr(
            tsdb.store, "materialize_padded",
            lambda *a, **k: (calls.__setitem__(
                "padded", calls["padded"] + 1) or orig_p(*a, **k)))
        monkeypatch.setattr(
            tsdb.store, "materialize",
            lambda *a, **k: (calls.__setitem__(
                "flat", calls["flat"] + 1) or orig_f(*a, **k)))
        # 41 series x Pmax 2000 = 82k cells vs 2040 points -> skewed
        # (guard: cells > 4*total and > 1e7? here cells < 1e7 so padded
        # is still fine -- force the threshold down to exercise the path)
        from opentsdb_tpu.query import engine as engine_mod
        q = TSQuery.from_json({
            "start": base - 10, "end": base + 3000,
            "queries": [{"aggregator": "sum", "metric": "m",
                         "downsample": "60s-sum"}]}).validate()
        res = tsdb.execute_query(q)
        assert res and calls["padded"] == 1   # small batch: padded ok
        # now shrink the absolute cell allowance to force flat
        monkeypatch.setattr(engine_mod, "_PADDED_ABS_MAX_CELLS", 1_000)
        res2 = tsdb.execute_query(q)
        assert calls["flat"] == 1
        # identical results either way
        assert dict(res[0].dps) == dict(res2[0].dps)


class TestEngineIrregular:
    def test_irregular_series_query_end_to_end(self, tsdb):
        """Series with different point counts/phases (off the dense
        path) still produce exact results."""
        base = 1356998400
        # web01: every 10s; web02: every 15s offset by 5s, fewer points
        for i in range(60):
            tsdb.add_point("m", base + i * 10, 1.0, {"host": "web01"})
        for i in range(30):
            tsdb.add_point("m", base + 5 + i * 15, 2.0,
                           {"host": "web02"})
        q = TSQuery.from_json({
            "start": base - 10, "end": base + 700,
            "queries": [{"aggregator": "sum", "metric": "m",
                         "downsample": "1m-sum",
                         "tags": {"host": "*"}}]}).validate()
        res = tsdb.execute_query(q)
        by_host = {r.tags["host"]: dict(r.dps) for r in res}
        # web01: 6 pts/min * 1.0; web02: 4 pts/min * 2.0
        assert by_host["web01"][base * 1000] == 6.0
        assert by_host["web02"][base * 1000] == 8.0
