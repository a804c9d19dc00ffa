"""Histogram / sketch pipeline tests.

Mirrors the reference suites ``test/core/TestSimpleHistogram.java``,
``TestHistogramCodecManager.java``, ``TestHistogramAggregation*.java``
and the histogram query routing of ``TestTsdbQueryHistogram*``
(ref: src/core/SimpleHistogram.java:43, HistogramCodecManager.java:47,
TsdbQuery.isHistogramQuery :776).
"""

import numpy as np
import pytest

from opentsdb_tpu.core.histogram import (HistogramCodecManager,
                                         SimpleHistogram,
                                         SimpleHistogramCodec)


def hist(bounds, counts, underflow=0, overflow=0):
    h = SimpleHistogram(bounds)
    h.counts = list(counts)
    h.underflow = underflow
    h.overflow = overflow
    return h


class TestSimpleHistogram:
    def test_add_routes_to_bucket(self):
        h = SimpleHistogram([0.0, 10.0, 20.0])
        h.add(5.0)
        h.add(15.0, count=3)
        assert h.counts == [1, 3]

    def test_add_under_over_flow(self):
        h = SimpleHistogram([0.0, 10.0])
        h.add(-1.0)
        h.add(10.0)   # hi edge is exclusive -> overflow
        h.add(99.0)
        assert h.underflow == 1 and h.overflow == 2

    def test_add_without_buckets_raises(self):
        with pytest.raises(ValueError):
            SimpleHistogram().add(1.0)

    def test_total_count(self):
        assert hist([0, 1, 2], [3, 4], 1, 2).total_count() == 10

    def test_percentile_midpoint_convention(self):
        # ref: SimpleHistogram.percentile :133 returns the midpoint of
        # the bucket whose cumulative count crosses the rank
        h = hist([0.0, 10.0, 20.0, 30.0], [10, 10, 10])
        assert h.percentile(10) == 5.0
        assert h.percentile(50) == 15.0
        assert h.percentile(95) == 25.0

    def test_percentile_overflow_returns_top_bound(self):
        h = hist([0.0, 10.0], [1], overflow=99)
        assert h.percentile(99) == 10.0

    def test_percentile_empty_is_zero(self):
        assert SimpleHistogram([0.0, 1.0]).percentile(50) == 0.0

    def test_percentile_validates_range(self):
        with pytest.raises(ValueError):
            hist([0, 1], [1]).percentile(101)

    def test_merge_bucket_wise_sum(self):
        a = hist([0.0, 1.0, 2.0], [1, 2], 1, 0)
        b = hist([0.0, 1.0, 2.0], [10, 20], 0, 5)
        a.merge(b)
        assert a.counts == [11, 22]
        assert a.underflow == 1 and a.overflow == 5

    def test_merge_mismatched_bounds_raises(self):
        a = hist([0.0, 1.0], [1])
        with pytest.raises(ValueError):
            a.merge(hist([0.0, 2.0], [1]))

    def test_merge_into_empty_adopts_bounds(self):
        a = SimpleHistogram()
        a.merge(hist([0.0, 1.0], [7]))
        assert a.bounds == [0.0, 1.0] and a.counts == [7]

    def test_set_bucket_append_and_prepend(self):
        h = SimpleHistogram()
        h.set_bucket(0.0, 1.0, 5)
        h.set_bucket(1.0, 2.0, 6)       # append adjacent
        h.set_bucket(-1.0, 0.0, 7)      # prepend adjacent
        assert h.bounds == [-1.0, 0.0, 1.0, 2.0]
        assert h.counts == [7, 5, 6]
        h.set_bucket(0.0, 1.0, 9)       # overwrite existing
        assert h.counts == [7, 9, 6]

    def test_set_bucket_overlap_raises(self):
        h = SimpleHistogram([0.0, 10.0])
        with pytest.raises(ValueError):
            h.set_bucket(5.0, 15.0, 1)

    def test_json_shape(self):
        js = hist([0.0, 1.0], [4], 1, 2).to_json()
        assert js == {"buckets": {"0.0,1.0": 4}, "underflow": 1,
                      "overflow": 2}


class TestCodec:
    def test_round_trip(self):
        h = hist([0.0, 1.5, 3.0], [5, 9], 2, 7)
        codec = SimpleHistogramCodec()
        blob = codec.encode(h, include_id=True)
        assert blob[0] == 0x01
        back = codec.decode(blob, includes_id=True)
        assert back.bounds == h.bounds
        assert back.counts == h.counts
        assert back.underflow == 2 and back.overflow == 7

    def test_manager_dispatch_by_leading_byte(self):
        mgr = HistogramCodecManager()
        h = hist([0.0, 1.0], [3])
        blob = mgr.encode(h, codec_id=1)
        assert mgr.decode(blob).counts == [3]

    def test_manager_unknown_codec(self):
        mgr = HistogramCodecManager()
        with pytest.raises(ValueError):
            mgr.decode(b"\x7fjunk")
        with pytest.raises(ValueError):
            mgr.decode(b"")

    def test_manager_config_registration(self):
        # ref: HistogramCodecManager.java:70 JSON id<->class config map
        from opentsdb_tpu.utils.config import Config
        cfg = Config(**{
            "tsd.core.histograms.config":
                '{"opentsdb_tpu.core.histogram.SimpleHistogramCodec": 2}',
        })
        mgr = HistogramCodecManager(cfg)
        h = hist([0.0, 1.0], [3])
        blob = mgr.encode(h, codec_id=2)
        assert blob[0] == 2
        assert mgr.decode(blob).counts == [3]


class TestDeviceKernels:
    """ops.histogram_kernels vs the host formulas (golden)."""

    @staticmethod
    def run_kernel(counts, seg, num_segments, bounds, qs, host=False):
        """``histogram_percentiles`` over rows that are one point
        each (one slot): the segment is the group. Returns (values
        [Q, segments], points [segments], widest)."""
        import jax.numpy as jnp
        from opentsdb_tpu.ops.histogram_kernels import (
            HistogramSpec, histogram_percentiles)
        n, nb = counts.shape
        spec = HistogramSpec(num_series=n, num_slots=1, num_buckets=1,
                             num_groups=num_segments, num_bins=nb,
                             host=host, merge_time=False)
        b = np.asarray(bounds, dtype=np.float64)
        values, points, widest = histogram_percentiles(
            jnp.asarray(counts, dtype=jnp.float32),
            jnp.ones((n, 1), dtype=jnp.float32),
            jnp.asarray(seg, dtype=jnp.int32),
            jnp.zeros(1, dtype=jnp.int32),
            jnp.asarray((b[:-1] + b[1:]) / 2.0, dtype=jnp.float32),
            jnp.asarray(np.asarray(qs) / 100.0, dtype=jnp.float32),
            spec)
        return (np.asarray(values)[:, :, 0], np.asarray(points)[:, 0],
                float(widest))

    @pytest.mark.parametrize("host", [False, True])
    def test_merge_matches_manual_sum(self, host):
        """The group merge: the q that lands in every bin in turn
        reads the merged counts back through the percentile."""
        rng = np.random.default_rng(0)
        counts = rng.integers(0, 50, (40, 8)).astype(np.float64)
        seg = rng.integers(0, 5, 40).astype(np.int32)
        gold = np.zeros((5, 8))
        for i, s in enumerate(seg):
            gold[s] += counts[i]
        from opentsdb_tpu.query.histogram_engine import \
            percentiles_from_counts
        bounds = np.arange(9.0)
        qs = [1.0, 10.0, 25.0, 50.0, 75.0, 90.0, 99.0, 100.0]
        got, points, widest = self.run_kernel(counts, seg, 5, bounds,
                                              qs, host=host)
        np.testing.assert_allclose(
            got, percentiles_from_counts(gold, bounds, qs))
        np.testing.assert_array_equal(points,
                                      np.bincount(seg, minlength=5))
        assert widest == gold.sum(axis=1).max()

    def test_percentiles_match_host_path(self):
        from opentsdb_tpu.query.histogram_engine import \
            percentiles_from_counts
        rng = np.random.default_rng(1)
        counts = rng.integers(0, 100, (7, 6)).astype(np.float64)
        counts[3] = 0  # an empty segment
        bounds = np.asarray([0.0, 1, 2, 4, 8, 16, 32])
        qs = [50.0, 95.0, 99.9]
        gold = percentiles_from_counts(counts, bounds, qs)
        got, _points, _widest = self.run_kernel(
            counts, np.arange(7, dtype=np.int32), 7, bounds, qs)
        np.testing.assert_allclose(got, gold, rtol=1e-6)

    def test_time_merge_and_dummies(self):
        """Slots merge into their downsample bucket, and what lands
        on the dummy group or the dummy bucket is in no answer."""
        import jax.numpy as jnp
        from opentsdb_tpu.ops.histogram_kernels import (
            HistogramSpec, histogram_percentiles)
        from opentsdb_tpu.query.histogram_engine import \
            percentiles_from_counts
        rng = np.random.default_rng(2)
        s, p, nb, g, t = 6, 4, 5, 3, 3
        counts = rng.integers(0, 30, (s, p, nb)).astype(np.float32)
        present = np.ones((s, p), dtype=np.float32)
        present[2, 1] = 0
        counts[2, 1] = 0
        labels = np.array([0, 1, 0, 2, 1, 2], dtype=np.int32)
        slot_bucket = np.array([0, 0, 1, 2], dtype=np.int32)
        bounds = np.arange(nb + 1.0)
        qs = [50.0, 99.0]
        spec = HistogramSpec(s, p, t, g, nb)
        values, points, _w = histogram_percentiles(
            jnp.asarray(counts.reshape(s, p * nb)),
            jnp.asarray(present), jnp.asarray(labels),
            jnp.asarray(slot_bucket),
            jnp.asarray((bounds[:-1] + bounds[1:]) / 2.0,
                        dtype=jnp.float32),
            jnp.asarray(np.asarray(qs) / 100.0, dtype=jnp.float32),
            spec)
        gold = np.zeros((g, t, nb))
        n = np.zeros((g, t))
        for i in range(s):
            for j in range(p):
                gold[labels[i], slot_bucket[j]] += counts[i, j]
                n[labels[i], slot_bucket[j]] += present[i, j]
        np.testing.assert_array_equal(np.asarray(points), n)
        want = percentiles_from_counts(
            gold.reshape(g * t, nb), bounds, qs).reshape(2, g, t)
        # group 2 and bucket 2 stand for the dummies: whatever they
        # hold leaves the real cells as they are
        np.testing.assert_allclose(np.asarray(values), want)

    def test_groupby_query_uses_device_path(self, tsdb):
        from opentsdb_tpu.query.model import TSQuery
        bounds = [0.0, 10.0, 20.0, 30.0]
        for host, counts in (("a", [10, 0, 0]), ("b", [0, 0, 10])):
            blob = tsdb.histogram_manager.encode(hist(bounds, counts))
            tsdb.add_histogram_point("req.lat", 1356998400, blob,
                                     {"host": host})
        q = TSQuery.from_json({
            "start": 1356998000, "end": 1356999000,
            "queries": [{"aggregator": "sum", "metric": "req.lat",
                         "percentiles": [50.0],
                         "tags": {"host": "*"}}]})
        results = tsdb.execute_query(q.validate())
        by_host = {r.tags["host"]: dict(r.dps) for r in results}
        assert by_host["a"][1356998400000] == 5.0
        assert by_host["b"][1356998400000] == 25.0

    def test_mixed_bounds_falls_back(self, tsdb):
        from opentsdb_tpu.query.model import TSQuery
        b1 = tsdb.histogram_manager.encode(
            hist([0.0, 10.0, 20.0], [10, 0]))
        b2 = tsdb.histogram_manager.encode(
            hist([0.0, 5.0, 10.0], [0, 10]))
        tsdb.add_histogram_point("req.lat", 1356998400, b1,
                                 {"host": "a"})
        tsdb.add_histogram_point("req.lat", 1356998460, b2,
                                 {"host": "a"})
        q = TSQuery.from_json({
            "start": 1356998000, "end": 1356999000,
            "queries": [{"aggregator": "sum", "metric": "req.lat",
                         "percentiles": [50.0]}]})
        results = tsdb.execute_query(q.validate())
        dps = dict(results[0].dps)
        assert dps[1356998400000] == 5.0    # [0,10) midpoint
        assert dps[1356998460000] == 7.5    # [5,10) midpoint

    def test_add_histogram_batch(self, tsdb):
        """Batch twin of add_histogram_point: per-series UID
        amortization, per-point errors, good points land."""
        blob = tsdb.histogram_manager.encode(
            hist([0.0, 10.0, 20.0], [10, 0]))
        seen = []
        written, errors = tsdb.add_histogram_batch([
            ("hb.m", 1356998400, blob, {"host": "a"}),
            ("hb.m", 1356998460, blob, {"host": "a"}),
            ("hb.m", -5, blob, {"host": "a"}),         # bad ts
            ("hb.m", 1356998400, b"", {"host": "a"}),  # bad blob
            ("hb.m", 1356998400, blob, {}),            # no tags
            ("hb.m", 1356998520, blob, {"host": "b"}),
        ], on_error=lambda i, e: seen.append(i))
        assert written == 3
        assert len(errors) == 3 and sorted(seen) == [2, 3, 4]
        # a fully-invalid batch must not pollute the UID table or
        # create empty series (r4 review finding)
        w2, e2 = tsdb.add_histogram_batch(
            [("never.metric", -5, blob, {"h": "a"})])
        assert w2 == 0 and len(e2) == 1
        assert not tsdb.uids.metrics.has_name("never.metric")
        arena = tsdb._histogram_arenas[
            tsdb.uids.metrics.get_id("hb.m")]
        assert arena.total_points == 3
        from opentsdb_tpu.query.model import TSQuery
        r = tsdb.execute_query(TSQuery.from_json({
            "start": 1356998000, "end": 1356999000,
            "queries": [{"aggregator": "sum", "metric": "hb.m",
                         "percentiles": [50.0]}]}).validate())
        assert len(dict(r[0].dps)) == 3

    def test_batch_matches_per_point_results(self, tsdb):
        blob = tsdb.histogram_manager.encode(
            hist([0.0, 10.0], [4], underflow=1))
        tsdb.add_histogram_batch(
            [("bm.a", 1356998400 + i, blob, {"h": "x"})
             for i in range(5)])
        for i in range(5):
            tsdb.add_histogram_point("bm.b", 1356998400 + i, blob,
                                     {"h": "x"})
        from opentsdb_tpu.query.model import TSQuery

        def q(metric):
            return tsdb.execute_query(TSQuery.from_json({
                "start": 1356998000, "end": 1356999000,
                "queries": [{"aggregator": "sum", "metric": metric,
                             "percentiles": [95.0]}]}).validate())

        assert [v for _, v in q("bm.a")[0].dps] == \
            [v for _, v in q("bm.b")[0].dps]

    def test_arena_growth_and_snapshot_stability(self):
        """Snapshots captured before a growth-resize must stay valid:
        np.resize REPLACES the arrays, so earlier views keep their
        [0, n) contents (the lock-free read contract)."""
        from opentsdb_tpu.core.histogram import (HistogramArena,
                                                 SimpleHistogram)
        arena = HistogramArena()
        h = SimpleHistogram([0.0, 1.0, 2.0])
        h.counts = [1, 2]
        for i in range(10):
            arena.append(i, i % 3, h)
        (sub,) = arena.groups.values()
        ts0, sid0, rows0 = sub.snapshot()
        # force growth past the initial capacity
        for i in range(3000):
            arena.append(100 + i, 0, h)
        np.testing.assert_array_equal(ts0, np.arange(10))
        np.testing.assert_array_equal(sid0, np.arange(10) % 3)
        np.testing.assert_array_equal(rows0, [[1.0, 2.0]] * 10)
        assert arena.total_points == 3010
        ts1, _, rows1 = sub.snapshot()
        assert len(ts1) == 3010 and rows1.shape == (3010, 2)

    def test_arena_preserves_underflow_overflow(self, tsdb, tmp_path):
        """under/overflow counters survive the columnar snapshot
        round trip (the v1 object store preserved them; v2 must too).
        """
        from opentsdb_tpu import TSDB, Config
        cfg = {"tsd.core.auto_create_metrics": "true",
               "tsd.storage.data_dir": str(tmp_path)}
        t = TSDB(Config(**cfg))
        blob = t.histogram_manager.encode(
            hist([0.0, 10.0], [5], underflow=7, overflow=9))
        t.add_histogram_point("uo.m", 1356998400, blob, {"h": "a"})
        t.flush()
        t2 = TSDB(Config(**cfg))
        (arena,) = t2._histogram_arenas.values()
        (sub,) = arena.groups.values()
        assert sub.under[0] == 7 and sub.over[0] == 9

    def test_uniform_window_keeps_device_path(self, tsdb):
        """A stray historic bounds class outside the window must NOT
        route a bounds-uniform window to the host fallback (r4 review:
        one bounds migration would otherwise disable the device path
        for every future query)."""
        from opentsdb_tpu.query.model import TSQuery
        old = tsdb.histogram_manager.encode(
            hist([0.0, 5.0, 10.0], [3, 3]))
        tsdb.add_histogram_point("u.lat", 1356990000, old,
                                 {"host": "a"})
        for i in range(3):
            blob = tsdb.histogram_manager.encode(
                hist([0.0, 10.0, 20.0], [10, 0]))
            tsdb.add_histogram_point("u.lat", 1356998400 + i * 60,
                                     blob, {"host": "a"})
        q = TSQuery.from_json({
            "start": 1356998000, "end": 1356999000,
            "queries": [{"aggregator": "sum", "metric": "u.lat",
                         "percentiles": [50.0]}]})
        results = tsdb.execute_query(q.validate())
        dps = dict(results[0].dps)
        assert len(dps) == 3
        assert all(v == 5.0 for v in dps.values())
        # the full span INCLUDING the old bounds class still answers
        # (host merge path, per-slot bounds)
        q2 = TSQuery.from_json({
            "start": 1356980000, "end": 1356999000,
            "queries": [{"aggregator": "sum", "metric": "u.lat",
                         "percentiles": [50.0]}]})
        r2 = tsdb.execute_query(q2.validate())
        assert len(dict(r2[0].dps)) == 4


# ---------------------------------------------------------------------------
# write + query path (ref: TestTsdbQueryHistogram*: /api/histogram
# ingest, percentile extraction routed via TSSubQuery.percentiles)
# ---------------------------------------------------------------------------

class TestHistogramQueryPath:
    BOUNDS = [0.0, 10.0, 20.0, 30.0]

    def seed(self, tsdb):
        for i, counts in enumerate(([10, 0, 0], [0, 10, 0])):
            blob = tsdb.histogram_manager.encode(hist(self.BOUNDS, counts))
            tsdb.add_histogram_point(
                "req.latency", 1356998400 + i * 60, blob,
                {"host": "web01"})

    def test_add_and_query_percentile(self, tsdb):
        from opentsdb_tpu.query.model import TSQuery
        self.seed(tsdb)
        q = TSQuery.from_json({
            "start": 1356998000, "end": 1356999000,
            "queries": [{"aggregator": "sum", "metric": "req.latency",
                         "percentiles": [50.0]}],
        })
        results = tsdb.execute_query(q.validate())
        assert len(results) == 1
        dps = dict(results[0].dps)
        # dp1: all mass in [0,10) -> p50 midpoint 5; dp2: [10,20) -> 15
        assert dps[1356998400000] == 5.0
        assert dps[1356998460000] == 15.0

    def test_histogram_merge_across_series(self, tsdb):
        from opentsdb_tpu.query.model import TSQuery
        h1 = tsdb.histogram_manager.encode(hist(self.BOUNDS, [10, 0, 0]))
        h2 = tsdb.histogram_manager.encode(hist(self.BOUNDS, [0, 0, 10]))
        tsdb.add_histogram_point("req.latency", 1356998400, h1,
                                 {"host": "a"})
        tsdb.add_histogram_point("req.latency", 1356998400, h2,
                                 {"host": "b"})
        q = TSQuery.from_json({
            "start": 1356998000, "end": 1356999000,
            "queries": [{"aggregator": "sum", "metric": "req.latency",
                         "percentiles": [50.0, 99.0]}],
        })
        results = tsdb.execute_query(q.validate())
        # one output series per requested percentile
        by_pct = {r.tags.get("_percentile") or r.metric: dict(r.dps)
                  for r in results}
        assert len(results) == 2
        # merged: 10 in [0,10) + 10 in [20,30): p50 -> 5.0, p99 -> 25.0
        vals = sorted(v[1356998400000] for v in by_pct.values())
        assert vals == [5.0, 25.0]


class TestHistogramDownsample:
    """``percentiles`` + ``downsample`` (ref: HistogramDownsampler.java
    wrapping each span before the HistogramSpanGroup merge — merge is
    bucket-wise SUM across both time and series)."""

    BOUNDS = [0.0, 10.0, 20.0, 30.0]
    BASE = 1356998400

    def _put(self, tsdb, ts_s, counts, host="web01"):
        blob = tsdb.histogram_manager.encode(hist(self.BOUNDS, counts))
        tsdb.add_histogram_point("req.latency", ts_s, blob,
                                 {"host": host})

    def test_downsample_merges_within_bucket(self, tsdb):
        from opentsdb_tpu.query.model import TSQuery
        # two points inside one 5m bucket, one in the next
        self._put(tsdb, self.BASE, [10, 0, 0])
        self._put(tsdb, self.BASE + 60, [0, 0, 10])
        self._put(tsdb, self.BASE + 300, [0, 10, 0])
        q = TSQuery.from_json({
            "start": self.BASE - 100, "end": self.BASE + 900,
            "queries": [{"aggregator": "sum", "metric": "req.latency",
                         "downsample": "5m-sum",
                         "percentiles": [50.0]}],
        })
        results = tsdb.execute_query(q.validate())
        assert len(results) == 1
        dps = dict(results[0].dps)
        assert len(dps) == 2
        # bucket 1 merged: 10@[0,10) + 10@[20,30): p50 -> 5.0 (rank 10
        # crosses in the first bucket); bucket 2: [10,20) -> 15
        b1 = (self.BASE - (self.BASE % 300)) * 1000
        assert dps[b1] == 5.0
        assert dps[b1 + 300_000] == 15.0

    def test_downsample_matches_per_point_oracle(self, tsdb):
        """Irregular data: device path == SimpleHistogram merge+
        percentile done per bucket by hand."""
        import numpy as np
        from opentsdb_tpu.query.model import TSQuery
        rng = np.random.default_rng(7)
        pts = []
        for host in ("a", "b"):
            for _ in range(40):
                ts = self.BASE + int(rng.integers(0, 1800))
                counts = rng.integers(0, 20, 3).tolist()
                pts.append((ts, counts))
                self._put(tsdb, ts, counts, host=host)
        q = TSQuery.from_json({
            "start": self.BASE - 100, "end": self.BASE + 2000,
            "queries": [{"aggregator": "sum", "metric": "req.latency",
                         "downsample": "5m-sum",
                         "percentiles": [50.0, 95.0]}],
        })
        results = tsdb.execute_query(q.validate())
        assert len(results) == 2
        # oracle: SimpleHistogram merge per 5m bucket, then percentile
        buckets: dict[int, "SimpleHistogram"] = {}
        for ts, counts in pts:
            b = (ts * 1000) // 300_000 * 300_000
            h = buckets.setdefault(b, hist(self.BOUNDS, [0, 0, 0]))
            h.merge(hist(self.BOUNDS, counts))
        for r in results:
            qv = 50.0 if r.metric.endswith("50") else 95.0
            dps = dict(r.dps)
            assert set(dps) == set(buckets)
            for b, h in buckets.items():
                assert dps[b] == h.percentile(qv), (qv, b)

    def test_downsample_mixed_bounds_fallback(self, tsdb):
        """Bounds that differ across buckets but agree within one."""
        from opentsdb_tpu.query.model import TSQuery
        self._put(tsdb, self.BASE, [10, 0, 0])
        blob = tsdb.histogram_manager.encode(
            hist([0.0, 4.0, 8.0], [0, 10]))
        tsdb.add_histogram_point("req.latency", self.BASE + 300, blob,
                                 {"host": "web01"})
        q = TSQuery.from_json({
            "start": self.BASE - 100, "end": self.BASE + 900,
            "queries": [{"aggregator": "sum", "metric": "req.latency",
                         "downsample": "5m-sum",
                         "percentiles": [50.0]}],
        })
        results = tsdb.execute_query(q.validate())
        dps = dict(results[0].dps)
        b1 = (self.BASE - (self.BASE % 300)) * 1000
        assert dps[b1] == 5.0          # [0,10) midpoint
        assert dps[b1 + 300_000] == 6.0  # [4,8) midpoint
