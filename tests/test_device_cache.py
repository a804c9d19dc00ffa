"""Device-resident grid/batch cache (query.device_cache) and the
storage-side bucket pre-reduction: correctness of invalidation (a hit
must be bit-identical to a fresh scan) and backend equivalence."""

import threading
import time

import jax
import numpy as np
import pytest

from opentsdb_tpu import TSDB, Config
from opentsdb_tpu.obs import trace as trace_mod
from opentsdb_tpu.query import device_cache as dc_mod
from opentsdb_tpu.query import engine as engine_mod
from opentsdb_tpu.query.limits import QueryLimitExceeded
from opentsdb_tpu.query.model import TSQuery
from opentsdb_tpu.stats.stats import QueryStat, QueryStats

BASE = 1356998400


def _tsdb(**extra):
    # the small fixtures here would otherwise take the host-tail path,
    # which bypasses the device cache by design — disable it so these
    # tests keep pinning the cache machinery itself
    return TSDB(Config(**{"tsd.core.auto_create_metrics": "true",
                          "tsd.query.host_tail_max_cells": "-1",
                          "tsd.query.host_tail_max_cells_linear": "-1",
                          # warm repeats must actually REACH the
                          # device cache under test, not the serve-
                          # path result cache in front of it
                          "tsd.query.cache.enable": "false",
                          **extra}))


def _q(agg="sum", ds="1m-avg", start=BASE, end=BASE + 3000):
    return TSQuery.from_json({
        "start": start * 1000, "end": end * 1000,
        "queries": [{"metric": "m", "aggregator": agg,
                     "downsample": ds}]}).validate()


def _seed(t, n=5, pts=50):
    rng = np.random.default_rng(0)
    for i in range(n):
        ts = BASE + np.sort(rng.choice(3000, pts, replace=False))
        t.add_points("m", ts, rng.normal(10, 3, pts),
                     {"host": f"h{i}"})


class TestDeviceCacheInvalidation:
    def test_write_invalidates(self):
        t = _tsdb()
        _seed(t)
        r1 = t.execute_query(_q())
        r1b = t.execute_query(_q())        # warm hit
        assert [x.dps for x in r1] == [x.dps for x in r1b]
        cache = t.device_grid_cache
        assert cache.hits >= 1
        # a new point must change the answer (no stale grid)
        t.add_point("m", BASE + 10, 1000.0, {"host": "h0"})
        r2 = t.execute_query(_q())
        assert [x.dps for x in r2] != [x.dps for x in r1]

    def test_delete_invalidates(self):
        t = _tsdb()
        _seed(t)
        r1 = t.execute_query(_q())
        mid = t.uids.metrics.get_id("m")
        sids = t.store.series_ids_for_metric(mid)
        t.store.delete_range(sids, BASE * 1000, (BASE + 100) * 1000)
        r2 = t.execute_query(_q())
        assert [x.dps for x in r2] != [x.dps for x in r1]

    def test_union_grid_path_cached_and_invalidated(self):
        t = _tsdb()
        _seed(t)
        q = TSQuery.from_json({
            "start": BASE * 1000, "end": (BASE + 3000) * 1000,
            "queries": [{"metric": "m", "aggregator": "sum"}]}) \
            .validate()
        r1 = t.execute_query(q)
        r1b = t.execute_query(q)
        assert [x.dps for x in r1] == [x.dps for x in r1b]
        t.add_point("m", BASE + 7, 77.0, {"host": "h1"})
        r2 = t.execute_query(q)
        assert [x.dps for x in r2] != [x.dps for x in r1]

    def test_different_agg_reuses_prepared_batch(self):
        # the prepared-batch key excludes the aggregator: sum and max
        # over the same window share the upload
        t = _tsdb()
        _seed(t)
        q_sum = TSQuery.from_json({
            "start": BASE * 1000, "end": (BASE + 3000) * 1000,
            "queries": [{"metric": "m", "aggregator": "sum"}]}) \
            .validate()
        q_max = TSQuery.from_json({
            "start": BASE * 1000, "end": (BASE + 3000) * 1000,
            "queries": [{"metric": "m", "aggregator": "max"}]}) \
            .validate()
        t.execute_query(q_sum)
        h0 = t.device_grid_cache.hits
        t.execute_query(q_max)
        assert t.device_grid_cache.hits == h0 + 1

    def test_drop_caches_clears(self):
        t = _tsdb()
        _seed(t)
        t.execute_query(_q())
        t.drop_caches()
        m0 = t.device_grid_cache.misses
        t.execute_query(_q())
        assert t.device_grid_cache.misses > m0

    def test_disabled_by_config(self):
        t = _tsdb(**{"tsd.query.device_cache_mb": "0"})
        _seed(t)
        assert t.device_grid_cache is None
        r1 = t.execute_query(_q())
        assert r1 and r1[0].dps

    def test_cache_matches_uncached_results(self):
        a = _tsdb()
        b = _tsdb(**{"tsd.query.device_cache_mb": "0"})
        _seed(a)
        _seed(b)
        for agg, ds in (("sum", "1m-avg"), ("avg", "5m-max"),
                        ("max", "1m-count"), ("dev", "2m-min")):
            ra = a.execute_query(_q(agg, ds))
            ra2 = a.execute_query(_q(agg, ds))  # warm
            rb = b.execute_query(_q(agg, ds))
            assert [x.dps for x in ra] == [x.dps for x in rb]
            assert [x.dps for x in ra2] == [x.dps for x in rb]


class TestAvgRollupCache:
    def test_avg_tier_warm_matches_cold(self):
        t = _tsdb(**{"tsd.rollups.enable": "true"})
        for i in range(6):
            for j in range(30):
                ts = BASE + j * 60
                t.add_aggregate_point("m", ts, float(i + j),
                                      {"host": f"h{i}"}, False, "1m",
                                      "sum")
                t.add_aggregate_point("m", ts, 3.0, {"host": f"h{i}"},
                                      False, "1m", "count")
        q = _q("sum", "5m-avg", end=BASE + 1800)
        cold = t.execute_query(q)
        warm = t.execute_query(q)
        assert cold and [x.dps for x in cold] == [x.dps for x in warm]
        # more tier data invalidates
        t.add_aggregate_point("m", BASE, 500.0, {"host": "h0"}, False,
                              "1m", "sum")
        r3 = t.execute_query(q)
        assert [x.dps for x in r3] != [x.dps for x in cold]

    def test_avgdiv_key_uses_instance_id_not_address(self):
        """Regression: the avgdiv cache key must be built from the
        stores' monotonic instance_ids (_store_id), not id(store) —
        id() can alias a freed store whose address was reused with a
        coincidentally equal (points_written, mutation_epoch)."""
        t = _tsdb(**{"tsd.rollups.enable": "true"})
        for j in range(30):
            t.add_aggregate_point("m", BASE + j * 60, float(j),
                                  {"host": "h0"}, False, "1m", "sum")
            t.add_aggregate_point("m", BASE + j * 60, 3.0,
                                  {"host": "h0"}, False, "1m", "count")
        cache = t.device_grid_cache
        seen = []
        orig_get = cache.get

        def spy(key, version, *lookup):
            if key[0] == "avgdiv":
                seen.append(key)
            return orig_get(key, version, *lookup)

        cache.get = spy
        try:
            t.execute_query(_q("sum", "5m-avg", end=BASE + 1800))
        finally:
            cache.get = orig_get
        assert seen, "avg-tier query did not consult the avgdiv cache"
        sum_store = t.rollup_store.tier("1m", "sum")
        cnt_store = t.rollup_store.tier("1m", "count")
        assert seen[0][1] == sum_store.instance_id
        assert seen[0][2] == cnt_store.instance_id

    @pytest.mark.parametrize("hosts, whole", [
        ("h0|h1|h2|h3", True), ("h0|h1|h2", True), ("h0|h1", False),
        ("h4", False)])
    def test_the_pair_is_the_metrics_from_half_of_it_on(self, hosts,
                                                        whole):
        """PR 48: a device-placed tail over at least half of its
        metric reads the METRIC's tier pair, keyed by scalars (the
        stores, the metric, its series count, the window); under a
        half a pair of its own rows, keyed by their digest. Either
        way the same cells come back."""
        t = _tsdb(**{"tsd.rollups.enable": "true"})
        _seed_rollup(t)

        def ask():
            return t.execute_query(TSQuery.from_json({
                "start": BASE * 1000, "end": (BASE + 1800) * 1000,
                "queries": [{"metric": "m", "aggregator": "sum",
                             "downsample": "5m-avg", "filters": [{
                                 "type": "literal_or", "tagk": "host",
                                 "filter": hosts,
                                 "groupBy": False}]}]}).validate())

        got = ask()
        (key,) = t.device_grid_cache._entries
        assert key[0] == "avgdiv"
        if whole:
            metric_id = t.uids.metrics.get_id("m")
            assert key[3:5] == (metric_id, 6)
            assert not any(isinstance(k, bytes) for k in key)
        else:
            assert isinstance(key[3], bytes)
        assert ask()[0].dps == got[0].dps
        assert t.device_grid_cache.hits == 1
        bare = _tsdb(**{"tsd.rollups.enable": "true",
                        "tsd.query.device_cache_mb": "0"})
        _seed_rollup(bare)
        _same_answers(bare.execute_query(TSQuery.from_json({
            "start": BASE * 1000, "end": (BASE + 1800) * 1000,
            "queries": [{"metric": "m", "aggregator": "sum",
                         "downsample": "5m-avg", "filters": [{
                             "type": "literal_or", "tagk": "host",
                             "filter": hosts, "groupBy": False}]}]}
        ).validate()), got, False, 1e-6)


class TestTierHasData:
    def test_emptied_tier_stops_winning_selection(self):
        """A rollup tier whose points were all deleted must stop
        winning tier selection (points_written never decrements, so
        has_data must consult the mutation epoch)."""
        t = _tsdb(**{"tsd.rollups.enable": "true"})
        # raw data AND tier data
        _seed(t, n=2)
        for j in range(30):
            t.add_aggregate_point("m", BASE + j * 60, 42.0,
                                  {"host": "h0"}, False, "1m", "sum")
        q = _q("sum", "1m-sum")
        r1 = t.execute_query(q)
        assert r1
        # empty the tier by deleting its whole range
        store = t.rollup_store.tier("1m", "sum")
        sids = store.series_ids_for_metric(t.uids.metrics.get_id("m"))
        store.delete_range(sids, 0, 2 ** 60)
        assert not t.rollup_store.has_data("1m", "sum")
        # the query now answers from raw data instead of returning []
        r2 = t.execute_query(q)
        assert r2 and r2[0].dps
        # and new tier writes flip it back
        t.add_aggregate_point("m", BASE, 7.0, {"host": "h0"}, False,
                              "1m", "sum")
        assert t.rollup_store.has_data("1m", "sum")


class TestBucketReduceBackends:
    @pytest.mark.parametrize("backend", ["memory", "native"])
    def test_matches_manual(self, backend):
        t = _tsdb(**{"tsd.storage.backend": backend})
        rng = np.random.default_rng(1)
        ts = BASE * 1000 + np.sort(
            rng.choice(600_000, 200, replace=False)).astype(np.int64)
        vals = rng.normal(5, 2, 200)
        vals[7] = np.nan  # stored NaN must be skipped
        sid = t.add_points("m", ts // 1000 * 0 + ts, vals,
                           {"host": "a"})  # ms timestamps
        start, end = BASE * 1000, BASE * 1000 + 599_999
        t0, iv, nb = BASE * 1000, 60_000, 10
        sums, cnts, mins, maxs = t.store.bucket_reduce(
            [sid], start, end, t0, iv, nb, want_minmax=True)
        for b in range(nb):
            sel = (ts >= t0 + b * iv) & (ts < t0 + (b + 1) * iv) & \
                ~np.isnan(vals)
            assert cnts[0, b] == sel.sum()
            if sel.any():
                np.testing.assert_allclose(sums[0, b], vals[sel].sum())
                np.testing.assert_allclose(mins[0, b], vals[sel].min())
                np.testing.assert_allclose(maxs[0, b], vals[sel].max())


class TestCompactRowLabels:
    def test_matches_numpy_unique_axis0(self):
        from opentsdb_tpu.query.plan import compact_row_labels
        rng = np.random.default_rng(2)
        for cols in (1, 2, 4):
            mat = rng.integers(-1, 5, (300, cols)).astype(np.int64)
            labels, n = compact_row_labels(mat)
            uniq, inv = np.unique(mat, axis=0, return_inverse=True)
            assert n == len(uniq)
            np.testing.assert_array_equal(labels, inv)

    def test_empty(self):
        from opentsdb_tpu.query.plan import compact_row_labels
        labels, n = compact_row_labels(np.empty((0, 3), dtype=np.int64))
        assert n == 0 and len(labels) == 0
        labels, n = compact_row_labels(np.empty((4, 0), dtype=np.int64))
        assert n == 1 and list(labels) == [0, 0, 0, 0]


class TestMatchSeriesByTags:
    def test_alignment(self):
        from opentsdb_tpu.query.plan import _match_series_by_tags
        a = _tsdb()
        # two stores with the same metric/tag universe, different order
        s1, s2 = a.store, type(a.store)()
        mid = 1
        keys = [[(1, i)] for i in range(10)]
        sids1 = [s1.get_or_create_series(mid, k) for k in keys]
        sids2 = [s2.get_or_create_series(mid, k)
                 for k in reversed(keys)]
        out = _match_series_by_tags(
            s1, s2, np.asarray(sids1, dtype=np.int64), mid)
        for i, dst in enumerate(out):
            assert s2.series(int(dst)).tags == s1.series(
                int(sids1[i])).tags

    def test_missing_marked(self):
        from opentsdb_tpu.query.plan import _match_series_by_tags
        a = _tsdb()
        s1, s2 = a.store, type(a.store)()
        mid = 1
        sids1 = [s1.get_or_create_series(mid, [(1, i)])
                 for i in range(4)]
        s2.get_or_create_series(mid, [(1, 2)])
        out = _match_series_by_tags(
            s1, s2, np.asarray(sids1, dtype=np.int64), mid)
        assert (out >= 0).sum() == 1
        assert out[2] >= 0


class TestRankPrepKeyGroupCount:
    """Single-device prep-cache key regression:
    the rank-class budget is cells * groups, so two group-by
    cardinalities over the same series set must NOT share a
    PreparedBatch placement — the bucketed group count is part of the
    key, mirroring the mesh ('pct', num_groups) key."""

    def _seed_two_cardinalities(self):
        t = _tsdb()
        rng = np.random.default_rng(4)
        ts = BASE + np.arange(0, 1200, 60)
        for i in range(40):
            t.add_points("rank.m", ts, rng.normal(10, 2, len(ts)),
                         {"host": f"h{i:02d}", "dc": f"d{i % 2}"})
        return t

    def _pq(self, gb_tagk):
        filters = []
        if gb_tagk:
            filters = [{"type": "wildcard", "tagk": gb_tagk,
                        "filter": "*", "groupBy": True}]
        return TSQuery.from_json({
            "start": BASE * 1000, "end": (BASE + 1200) * 1000,
            "queries": [{"metric": "rank.m", "aggregator": "p95",
                         "filters": filters}]}).validate()

    def test_cardinalities_get_distinct_prep_entries(self):
        t = self._seed_two_cardinalities()
        t.execute_query(self._pq("host"))   # 40 groups
        t.execute_query(self._pq("dc"))     # 2 groups
        cache = t.device_grid_cache
        prep_keys = [k for k in cache._entries if k[0] == "prep"]
        assert len(prep_keys) == 2, prep_keys
        # both carry the rank class WITH a bucketed group count
        classes = {k[-1] for k in prep_keys}
        assert all(isinstance(c, tuple) and c[0] == "rank"
                   for c in classes)
        assert len(classes) == 2  # distinct group-count buckets

    def test_no_groupby_vs_groupby_distinct(self):
        t = self._seed_two_cardinalities()
        t.execute_query(self._pq(None))     # 1 group
        t.execute_query(self._pq("host"))   # 40 groups
        cache = t.device_grid_cache
        prep_keys = [k for k in cache._entries if k[0] == "prep"]
        assert len(prep_keys) == 2, prep_keys


# ---------------------------------------------------------------------
# the metric's resident grid (engine._resident_grid, PR 43): one entry a
# (store, metric, index version, window, downsample); a request's
# filter goes up as one label a row
# ---------------------------------------------------------------------

RES_HOSTS = 40
RES_END = BASE + 1800
GROUP_DC = {"type": "wildcard", "tagk": "dc", "filter": "*",
            "groupBy": True}
NOT_RACK3 = {"type": "not_literal_or", "tagk": "rack", "filter": "r3",
             "groupBy": False}


def _seed_fleet(t, hosts=RES_HOSTS):
    """A counter a host, a point every 30 s; a tenth of the hosts
    gappy (half their points missing); tags host (unique), dc (4),
    rack (8); every seventh host carries a key of its own."""
    rng = np.random.default_rng(7)
    ts = BASE + np.arange(60) * 30
    for i in range(hosts):
        keep = rng.random(60) > (0.5 if i % 10 == 0 else 0.0)
        tags = {"host": f"h{i:02d}", "dc": f"d{i % 4}",
                "rack": f"r{i % 8}"}
        if i % 7 == 6:
            tags["extra"] = "x"
        t.add_points("m", ts[keep], np.cumsum(
            rng.integers(1, 50, 60))[keep].astype(float), tags)


def _rq(agg="sum", ds="1m-avg", rate=False, filters=(GROUP_DC,
                                                    NOT_RACK3),
        explicit=False, delete=False, subs=1, end=RES_END):
    sub = {"metric": "m", "aggregator": agg, "downsample": ds,
           "filters": list(filters), "explicitTags": explicit}
    if rate:
        sub["rate"] = True
        sub["rateOptions"] = {"counter": True, "counterMax": 10000}
    more = [dict(sub, aggregator="max")] * (subs - 1)
    return TSQuery.from_json({
        "start": BASE * 1000, "end": end * 1000, "delete": delete,
        "queries": [sub] + more}).validate()


def _kinds(t):
    """The kinds of the window-keyed entries: the per-bucket columns a
    metric's grid is assembled from on the native store (PR 50,
    ``tests/test_moving_window.py``) lie beside them and are not
    listed."""
    return sorted(k[0] for k in t.device_grid_cache._entries
                  if k[0] != engine_mod.RESIDENT_COLUMN_KEY)


def _built(t, windows=1):
    """The misses ``windows`` builds of ``_rq``'s window cost: one a
    window, and on a store with the column pass (PR 50) one for each
    of the window's 30 whole buckets, looked up before the one pass
    that builds them all."""
    return windows * (31 if hasattr(t.store, "bucket_columns") else 1)


def _scanned(t, monkeypatch, query):
    """The answer of today's path over the same store: a grid of the
    selection's own rows, scanned for this request."""
    with monkeypatch.context() as m:
        m.setattr(engine_mod, "RESIDENT_GRID_MIN_SHARE", 2.0)
        before = _kinds(t).count(engine_mod.RESIDENT_GRID_KEY)
        out = t.execute_query(query)
        assert _kinds(t).count(engine_mod.RESIDENT_GRID_KEY) == before
    return out


def _same_answers(got, want, exact: bool, rtol: float):
    assert [(r.metric, r.tags, r.aggregated_tags) for r in got] == \
        [(r.metric, r.tags, r.aggregated_tags) for r in want]
    for g, w in zip(got, want):
        # the emit mask: the same timestamps on both sides
        assert [ts for ts, _ in g.dps] == [ts for ts, _ in w.dps]
        gv = np.asarray([v for _, v in g.dps], dtype=np.float64)
        wv = np.asarray([v for _, v in w.dps], dtype=np.float64)
        if exact:
            np.testing.assert_array_equal(gv, wv)
        else:
            np.testing.assert_allclose(
                gv, wv, rtol=0, atol=rtol * np.nanmax(np.abs(wv)))


# max / min / percentiles pick a member's value: bit for bit. A sum
# adds a group's rows in another order (the metric's rows, the
# selection's): eight roundings of the compute dtype, set beforehand
ROUTE_CASES = [
    ("sum", "1m-avg", False, False),
    ("max", "1m-avg", False, True),
    ("min", "1m-max", False, True),
    ("p95", "1m-avg", False, True),
    ("sum", "1m-avg", True, False),
    ("sum", "1m-avg-zero", False, False),
    ("max", "1m-avg-zero", False, True),
    ("p95", "1m-sum-zero", False, True),
    ("avg", "5m-sum", True, False),
]


class TestResidentGridRoute:
    @pytest.mark.parametrize("x64", [False, True], ids=["f32", "f64"])
    @pytest.mark.parametrize(
        "agg, ds, rate, exact", ROUTE_CASES,
        ids=[f"{a}:{d}{':rate' if r else ''}"
             for a, d, r, _ in ROUTE_CASES])
    def test_answers_as_a_scan_of_the_selection(
            self, monkeypatch, agg, ds, rate, exact, x64):
        with jax.enable_x64(x64):
            t = _tsdb()
            _seed_fleet(t)
            q = _rq(agg, ds, rate)
            got = t.execute_query(q)
            assert got and _kinds(t) == [engine_mod.RESIDENT_GRID_KEY]
            want = _scanned(t, monkeypatch, q)
            _same_answers(got, want, exact,
                          8 * float(np.finfo(
                              np.float64 if x64 else np.float32).eps))
            cache = t.device_grid_cache
            hits = cache.hits
            again = t.execute_query(q)
            assert cache.hits == hits + 1
            assert [r.dps for r in again] == [r.dps for r in got]

    def test_one_entry_serves_every_filter_and_aggregator(self):
        t = _tsdb()
        _seed_fleet(t)
        cache = t.device_grid_cache
        for rack in range(8):
            for agg in ("sum", "max"):
                t.execute_query(_rq(agg, filters=(GROUP_DC, dict(
                    NOT_RACK3, filter=f"r{rack}"))))
        assert (cache.misses, cache.hits) == (_built(t), 15)
        assert _kinds(t) == [engine_mod.RESIDENT_GRID_KEY]

    def test_a_group_the_filter_left_without_members(self,
                                                     monkeypatch):
        t = _tsdb()
        _seed_fleet(t)
        q = _rq("sum", filters=(GROUP_DC, {
            "type": "not_literal_or", "tagk": "dc", "filter": "d3",
            "groupBy": False}))
        got = t.execute_query(q)
        assert _kinds(t) == [engine_mod.RESIDENT_GRID_KEY]
        assert [r.tags["dc"] for r in got] == ["d0", "d1", "d2"]
        _same_answers(got, _scanned(t, monkeypatch, q), False, 1e-12)

    def test_explicit_tags(self, monkeypatch):
        t = _tsdb()
        _seed_fleet(t)
        q = _rq("max", explicit=True, filters=(
            GROUP_DC, NOT_RACK3, {"type": "wildcard", "tagk": "host",
                                  "filter": "*", "groupBy": False}))
        got = t.execute_query(q)
        assert got and _kinds(t) == [engine_mod.RESIDENT_GRID_KEY]
        want = _scanned(t, monkeypatch, q)
        _same_answers(got, want, True, 0.0)
        # the hosts with a key of their own are out of both
        loose = t.execute_query(_rq("max"))
        assert [r.dps for r in loose] != [r.dps for r in got]

    def test_a_selection_under_half_the_metric_is_scanned(self):
        t = _tsdb()
        _seed_fleet(t)
        few = {"type": "literal_or", "tagk": "rack",
               "filter": "r1|r2|r3", "groupBy": False}
        assert t.execute_query(_rq("sum", filters=(GROUP_DC, few)))
        assert _kinds(t) == ["grid"]          # 15 of 40 rows
        half = dict(few, filter="r1|r2|r3|r4")
        assert t.execute_query(_rq("sum", filters=(GROUP_DC, half)))
        assert _kinds(t) == ["grid", engine_mod.RESIDENT_GRID_KEY]

    @pytest.mark.parametrize("warm", [False, True],
                             ids=["built", "hit"])
    def test_points_and_limits_read_the_selection(self, monkeypatch,
                                                  warm):
        t = _tsdb()
        _seed_fleet(t)
        q = _rq("sum")
        mid = t.uids.metrics.get_id("m")
        all_sids = t.store.series_ids_for_metric(mid)
        picked = [s for s in all_sids if ("rack", "r3") not in {
            (t.uids.tag_names.get_name(k), t.uids.tag_values.get_name(v))
            for k, v in t.store.series(int(s)).tags}]
        assert len(picked) == RES_HOSTS - 5
        want = int(t.store.count_range(picked, BASE * 1000,
                                       RES_END * 1000).sum())
        whole = int(t.store.count_range(all_sids, BASE * 1000,
                                        RES_END * 1000).sum())
        if warm:
            t.execute_query(q)
        stats = QueryStats(remote="test", query=None)
        t.new_query().run(q, stats)
        stats.mark_complete()
        assert _kinds(t) == [engine_mod.RESIDENT_GRID_KEY]
        assert stats.stats[QueryStat.DPS_POST_FILTER.value] == want
        assert stats.stats[QueryStat.ROWS_FROM_STORAGE.value] == \
            len(picked)
        # a limit between the selection's points and the metric's
        # lets the request through; one under the selection's refuses
        monkeypatch.setattr(t.query_limits, "default_data_points_limit",
                            (want + whole) // 2)
        assert t.execute_query(q)
        monkeypatch.setattr(t.query_limits, "default_data_points_limit",
                            want - 1)
        with pytest.raises(QueryLimitExceeded):
            t.execute_query(q)


def _write_inside(t):
    t.add_point("m", BASE + 45, 5000.0, {"host": "h01", "dc": "d1",
                                         "rack": "r1"})


def _write_outside(t):
    t.add_point("m", RES_END + 600, 1.0, {"host": "h01", "dc": "d1",
                                          "rack": "r1"})


def _write_before(t):
    # a backfill behind the window: only the OLDEST timestamp written
    # since a version is known, so the entry goes, needlessly
    t.add_point("m", BASE - 600, 1.0, {"host": "h01", "dc": "d1",
                                       "rack": "r1"})


def _delete(t):
    sids = t.store.series_ids_for_metric(t.uids.metrics.get_id("m"))
    assert t.store.delete_range(sids[:3], BASE * 1000,
                                (BASE + 600) * 1000)


def _epoch_bump(t):
    # what a lifecycle sweep or an fsck repair does to the store
    t.store.mutation_epoch += 1


def _new_series(t):
    t.add_point("m", BASE + 60, 7.0, {"host": "h99", "dc": "d1",
                                      "rack": "r1"})


class TestResidentGridValidity:
    @pytest.mark.parametrize("change, moves, rebuilds", [
        (_write_inside, True, True), (_write_outside, False, False),
        (_write_before, False, True), (_delete, True, True),
        (_epoch_bump, False, True), (_new_series, True, True)],
        ids=["write_inside", "write_outside", "write_before", "delete",
             "epoch_bump", "new_series"])
    def test_a_change_of_the_store_rebuilds(self, monkeypatch, change,
                                            moves, rebuilds):
        """All but an append AFTER the window's end (PR 51: the store
        says where the writes since the entry's version landed)."""
        t = _tsdb()
        _seed_fleet(t)
        q = _rq("sum")
        first = t.execute_query(q)
        cache = t.device_grid_cache
        change(t)
        misses = cache.misses
        got = t.execute_query(q)
        # the write at BASE + 45 leaves no whole bucket before it
        assert cache.misses == misses + rebuilds * _built(t)
        assert cache.stale_kept == (not rebuilds)
        assert ([r.dps for r in got] != [r.dps for r in first]) == moves
        _same_answers(got, _scanned(t, monkeypatch, q), False, 1e-12)
        hits = cache.hits
        t.execute_query(q)
        assert cache.hits == hits + 1

    def test_a_delete_query_scans_its_own_rows(self):
        t = _tsdb()
        _seed_fleet(t)
        t.execute_query(_rq("sum"))
        cache = t.device_grid_cache
        seen = (cache.hits, cache.misses)
        removed = t.execute_query(_rq("sum", delete=True,
                                      end=BASE + 600))
        assert removed
        assert not any(k[0] == engine_mod.RESIDENT_GRID_KEY
                       and k[5] == (BASE + 600) * 1000
                       for k in cache._entries)
        assert cache.hits == seen[0]
        # and what it removed is gone from the next answer
        after = t.execute_query(_rq("sum", end=BASE + 600))
        assert after == []

    def test_two_sub_queries_released_together_build_one_entry(self):
        t = _tsdb()
        _seed_fleet(t)
        engine = t.new_query()
        scans = []
        gate = threading.Barrier(2, timeout=30)
        # the pass that builds the metric's grid: its columns' where
        # the store has that pass, else the whole grid's
        name = "bucket_columns" if hasattr(t.store, "bucket_columns") \
            else "bucket_grid"
        fused = getattr(t.store, name)

        def counted(*args):
            scans.append(len(args[0]))
            return fused(*args)

        setattr(t.store, name, counted)
        out = [None, None]

        def sub(i, agg):
            gate.wait()
            out[i] = engine.run(_rq(agg))

        threads = [threading.Thread(target=sub, args=(0, "sum")),
                   threading.Thread(target=sub, args=(1, "max"))]
        for th in threads:
            th.start()
        for th in threads:
            th.join(60)
            assert not th.is_alive()
        cache = t.device_grid_cache
        assert (cache.misses, cache.hits) == (_built(t), 1)
        assert scans == [RES_HOSTS]
        assert out[0] and out[1]
        # one request of two sub-queries (the live dashboards'): the same
        t.drop_caches()
        both = t.execute_query(_rq("sum", subs=2))
        assert (cache.misses, cache.hits) == (_built(t, 2), 2)
        assert [r.dps for r in both] == \
            [r.dps for r in out[0]] + [r.dps for r in out[1]]

    def test_many_requests_side_by_side_build_once(self):
        """More workers than cores, each with a filter of its own,
        while a writer adds points in bursts: every burst costs one
        build, never one a request, and once the writer has stopped
        every worker's answer is the serial one."""
        import sys
        t = _tsdb()
        _seed_fleet(t)
        engine = t.new_query()
        queries = [_rq(agg, filters=(GROUP_DC, dict(
            NOT_RACK3, filter=f"r{i % 8}")))
            for i, agg in enumerate(["sum", "max"] * 8)]
        bursts = 5
        stop = threading.Event()
        errors = []

        def worker(i):
            try:
                while not stop.is_set():
                    engine.run(queries[i])
            except Exception as exc:  # noqa: BLE001
                errors.append(exc)

        def writer():
            try:
                for j in range(bursts):
                    t.add_point("m", BASE + 200 + j, 1.0 + j,
                                {"host": "h02", "dc": "d2",
                                 "rack": "r2"})
                    time.sleep(0.05)
            except Exception as exc:  # noqa: BLE001
                errors.append(exc)
            finally:
                stop.set()

        interval = sys.getswitchinterval()
        sys.setswitchinterval(1e-5)
        try:
            threads = [threading.Thread(target=worker, args=(i,))
                       for i in range(len(queries))]
            threads.append(threading.Thread(target=writer))
            for th in threads:
                th.start()
            for th in threads:
                th.join(120)
                assert not th.is_alive()
        finally:
            stop.set()
            sys.setswitchinterval(interval)
        assert not errors, errors
        cache = t.device_grid_cache
        # a build a version of the store at most: the seeded one and
        # one a burst
        assert 1 <= cache.misses <= _built(t, bursts + 1)
        assert _kinds(t) == [engine_mod.RESIDENT_GRID_KEY]
        misses = cache.misses
        got = [engine.run(q) for q in queries]
        assert cache.misses in (misses, misses + _built(t))
        t.drop_caches()
        assert [[r.dps for r in rs] for rs in got] == \
            [[r.dps for r in engine.run(q)] for q in queries]

    def test_an_entry_larger_than_the_cache_is_not_resident(
            self, monkeypatch):
        t = _tsdb()
        _seed_fleet(t)
        q = _rq("sum")
        want = [r.dps for r in t.execute_query(q)]
        t.drop_caches()
        # the metric's padded grid is 40 x 32 cells of 9 bytes; the
        # selection's (35 rows) pads to the same shape, so nothing of
        # this window fits and nothing is kept
        monkeypatch.setattr(t.device_grid_cache, "max_bytes",
                            40 * 32 * 9 - 1)
        assert [r.dps for r in t.execute_query(q)] == want
        assert _kinds(t) == []
        monkeypatch.setattr(t.device_grid_cache, "max_bytes",
                            40 * 32 * 9)
        assert [r.dps for r in t.execute_query(q)] == want
        assert _kinds(t) == [engine_mod.RESIDENT_GRID_KEY]

    def test_no_cache_answers_as_before(self, monkeypatch):
        a = _tsdb()
        b = _tsdb(**{"tsd.query.device_cache_mb": "0"})
        _seed_fleet(a)
        _seed_fleet(b)
        assert b.device_grid_cache is None
        for agg, exact in (("sum", False), ("max", True)):
            q = _rq(agg)
            _same_answers(b.execute_query(q), a.execute_query(q),
                          exact, 1e-12)
            assert [r.dps for r in b.execute_query(q)] == \
                [r.dps for r in _scanned(a, monkeypatch, q)]

    def test_the_stats_count_look_ups_by_source(self):
        t = _tsdb(**{"tsd.trace.sample": "1"})
        _seed_fleet(t)
        few = {"type": "literal_or", "tagk": "rack", "filter": "r1",
               "groupBy": False}
        for q in (_rq("sum"), _rq("max"), _rq("sum", subs=2),
                  _rq("sum", filters=(GROUP_DC, few))):
            ctx = t.tracer.start_request("query.http")
            with trace_mod.use(ctx):
                t.execute_query(q)
            t.tracer.finish(ctx)
        by_column = hasattr(t.store, "bucket_columns")
        assert t.tracer.grids == {
            "resident_built": int(not by_column),
            "resident_columns": int(by_column),
            "resident_hit": 3, "selection": 1}
        rows = {}

        class Collector:
            def record(self, name, value, **tags):
                if name == "query.grid":
                    rows[tags["source"]] = value

        t.tracer.collect_stats(Collector())
        assert rows == t.tracer.grids

    def test_the_look_up_span_ends_before_a_hit_counts_its_points(
            self, monkeypatch):
        """``grid_build.ms`` reads the look-up span: the key, the
        look-up and the wait, not the selection's point count (1.3 ms
        a million rows), which is the request's own work."""
        t = _tsdb()
        _seed_fleet(t)
        t.execute_query(_rq("sum"))
        events = []

        class Counts(np.ndarray):
            def __getitem__(self, item):
                events.append("counted")
                return np.asarray(self)[item]

        class Lookup:
            def tag(self, **tags):
                events.append(tags["grid"])

            def finish(self):
                events.append("ended")

        (entry,) = [e for k, e in t.device_grid_cache._entries.items()
                    if k[0] == engine_mod.RESIDENT_GRID_KEY]
        entry[2]["counts"] = entry[2]["counts"].view(Counts)
        monkeypatch.setattr(
            engine_mod, "trace_begin",
            lambda name, **tags: Lookup()
            if tags.get("stage") == "cache_lookup" else None)
        assert t.execute_query(_rq("max"))
        assert events == ["resident_hit", "ended", "counted"]


# ---------------------------------------------------------------------
# the one entry (device_cache.resident, PR 45): every kind of entry is
# looked up, built, kept and dropped through it
# ---------------------------------------------------------------------

class _Versioned:
    """What ``store_version`` reads of a store."""

    def __init__(self):
        self.points_written = 0
        self.mutation_epoch = 0


class _Arena:
    """The histogram arenas' one counter (``TSDB._histogram_version``)."""

    def __init__(self):
        self.version = 0


def _one_store():
    store = _Versioned()

    def write():
        store.points_written += 1
    return (lambda: dc_mod.store_version(store)), write


def _two_stores():
    stores = _Versioned(), _Versioned()

    def write():
        stores[1].mutation_epoch += 1
    return (lambda: dc_mod.store_version(*stores)), write


def _arena():
    arena = _Arena()

    def write():
        arena.version += 1
    return (lambda: arena.version), write


# kind -> (the rest of a key of its shape, what versions it)
KINDS = {
    "metricgrid": ((1, 7, 40, 0, 1800, 0, 60, 30, "avg"), _one_store),
    "grid": ((1, b"digest", 0, 1800, 0, 60, 30, "avg", None),
             _one_store),
    # a metric's tier pair (PR 48); a selection's own pair keeps a
    # digest where the metric's id and series count stand
    "avgdiv": ((1, 2, 7, 40, 0, 1800, 0, 60, 30), _two_stores),
    "prep": ((1, b"digest", 0, 1800, "union", None, None, "lin"),
             _one_store),
    "hist": ((7, 0, 1800), _arena),
}
by_kind = pytest.mark.parametrize("kind", sorted(KINDS))


def _entry(kind, which=0):
    """(key, version_of, write) of one entry of ``kind``."""
    rest, versioned = KINDS[kind]
    return ((kind, which) + rest, *versioned())


def _arrays(nbytes=64):
    return (np.zeros(nbytes // 8), ), {"num_points": 3}


class TestResidentEntry:
    @by_kind
    def test_the_version_is_read_before_the_build(self, kind):
        """A write made inside ``build`` leaves an entry the next call
        rebuilds: the entry carries the version from before it."""
        cache = dc_mod.DeviceGridCache(1 << 20)
        key, version_of, write = _entry(kind)
        order = []

        def read():
            order.append("version")
            return version_of()

        def build():
            order.append("build")
            write()
            return _arrays()

        # (a look-up without the flight reads the version too, first)
        assert cache.resident(key, read, build)[2] == dc_mod.BUILT
        assert order[-2:] == ["version", "build"]
        assert cache.resident(key, read, build)[2] == dc_mod.BUILT
        assert order[-2:] == ["version", "build"]
        assert order.count("build") == 2

        def quiet():
            order.append("build")
            return _arrays()

        made = cache.resident(key, read, quiet)
        assert made[2] == dc_mod.BUILT
        hit = cache.resident(key, read, quiet)
        assert hit[2] == dc_mod.HIT and hit[0] is made[0] \
            and hit[1] is made[1]
        assert order.count("build") == 3
        assert (cache.misses, cache.hits) == (3, 1)
        assert cache.bytes_of(kind) == 64

    @by_kind
    def test_threads_on_one_key_build_once(self, kind):
        cache = dc_mod.DeviceGridCache(1 << 20)
        key, version_of, _ = _entry(kind)
        gate = threading.Barrier(8, timeout=30)
        builds, got = [], [None] * 8

        def build():
            builds.append(1)
            time.sleep(0.05)
            return _arrays()

        def ask(i):
            gate.wait()
            got[i] = cache.resident(key, version_of, build)

        threads = [threading.Thread(target=ask, args=(i,))
                   for i in range(8)]
        for th in threads:
            th.start()
        for th in threads:
            th.join(30)
            assert not th.is_alive()
        assert len(builds) == 1
        assert sorted(g[2] for g in got) == \
            [dc_mod.BUILT] + [dc_mod.HIT] * 7
        assert all(g[0] is got[0][0] for g in got)
        # the waiters count as hits, and nobody is left in flight
        assert (cache.misses, cache.hits) == (1, 7)
        assert cache._flights == {}

    @by_kind
    @pytest.mark.parametrize("cached", [True, False])
    def test_two_keys_build_side_by_side_unless_the_kind_takes_turns(
            self, kind, cached):
        """Two keys do not wait for each other, except where one
        layout is a large share of HBM (``hist``): there the second
        build starts when the first has ended, cache or no cache."""
        cache = dc_mod.DeviceGridCache(1 << 20) if cached else None
        slow_key, version_of, _ = _entry(kind, 0)
        other_key = _entry(kind, 1)[0]
        building, release = threading.Event(), threading.Event()
        other_began, slow_inside = threading.Event(), threading.Event()

        def slow():
            slow_inside.set()
            building.set()
            assert release.wait(30)
            slow_inside.clear()
            return _arrays()

        def other():
            """Ones where it ran inside the slow build, else zeros."""
            other_began.set()
            return ((np.full(8, float(slow_inside.is_set())), ),
                    {"num_points": 3})

        got = []
        first = threading.Thread(target=dc_mod.resident, args=(
            cache, slow_key, version_of, slow))
        second = threading.Thread(target=lambda: got.append(
            dc_mod.resident(cache, other_key, version_of, other)))
        first.start()
        try:
            assert building.wait(30)
            second.start()
            takes_turns = kind in dc_mod.SERIAL_BUILD_KINDS
            # the other key's build runs to its end meanwhile, or not
            # at all until the first has ended
            assert other_began.wait(0.2 if takes_turns else 30) \
                != takes_turns
            if not takes_turns:
                second.join(30)
                assert not second.is_alive()
        finally:
            release.set()
            first.join(30)
            second.join(30)
        assert not first.is_alive() and not second.is_alive()
        assert got[0][2] == (dc_mod.BUILT if cached else dc_mod.NOT_KEPT)
        assert bool(got[0][0][0].all()) != takes_turns
        if cached:
            assert sorted(k[1] for k in cache._entries) == [0, 1]
            assert cache._flights == {}

    @by_kind
    def test_a_build_that_raises_frees_the_waiters(self, kind):
        cache = dc_mod.DeviceGridCache(1 << 20)
        key, version_of, _ = _entry(kind)
        building, release = threading.Event(), threading.Event()
        errors, got = [], []

        def failing():
            building.set()
            assert release.wait(30)
            raise RuntimeError("the upload failed")

        def leader():
            try:
                cache.resident(key, version_of, failing)
            except RuntimeError as exc:
                errors.append(exc)

        def waiter():
            got.append(cache.resident(key, version_of, _arrays))

        first = threading.Thread(target=leader)
        first.start()
        assert building.wait(30)
        rest = [threading.Thread(target=waiter) for _ in range(3)]
        for th in rest:
            th.start()
        time.sleep(0.05)
        assert not got and not cache._entries
        release.set()
        for th in [first] + rest:
            th.join(30)
            assert not th.is_alive()
        # the failure is the leader's alone and nothing of it is kept:
        # the first waiter through builds, the others hit
        assert len(errors) == 1
        assert sorted(g[2] for g in got) == \
            [dc_mod.BUILT, dc_mod.HIT, dc_mod.HIT]
        assert cache._flights == {}

    @by_kind
    def test_what_is_not_kept_is_still_answered(self, kind):
        cache = dc_mod.DeviceGridCache(64)
        key, version_of, _ = _entry(kind)
        # larger than the whole cache
        arrays, meta, how = cache.resident(key, version_of,
                                           lambda: _arrays(72))
        assert how == dc_mod.NOT_KEPT and arrays[0].nbytes == 72 \
            and meta == {"num_points": 3}
        # the builder's say: nothing to keep of an empty window
        arrays, meta, how = cache.resident(
            key, version_of, lambda: (None, {"num_points": 0}))
        assert (arrays, meta, how) == (None, {"num_points": 0},
                                       dc_mod.NOT_KEPT)
        assert not cache._entries and cache.bytes_of(kind) == 0
        assert cache.resident(key, version_of, _arrays)[2] == \
            dc_mod.BUILT
        assert cache.bytes_of(kind) == 64

    @by_kind
    def test_no_cache_same_answer(self, kind):
        key, version_of, _ = _entry(kind)
        cache = dc_mod.DeviceGridCache(1 << 20)
        for _ in range(2):
            with_cache = cache.resident(key, version_of, _arrays)
            without = dc_mod.resident(None, key, version_of, _arrays)
            np.testing.assert_array_equal(without[0][0],
                                          with_cache[0][0])
            assert without[1] == with_cache[1]
            assert without[2] == dc_mod.NOT_KEPT
        assert dc_mod.resident(cache, key, version_of, _arrays)[2] == \
            dc_mod.HIT


def _seed_rollup(t):
    for i in range(6):
        for j in range(30):
            for agg, v in (("sum", float(i + j)), ("count", 3.0)):
                t.add_aggregate_point("m", BASE + j * 60, v,
                                      {"host": f"h{i}"}, False, "1m",
                                      agg)


def _seed_hist(t):
    from opentsdb_tpu.core.histogram import SimpleHistogram
    for i in range(6):
        for j in range(10):
            h = SimpleHistogram([0.0, 1.0, 2.0, 4.0])
            h.counts = [1 + i, 2 + j, 3]
            t.add_histogram_point("m", BASE + j * 60,
                                  t.histogram_manager.encode(h),
                                  {"host": f"h{i}"})


def _write_scalar(t):
    t.add_point("m", BASE + 90, 1e6, {"host": "h01", "dc": "d1",
                                      "rack": "r1"})


def _write_rollup(t):
    t.add_aggregate_point("m", BASE, 500.0, {"host": "h0"}, False,
                          "1m", "sum")


def _write_hist(t):
    from opentsdb_tpu.core.histogram import SimpleHistogram
    h = SimpleHistogram([0.0, 1.0, 2.0, 4.0])
    h.counts = [0, 0, 500]
    t.add_histogram_point("m", BASE + 60,
                          t.histogram_manager.encode(h), {"host": "h0"})


FEW = {"type": "literal_or", "tagk": "rack", "filter": "r1",
       "groupBy": False}

# kind -> (config, seed, the query that keeps an entry of it, a write
# inside its window)
SITES = {
    "metricgrid": ({}, _seed_fleet, lambda: _rq("max"), _write_scalar),
    "grid": ({}, _seed_fleet,
             lambda: _rq("max", filters=(GROUP_DC, FEW)), _write_scalar),
    "avgdiv": ({"tsd.rollups.enable": "true"}, _seed_rollup,
               lambda: _q("sum", "5m-avg", end=BASE + 1800),
               _write_rollup),
    "prep": ({}, _seed_fleet, lambda: _rq("max", ds=None),
             _write_scalar),
    "hist": ({}, _seed_hist, lambda: TSQuery.from_json({
        "start": BASE * 1000, "end": (BASE + 1800) * 1000,
        "queries": [{"metric": "m", "aggregator": "sum",
                     "percentiles": [50.0, 99.0]}]}).validate(),
        _write_hist),
}


# ---------------------------------------------------------------------
# the finer version rule (PR 51): an entry that covers a span of time
# of one store outlives the appends that landed after the span's end
# ---------------------------------------------------------------------

class _Logged(_Versioned):
    """A store that says where its appends landed: the real log
    (``core/store.py WrittenLog``) behind ``_Versioned``'s counters."""

    def __init__(self):
        super().__init__()
        from opentsdb_tpu.core.store import WrittenLog
        self.log = WrittenLog()

    def append(self, ts_ms: int) -> None:
        with self.log.lock:
            self.points_written += 1
            self.log.note(self.points_written, ts_ms)

    def oldest_written_since(self, points_written: int):
        return self.log.oldest_since(points_written)


COVERS = (1_000, 1_999)         # what the entry covers, inclusive


def _nothing(store):
    pass


def _epoch(store):
    store.append(5_000)
    store.mutation_epoch += 1


def _forgotten(store):
    store.append(5_000)
    store.log.floor_version = store.points_written


# what happened to the store since the entry's version -> kept?
SINCE = {
    "nothing": (_nothing, True),
    "above_hi": (lambda s: s.append(2_000), True),
    "far_above_hi": (lambda s: [s.append(9_000), s.append(8_000)], True),
    "at_hi": (lambda s: s.append(1_999), False),
    "inside": (lambda s: s.append(1_500), False),
    "at_lo": (lambda s: s.append(1_000), False),
    "below_lo": (lambda s: s.append(10), False),
    "above_then_inside": (lambda s: [s.append(5_000), s.append(1_200)],
                          False),
    "inside_then_above": (lambda s: [s.append(1_200), s.append(5_000)],
                          False),
    "epoch_moved": (_epoch, False),
    "the_log_forgot": (_forgotten, False),
}


class TestWhatAWriteDrops:
    @pytest.mark.parametrize("since", sorted(SINCE))
    @pytest.mark.parametrize("door", ["resident", "resident_columns"])
    def test_the_rules_table(self, since, door):
        change, kept = SINCE[since]
        store = _Logged()
        cache = dc_mod.DeviceGridCache(1 << 20)
        key = ("metriccol", 1, 7, 40, 60, "avg", COVERS[0])
        builds = []

        def ask():
            found = dc_mod.Lookup(store)
            if door == "resident":
                def build():
                    builds.append(1)
                    return _arrays()
                arrays = cache.resident(
                    key, lambda: dc_mod.store_version(store), build,
                    COVERS, found)[0]
            else:
                def build(missing):
                    builds.extend(missing)
                    return [_arrays()[0] for _ in missing], None
                (arrays,), _ = cache.resident_columns(
                    key[:-1], [key], dc_mod.store_version(store),
                    build, [COVERS], found)
            return arrays, found.stale

        first, stale = ask()
        assert stale == "none" and len(builds) == 1
        change(store)
        moved = dc_mod.store_version(store) != (0, 0)
        again, stale = ask()
        assert (again is first) == kept
        assert len(builds) == 2 - kept
        assert stale == ("none" if not moved
                         else "kept" if kept else "dropped")
        assert (cache.stale_kept, cache.stale_dropped) \
            == (int(moved and kept), int(not kept))
        assert cache.stale_dropped_bytes == (0 if kept else 64)
        # whatever it was, it now carries the version just read: a
        # third look-up asks the store nothing
        asked = []
        store.oldest_written_since = asked.append
        assert ask() == (again, "none") and not asked
        (entry,) = cache._entries.values()
        assert entry[0] == dc_mod.store_version(store)
        assert entry[4] == COVERS

    @pytest.mark.parametrize("case", ["no_method", "no_covers",
                                      "no_lookup", "two_stores"])
    def test_without_the_stores_word_any_write_drops(self, case):
        """A store without ``oldest_written_since`` (the cold store,
        the rollup tiers' wrapper, the histogram arenas) answers
        "everything"; so does an entry kept without a span and a
        look-up that brings no store; and the span is of the version's
        FIRST store: a write to any other drops the entry."""
        store = _Versioned() if case == "no_method" else _Logged()
        stores = (store, _Versioned()) if case == "two_stores" \
            else (store,)
        cache = dc_mod.DeviceGridCache(1 << 20)
        builds = []

        def build():
            builds.append(1)
            return _arrays()

        def ask():
            found = None if case == "no_lookup" \
                else dc_mod.Lookup(store)
            cache.resident(("metricgrid", 1),
                           lambda: dc_mod.store_version(*stores), build,
                           None if case == "no_covers" else COVERS,
                           found)
            return found.stale if found else None

        ask()
        if case == "two_stores":
            stores[1].points_written += 1
        elif isinstance(store, _Logged):
            store.append(5_000)         # far beyond the span
        else:
            store.points_written += 1
        assert ask() == (None if case == "no_lookup" else "dropped")
        assert len(builds) == 2
        assert (cache.stale_kept, cache.stale_dropped) == (0, 1)

    def test_a_look_up_asks_the_store_once_a_version(self):
        """Eleven columns of one version: one question."""
        store = _Logged()
        asked = []
        real = store.oldest_written_since
        store.oldest_written_since = \
            lambda v: asked.append(v) or real(v)
        cache = dc_mod.DeviceGridCache(1 << 20)
        keys = [("metriccol", 1, 7, 40, 60, "avg", 60 * k)
                for k in range(11)]
        covers = [(60 * k, 60 * k + 59) for k in range(11)]

        def build(missing):
            return [_arrays()[0] for _ in missing], None

        cache.resident_columns(
            keys[0][:-1], keys, dc_mod.store_version(store), build,
            covers, dc_mod.Lookup(store))
        store.append(60 * 7 + 5)          # into the eighth bucket
        found = dc_mod.Lookup(store)
        built = []
        cache.resident_columns(
            keys[0][:-1], keys, dc_mod.store_version(store),
            lambda missing: (built.extend(missing) or build(missing)),
            covers, found)
        assert asked == [0] and built == [7, 8, 9, 10]
        assert (found.kept, found.dropped, found.stale) \
            == (7, 4, "dropped")

    def test_replace_keeps_the_span(self):
        """The grid a program assembled takes its columns' place under
        the window's key with the span the columns were kept with."""
        store = _Logged()
        cache = dc_mod.DeviceGridCache(1 << 20)
        key = ("metricgrid", 1)
        old = cache.resident(key, lambda: dc_mod.store_version(store),
                             _arrays, COVERS, dc_mod.Lookup(store))[0]
        assert cache.replace(key, old, _arrays(128)[0], {})
        store.append(2_000)
        found = dc_mod.Lookup(store)
        assert cache.resident(
            key, lambda: dc_mod.store_version(store), _arrays, COVERS,
            found)[2] == dc_mod.HIT and found.stale == "kept"

    def test_the_counters_are_exported(self):
        from opentsdb_tpu.stats.stats import StatsCollector
        store = _Logged()
        cache = dc_mod.DeviceGridCache(1 << 20)
        for ts in (5_000, 1_500):
            cache.resident(("metricgrid", 1),
                           lambda: dc_mod.store_version(store), _arrays,
                           COVERS, dc_mod.Lookup(store))
            store.append(ts)
        cache.resident(("metricgrid", 1),
                       lambda: dc_mod.store_version(store), _arrays,
                       COVERS, dc_mod.Lookup(store))
        collector = StatsCollector("tsd")
        cache.collect_stats(collector)
        got = {(name, tags.get("outcome")): value
               for name, value, tags in collector.records
               if "residency" in name}
        assert got == {("tsd.query.residency", "kept"): 1,
                       ("tsd.query.residency", "dropped"): 1,
                       ("tsd.query.residency.dropped_bytes", None): 64}


class TestEveryKindThroughTheOneEntry:
    @pytest.mark.parametrize("kind", sorted(SITES))
    def test_built_once_hit_on_repeat_rebuilt_after_a_write(self,
                                                            kind):
        config, seed, query, write = SITES[kind]
        t = _tsdb(**config)
        seed(t)
        cache = t.device_grid_cache
        build = _built(t) if kind == "metricgrid" else 1
        cold = t.execute_query(query())
        assert cold and _kinds(t) == [kind]
        assert (cache.misses, cache.hits) == (build, 0)
        warm = t.execute_query(query())
        assert (cache.misses, cache.hits) == (build, 1)
        assert [r.dps for r in warm] == [r.dps for r in cold]
        write(t)
        moved = t.execute_query(query())
        # the metric's columns: the bucket that ends before the write
        # stays resident (PR 51); every other kind drops whole
        kept = int(kind == "metricgrid" and build > 1)
        assert (cache.misses, cache.hits) == (2 * build - kept,
                                              1 + kept)
        assert [r.dps for r in moved] != [r.dps for r in cold]
        assert _kinds(t) == [kind] and cache._flights == {}
        # and the same answers with nothing resident at all
        bare = _tsdb(**{**config, "tsd.query.device_cache_mb": "0"})
        seed(bare)
        write(bare)
        _same_answers(bare.execute_query(query()), moved, True, 0.0)


    @pytest.mark.parametrize("kind,kept", [
        ("metricgrid", True), ("grid", False), ("avgdiv", True)])
    def test_a_request_the_limits_refuse_keeps_what_it_always_did(
            self, monkeypatch, kind, kept):
        """The selection's own grid is checked between its scan and
        its upload: a refused request puts nothing up and keeps
        nothing. The metric's grid (of use to the selections the
        limits let through) and the rollup's pair are kept first."""
        from opentsdb_tpu.ops import pipeline as pipeline_mod
        config, seed, query, _ = SITES[kind]
        t = _tsdb(**config)
        seed(t)
        uploads = []
        # the metric's grid goes up whole, or a column a bucket
        for name in ("put_grid", "put_columns"):
            monkeypatch.setattr(
                pipeline_mod, name,
                lambda *a, real=getattr(pipeline_mod, name):
                uploads.append(1) or real(*a))
        monkeypatch.setattr(t.query_limits, "default_data_points_limit",
                            1)
        for _ in range(2):
            with pytest.raises(QueryLimitExceeded):
                t.execute_query(query())
        assert _kinds(t) == ([kind] if kept else [])
        assert len(uploads) == (kind == "metricgrid")
        assert t.device_grid_cache._flights == {}
        # and the request goes through once the limit lets it
        monkeypatch.setattr(t.query_limits, "default_data_points_limit",
                            0)
        assert t.execute_query(query()) and _kinds(t) == [kind]

    def test_two_histogram_windows_are_laid_out_one_at_a_time(
            self, monkeypatch):
        """The window is in the ``hist`` key, so two windows have a
        flight each: the kind's turn keeps their layouts (3.8 GB each
        at 12M points) from standing in HBM side by side."""
        from opentsdb_tpu.query import histogram_engine as hist_mod
        t = _tsdb()
        _seed_hist(t)
        real = hist_mod._make_resident
        gate = threading.Barrier(4, timeout=30)
        inside, most = [], []

        def counted(*args):
            inside.append(1)
            most.append(len(inside))
            time.sleep(0.05)
            try:
                return real(*args)
            finally:
                inside.pop()

        monkeypatch.setattr(hist_mod, "_make_resident", counted)

        def ask(i):
            gate.wait()
            t.execute_query(TSQuery.from_json({
                "start": BASE * 1000, "end": (BASE + 1800 + i) * 1000,
                "queries": [{"metric": "m", "aggregator": "sum",
                             "percentiles": [50.0]}]}).validate())

        threads = [threading.Thread(target=ask, args=(i,))
                   for i in range(4)]
        for th in threads:
            th.start()
        for th in threads:
            th.join(60)
            assert not th.is_alive()
        assert most == [1] * 4 and _kinds(t) == ["hist"] * 4


def test_the_plan_formats_import_no_engine():
    """``query/plan.py`` and ``query/filters.py`` stand below the
    engine: importing them loads none."""
    import subprocess
    import sys
    done = subprocess.run(
        [sys.executable, "-c",
         "import sys; import opentsdb_tpu.query.plan; "
         "import opentsdb_tpu.query.filters; "
         "import opentsdb_tpu.query.device_cache; "
         "sys.exit('opentsdb_tpu.query.engine' in sys.modules)"],
        capture_output=True, text=True, timeout=120)
    assert done.returncode == 0, done.stderr


def test_the_store_holds_no_lock_of_the_query_engine():
    t = _tsdb()
    assert not [name for name in vars(t)
                if name.endswith(("_resident_lock",
                                  "_resident_grid_lock"))]
