"""Host-tail placement for the wildcard group-by dashboard class.

Covers: the linear-vs-rank budget split (engine.host_tail_device;
the linear budget is the crossover measured on the chip, PR 32),
the segment-lowered group stage (PipelineSpec.host), the verified-
complete-grid interpolation skip (PipelineSpec.complete), and the
host-RAM prepared-batch cache (tsdb.host_prep_cache).
"""

from __future__ import annotations

import numpy as np
import pytest

from opentsdb_tpu import TSDB, Config
from opentsdb_tpu.query.engine import host_tail_device, host_tail_for_dims
from opentsdb_tpu.query.model import TSQuery
from opentsdb_tpu.utils.config import Config as _Cfg

BASE = 1356998400


def _cfg(**kw):
    return Config(**{str(k): str(v) for k, v in kw.items()})


# The placement sweep's shape classes (PERF.md section 6, PR 32: host
# and chip timed at 8 ... 114,688 padded series x 64 x 112) and where
# the default rule places each: raw series, buckets, groups, aggregator.
SHAPE_CLASSES = [
    # the panels' class: 8 hosts x 60 buckets, 0.7 ms on the host
    pytest.param(8, 60, 8, "max", "host", id="panels-max"),
    pytest.param(8, 60, 8, "sum", "host", id="panels-sum"),
    pytest.param(128, 60, 100, "sum", "host", id="sweep-128"),
    # 1,024 x 64 = 65,536 padded cells: the last class the host wins
    pytest.param(1024, 60, 100, "sum", "host", id="sweep-1024-sum"),
    pytest.param(1024, 60, 100, "max", "host", id="sweep-1024-max"),
    # one shape bucket on (1,280 x 64) and every class above it
    pytest.param(1025, 60, 100, "sum", "device", id="sweep-1280"),
    pytest.param(8192, 60, 100, "sum", "device", id="sweep-8192-sum"),
    pytest.param(8192, 60, 100, "max", "device", id="sweep-8192-max"),
    pytest.param(32768, 60, 100, "avg", "device", id="sweep-32768"),
    # BASELINE config 2: 100,000 series, sum + max by dc over an hour
    pytest.param(100_000, 60, 100, "sum", "device", id="config2-sum"),
    pytest.param(100_000, 60, 100, "max", "device", id="config2-max"),
    pytest.param(100_000, 30, 1000, "sum", "device",
                 id="config2-1000-groups"),
    # the north star: 1M series
    pytest.param(1_000_000, 60, 100, "sum", "device",
                 id="north-star-60"),
    pytest.param(1_000_000, 12, 100, "sum", "device",
                 id="north-star-12"),
    # the rank class keeps its own budgets (cells and cells x groups)
    pytest.param(1024, 60, 100, "p99", "host", id="rank-small"),
    pytest.param(16384, 60, 4, "median", "host", id="rank-16384x8"),
    pytest.param(100_000, 30, 1000, "p99", "device",
                 id="rank-cells-x-groups"),
    pytest.param(100_000, 60, 1, "p95", "device", id="rank-cells"),
]


class TestDecision:
    @pytest.mark.parametrize("s, b, g, agg, placement", SHAPE_CLASSES)
    def test_default_placement_by_shape_class(self, s, b, g, agg,
                                              placement):
        dev = host_tail_for_dims(_cfg(), s, b, g, agg_name=agg)
        if placement == "host":
            assert dev is not None and dev.platform == "cpu"
        else:
            assert dev is None

    def test_linear_budget_is_the_measured_crossover(self):
        """65,536 padded cells: a power of two, at which the host
        still wins (<=) and past which the chip does; smaller than
        the rank class's cells budget, which nothing has measured."""
        from opentsdb_tpu.query.engine import (
            HOST_TAIL_DEFAULT_CELLS, HOST_TAIL_DEFAULT_CELLS_LINEAR)
        limit = HOST_TAIL_DEFAULT_CELLS_LINEAR
        assert limit == 1 << 16 < HOST_TAIL_DEFAULT_CELLS
        cfg = _cfg()
        assert host_tail_device(cfg, limit, 1024,
                                linear_agg=True) is not None
        assert host_tail_device(cfg, limit + 1, 1024,
                                linear_agg=True) is None

    def test_linear_budget_has_no_group_factor(self):
        cfg = _cfg()
        # 1,024 groups over 64 x 512 cells: cells x groups is at the
        # rank class's cap and far past it one bucket on; the linear
        # rule does not look
        assert host_tail_for_dims(cfg, 64, 512, 1000,
                                  agg_name="sum") is not None
        assert host_tail_for_dims(cfg, 64, 512, 1000,
                                  agg_name="p99") is not None
        assert host_tail_for_dims(cfg, 64, 512, 2000,
                                  agg_name="sum") is not None
        assert host_tail_for_dims(cfg, 64, 512, 2000,
                                  agg_name="p99") is None

    def test_disable_keys(self):
        assert host_tail_for_dims(
            _cfg(**{"tsd.query.host_tail_max_cells_linear": -1}),
            100, 10, 2, agg_name="sum") is None
        assert host_tail_for_dims(
            _cfg(**{"tsd.query.host_tail_max_cells": -1}),
            100, 10, 2, agg_name="p99") is None

    def test_linear_key_overrides_the_default(self):
        # an operator's own crossover, either way from the default
        wide = _cfg(**{"tsd.query.host_tail_max_cells_linear": 1 << 23})
        assert host_tail_for_dims(wide, 100_000, 60, 100,
                                  agg_name="sum") is not None
        narrow = _cfg(**{"tsd.query.host_tail_max_cells_linear": 256})
        assert host_tail_for_dims(narrow, 8, 60, 8,
                                  agg_name="max") is None

    def test_rank_class_detection(self):
        from opentsdb_tpu.query.engine import _rank_class_agg
        for name in ("median", "p50", "p999", "ep95r3"):
            assert _rank_class_agg(name), name
        for name in ("sum", "min", "max", "avg", "dev", "count",
                     "zimsum", "mimmin", "mimmax", "first", "last",
                     "diff", "multiply", "squareSum", "none"):
            assert not _rank_class_agg(name), name

    def test_unknown_agg_is_conservative(self):
        from opentsdb_tpu.query.engine import _rank_class_agg
        assert _rank_class_agg("definitely-not-an-agg")

    def test_host_tail_device_linear_flag(self):
        cfg = _cfg()
        # between the two budgets: past the linear one, under the rank
        # class's cells cap (and, at 8 groups, its cells x groups cap)
        mid = 1 << 19
        assert host_tail_device(cfg, mid, 8, linear_agg=True) is None
        assert host_tail_device(cfg, mid, 8,
                                linear_agg=False) is not None


def _seed_groupby(n_series=3000, pts=20, groups=50, **extra):
    t = TSDB(Config(**{"tsd.core.auto_create_metrics": "true",
                       # pin the host PREP cache itself: the serve-
                       # path result cache would answer warm repeats
                       # before they reach it
                       "tsd.query.cache.enable": "false",
                       **{str(k): str(v) for k, v in extra.items()}}))
    ts = np.arange(BASE, BASE + pts * 60, 60, dtype=np.int64)
    rng = np.random.default_rng(9)
    vals = rng.normal(50, 5, (n_series, pts))
    for i in range(n_series):
        t.add_points("hosttail.m", ts, vals[i],
                     {"host": f"h{i % groups:03d}",
                      "task": f"t{i // groups}"})
    return t, ts, vals, groups


def _groupby_query(pts=20):
    return TSQuery.from_json({
        "start": BASE * 1000, "end": (BASE + pts * 60) * 1000,
        "queries": [{"metric": "hosttail.m", "aggregator": "sum",
                     "filters": [{"type": "wildcard", "tagk": "host",
                                  "filter": "*", "groupBy": True}]}]
    }).validate()


class TestHostCacheAndCorrectness:
    def test_union_groupby_served_from_host_cache(self):
        t, ts, vals, groups = _seed_groupby()
        t.execute_query(_groupby_query())
        hc = t.host_prep_cache
        assert hc is not None and hc.misses >= 1
        res = t.execute_query(_groupby_query())
        assert hc.hits >= 1
        # device cache untouched by this class (separate pools)
        assert t.device_grid_cache._bytes == 0
        g0 = [r for r in res if r.tags.get("host") == "h000"][0]
        want = vals[np.arange(len(vals)) % groups == 0].sum(axis=0)
        np.testing.assert_allclose([v for _, v in g0.dps], want,
                                   rtol=1e-9)
        assert [tt for tt, _ in g0.dps] == (ts * 1000).tolist()

    def test_write_invalidates_host_cache(self):
        t, ts, vals, groups = _seed_groupby()
        r1 = t.execute_query(_groupby_query())
        t.add_point("hosttail.m", int(ts[0]), 1000.0,
                    {"host": "h000", "task": "t0"})
        r2 = t.execute_query(_groupby_query())
        g1 = [r for r in r1 if r.tags.get("host") == "h000"][0]
        g2 = [r for r in r2 if r.tags.get("host") == "h000"][0]
        # LWW dedupe: the new value replaces the old at ts[0]
        assert g2.dps[0][1] != pytest.approx(g1.dps[0][1])

    def test_incomplete_grid_still_interpolates(self):
        """A missing cell must NOT be zero-filled by the complete-grid
        fast path: sum LERPs across the gap (reference semantics)."""
        t = TSDB(Config(**{"tsd.core.auto_create_metrics": "true"}))
        ts = np.arange(BASE, BASE + 10 * 60, 60, dtype=np.int64)
        t.add_points("m.gap", ts, np.ones(10), {"host": "a"})
        keep = np.ones(10, dtype=bool)
        keep[5] = False  # hole in series b at ts[5]
        t.add_points("m.gap", ts[keep], np.full(9, 10.0), {"host": "b"})
        res = t.execute_query(TSQuery.from_json({
            "start": BASE * 1000, "end": (BASE + 600) * 1000,
            "queries": [{"metric": "m.gap",
                         "aggregator": "sum"}]}).validate())
        dps = dict(res[0].dps)
        # at the hole, b lerps 10 -> 10, so sum = 11 (not 1)
        assert dps[int(ts[5]) * 1000] == pytest.approx(11.0)

    def test_drop_caches_clears_host_cache(self):
        t, *_ = _seed_groupby(n_series=500, groups=10)
        t.execute_query(_groupby_query())
        assert t.host_prep_cache._bytes > 0
        t.drop_caches()
        assert t.host_prep_cache._bytes == 0

    def test_rate_drop_resets_not_marked_complete(self):
        """drop_resets punches per-series holes post-rate, so the
        complete-grid skip must not engage; mesh-vs-host agreement is
        pinned by the dryrun matrix — here just correctness vs a tiny
        hand check."""
        t = TSDB(Config(**{"tsd.core.auto_create_metrics": "true"}))
        ts = np.arange(BASE, BASE + 6 * 60, 60, dtype=np.int64)
        t.add_points("m.ctr", ts,
                     np.asarray([10., 20., 5., 30., 40., 50.]),
                     {"host": "a"})
        t.add_points("m.ctr", ts,
                     np.asarray([1., 2., 3., 4., 5., 6.]),
                     {"host": "b"})
        res = t.execute_query(TSQuery.from_json({
            "start": BASE * 1000, "end": (BASE + 360) * 1000,
            "queries": [{"metric": "m.ctr", "aggregator": "sum",
                         "rate": True,
                         "rateOptions": {"counter": True,
                                         "counterMax": 65535,
                                         "dropResets": True}}]
        }).validate())
        dps = dict(res[0].dps)
        # at ts[2] series a's reset (20 -> 5) is dropped; the merge
        # then LERPs a across its hole — (10/60 + 25/60)/2 — and adds
        # b's 1/60 (ref: RateSpan suppression + AggregationIterator
        # interpolation). The complete-grid skip must NOT zero-fill.
        want = (10 / 60 + 25 / 60) / 2 + 1 / 60
        assert dps[int(ts[2]) * 1000] == pytest.approx(want)
