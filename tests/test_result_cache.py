"""Serve-path result cache (query/result_cache.py): correctness of
epoch invalidation (no test may ever observe a stale result after ANY
write to a store the query reads), single-flight coalescing (N
concurrent identical queries -> exactly one engine execution), the
byte-budget LRU, relative-time TTL semantics, and the parallel
sub-query fan-out (ordering + QueryStats attribution + speedup)."""

from __future__ import annotations

import threading
import time

import numpy as np
import pytest

from opentsdb_tpu import TSDB, Config
from opentsdb_tpu.query.model import TSQuery
from opentsdb_tpu.query.result_cache import QueryResultCache

BASE = 1356998400


_LIVE: list = []


def _tsdb(**extra):
    # the memory backend so store methods are monkeypatchable
    t = TSDB(Config(**{"tsd.core.auto_create_metrics": "true",
                       "tsd.storage.backend": "memory",
                       **extra}))
    _LIVE.append(t)
    return t


@pytest.fixture(autouse=True)
def _shutdown_tsdbs():
    """Every TSDB a test made is shut down after it: the fan-out
    tests start ``tsd-subq`` pools, and one left alive shows in a
    later module's thread census (the profiler's role counts)."""
    yield
    while _LIVE:
        _LIVE.pop().shutdown()


def _seed(t, metric="m", n=5, pts=50):
    rng = np.random.default_rng(0)
    for i in range(n):
        ts = BASE + np.sort(rng.choice(3000, pts, replace=False))
        t.add_points(metric, ts, rng.normal(10, 3, pts),
                     {"host": f"h{i}"})


def _q(metric="m", agg="sum", ds="1m-avg", start=BASE,
       end=BASE + 3000, **extra):
    sub = {"metric": metric, "aggregator": agg}
    if ds:
        sub["downsample"] = ds
    return TSQuery.from_json({
        "start": start * 1000, "end": end * 1000,
        "queries": [sub], **extra}).validate()


def _dps(results):
    return [(r.tags, r.dps) for r in results]


class TestInvalidation:
    """Every write class a query can read must invalidate: raw write,
    delete_range, rollup tier write, preagg write, annotation write."""

    def test_write_then_epoch_bump_then_miss(self):
        t = _tsdb()
        _seed(t)
        r1 = t.execute_query(_q())
        r2 = t.execute_query(_q())
        rc = t.result_cache
        assert rc.hits == 1 and rc.misses == 1
        assert _dps(r1) == _dps(r2)
        t.add_point("m", BASE + 10, 1000.0, {"host": "h0"})
        r3 = t.execute_query(_q())
        assert rc.hits == 1 and rc.misses == 2
        assert _dps(r3) != _dps(r1)

    def test_delete_range_misses(self):
        t = _tsdb()
        _seed(t)
        r1 = t.execute_query(_q())
        sids = t.store.series_ids_for_metric(
            t.uids.metrics.get_id("m"))
        t.store.delete_range(sids, BASE * 1000, (BASE + 200) * 1000)
        r2 = t.execute_query(_q())
        assert _dps(r2) != _dps(r1)
        assert t.result_cache.hits == 0

    def test_rollup_writes_invalidate_with_plan_precision(self):
        # invalidation is per-PLAN: a write to a store this query
        # does not read must NOT evict it (dashboards keep hitting
        # while unrelated tiers ingest) — but a write that flips the
        # plan's tier SELECTION must miss
        t = _tsdb(**{"tsd.rollups.enable": "true"})
        _seed(t)
        t.execute_query(_q(ds="1m-sum"))
        rc = t.result_cache
        # a preagg write does not touch the raw-served 1m-sum plan
        t.add_aggregate_point("m", BASE + 60, 5.0, {"host": "h0"},
                              True, None, None, "SUM")
        t.execute_query(_q(ds="1m-sum"))
        assert rc.hits == 1 and rc.misses == 1
        # the first point landing in the 1m sum tier flips the
        # plan's selection raw -> tier: must miss, and the tier-read
        # answer reflects tier data only
        t.add_aggregate_point("m", BASE + 60, 5.0, {"host": "h0"},
                              False, "1m", "sum")
        r = t.execute_query(_q(ds="1m-sum"))
        assert rc.hits == 1 and rc.misses == 2
        assert _dps(r) == [({"host": "h0"},
                            [((BASE + 60) * 1000, 5.0)])]
        # further tier writes keep invalidating the tier-served plan
        t.add_aggregate_point("m", BASE + 120, 7.0, {"host": "h0"},
                              False, "1m", "sum")
        r2 = t.execute_query(_q(ds="1m-sum"))
        assert rc.misses == 3 and _dps(r2) != _dps(r)

    def test_unrelated_raw_ingest_does_not_evict_tier_plan(self):
        # the north-star shape: dashboards answered from a rollup
        # tier must keep hitting while raw ingest streams in
        t = _tsdb(**{"tsd.rollups.enable": "true"})
        for ts_off in range(0, 600, 60):
            t.add_aggregate_point("r.m", BASE + ts_off, 10.0,
                                  {"host": "a"}, False, "1m", "sum")
        q = lambda: _q(metric="r.m", ds="1m-sum", end=BASE + 600)
        r1 = t.execute_query(q())
        t.add_point("other.metric", BASE + 1, 1.0, {"host": "x"})
        r2 = t.execute_query(q())
        rc = t.result_cache
        assert rc.hits == 1 and rc.misses == 1
        assert _dps(r1) == _dps(r2)

    def test_rollup_tier_query_invalidated_by_tier_write(self):
        # the query actually ANSWERED from a tier must see new tier
        # points (the tier store's own counters are in the version)
        t = _tsdb(**{"tsd.rollups.enable": "true"})
        for ts_off in range(0, 600, 60):
            t.add_aggregate_point("r.m", BASE + ts_off, 10.0,
                                  {"host": "a"}, False, "1m", "sum")
        q = lambda: _q(metric="r.m", ds="1m-sum", end=BASE + 600)
        r1 = t.execute_query(q())
        t.add_aggregate_point("r.m", BASE + 300, 99.0, {"host": "a"},
                              False, "1m", "sum")
        r2 = t.execute_query(q())
        assert _dps(r2) != _dps(r1)

    def test_annotation_write_invalidates(self):
        from opentsdb_tpu.meta.annotation import Annotation
        t = _tsdb()
        _seed(t)
        r1 = t.execute_query(_q())
        tsuid = r1[0].tsuids if r1[0].tsuids else None
        t.annotations.store(Annotation(
            tsuid="", start_time=BASE + 10, description="global"))
        t.execute_query(_q(globalAnnotations=True))
        # the plain query must also miss (version moved)
        t.execute_query(_q())
        assert t.result_cache.hits == 0

    def test_dropcaches_empties(self):
        t = _tsdb()
        _seed(t)
        t.execute_query(_q())
        rc = t.result_cache
        assert rc.total_entries == 1 and rc.total_bytes > 0
        t.drop_caches()
        assert rc.total_entries == 0 and rc.total_bytes == 0
        t.execute_query(_q())
        assert rc.misses == 2

    def test_delete_queries_bypass(self):
        t = _tsdb(**{"tsd.http.query.allow_delete": "true"})
        _seed(t)
        q = _q()
        q.delete = True
        t.execute_query(q)
        rc = t.result_cache
        assert rc.bypasses == 1 and rc.total_entries == 0
        # and the delete's epoch bump invalidates older entries too
        r = t.execute_query(_q())
        assert rc.misses == 1


class TestSingleFlight:
    def test_n_concurrent_identical_one_execution(self):
        t = _tsdb()
        _seed(t)
        calls = []
        release = threading.Event()
        orig = t.store.materialize_padded
        orig_flat = t.store.materialize

        def counted(*a, **k):
            calls.append(threading.get_ident())
            release.wait(5)
            return orig(*a, **k)

        def counted_flat(*a, **k):
            calls.append(threading.get_ident())
            release.wait(5)
            return orig_flat(*a, **k)

        t.store.materialize_padded = counted
        t.store.materialize = counted_flat
        n = 6
        results: list = [None] * n
        errors: list = []

        def worker(i):
            try:
                results[i] = t.execute_query(_q(ds=None))
            except Exception as exc:  # noqa: BLE001
                errors.append(exc)

        threads = [threading.Thread(target=worker, args=(i,))
                   for i in range(n)]
        for th in threads:
            th.start()
        # let every thread reach the cache before the leader finishes
        deadline = time.monotonic() + 5
        while t.result_cache.coalesced + len(calls) < n \
                and time.monotonic() < deadline:
            time.sleep(0.01)
        release.set()
        for th in threads:
            th.join(10)
        assert not errors, errors
        assert len(calls) == 1, f"engine executed {len(calls)} times"
        rc = t.result_cache
        assert rc.coalesced == n - 1 and rc.misses == 1
        base = _dps(results[0])
        for r in results[1:]:
            assert _dps(r) == base

    def test_failed_leader_propagates_and_does_not_poison(self):
        t = _tsdb()
        _seed(t)
        release = threading.Event()

        def boom(*a, **k):
            release.wait(5)
            raise OSError("injected scan failure")

        orig = t.store.materialize_padded
        orig_flat = t.store.materialize
        t.store.materialize_padded = boom
        t.store.materialize = boom
        n = 4
        errors: list = []

        def worker():
            try:
                t.execute_query(_q(ds=None))
            except OSError as exc:
                errors.append(exc)

        threads = [threading.Thread(target=worker) for _ in range(n)]
        for th in threads:
            th.start()
        deadline = time.monotonic() + 5
        rc = t.result_cache
        while rc.misses + rc.coalesced < n \
                and time.monotonic() < deadline:
            time.sleep(0.01)
        release.set()
        for th in threads:
            th.join(10)
        assert len(errors) == n
        assert rc.total_entries == 0  # the error was never cached
        # a recovered store answers correctly on the next query
        t.store.materialize_padded = orig
        t.store.materialize = orig_flat
        assert t.execute_query(_q(ds=None))


class TestRelativeTimeTTL:
    def test_relative_with_downsample_hits_within_ttl(self):
        t = _tsdb()
        _seed(t)
        now_ms = (BASE + 3000) * 1000

        def rq():
            return TSQuery.from_json({
                "start": "1h-ago",
                "queries": [{"metric": "m", "aggregator": "sum",
                             "downsample": "1m-avg"}]
            }).validate(now_ms=now_ms)

        r1 = t.execute_query(rq())
        r2 = t.execute_query(rq())
        rc = t.result_cache
        assert rc.hits == 1 and rc.misses == 1
        assert _dps(r1) == _dps(r2)

    def test_ttl_expiry_recomputes(self):
        t = _tsdb()
        _seed(t)
        now_ms = (BASE + 3000) * 1000
        rq = lambda: TSQuery.from_json({
            "start": "1h-ago",
            "queries": [{"metric": "m", "aggregator": "sum",
                         "downsample": "1m-avg"}]}).validate(
                             now_ms=now_ms)
        t.execute_query(rq())
        rc = t.result_cache
        # age the entry past its 60s (1m downsample) TTL
        rc._clock = lambda base=time.monotonic: base() + 61.0
        t.execute_query(rq())
        assert rc.hits == 0 and rc.misses == 2

    def test_relative_without_downsample_bypasses(self):
        t = _tsdb()
        _seed(t)
        now_ms = (BASE + 3000) * 1000
        tsq = TSQuery.from_json({
            "start": "1h-ago",
            "queries": [{"metric": "m", "aggregator": "sum"}]
        }).validate(now_ms=now_ms)
        t.execute_query(tsq)
        assert t.result_cache.bypasses == 1

    def test_absolute_entries_have_no_ttl(self):
        t = _tsdb()
        _seed(t)
        t.execute_query(_q())
        rc = t.result_cache
        rc._clock = lambda base=time.monotonic: base() + 3600.0
        t.execute_query(_q())
        assert rc.hits == 1


class TestEvictionAndBudget:
    def _results(self, nbytes):
        class R:
            dps_arrays = (np.zeros(max(nbytes // 16, 1)),
                          np.zeros(max(nbytes // 16, 1)))
            tsuids: list = []
            annotations: list = []
        return [R()]

    def test_byte_budget_evicts_lru(self):
        cache = QueryResultCache(8192, shards=1)
        v = (1,)
        for i in range(16):
            cache.get_or_compute(
                ("k", i), v, lambda: self._results(2048))
        assert cache.evicted > 0
        assert cache.total_bytes <= cache.max_bytes
        # the most recent key survived; the oldest was evicted
        assert cache._get(("k", 15), v, 0) is not None
        from opentsdb_tpu.query.result_cache import _MISSING
        assert cache._get(("k", 0), v, 0) is _MISSING

    def test_oversized_value_never_cached(self):
        cache = QueryResultCache(1024, shards=1)
        cache.get_or_compute(("big",), (1,),
                             lambda: self._results(1 << 20))
        assert cache.total_entries == 0

    def test_version_mismatch_drops_entry_bytes(self):
        cache = QueryResultCache(1 << 20, shards=2)
        cache.get_or_compute(("k",), (1,), lambda: self._results(512))
        b1 = cache.total_bytes
        assert b1 > 0
        cache.get_or_compute(("k",), (2,), lambda: self._results(512))
        assert cache.total_bytes == b1  # replaced, not leaked
        assert cache.total_entries == 1

    def test_cache_mb_zero_disables(self):
        t = _tsdb(**{"tsd.query.cache.mb": "0"})
        _seed(t)
        t.execute_query(_q())
        assert t.result_cache is None

    def test_enable_false_disables_but_is_runtime_togglable(self):
        t = _tsdb(**{"tsd.query.cache.enable": "false"})
        _seed(t)
        t.execute_query(_q())
        assert t.result_cache is None
        t.config.override_config("tsd.query.cache.enable", "true")
        t.execute_query(_q())
        t.execute_query(_q())
        assert t.result_cache.hits == 1


class TestFanout:
    def _multi_q(self, n, metric="m", start=BASE, end=BASE + 3000):
        return TSQuery.from_json({
            "start": start * 1000, "end": end * 1000,
            "queries": [{"metric": metric, "aggregator": agg,
                         "downsample": "1m-avg"}
                        for agg in ("sum", "max", "min", "avg",
                                    "count")[:n]]}).validate()

    def test_ordering_and_stats_attribution(self):
        from opentsdb_tpu.stats.stats import QueryStat, QueryStats
        t = _tsdb()
        _seed(t)
        stats = QueryStats(remote="test", query=None)
        results = t.new_query().run(self._multi_q(4), stats)
        stats.mark_complete()
        # per-sub ordering: results arrive grouped by sub index,
        # ascending, regardless of completion order
        idxs = [r.sub_query_index for r in results]
        assert idxs == sorted(idxs) and set(idxs) == {0, 1, 2, 3}
        # per-sub attribution: each of the 4 subs recorded its scan
        assert stats.stats[QueryStat.SUCCESSFUL_SCAN.value] == 4
        # and matches a serial run exactly
        t2 = _tsdb(**{"tsd.query.fanout.workers": "0"})
        _seed(t2)
        serial = t2.new_query().run(self._multi_q(4), None)
        assert _dps(results) == _dps(serial)

    def test_parallel_faster_than_serial_on_4_subs(self):
        # a store stub with a fixed per-scan latency makes the speedup
        # deterministic: 4 subs x 150 ms serial vs ~150 ms fanned out
        delay = 0.15

        def slow_store(t):
            orig = t.store.bucket_reduce

            def slow(*a, **k):
                time.sleep(delay)
                return orig(*a, **k)
            t.store.bucket_reduce = slow

        t_par = _tsdb()
        _seed(t_par)
        t_ser = _tsdb(**{"tsd.query.fanout.workers": "0"})
        _seed(t_ser)
        # warm both engines (compile/upload) before timing
        t_par.execute_query(self._multi_q(4))
        t_ser.execute_query(self._multi_q(4))
        slow_store(t_par)
        slow_store(t_ser)
        q = self._multi_q(4, start=BASE + 1)  # new window: no hits
        t0 = time.perf_counter()
        r_par = t_par.execute_query(q)
        par_s = time.perf_counter() - t0
        q = self._multi_q(4, start=BASE + 1)
        t0 = time.perf_counter()
        r_ser = t_ser.execute_query(q)
        ser_s = time.perf_counter() - t0
        assert _dps(r_par) == _dps(r_ser)
        assert ser_s >= 4 * delay
        assert par_s < ser_s - delay, (par_s, ser_s)

    def test_fanout_error_propagates_earliest_sub(self):
        t = _tsdb()
        _seed(t)
        with pytest.raises(Exception) as exc_info:
            t.execute_query(TSQuery.from_json({
                "start": BASE * 1000, "end": (BASE + 3000) * 1000,
                "queries": [
                    {"metric": "m", "aggregator": "sum"},
                    {"metric": "no.such.metric",
                     "aggregator": "sum"},
                    {"metric": "m", "aggregator": "max"},
                ]}).validate())
        assert "no.such.metric" in str(exc_info.value)

    def test_identical_subs_in_one_query_coalesce(self):
        # POST bodies keep duplicate subs; fanned out in parallel they
        # single-flight onto one execution and both get results
        t = _tsdb()
        _seed(t)
        tsq = TSQuery.from_json({
            "start": BASE * 1000, "end": (BASE + 3000) * 1000,
            "queries": [{"metric": "m", "aggregator": "sum",
                         "downsample": "1m-avg"}] * 2}).validate()
        results = t.execute_query(tsq)
        idxs = sorted({r.sub_query_index for r in results})
        assert idxs == [0, 1]
        rc = t.result_cache
        assert rc.misses == 1
        assert rc.coalesced + rc.hits == 1


class TestCacheKeying:
    def test_output_flags_are_part_of_the_key(self):
        t = _tsdb()
        _seed(t)
        t.execute_query(_q())
        t.execute_query(_q(showTSUIDs=True))
        rc = t.result_cache
        assert rc.misses == 2 and rc.hits == 0
        r = t.execute_query(_q(showTSUIDs=True))
        assert rc.hits == 1
        assert r[0].tsuids

    def test_sub_index_relabeled_on_cross_query_hit(self):
        t = _tsdb()
        _seed(t, metric="a")
        _seed(t, metric="b")
        tsq = TSQuery.from_json({
            "start": BASE * 1000, "end": (BASE + 3000) * 1000,
            "queries": [
                {"metric": "a", "aggregator": "sum",
                 "downsample": "1m-avg"},
                {"metric": "b", "aggregator": "sum",
                 "downsample": "1m-avg"}]}).validate()
        t.execute_query(tsq)
        # sub "b" alone now hits the cached entry (keyed without the
        # index) but must carry ITS index, 0
        rb = t.execute_query(_q(metric="b"))
        assert t.result_cache.hits == 1
        assert all(r.sub_query_index == 0 for r in rb)


@pytest.mark.robustness
class TestTierDegradation:
    """Fault sites in lazily-created rollup tier stores (ROADMAP open
    item): an armed ``rollup.store`` site fails TIER scans only, the
    result cache is never poisoned by the failure, and recovery
    resumes caching."""

    def _tier_tsdb(self):
        t = _tsdb(**{"tsd.rollups.enable": "true"})
        for ts_off in range(0, 600, 60):
            t.add_aggregate_point("r.m", BASE + ts_off, 10.0,
                                  {"host": "a"}, False, "1m", "sum")
        _seed(t)  # raw data rides along
        return t

    def test_lazily_created_tiers_carry_fault_sites(self):
        t = self._tier_tsdb()
        tier = t.rollup_store.tier("1m", "sum")
        assert tier.fault_injector is t.faults
        assert tier.fault_site == "rollup.store"
        assert t.rollup_store.preagg_store().fault_site \
            == "rollup.store"

    def test_degraded_tier_fails_loudly_and_cache_unpoisoned(self):
        t = self._tier_tsdb()
        q = lambda: _q(metric="r.m", ds="1m-sum", end=BASE + 600)
        r1 = t.execute_query(q())
        assert r1
        t.faults.arm("rollup.store", error_count=10)
        # the tier-answered query now fails mid-flight; raw-store
        # queries are untouched (distinct site). The in-window tier
        # write both invalidates and changes the eventual answer
        # (last write wins on the duplicate timestamp).
        t.add_aggregate_point("r.m", BASE + 300, 99.0, {"host": "a"},
                              False, "1m", "sum")
        with pytest.raises(OSError):
            t.execute_query(q())
        assert t.execute_query(_q())  # raw path unaffected
        rc = t.result_cache
        entries_during_fault = rc.total_entries
        # recovery: disarm, recompute, re-cache — and the answer
        # reflects the tier write that landed before the fault
        t.faults.disarm("rollup.store")
        r2 = t.execute_query(q())
        assert _dps(r2) != _dps(r1)
        assert rc.total_entries == entries_during_fault + 1
        r3 = t.execute_query(q())
        assert _dps(r3) == _dps(r2)


class TestWaiterReadAfterWrite:
    """A waiter that captured a NEWER serve version than the flight
    leader must not share the leader's (pre-write) result — it
    re-enters and computes under its own version."""

    def test_newer_version_waiter_recomputes(self):
        cache = QueryResultCache(1 << 20, shards=1)
        in_compute = threading.Event()
        release = threading.Event()

        def slow_old():
            in_compute.set()
            release.wait(5)
            return ["old"]

        out = {}

        def leader():
            out["leader"] = cache.get_or_compute(
                ("k",), (1,), slow_old)

        def waiter():
            in_compute.wait(5)
            # version (2,): a write landed after the leader started
            out["waiter"] = cache.get_or_compute(
                ("k",), (2,), lambda: ["new"])

        tl = threading.Thread(target=leader)
        tw = threading.Thread(target=waiter)
        tl.start()
        in_compute.wait(5)
        tw.start()
        time.sleep(0.1)  # waiter is parked on the flight
        release.set()
        tl.join(5)
        tw.join(5)
        assert out["leader"] == (["old"], "miss")
        value, outcome = out["waiter"]
        assert value == ["new"]          # NOT the stale leader value
        # and the stale entry does not satisfy version (2,) lookups
        got, how = cache.get_or_compute(("k",), (2,),
                                        lambda: ["recomputed"])
        assert got == ["new"] and how == "hit"

    def test_same_version_waiter_still_coalesces(self):
        cache = QueryResultCache(1 << 20, shards=1)
        in_compute = threading.Event()
        release = threading.Event()
        calls = []

        def slow():
            calls.append(1)
            in_compute.set()
            release.wait(5)
            return ["v"]

        out = {}
        tl = threading.Thread(target=lambda: out.update(
            leader=cache.get_or_compute(("k",), (1,), slow)))
        tw = threading.Thread(target=lambda: (
            in_compute.wait(5),
            out.update(waiter=cache.get_or_compute(
                ("k",), (1,), slow))))
        tl.start()
        in_compute.wait(5)
        tw.start()
        time.sleep(0.1)
        release.set()
        tl.join(5)
        tw.join(5)
        assert len(calls) == 1
        assert out["waiter"] == (["v"], "coalesced")

    def test_flight_completes_even_when_put_fails(self):
        cache = QueryResultCache(1 << 20, shards=1)
        orig_put = cache._put
        cache._put = lambda *a, **k: (_ for _ in ()).throw(
            RuntimeError("bookkeeping"))
        value, outcome = cache.get_or_compute(
            ("k",), (1,), lambda: ["v"])
        assert value == ["v"] and outcome == "miss"
        assert not cache._inflight  # no dead flight left behind
        cache._put = orig_put
        # and the key is immediately usable again
        assert cache.get_or_compute(("k",), (1,),
                                    lambda: ["w"])[0] == ["w"]


class TestDeleteQueriesStaySerial:
    def test_multi_sub_delete_never_fans_out(self, monkeypatch):
        # a sub's delete_range mutates series buffers in place while a
        # parallel sibling may hold live views: delete=true must take
        # the serial path regardless of the fan-out pool
        from opentsdb_tpu.query.engine import QueryEngine
        t = _tsdb(**{"tsd.http.query.allow_delete": "true"})
        _seed(t)

        def no_fanout(*a, **k):
            raise AssertionError("delete query took the fan-out path")

        monkeypatch.setattr(QueryEngine, "_run_fanout", no_fanout)
        tsq = TSQuery.from_json({
            "start": BASE * 1000, "end": (BASE + 3000) * 1000,
            "queries": [{"metric": "m", "aggregator": "sum"},
                        {"metric": "m", "aggregator": "max"}]
        }).validate()
        tsq.delete = True
        results = t.execute_query(tsq)
        # scanned-and-deleted: the first sub still reports the data...
        assert any(r.sub_query_index == 0 and r.num_dps for r in results)
        # ...and the data is gone afterwards
        assert t.execute_query(_q(ds=None)) == []
        # non-delete multi-sub queries still fan out
        with pytest.raises(AssertionError, match="fan-out"):
            t.execute_query(TSQuery.from_json({
                "start": BASE * 1000, "end": (BASE + 3000) * 1000,
                "queries": [{"metric": "m", "aggregator": "sum"},
                            {"metric": "m", "aggregator": "max"}]
            }).validate())
