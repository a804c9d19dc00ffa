"""End-to-end /api/query benchmark over the BASELINE.json configs.

Times the FULL query path the TSD server runs — store materialize ->
filter/group construction -> device pipeline -> result assembly ->
HTTP JSON serialization — not just the device kernels.
This is the north-star measurement: p50 latency of config 3
(1M series x 1h@1s, 5m avg downsample + rate) answered from the 1m
rollup tier, target < 2 s (BASELINE.json "north_star";
ref: the single-threaded Java iterator chain behind
/root/reference/src/core/TsdbQuery.java:742).

Data setup writes the rollup tiers directly through the store layer —
in the reference, rollups are also produced by external jobs and
written through the API (SURVEY.md §2.3), so a query benchmark may
legitimately start from populated tiers. Raw configs (1, 2) ingest
through ``tsdb.add_points``.

Usage: python bench_e2e.py [--cpu] [--configs 1,2,3,4] [--repeats N]
Prints one JSON line per config plus a summary line. Every row names
the platform, device kind and device count it ran on; without --cpu a
default backend that is not a TPU is an error.
"""

from __future__ import annotations

import argparse
import json
import sys
import time

import numpy as np

BASE_S = 1356998400
BASE_MS = BASE_S * 1000


def _percentile(times: list[float], q: float) -> float:
    return float(np.percentile(np.asarray(times), q))


def _run_query(tsdb, serializer, query_obj, repeats: int
               ) -> tuple[dict, bytes]:
    """Execute + serialize `repeats` times; returns timing stats and
    the last response body. One untimed warmup run absorbs the
    first-compile cost (recorded as cold_ms) — production servers
    pre-compile the shape buckets at start (tsd.tpu.warmup), so warm
    timings are the steady-state number and the criterion is
    max_ms < 2x p50 across the timed runs."""
    from opentsdb_tpu.query.model import TSQuery
    times = []
    body = b""
    # the serve-path RESULT cache is disabled for the warm loop so
    # p50 stays comparable with earlier rounds (it measures the real
    # scan -> pipeline -> serialize chain); the repeat-query loop at
    # the end re-enables it and reports the cache-hit numbers
    tsdb.config.override_config("tsd.query.cache.enable", "false")
    # server-start warmup first (tsd.tpu.warmup): cold_ms below then
    # measures the first query of a WARMED server — the production
    # number (VERDICT r03 #3: cold tails were 14-16s unwarmed)
    from opentsdb_tpu.tsd.warmup import run_warmup
    t0 = time.perf_counter()
    run_warmup(tsdb)
    warmup_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    tsq = TSQuery.from_json(query_obj).validate()
    tsdb.execute_query(tsq)
    cold = time.perf_counter() - t0
    exec_times, ser_times = [], []
    for _ in range(repeats):
        t0 = time.perf_counter()
        tsq = TSQuery.from_json(query_obj).validate()
        results = tsdb.execute_query(tsq)
        t1 = time.perf_counter()
        body = serializer.format_query(tsq, results)
        t2 = time.perf_counter()
        times.append(t2 - t0)
        exec_times.append(t1 - t0)
        ser_times.append(t2 - t1)
    # per-stage breakdown (VERDICT r4 weak #1: no stage evidence in
    # the artifact even though QueryStats exists): one extra run
    # traced through QueryStats, plus the engine/serializer split
    # medians from the timed runs above
    from opentsdb_tpu.stats.stats import QueryStats
    st = QueryStats(remote="bench_e2e", query=None)
    tsq = TSQuery.from_json(query_obj).validate()
    tsdb.new_query().run(tsq, st)
    st.mark_complete()
    stages = {k: round(v, 1) for k, v in sorted(st.stats.items())}
    stages["engineMedianMs"] = round(_percentile(exec_times, 50) * 1e3,
                                     1)
    stages["serializeMedianMs"] = round(
        _percentile(ser_times, 50) * 1e3, 1)
    # repeat-query (cache-hit) metric: the same dashboard refresh
    # answered from the serve-path result cache — one populating run,
    # then timed hits. repeat_exec is the engine-only number (what the
    # cache removes); repeat_p50 includes serialization, which a hit
    # still pays.
    tsdb.config.override_config("tsd.query.cache.enable", "true")
    tsq = TSQuery.from_json(query_obj).validate()
    tsdb.execute_query(tsq)  # populate
    hit_full, hit_exec = [], []
    for _ in range(repeats):
        t0 = time.perf_counter()
        tsq = TSQuery.from_json(query_obj).validate()
        results = tsdb.execute_query(tsq)
        t1 = time.perf_counter()
        serializer.format_query(tsq, results)
        t2 = time.perf_counter()
        hit_exec.append(t1 - t0)
        hit_full.append(t2 - t0)
    rcache = tsdb.result_cache
    assert rcache is not None and rcache.hits >= repeats, \
        "repeat loop did not hit the result cache"
    repeat_exec_p50 = _percentile(hit_exec, 50) * 1e3
    warm_exec_p50 = _percentile(exec_times, 50) * 1e3
    out_extra = {
        "repeat_p50_ms": round(_percentile(hit_full, 50) * 1e3, 1),
        "repeat_exec_p50_ms": round(repeat_exec_p50, 2),
        "cache_speedup": round(
            warm_exec_p50 / max(repeat_exec_p50, 1e-3), 1),
    }
    return {
        **out_extra,
        "p50_ms": round(_percentile(times, 50) * 1e3, 1),
        "min_ms": round(min(times) * 1e3, 1),
        "max_ms": round(max(times) * 1e3, 1),
        "cold_ms": round(cold * 1e3, 1),
        "warmup_s": round(warmup_s, 1),
        "runs": repeats,
        "stages": stages,
    }, body


def _mk_tsdb(rollups: bool = False):
    from opentsdb_tpu import TSDB, Config
    cfg = {
        "tsd.core.auto_create_metrics": "true",
        "tsd.storage.backend": "native",
    }
    if rollups:
        cfg["tsd.rollups.enable"] = "true"
    return TSDB(Config(**cfg))


def bench_config1(repeats: int) -> dict:
    """1k series x 1h @ 10s, avg downsample 1m (ref: CliQuery path)."""
    tsdb = _mk_tsdb()
    ts = np.arange(BASE_S, BASE_S + 3600, 10, dtype=np.int64)
    rng = np.random.default_rng(0)
    t0 = time.perf_counter()
    for i in range(1000):
        tsdb.add_points("sys.bench1", ts,
                        rng.normal(100, 10, len(ts)),
                        {"host": f"h{i:04d}"})
    ingest_s = time.perf_counter() - t0
    n = 1000 * len(ts)
    stats, body = _run_query(
        tsdb, _serializer(), {
            "start": BASE_MS, "end": BASE_MS + 3_600_000,
            "queries": [{"metric": "sys.bench1", "aggregator": "avg",
                         "downsample": "1m-avg"}]}, repeats)
    return {"config": 1, "series": 1000, "points": n,
            "ingest_mpps": round(n / ingest_s / 1e6, 2),
            "resp_bytes": len(body), **stats}


def bench_config2(repeats: int) -> dict:
    """100k series, sum+max multi-aggregator, wildcard tagv group-by
    (ref: GroupByAndAggregateCB + TagVWildcardFilter)."""
    tsdb = _mk_tsdb()
    n_series = 100_000
    pts_per = 30  # 30m @ 1/min
    ts = np.arange(BASE_S, BASE_S + pts_per * 60, 60, dtype=np.int64)
    rng = np.random.default_rng(1)
    vals = rng.normal(50, 5, (n_series, pts_per))
    t0 = time.perf_counter()
    for i in range(n_series):
        tsdb.add_points("sys.bench2", ts, vals[i],
                        {"host": f"h{i % 1000:04d}",
                         "task": f"t{i // 1000:03d}"})
    ingest_s = time.perf_counter() - t0
    n = n_series * pts_per
    stats, body = _run_query(
        tsdb, _serializer(), {
            "start": BASE_MS, "end": BASE_MS + pts_per * 60_000,
            "queries": [
                {"metric": "sys.bench2", "aggregator": "sum",
                 "filters": [{"type": "wildcard", "tagk": "host",
                              "filter": "*", "groupBy": True}]},
                {"metric": "sys.bench2", "aggregator": "max",
                 "filters": [{"type": "wildcard", "tagk": "host",
                              "filter": "*", "groupBy": True}]},
            ]}, repeats)
    return {"config": 2, "series": n_series, "points": n,
            "groups": 1000, "ingest_mpps": round(n / ingest_s / 1e6, 2),
            "resp_bytes": len(body), **stats}


def _populate_tier(tsdb, metric: str, n_series: int, n_buckets: int,
                   interval_ms: int, chunk: int = 50_000) -> float:
    """Write 1m rollup tiers (sum/count/min/max) for n_series, each
    with n_buckets aligned points — the state an external rollup job
    leaves behind (ref: TSDB.addAggregatePoint writers)."""
    from opentsdb_tpu.rollup.job import ROLLUP_AGGS
    mid = tsdb.uids.metrics.get_or_create_id(metric)
    kid = tsdb.uids.tag_names.get_or_create_id("host")
    bucket_ts = BASE_MS + np.arange(n_buckets, dtype=np.int64) \
        * interval_ms
    rng = np.random.default_rng(2)
    t0 = time.perf_counter()
    mask = np.ones((0, n_buckets), dtype=bool)
    for lo in range(0, n_series, chunk):
        hi = min(lo + chunk, n_series)
        tags_list = [((kid, tsdb.uids.tag_values.get_or_create_id(
            f"h{i:07d}")),) for i in range(lo, hi)]
        sids = {}
        for agg in ROLLUP_AGGS:
            sids[agg] = tsdb.rollup_store.tier("1m", agg) \
                .get_or_create_series_bulk(mid, tags_list)
        m = hi - lo
        if mask.shape[0] != m:
            mask = np.ones((m, n_buckets), dtype=bool)
        base_vals = rng.normal(100, 10, (m, n_buckets))
        grids = {"sum": base_vals * 60.0,
                 "count": np.full((m, n_buckets), 60.0),
                 "min": base_vals - 3.0, "max": base_vals + 3.0}
        for agg in ROLLUP_AGGS:
            tsdb.rollup_store.tier(agg=agg, interval="1m") \
                .append_grid(sids[agg], bucket_ts, grids[agg], mask)
    return time.perf_counter() - t0


def bench_config3(repeats: int, n_series: int = 1_000_000) -> dict:
    """North star: 1M series x 1h@1s, 5m avg downsample + rate,
    answered from the 1m rollup tier (sum/count division) — the only
    tier-correct way to satisfy the < 2 s budget; the raw window is
    3.6e9 points (ref: TsdbQuery rollup best-match :143, RollupSpan
    sum/count qualifiers)."""
    tsdb = _mk_tsdb(rollups=True)
    setup_s = _populate_tier(tsdb, "sys.bench3", n_series, 60, 60_000)
    raw_equiv = n_series * 3600          # 1h @ 1s
    tier_pts = n_series * 60 * 2         # sum + count read by the query
    stats, body = _run_query(
        tsdb, _serializer(), {
            "start": BASE_MS, "end": BASE_MS + 3_600_000,
            "queries": [{"metric": "sys.bench3", "aggregator": "sum",
                         "downsample": "5m-avg", "rate": True}]},
        repeats)
    return {"config": 3, "series": n_series,
            "raw_equiv_points": raw_equiv, "tier_points": tier_pts,
            "setup_s": round(setup_s, 1), "resp_bytes": len(body),
            **stats, "north_star_pass": stats["p50_ms"] < 2000.0}


def bench_config4(repeats: int, n_series: int = 200_000) -> dict:
    """p99/p999 percentiles over histogram series (ref:
    SimpleHistogram.percentile via the device merge kernel)."""
    from opentsdb_tpu.core.histogram import SimpleHistogram
    tsdb = _mk_tsdb()
    bounds = [float(b) for b in np.logspace(0, 4, 65)]
    rng = np.random.default_rng(3)
    all_counts = rng.integers(0, 50, (n_series, 64))
    t0 = time.perf_counter()
    batch = []
    for i in range(n_series):
        h = SimpleHistogram(bounds)
        h.counts = all_counts[i].tolist()
        batch.append(("sys.bench4", BASE_S,
                      tsdb.histogram_manager.encode(h),
                      {"host": f"h{i:07d}"}))
        if len(batch) == 25_000:
            tsdb.add_histogram_batch(batch)
            batch = []
    if batch:
        tsdb.add_histogram_batch(batch)
    ingest_s = time.perf_counter() - t0
    stats, body = _run_query(
        tsdb, _serializer(), {
            "start": BASE_MS, "end": BASE_MS + 60_000,
            "queries": [{"metric": "sys.bench4", "aggregator": "sum",
                         "percentiles": [99.0, 99.9]}]}, repeats)
    return {"config": 4, "series": n_series,
            "ingest_s": round(ingest_s, 1), "resp_bytes": len(body),
            **stats}


def bench_config5(repeats: int, n_series: int = 100_000,
                  hours: int = 2) -> dict:
    """Rollup job: raw @1s -> 1m/1h tiers (ref: BASELINE config 5;
    RollupUtils.java:27, TSDB.java:1320). Sized to the bench host's
    RAM; the reported rate is raw points processed per second, which
    scales linearly in series count (the job streams fixed-size
    series-chunk x window tiles)."""
    from opentsdb_tpu.rollup.job import run_rollup_job
    tsdb = _mk_tsdb(rollups=True)
    span = hours * 3600
    # ingest raw @1s via bulk grids: [chunk, span] per chunk
    rng = np.random.default_rng(5)
    t0 = time.perf_counter()
    ts_grid = BASE_MS + np.arange(span, dtype=np.int64) * 1000
    chunk = max(1, 20_000_000 // span)
    mid = tsdb.uids.metrics.get_or_create_id("sys.bench5")
    kid = tsdb.uids.tag_names.get_or_create_id("host")
    mask = np.ones((chunk, span), dtype=bool)
    for lo in range(0, n_series, chunk):
        hi = min(lo + chunk, n_series)
        sids = np.asarray([
            tsdb.store.get_or_create_series(
                mid, [(kid, tsdb.uids.tag_values.get_or_create_id(
                    f"h{i:07d}"))])
            for i in range(lo, hi)], dtype=np.int64)
        vals = rng.normal(100, 10, (hi - lo, span))
        tsdb.store.append_grid(sids, ts_grid,
                               vals, mask[:hi - lo])
    n_raw = n_series * span
    ingest_s = time.perf_counter() - t0
    times = []
    written = None
    for _ in range(max(1, repeats)):
        # fresh tier stores per run so repeats measure the same work
        tsdb.rollup_store._tiers.clear()
        tsdb.rollup_store._has_data_cache.clear()
        t0 = time.perf_counter()
        written = run_rollup_job(tsdb, BASE_MS,
                                 BASE_MS + span * 1000 - 1,
                                 intervals=["1m", "1h"])
        times.append(time.perf_counter() - t0)
    job_s = min(times)
    return {"config": 5, "series": n_series, "raw_points": n_raw,
            "hours": hours,
            "ingest_mpps": round(n_raw / ingest_s / 1e6, 1),
            "rollup_written": written,
            "job_s": round(job_s, 1), "runs": len(times),
            "job_raw_mpps": round(n_raw / job_s / 1e6, 1)}


def bench_live(repeats: int, n_series: int = 5_000,
               span_s: int = 1800) -> dict:
    """Live-dashboard config: a standing query maintained by the
    continuous-query subsystem under sustained ingest. Reports the
    p50 of a refresh served from maintained windows (fold pending +
    pipeline tail, no store scan) vs the p50 of a full recompute
    (streaming serve + result cache disabled: scan -> grid -> tail),
    plus the SSE push latency from acknowledged write to delivered
    event. Acceptance: incremental refresh >= 10x cheaper than full
    recompute."""
    from opentsdb_tpu.query.model import TSQuery
    tsdb = _mk_tsdb()
    # explicit flush-driven publishes only: the bench times the push
    # itself, not the rate limiter
    tsdb.config.override_config(
        "tsd.streaming.publish_min_interval_ms", "1000000000")
    rng = np.random.default_rng(11)
    mid = tsdb.uids.metrics.get_or_create_id("sys.live")
    kid = tsdb.uids.tag_names.get_or_create_id("host")
    ts_grid = BASE_MS + np.arange(span_s, dtype=np.int64) * 1000
    chunk = max(1, 10_000_000 // span_s)
    t0 = time.perf_counter()
    for lo in range(0, n_series, chunk):
        hi = min(lo + chunk, n_series)
        sids = np.asarray([
            tsdb.store.get_or_create_series(
                mid, [(kid, tsdb.uids.tag_values.get_or_create_id(
                    f"h{i:05d}"))])
            for i in range(lo, hi)], dtype=np.int64)
        vals = rng.normal(100, 10, (hi - lo, span_s))
        tsdb.store.append_grid(sids, ts_grid, vals,
                               np.ones((hi - lo, span_s), dtype=bool))
    ingest_s = time.perf_counter() - t0
    end_ms = BASE_MS + span_s * 1000
    qobj = {"start": BASE_MS, "end": end_ms,
            "queries": [{"metric": "sys.live", "aggregator": "sum",
                         "downsample": "1m-avg"}]}
    reg = tsdb.streaming
    cq = reg.register(qobj, now_ms=end_ms)

    def run_query():
        return tsdb.execute_query(TSQuery.from_json(qobj).validate())

    def run_full():
        tsdb.config.override_config("tsd.streaming.serve", "false")
        tsdb.config.override_config("tsd.query.cache.enable", "false")
        try:
            t0 = time.perf_counter()
            run_query()
            return time.perf_counter() - t0
        finally:
            tsdb.config.override_config("tsd.streaming.serve", "true")
            tsdb.config.override_config("tsd.query.cache.enable",
                                        "true")
    run_query()   # warm the incremental tail compile
    run_full()    # warm the batch pipeline compile
    sub = reg.subscribe(cq)
    while not sub.queue.empty():
        sub.queue.get_nowait()  # drop the snapshot
    rounds = max(repeats, 5)
    incr, full, sse_lat = [], [], []
    tick_hosts = min(n_series, 500)
    for r in range(rounds):
        # sustained ingest: one fresh point per tick host, landing in
        # the live window
        ts_s = BASE_MS // 1000 + span_s - 30 + (r % 20)
        for j in range(tick_hosts):
            tsdb.add_point("sys.live", ts_s, 100.0 + r,
                           {"host": f"h{j:05d}"})
        hits0 = reg.serve_hits
        t0 = time.perf_counter()
        run_query()
        incr.append(time.perf_counter() - t0)
        assert reg.serve_hits == hits0 + 1, \
            "refresh was not served from maintained windows"
        while not sub.queue.empty():
            sub.queue.get_nowait()
        t0 = time.perf_counter()
        tsdb.add_point("sys.live", ts_s, 1.0, {"host": "h00000"})
        reg.flush()
        sub.queue.get(timeout=10)
        sse_lat.append(time.perf_counter() - t0)
        full.append(run_full())
    incr_p50 = _percentile(incr, 50) * 1e3
    full_p50 = _percentile(full, 50) * 1e3
    speedup = full_p50 / max(incr_p50, 1e-3)
    return {"config": "live", "series": n_series,
            "points": n_series * span_s,
            "ingest_mpps": round(n_series * span_s / ingest_s / 1e6, 1),
            "tick_points": tick_hosts,
            "incremental_p50_ms": round(incr_p50, 2),
            "full_p50_ms": round(full_p50, 2),
            "refresh_speedup": round(speedup, 1),
            "sse_push_p50_ms": round(_percentile(sse_lat, 50) * 1e3, 2),
            "rounds": rounds,
            "criterion_pass": bool(speedup >= 10.0)}


def bench_streamv2(repeats: int, n_ticks: int = 400,
                   n_points_fold: int = 240_000) -> dict:
    """Streaming engine v2: (1) durable per-point ingest p50 with
    0 / 10 / 50 standing tumbling CQs over the ingested metric — the
    tap is an O(1) enqueue into shared partials and folds run on the
    worker pool, so the 50-CQ tax must stay <= 1.25x the zero-CQ
    p50; (2) shared-plan fold scaling — total fold time for 16 CQs
    sharing one (metric, downsample) <= 2x a single CQ's (one
    partial array serves all 16); (3) sliding-window serve p50 from
    the maintained partials; (4) a tier-seeded bootstrap serving a
    pre-demotion-boundary window incrementally (no batch fallback)."""
    import shutil
    import tempfile
    from opentsdb_tpu import TSDB, Config
    from opentsdb_tpu.query.model import TSQuery

    end_ms = BASE_MS + 1800 * 1000
    fns = ["1m-sum", "1m-avg", "1m-max", "1m-min", "1m-count",
           "2m-sum", "2m-avg", "2m-max", "2m-min", "2m-count"]
    aggs = ["sum", "avg", "max", "min", "sum"]

    def qobj(i=0, ds=None):
        return {"start": BASE_MS, "end": end_ms, "queries": [
            {"metric": "sys.sv2", "aggregator": aggs[i % len(aggs)],
             "downsample": ds or fns[i % len(fns)]}]}

    # --- (1) durable ingest tax at 0 / 10 / 50 standing CQs
    def ingest_p50_us(n_cqs: int) -> float:
        d = tempfile.mkdtemp(prefix="sv2bench-")
        t = TSDB(Config(**{
            "tsd.core.auto_create_metrics": "true",
            "tsd.storage.backend": "memory",
            "tsd.storage.data_dir": d}))
        try:
            for i in range(n_cqs):
                t.streaming.register(qobj(i), now_ms=end_ms)
            best = None
            for _ in range(max(repeats, 3)):
                times = []
                for i in range(n_ticks):
                    t0 = time.perf_counter()
                    t.add_point("sys.sv2", BASE_S + i, 1.0,
                                {"host": f"h{i % 8:02d}"})
                    times.append(time.perf_counter() - t0)
                p50 = _percentile(times, 50) * 1e6
                best = p50 if best is None else min(best, p50)
            return best
        finally:
            t.shutdown()
            shutil.rmtree(d, ignore_errors=True)

    p50_0 = ingest_p50_us(0)
    p50_10 = ingest_p50_us(10)
    p50_50 = ingest_p50_us(50)
    tax_10 = p50_10 / max(p50_0, 1e-3)
    tax_50 = p50_50 / max(p50_0, 1e-3)

    # --- (2) shared-plan fold scaling: 1 CQ vs 16 CQs, same
    # (metric, downsample) — workers off so the drain is timed
    # deterministically on this thread
    def fold_time_s(n_cqs: int) -> float:
        t = TSDB(Config(**{
            "tsd.core.auto_create_metrics": "true",
            "tsd.streaming.workers.count": "0",
            "tsd.streaming.buffer_points": str(1 << 30),
            "tsd.streaming.workers.max_pending_points":
                str(1 << 30)}))
        reg = t.streaming
        for i in range(n_cqs):
            obj = qobj(0)
            obj["id"] = f"f{i}"
            reg.register(obj, now_ms=end_ms)
        rng = np.random.default_rng(3)
        n_series = 64
        per = n_points_fold // n_series
        ts = BASE_MS + (np.arange(per, dtype=np.int64) * 1800_000
                        // per)
        best = None
        for _ in range(max(repeats, 3)):
            for g in reg._partials:
                g.take_pending()
            for i in range(n_series):
                t.add_points("sys.sv2", ts + i % 7,
                             rng.normal(100, 10, per),
                             {"host": f"h{i:03d}"})
            groups = list(reg._partials)
            t0 = time.perf_counter()
            for g in groups:
                reg._drain_group(g)
            dt = time.perf_counter() - t0
            best = dt if best is None else min(best, dt)
        folded = sum(g.points_folded for g in reg._partials)
        assert folded >= n_points_fold, folded
        return best

    fold_1 = fold_time_s(1)
    fold_16 = fold_time_s(16)
    fold_ratio = fold_16 / max(fold_1, 1e-9)

    # --- (3) sliding-window serve p50 from maintained partials
    t = TSDB(Config(**{"tsd.core.auto_create_metrics": "true"}))
    rng = np.random.default_rng(5)
    ts = np.arange(BASE_S, BASE_S + 1800, 2, dtype=np.int64)
    for i in range(200):
        t.add_points("sys.sv2", ts, rng.normal(100, 10, len(ts)),
                     {"host": f"h{i:03d}"})
    cq = t.streaming.register(
        dict(qobj(0, ds="1m-sum"),
             window={"type": "sliding", "size": "5m"}),
        now_ms=end_ms)
    t.streaming.current_results(cq, now_ms=end_ms)  # warm the tail
    sliding = []
    for r in range(max(repeats, 5)):
        t.add_point("sys.sv2", BASE_S + 1700 + r, 1.0,
                    {"host": "h000"})
        t0 = time.perf_counter()
        rows = t.streaming.current_results(cq, now_ms=end_ms)
        sliding.append(time.perf_counter() - t0)
        assert rows and rows[0]["dps"]
    sliding_p50 = _percentile(sliding, 50) * 1e3

    # --- (4) tier-seeded bootstrap: pre-boundary window serves
    # incrementally (no batch fallback)
    tl = TSDB(Config(**{
        "tsd.core.auto_create_metrics": "true",
        "tsd.rollups.enable": "true",
        "tsd.lifecycle.enable": "true",
        "tsd.lifecycle.demote_after": "30m",
        "tsd.lifecycle.demote_tiers": "1m"}))
    span = 7200
    now_ms = BASE_MS + span * 1000
    ts = np.arange(BASE_S, BASE_S + span, 5, dtype=np.int64)
    for i in range(4):
        tl.add_points("sys.sv2", ts, rng.normal(100, 10, len(ts)),
                      {"host": f"h{i}"})
    tl.lifecycle.sweep(now_ms=now_ms)
    reg = tl.streaming
    reg.register({"start": BASE_MS, "end": now_ms, "queries": [
        {"metric": "sys.sv2", "aggregator": "sum",
         "downsample": "5m-avg"}]}, now_ms=now_ms)
    tsq = TSQuery.from_json({
        "start": BASE_MS, "end": now_ms, "queries": [
            {"metric": "sys.sv2", "aggregator": "sum",
             "downsample": "5m-avg"}]}).validate()
    tl.execute_query(tsq)
    tier_ok = bool(reg.serve_hits == 1 and reg.serve_fallbacks == 0
                   and reg._partials[0].tier_seeded)

    return {"config": "streamv2",
            "ingest_p50_us_0cq": round(p50_0, 1),
            "ingest_p50_us_10cq": round(p50_10, 1),
            "ingest_p50_us_50cq": round(p50_50, 1),
            "ingest_tax_10cq": round(tax_10, 3),
            "ingest_tax_50cq": round(tax_50, 3),
            "fold_s_1cq": round(fold_1, 4),
            "fold_s_16cq_shared": round(fold_16, 4),
            "fold_scaling_16cq": round(fold_ratio, 2),
            "fold_points": n_points_fold,
            "sliding_serve_p50_ms": round(sliding_p50, 2),
            "tier_seeded_preboundary_serve": tier_ok,
            "criterion_pass": bool(tax_50 <= 1.25
                                   and fold_ratio <= 2.0
                                   and tier_ok)}


def bench_lifecycle(repeats: int, n_series: int = 2000,
                    span_s: int = 7200) -> dict:
    """Aged-store lifecycle config: n_series x span @1s raw, a
    demote_after=30m policy folding everything older into the 1m
    rollup tiers (sum/count/min/max) and compacting the tail. Reports
    resident bytes before/after the sweep (criterion: >= 2x reduction)
    and the p50 of a boundary-spanning 1m-avg query on the swept
    store vs an identical all-raw baseline store (criterion: within
    1.5x — the stitched tier+tail read must not tax the dashboard).
    Sanity-checks the stitched result against the all-raw answer."""
    from opentsdb_tpu import TSDB, Config
    from opentsdb_tpu.query.model import TSQuery

    def mk(lifecycle: bool):
        cfg = {"tsd.core.auto_create_metrics": "true",
               "tsd.storage.backend": "memory",
               "tsd.rollups.enable": "true"}
        if lifecycle:
            cfg.update({"tsd.lifecycle.enable": "true",
                        "tsd.lifecycle.demote_after": "30m",
                        "tsd.lifecycle.demote_tiers": "1m"})
        return TSDB(Config(**cfg))

    t_raw, t_lc = mk(False), mk(True)
    ts = np.arange(BASE_S, BASE_S + span_s, dtype=np.int64)
    rng = np.random.default_rng(13)
    t0 = time.perf_counter()
    for i in range(n_series):
        vals = rng.normal(100, 10, span_s)
        for t in (t_raw, t_lc):
            t.add_points("sys.aged", ts, vals, {"host": f"h{i:05d}"})
    ingest_s = time.perf_counter() - t0
    now_ms = BASE_MS + span_s * 1000
    before = t_lc.storage_memory_info()["total"]["resident_bytes"]
    t0 = time.perf_counter()
    rep = t_lc.lifecycle.sweep(now_ms=now_ms)
    sweep_s = time.perf_counter() - t0
    after = t_lc.storage_memory_info()["total"]["resident_bytes"]
    qobj = {"start": BASE_MS, "end": now_ms,
            "queries": [{"metric": "sys.aged", "aggregator": "sum",
                         "downsample": "1m-avg"}]}

    def p50(tsdb):
        tsdb.config.override_config("tsd.query.cache.enable", "false")
        times = []
        tsdb.execute_query(TSQuery.from_json(qobj).validate())  # warm
        for _ in range(max(repeats, 3)):
            t0 = time.perf_counter()
            out = tsdb.execute_query(TSQuery.from_json(qobj).validate())
            times.append(time.perf_counter() - t0)
        return _percentile(times, 50) * 1e3, out

    lc_p50, lc_out = p50(t_lc)
    raw_p50, raw_out = p50(t_raw)
    d_lc, d_raw = dict(lc_out[0].dps), dict(raw_out[0].dps)
    assert d_lc.keys() == d_raw.keys(), "stitched dropped buckets"
    worst = max(abs(d_lc[k] - d_raw[k]) / max(abs(d_raw[k]), 1e-12)
                for k in d_raw)
    bytes_ratio = before / max(after, 1)
    p50_ratio = lc_p50 / max(raw_p50, 1e-3)
    return {"config": "lifecycle", "series": n_series,
            "points": n_series * span_s,
            "ingest_mpps": round(n_series * span_s / ingest_s / 1e6,
                                 1),
            "sweep_s": round(sweep_s, 1),
            "points_demoted": rep.get("demoted", 0),
            "tier_points_written": rep.get("tierPointsWritten", 0),
            "resident_bytes_before": before,
            "resident_bytes_after": after,
            "bytes_ratio": round(bytes_ratio, 1),
            "boundary_p50_ms": round(lc_p50, 1),
            "all_raw_p50_ms": round(raw_p50, 1),
            "p50_ratio": round(p50_ratio, 2),
            "stitch_worst_rel_err": float(f"{worst:.2e}"),
            "criterion_pass": bool(bytes_ratio >= 2.0
                                   and p50_ratio <= 1.5)}


def bench_cold(repeats: int, n_series: int = 2000,
               span_s: int = 7200) -> dict:
    """Aged-spilled cold-tier config: n_series x span @1s raw, a
    demote_after=30m policy folding aged raw into the 1m tiers, then
    spill_after=32m moving all but the freshest tier band into
    mmap-backed cold segments (opentsdb_tpu/coldstore/) and releasing
    the tier RAM. Compares against an identical no-spill store (tiers
    stay in RAM). Criteria: resident RAM for AGED history (the rollup
    tier stores) >= 5x lower than no-spill, and the p50 of a
    boundary-spanning 1m-avg query over cold+tier+raw within 2x of
    the all-RAM store. Sanity-checks the stitched result against the
    no-spill answer."""
    import shutil
    import tempfile
    from opentsdb_tpu import TSDB, Config
    from opentsdb_tpu.query.model import TSQuery

    cold_dir = tempfile.mkdtemp(prefix="coldbench-")

    def mk(spill: bool):
        cfg = {"tsd.core.auto_create_metrics": "true",
               "tsd.storage.backend": "memory",
               "tsd.rollups.enable": "true",
               "tsd.lifecycle.enable": "true",
               "tsd.lifecycle.demote_after": "30m",
               "tsd.lifecycle.demote_tiers": "1m"}
        if spill:
            cfg.update({"tsd.lifecycle.spill_after": "32m",
                        "tsd.coldstore.dir": cold_dir})
        return TSDB(Config(**cfg))

    def aged_bytes(tsdb):
        """Resident bytes of the rollup tier stores — where aged
        (demoted) history lives in RAM."""
        info = tsdb.storage_memory_info()
        return sum(v["resident_bytes"] for k, v in info.items()
                   if k.startswith("rollup:"))

    t_ram, t_cold = mk(False), mk(True)
    ts = np.arange(BASE_S, BASE_S + span_s, dtype=np.int64)
    rng = np.random.default_rng(17)
    t0 = time.perf_counter()
    for i in range(n_series):
        vals = rng.normal(100, 10, span_s)
        for t in (t_ram, t_cold):
            t.add_points("sys.aged", ts, vals, {"host": f"h{i:05d}"})
    ingest_s = time.perf_counter() - t0
    now_ms = BASE_MS + span_s * 1000
    for t in (t_ram, t_cold):
        rep = t.lifecycle.sweep(now_ms=now_ms)
        assert rep.get("demoted", 0) > 0, rep
    spilled = rep.get("spilled", 0)
    cold = t_cold.lifecycle.coldstore
    aged_ram = aged_bytes(t_ram)
    aged_spill = aged_bytes(t_cold)
    total_ram = t_ram.storage_memory_info()["total"]["resident_bytes"]
    total_spill = t_cold.storage_memory_info()["total"][
        "resident_bytes"]
    qobj = {"start": BASE_MS, "end": now_ms,
            "queries": [{"metric": "sys.aged", "aggregator": "sum",
                         "downsample": "1m-avg"}]}

    def p50(tsdb):
        tsdb.config.override_config("tsd.query.cache.enable", "false")
        times = []
        tsdb.execute_query(TSQuery.from_json(qobj).validate())  # warm
        for _ in range(max(repeats, 3)):
            t0 = time.perf_counter()
            out = tsdb.execute_query(
                TSQuery.from_json(qobj).validate())
            times.append(time.perf_counter() - t0)
        return _percentile(times, 50) * 1e3, out

    cold_p50, cold_out = p50(t_cold)
    ram_p50, ram_out = p50(t_ram)
    d_cold, d_ram = dict(cold_out[0].dps), dict(ram_out[0].dps)
    assert d_cold.keys() == d_ram.keys(), "stitch dropped buckets"
    worst = max(abs(d_cold[k] - d_ram[k]) / max(abs(d_ram[k]), 1e-12)
                for k in d_ram)
    aged_ratio = aged_ram / max(aged_spill, 1)
    p50_ratio = cold_p50 / max(ram_p50, 1e-3)
    out = {"config": "cold", "series": n_series,
           "points": n_series * span_s,
           "ingest_mpps": round(n_series * span_s / ingest_s / 1e6,
                                1),
           "points_spilled": spilled,
           "cold_segments": cold.segments_written,
           "cold_disk_bytes": cold.cold_bytes(),
           "aged_resident_bytes_nospill": aged_ram,
           "aged_resident_bytes_spill": aged_spill,
           "aged_bytes_ratio": round(aged_ratio, 1),
           "total_resident_bytes_nospill": total_ram,
           "total_resident_bytes_spill": total_spill,
           "total_bytes_ratio": round(
               total_ram / max(total_spill, 1), 2),
           "boundary_p50_ms": round(cold_p50, 1),
           "all_ram_p50_ms": round(ram_p50, 1),
           "p50_ratio": round(p50_ratio, 2),
           "stitch_worst_rel_err": float(f"{worst:.2e}"),
           "criterion_pass": bool(aged_ratio >= 5.0
                                  and p50_ratio <= 2.0)}
    shutil.rmtree(cold_dir, ignore_errors=True)
    return out


def bench_sketch(repeats: int, n_series: int = 64,
                 span_s: int = 7200) -> dict:
    """Quantile-sketch config: p99 percentile queries over the three
    storage shapes the sketch column serves — all-raw (live fold),
    tier-demoted (persisted sketch cells), and cold-spilled (mmap
    sketch blobs stitched with tier + raw tail) — plus a 3-shard
    scatter/gather whose merged partials must be bit-equal to a
    single-node oracle. Every answer is checked against the exact
    lower order statistic of the pooled raw values per bucket;
    criterion: worst relative error <= 1.1 * alpha for all shapes
    and a bit-equal cluster merge."""
    import shutil
    import tempfile
    from opentsdb_tpu import TSDB, Config
    from opentsdb_tpu.query.model import TSQuery

    cold_dir = tempfile.mkdtemp(prefix="sketchbench-")

    def mk(shape: str):
        cfg = {"tsd.core.auto_create_metrics": "true",
               "tsd.storage.backend": "memory",
               "tsd.query.cache.enable": "false",
               "tsd.tpu.warmup": "false"}
        if shape in ("demoted", "cold"):
            cfg.update({"tsd.rollups.enable": "true",
                        "tsd.lifecycle.enable": "true",
                        "tsd.lifecycle.demote_after": "30m",
                        "tsd.lifecycle.demote_tiers": "1m"})
        if shape == "cold":
            cfg.update({"tsd.lifecycle.spill_after": "60m",
                        "tsd.coldstore.dir": cold_dir})
        return TSDB(Config(**cfg))

    stores = {s: mk(s) for s in ("raw", "demoted", "cold")}
    alpha = stores["raw"].config.get_float("tsd.sketch.alpha", 0.01)
    bound = 1.1 * alpha
    ts = np.arange(BASE_S, BASE_S + span_s, dtype=np.int64)
    rng = np.random.default_rng(23)
    vals = rng.lognormal(3.0, 1.0, (n_series, span_s))
    t0 = time.perf_counter()
    for i in range(n_series):
        for t in stores.values():
            t.add_points("sys.lat", ts, vals[i],
                         {"host": f"h{i:04d}"})
    ingest_s = time.perf_counter() - t0
    now_ms = BASE_MS + span_s * 1000
    rep = stores["demoted"].lifecycle.sweep(now_ms=now_ms)
    assert rep.get("demoted", 0) > 0, rep
    rep = stores["cold"].lifecycle.sweep(now_ms=now_ms)
    assert rep.get("spilled", 0) > 0, rep

    # exact p99 per 5m bucket over the pooled raw values
    bucket_ms = 300_000
    slots = (ts * 1000) - (ts * 1000) % bucket_ms
    exact = {int(s): float(np.percentile(
        vals[:, slots == s].ravel(), 99.0, method="lower"))
        for s in np.unique(slots)}

    qobj = {"start": BASE_MS, "end": now_ms,
            "queries": [{"metric": "sys.lat", "aggregator": "sum",
                         "downsample": "5m-avg",
                         "percentiles": [99.0]}]}

    def p50(tsdb):
        tsdb.execute_query(TSQuery.from_json(qobj).validate())  # warm
        times, out = [], None
        for _ in range(max(repeats, 3)):
            t0 = time.perf_counter()
            out = tsdb.execute_query(
                TSQuery.from_json(qobj).validate())
            times.append(time.perf_counter() - t0)
        return _percentile(times, 50) * 1e3, out

    lat, err = {}, {}
    for shape, t in stores.items():
        ms, out_rows = p50(t)
        rows = [r for r in out_rows
                if r.metric.endswith("_pct_99")]
        got = {}
        for r in rows:
            got.update(r.dps)
        assert set(got) == set(exact), (shape, "buckets differ")
        lat[shape] = ms
        err[shape] = max(
            abs(got[s] - exact[s]) / max(abs(exact[s]), 1e-12)
            for s in exact)

    cluster = _bench_sketch_cluster(repeats)
    out = {"config": "sketch", "alpha": alpha,
           "error_bound": round(bound, 4),
           "series": n_series, "points": n_series * span_s,
           "ingest_mpps": round(
               3 * n_series * span_s / ingest_s / 1e6, 2),
           "points_spilled": rep["spilled"],
           "p99_raw_p50_ms": round(lat["raw"], 1),
           "p99_demoted_p50_ms": round(lat["demoted"], 1),
           "p99_cold_p50_ms": round(lat["cold"], 1),
           "cold_vs_raw_ratio": round(
               lat["cold"] / max(lat["raw"], 1e-3), 2),
           "worst_rel_err": {k: float(f"{v:.2e}")
                             for k, v in err.items()},
           "cluster": cluster,
           "criterion_pass": bool(
               all(v <= bound for v in err.values())
               and cluster["merged_bit_equal"])}
    for t in stores.values():
        t.shutdown()
    shutil.rmtree(cold_dir, ignore_errors=True)
    return out


def _bench_sketch_cluster(repeats: int, n_hosts: int = 24,
                          span_s: int = 600) -> dict:
    """3-shard percentile scatter/gather leg of the sketch config:
    the router folds per-shard serialized sketch partials and must
    answer bit-equal to a single node holding all the points."""
    import asyncio
    import json as _json
    import threading

    from opentsdb_tpu import TSDB, Config
    from opentsdb_tpu.tsd.http_api import HttpRequest, HttpRpcRouter
    from opentsdb_tpu.tsd.server import TSDServer

    peer_cfg = {"tsd.core.auto_create_metrics": "true",
                "tsd.tpu.warmup": "false"}

    class Peer:
        def __init__(self):
            self.tsdb = TSDB(Config(**peer_cfg))
            self.loop = asyncio.new_event_loop()
            self.server = TSDServer(self.tsdb, host="127.0.0.1",
                                    port=0)
            started = threading.Event()

            def run():
                asyncio.set_event_loop(self.loop)
                self.loop.run_until_complete(self.server.start())
                started.set()
                self.loop.run_forever()

            self._thread = threading.Thread(target=run, daemon=True)
            self._thread.start()
            assert started.wait(30)
            self.port = (self.server._server.sockets[0]
                         .getsockname()[1])

        def stop(self):
            try:
                asyncio.run_coroutine_threadsafe(
                    self.server.stop(), self.loop).result(20)
            except Exception:  # noqa: BLE001
                pass
            self.loop.call_soon_threadsafe(self.loop.stop)

    def req(method, path, body=None, **params):
        return HttpRequest(
            method=method, path=path,
            params={k: [str(v)] for k, v in params.items()},
            body=_json.dumps(body).encode()
            if body is not None else b"")

    peers = [Peer() for _ in range(3)]
    spec = ",".join(f"s{i}=127.0.0.1:{p.port}"
                    for i, p in enumerate(peers))
    router = TSDB(Config(**{
        "tsd.cluster.role": "router", "tsd.cluster.peers": spec,
        "tsd.query.cache.enable": "false",
        "tsd.tpu.warmup": "false"}))
    http = HttpRpcRouter(router)
    router.cluster.start()
    single = TSDB(Config(**{**peer_cfg,
                            "tsd.query.cache.enable": "false"}))
    single_http = HttpRpcRouter(single)

    rng = np.random.default_rng(29)
    points = [{"metric": "bench.sk", "timestamp": BASE_S + i,
               "value": float(v),
               "tags": {"host": f"h{h:03d}"}}
              for h in range(n_hosts)
              for i, v in enumerate(rng.lognormal(2, 1, span_s))]
    for target in (http, single_http):
        for i in range(0, len(points), 4000):
            resp = target.handle(req("POST", "/api/put",
                                     points[i:i + 4000],
                                     summary="true"))
            assert resp.status == 200
            assert _json.loads(resp.body)["failed"] == 0

    qbody = {"start": BASE_MS - 1000,
             "end": BASE_MS + span_s * 1000,
             "queries": [{"metric": "bench.sk", "aggregator": "sum",
                          "downsample": "1m-avg",
                          "percentiles": [99.0]}]}

    def read_p50(target):
        target.handle(req("POST", "/api/query", qbody))  # warm
        times, body = [], b""
        for _ in range(max(repeats, 3)):
            t0 = time.perf_counter()
            resp = target.handle(req("POST", "/api/query", qbody))
            times.append(time.perf_counter() - t0)
            assert resp.status == 200
            body = resp.body
        return _percentile(times, 50) * 1e3, body

    scatter_p50, scatter_body = read_p50(http)
    single_p50, single_body = read_p50(single_http)

    def rows(body):
        doc = _json.loads(body)
        if doc and isinstance(doc[-1], dict) \
                and "shardsDegraded" in doc[-1]:
            doc = doc[:-1]
        return sorted((r["metric"], sorted(r["tags"].items()),
                       sorted(r["dps"].items())) for r in doc)

    merged = rows(scatter_body)
    bit_equal = bool(merged and merged == rows(single_body))
    for p in peers:
        p.stop()
    router.shutdown()
    single.shutdown()
    return {"shards": 3, "series": n_hosts,
            "points": len(points),
            "scatter_p99_p50_ms": round(scatter_p50, 1),
            "single_p99_p50_ms": round(single_p50, 1),
            "scatter_gather_overhead": round(
                scatter_p50 / max(single_p50, 1e-3), 2),
            "merged_bit_equal": bit_equal}


def bench_wal(repeats: int, n_series: int = 500,
              pts_per: int = 4000) -> dict:
    """Ingest throughput with the write-ahead log off / on. 'on'
    fsyncs per write call (group commit), the acked-means-durable
    default; 'on_nosync' appends but never fsyncs (the OS flushes) —
    the reference's setDurable(false) class of durability."""
    import shutil
    import tempfile
    from opentsdb_tpu import TSDB, Config
    ts = np.arange(BASE_S, BASE_S + pts_per, dtype=np.int64)
    rng = np.random.default_rng(7)
    vals = rng.normal(100, 10, (n_series, pts_per))
    out = {"config": "wal", "series": n_series,
           "points": n_series * pts_per}
    for label, cfg in (
            ("off", {"tsd.storage.wal.enable": "false"}),
            ("on", {"tsd.storage.wal.fsync": "always"}),
            ("on_nosync", {"tsd.storage.wal.fsync": "never"})):
        best = float("inf")
        for _ in range(max(1, repeats // 2)):
            d = tempfile.mkdtemp(prefix="walbench-")
            try:
                tsdb = TSDB(Config(**{
                    "tsd.core.auto_create_metrics": "true",
                    "tsd.storage.data_dir": d, **cfg}))
                t0 = time.perf_counter()
                for i in range(n_series):
                    tsdb.add_points("sys.walbench", ts, vals[i],
                                    {"host": f"h{i:04d}"})
                best = min(best, time.perf_counter() - t0)
                if tsdb.wal is not None:
                    tsdb.wal.close()
            finally:
                shutil.rmtree(d, ignore_errors=True)
        out[f"ingest_mpps_{label}"] = round(
            n_series * pts_per / best / 1e6, 2)
    return out


def bench_ingest(repeats: int, n_points: int = 120_000,
                 n_series: int = 200) -> dict:
    """Durable ingest raw speed through the three front doors —
    telnet ``put`` line bursts (columnar batch decode), HTTP
    ``/api/put`` JSON bodies, and the import buffer — with the WAL
    off vs ``fsync=always`` (acked => fsynced). Also measures the
    PER-REQUEST durable rate (one point per telnet line / HTTP body,
    one fsync each — the pre-group-commit behavior) as the baseline
    the batch path must beat.

    Criteria: durable batch ingest >= 1/3 of the WAL-off rate on the
    import path (the 10x durability tax collapses to <= 3x), and the
    batched telnet/HTTP durable rates >= 3x their per-request rates.
    """
    import json as _json
    import shutil
    import tempfile
    from opentsdb_tpu import TSDB, Config
    from opentsdb_tpu.tsd.http_api import HttpRequest, HttpRpcRouter
    from opentsdb_tpu.tsd.telnet import TelnetRouter

    rng = np.random.default_rng(23)
    ts = BASE_S + np.arange(n_points, dtype=np.int64) % 7200
    hosts = np.arange(n_points) % n_series
    vals = np.round(rng.normal(100, 10, n_points), 2)
    telnet_lines = [f"put sys.ing {ts[i]} {vals[i]} host=h{hosts[i]:04d}"
                    for i in range(n_points)]
    import_buf = "".join(
        f"sys.ing {ts[i]} {vals[i]} host=h{hosts[i]:04d}\n"
        for i in range(n_points)).encode()
    put_dicts = [{"metric": "sys.ing", "timestamp": int(ts[i]),
                  "value": float(vals[i]),
                  "tags": {"host": f"h{hosts[i]:04d}"}}
                 for i in range(n_points)]

    def mk(cfg):
        d = tempfile.mkdtemp(prefix="ingbench-")
        t = TSDB(Config(**{
            "tsd.core.auto_create_metrics": "true",
            "tsd.storage.backend": "memory",
            "tsd.storage.data_dir": d, **cfg}))
        return d, t

    def run(door, cfg, points) -> float:
        """Best-of-repeats Mpps for one front door x WAL config."""
        best = float("inf")
        for _ in range(max(1, repeats // 2)):
            d, t = mk(cfg)
            try:
                if door == "import":
                    t0 = time.perf_counter()
                    written, errs = t.import_buffer(import_buf)
                    dt = time.perf_counter() - t0
                elif door == "telnet":
                    router = TelnetRouter(t)
                    burst = 4096  # ~one socket read's worth of lines
                    t0 = time.perf_counter()
                    for lo in range(0, points, burst):
                        resp, _exc = router.execute_lines(
                            telnet_lines[lo:lo + burst])
                        assert not resp, resp
                    dt = time.perf_counter() - t0
                elif door == "http":
                    router = HttpRpcRouter(t)
                    body_pts = 2000  # one /api/put body
                    bodies = [
                        _json.dumps(put_dicts[lo:lo + body_pts])
                        .encode()
                        for lo in range(0, points, body_pts)]
                    t0 = time.perf_counter()
                    for body in bodies:
                        r = router.handle(HttpRequest(
                            "POST", "/api/put", {}, body=body))
                        assert r.status == 204, r.body
                    dt = time.perf_counter() - t0
                elif door == "telnet_scalar":
                    router = TelnetRouter(t)
                    t0 = time.perf_counter()
                    for ln in telnet_lines[:points]:
                        out = router.execute(ln)
                        assert not out, out
                    dt = time.perf_counter() - t0
                else:  # http_scalar: one point per request body
                    router = HttpRpcRouter(t)
                    bodies = [_json.dumps([dp]).encode()
                              for dp in put_dicts[:points]]
                    t0 = time.perf_counter()
                    for body in bodies:
                        r = router.handle(HttpRequest(
                            "POST", "/api/put", {}, body=body))
                        assert r.status == 204, r.body
                    dt = time.perf_counter() - t0
                assert t.store.total_points() > 0
                best = min(best, dt)
                if t.wal is not None:
                    t.wal.close()
            finally:
                shutil.rmtree(d, ignore_errors=True)
        return best

    wal_off = {"tsd.storage.wal.enable": "false"}
    wal_on = {"tsd.storage.wal.fsync": "always"}
    out = {"config": "ingest", "points": n_points,
           "series": n_series}
    for door in ("import", "telnet", "http"):
        n = n_points
        out[f"{door}_mpps_off"] = round(n / run(door, wal_off, n) / 1e6,
                                        3)
        out[f"{door}_mpps_durable"] = round(
            n / run(door, wal_on, n) / 1e6, 3)
    # per-request (pre-overhaul) durable baselines: one fsync per
    # point — sized down, these are the slow paths being replaced
    scalar_n = 3000
    out["telnet_scalar_kpps_durable"] = round(
        scalar_n / run("telnet_scalar", wal_on, scalar_n) / 1e3, 2)
    out["http_scalar_kpps_durable"] = round(
        scalar_n / run("http_scalar", wal_on, scalar_n) / 1e3, 2)
    out["durability_tax"] = round(
        out["import_mpps_off"] / max(out["import_mpps_durable"], 1e-9),
        2)
    out["telnet_batch_vs_scalar"] = round(
        out["telnet_mpps_durable"] * 1e3
        / max(out["telnet_scalar_kpps_durable"], 1e-9), 1)
    out["http_batch_vs_scalar"] = round(
        out["http_mpps_durable"] * 1e3
        / max(out["http_scalar_kpps_durable"], 1e-9), 1)
    out["criterion_pass"] = bool(
        out["durability_tax"] <= 3.0
        and out["telnet_batch_vs_scalar"] >= 3.0
        and out["http_batch_vs_scalar"] >= 3.0)
    return out


def bench_obs(repeats: int, n_points: int = 60_000,
              n_series: int = 200) -> dict:
    """Tracing overhead config: the ``ingest`` (HTTP /api/put door)
    and ``viz`` (dense dashboard query) workloads with tracing ON at
    default sampling (tsd.trace.enable=true, sample=64) vs OFF.
    Requests route through HttpRpcRouter.handle so they pay the real
    root-trace + stage-span cost. WAL off and result cache off — the
    strictest (least-amortized) setting for relative overhead.
    Criterion: p50 overhead <= 5% on both workloads."""
    import json as _json
    import shutil
    import tempfile
    from opentsdb_tpu import TSDB, Config
    from opentsdb_tpu.tsd.http_api import HttpRequest, HttpRpcRouter

    rng = np.random.default_rng(31)
    ts = BASE_S + np.arange(n_points, dtype=np.int64) % 7200
    hosts = np.arange(n_points) % n_series
    vals = np.round(rng.normal(100, 10, n_points), 2)
    body_pts = 2000
    put_dicts = [{"metric": "sys.obs", "timestamp": int(ts[i]),
                  "value": float(vals[i]),
                  "tags": {"host": f"h{hosts[i]:04d}"}}
                 for i in range(n_points)]
    bodies = [_json.dumps(put_dicts[lo:lo + body_pts]).encode()
              for lo in range(0, n_points, body_pts)]

    def mk(trace_on: bool):
        d = tempfile.mkdtemp(prefix="obsbench-")
        t = TSDB(Config(**{
            "tsd.core.auto_create_metrics": "true",
            "tsd.storage.backend": "memory",
            "tsd.storage.data_dir": d,
            "tsd.storage.wal.enable": "false",
            "tsd.query.cache.enable": "false",
            "tsd.tpu.warmup": "false",
            "tsd.trace.enable": "true" if trace_on else "false",
        }))
        return d, t, HttpRpcRouter(t)

    def ingest_pass(trace_on: bool) -> float:
        d, t, router = mk(trace_on)
        try:
            t0 = time.perf_counter()
            for body in bodies:
                r = router.handle(HttpRequest(
                    "POST", "/api/put", {}, body=body))
                assert r.status == 204, r.body
            return time.perf_counter() - t0
        finally:
            t.shutdown()
            shutil.rmtree(d, ignore_errors=True)

    # interleave off/on passes (host noise on a shared box swings
    # single-config timings by +-30% — far more than the effect under
    # test; alternation distributes it fairly) and compare best-of
    ing = {False: [], True: []}
    for _ in range(max(repeats, 4)):
        for mode in (False, True):
            ing[mode].append(ingest_pass(mode))

    span_s = 4 * 3600  # 4h @ 1s x 12 series: serialization-heavy
    ts_grid = BASE_MS + np.arange(span_s, dtype=np.int64) * 1000

    def mk_viz(trace_on: bool):
        d, t, router = mk(trace_on)
        mid = t.uids.metrics.get_or_create_id("sys.viz")
        kid = t.uids.tag_names.get_or_create_id("host")
        sids = np.asarray([
            t.store.get_or_create_series(
                mid, [(kid,
                       t.uids.tag_values.get_or_create_id(
                           f"h{j}"))])
            for j in range(12)], dtype=np.int64)
        t.store.append_grid(
            sids, ts_grid, rng.normal(100, 10, (12, span_s)),
            np.ones((12, span_s), dtype=bool))
        return d, t, router

    qb = _json.dumps({
        "start": BASE_MS, "end": BASE_MS + span_s * 1000,
        "queries": [{"metric": "sys.viz", "aggregator": "sum",
                     "downsample": "1s-avg",
                     "filters": [{"type": "wildcard", "tagk": "host",
                                  "filter": "*",
                                  "groupBy": True}]}],
        "pixels": 1500}).encode()
    viz = {False: mk_viz(False), True: mk_viz(True)}
    times = {False: [], True: []}
    try:
        for mode in (False, True):  # warm compiles (shared cache)
            r = viz[mode][2].handle(HttpRequest(
                "POST", "/api/query", {}, body=qb))
            assert r.status == 200, r.body
        for _ in range(max(repeats, 9)):
            for mode in (False, True):
                t0 = time.perf_counter()
                r = viz[mode][2].handle(HttpRequest(
                    "POST", "/api/query", {}, body=qb))
                times[mode].append(time.perf_counter() - t0)
                assert r.status == 200
        trace_counters = viz[True][1].tracer.health_info()
    finally:
        for mode in (False, True):
            viz[mode][1].shutdown()
            shutil.rmtree(viz[mode][0], ignore_errors=True)

    out = {
        "config": "obs", "points": n_points,
        "ingest_s_trace_off": round(min(ing[False]), 4),
        "ingest_s_trace_on": round(min(ing[True]), 4),
        "ingest_overhead": round(
            min(ing[True]) / max(min(ing[False]), 1e-9), 4),
        "viz_p50_ms_trace_off": round(
            _percentile(times[False], 50) * 1e3, 2),
        "viz_p50_ms_trace_on": round(
            _percentile(times[True], 50) * 1e3, 2),
        "viz_overhead": round(
            _percentile(times[True], 50)
            / max(_percentile(times[False], 50), 1e-9), 4),
        "trace_counters_on": trace_counters,
    }
    out["criterion_pass"] = bool(out["ingest_overhead"] <= 1.05
                                 and out["viz_overhead"] <= 1.05)
    return out


def bench_obs2(repeats: int, n_points: int = 40_000,
               n_series: int = 200) -> dict:
    """Fleet-observability overhead config: (1) ``GET /metrics``
    render cost on a registry populated with realistic histogram +
    counter state (what a Prometheus scrape pays), and (2) the
    ingest/viz workloads with the continuous profiler ON at its
    default rate (tsd.profile.hz=4) AND a concurrent /metrics
    scraper — vs both off. Criterion: p50 overhead <= 5% on both
    workloads (the ISSUE-15 acceptance bound)."""
    import json as _json
    import shutil
    import tempfile
    import threading as _threading
    from opentsdb_tpu import TSDB, Config
    from opentsdb_tpu.tsd.http_api import HttpRequest, HttpRpcRouter

    # -- part 1: /metrics render cost ----------------------------------
    t = TSDB(Config(**{
        "tsd.core.auto_create_metrics": "true",
        "tsd.storage.backend": "memory",
        "tsd.tpu.warmup": "false",
    }))
    rng = np.random.default_rng(41)
    for v in rng.gamma(2.0, 20.0, size=4000):
        t.stats.latency_query.add(float(v))
        t.stats.latency_put.add(float(v) / 4)
    for stage in ("query.plan", "query.execute", "query.assemble",
                  "query.serialize", "ingest.decode",
                  "store.scatter", "wal.commit_wait",
                  "query.admission"):
        for v in rng.gamma(2.0, 8.0, size=2000):
            t.stats.observe_stage(stage, float(v))
    router = HttpRpcRouter(t)
    render_times = []
    body_bytes = 0
    for _ in range(max(repeats * 4, 20)):
        t0 = time.perf_counter()
        resp = router.handle(HttpRequest("GET", "/metrics", {}))
        render_times.append(time.perf_counter() - t0)
        assert resp.status == 200
        body_bytes = len(resp.body)
    t.shutdown()

    # -- part 2: profiler + scrape overhead on real workloads ----------
    ts = BASE_S + np.arange(n_points, dtype=np.int64) % 7200
    hosts = np.arange(n_points) % n_series
    vals = np.round(rng.normal(100, 10, n_points), 2)
    body_pts = 2000
    put_dicts = [{"metric": "sys.obs2", "timestamp": int(ts[i]),
                  "value": float(vals[i]),
                  "tags": {"host": f"h{hosts[i]:04d}"}}
                 for i in range(n_points)]
    bodies = [_json.dumps(put_dicts[lo:lo + body_pts]).encode()
              for lo in range(0, n_points, body_pts)]

    def mk(obs_on: bool):
        d = tempfile.mkdtemp(prefix="obs2bench-")
        tt = TSDB(Config(**{
            "tsd.core.auto_create_metrics": "true",
            "tsd.storage.backend": "memory",
            "tsd.storage.data_dir": d,
            "tsd.storage.wal.enable": "false",
            "tsd.query.cache.enable": "false",
            "tsd.tpu.warmup": "false",
            "tsd.profile.enable": "true" if obs_on else "false",
        }))
        rt = HttpRpcRouter(tt)
        stop = None
        if obs_on:
            tt.profiler.start()   # default 4 Hz, the always-on rate
            stop = _threading.Event()

            def scrape():
                while not stop.wait(0.25):
                    rt.handle(HttpRequest("GET", "/metrics", {}))

            scr = _threading.Thread(target=scrape, daemon=True)
            scr.start()
            stop.thread = scr
        return d, tt, rt, stop

    def fin(d, tt, stop):
        if stop is not None:
            stop.set()
            stop.thread.join(5)
        tt.shutdown()
        shutil.rmtree(d, ignore_errors=True)

    def ingest_pass(obs_on: bool) -> float:
        d, tt, rt, stop = mk(obs_on)
        try:
            t0 = time.perf_counter()
            for body in bodies:
                r = rt.handle(HttpRequest("POST", "/api/put", {},
                                          body=body))
                assert r.status == 204, r.body
            return time.perf_counter() - t0
        finally:
            fin(d, tt, stop)

    ing = {False: [], True: []}
    for _ in range(max(repeats, 4)):
        for mode in (False, True):
            ing[mode].append(ingest_pass(mode))

    span_s = 2 * 3600
    ts_grid = BASE_MS + np.arange(span_s, dtype=np.int64) * 1000

    def mk_viz(obs_on: bool):
        d, tt, rt, stop = mk(obs_on)
        mid = tt.uids.metrics.get_or_create_id("sys.viz")
        kid = tt.uids.tag_names.get_or_create_id("host")
        sids = np.asarray([
            tt.store.get_or_create_series(
                mid, [(kid, tt.uids.tag_values.get_or_create_id(
                    f"h{j}"))])
            for j in range(8)], dtype=np.int64)
        tt.store.append_grid(
            sids, ts_grid, rng.normal(100, 10, (8, span_s)),
            np.ones((8, span_s), dtype=bool))
        return d, tt, rt, stop

    qb = _json.dumps({
        "start": BASE_MS, "end": BASE_MS + span_s * 1000,
        "queries": [{"metric": "sys.viz", "aggregator": "sum",
                     "downsample": "1s-avg",
                     "filters": [{"type": "wildcard", "tagk": "host",
                                  "filter": "*",
                                  "groupBy": True}]}],
        "pixels": 1500}).encode()
    viz = {False: mk_viz(False), True: mk_viz(True)}
    times = {False: [], True: []}
    try:
        for mode in (False, True):  # warm compiles (shared cache)
            r = viz[mode][2].handle(HttpRequest(
                "POST", "/api/query", {}, body=qb))
            assert r.status == 200, r.body
        for _ in range(max(repeats, 9)):
            for mode in (False, True):
                t0 = time.perf_counter()
                r = viz[mode][2].handle(HttpRequest(
                    "POST", "/api/query", {}, body=qb))
                times[mode].append(time.perf_counter() - t0)
                assert r.status == 200
        profiler_counters = viz[True][1].profiler.health_info()
    finally:
        for mode in (False, True):
            fin(viz[mode][0], viz[mode][1], viz[mode][3])

    out = {
        "config": "obs2", "points": n_points,
        "metrics_render_p50_ms": round(
            _percentile(render_times, 50) * 1e3, 3),
        "metrics_body_bytes": body_bytes,
        "ingest_s_obs_off": round(min(ing[False]), 4),
        "ingest_s_obs_on": round(min(ing[True]), 4),
        "ingest_overhead": round(
            min(ing[True]) / max(min(ing[False]), 1e-9), 4),
        "viz_p50_ms_obs_off": round(
            _percentile(times[False], 50) * 1e3, 2),
        "viz_p50_ms_obs_on": round(
            _percentile(times[True], 50) * 1e3, 2),
        "viz_overhead": round(
            _percentile(times[True], 50)
            / max(_percentile(times[False], 50), 1e-9), 4),
        "profiler_counters_on": profiler_counters,
    }
    out["criterion_pass"] = bool(out["ingest_overhead"] <= 1.05
                                 and out["viz_overhead"] <= 1.05)
    return out


def bench_viz(repeats: int, n_hosts: int = 8, per_host: int = 5,
              span_s: int = 172_800) -> dict:
    """Pixel-aware serve-path downsampling config: a config2-style
    wildcard group-by dashboard query over a DENSE window (48h @ 1s
    per series — the response class where serialization dominates the
    warm p50), answered at full resolution and with
    ``downsample=1500px`` (M4). Criteria: response bytes reduced
    >= 20x and e2e p50 (engine + serialize) reduced >= 2x, with
    identical per-pixel min/max/first/last guaranteed by the oracle
    battery (tests/test_visual_downsample.py). Also records the SSE
    frame-size delta for a live continuous query carrying a pixel
    budget."""
    import json as _json
    from opentsdb_tpu.query.model import TSQuery
    tsdb = _mk_tsdb()
    serializer = _serializer()
    rng = np.random.default_rng(29)
    mid = tsdb.uids.metrics.get_or_create_id("sys.viz")
    kid_h = tsdb.uids.tag_names.get_or_create_id("host")
    kid_t = tsdb.uids.tag_names.get_or_create_id("task")
    ts_grid = BASE_MS + np.arange(span_s, dtype=np.int64) * 1000
    n_series = n_hosts * per_host
    t0 = time.perf_counter()
    mask = np.ones((per_host, span_s), dtype=bool)
    for h in range(n_hosts):
        hv = tsdb.uids.tag_values.get_or_create_id(f"h{h:04d}")
        sids = np.asarray([
            tsdb.store.get_or_create_series(
                mid, [(kid_h, hv),
                      (kid_t, tsdb.uids.tag_values.get_or_create_id(
                          f"t{j}"))])
            for j in range(per_host)], dtype=np.int64)
        tsdb.store.append_grid(
            sids, ts_grid, rng.normal(100, 10, (per_host, span_s)),
            mask)
    ingest_s = time.perf_counter() - t0
    end_ms = BASE_MS + span_s * 1000
    base_q = {"start": BASE_MS, "end": end_ms,
              "queries": [{"metric": "sys.viz", "aggregator": "sum",
                           "downsample": "1s-avg",
                           "filters": [{"type": "wildcard",
                                        "tagk": "host", "filter": "*",
                                        "groupBy": True}]}]}
    px_q = _json.loads(_json.dumps(base_q))
    px_q["pixels"] = 1500

    tsdb.config.override_config("tsd.query.cache.enable", "false")

    def measure(qobj):
        tsq = TSQuery.from_json(qobj).validate()
        results = tsdb.execute_query(tsq)          # warm compile
        serializer.format_query(tsq, results)
        tot, ex, ser = [], [], []
        body = b""
        for _ in range(max(repeats, 3)):
            t0 = time.perf_counter()
            tsq = TSQuery.from_json(qobj).validate()
            results = tsdb.execute_query(tsq)
            t1 = time.perf_counter()
            body = serializer.format_query(tsq, results)
            t2 = time.perf_counter()
            tot.append(t2 - t0)
            ex.append(t1 - t0)
            ser.append(t2 - t1)
        dps = sum(r.num_dps for r in results)
        return {"p50_ms": _percentile(tot, 50) * 1e3,
                "exec_p50_ms": _percentile(ex, 50) * 1e3,
                "serialize_p50_ms": _percentile(ser, 50) * 1e3,
                "resp_bytes": len(body), "dps": dps}

    full = measure(base_q)
    px = measure(px_q)
    bytes_ratio = full["resp_bytes"] / max(px["resp_bytes"], 1)
    p50_ratio = full["p50_ms"] / max(px["p50_ms"], 1e-3)

    # SSE frame-size delta: the same live standing query registered
    # with and without a pixel budget (40min @ 1s-avg windows)
    tsdb.config.override_config(
        "tsd.streaming.publish_min_interval_ms", "1000000000")
    reg = tsdb.streaming
    live_start = end_ms - 2400 * 1000
    cq_body = {"start": live_start, "end": end_ms,
               "queries": [{"metric": "sys.viz", "aggregator": "sum",
                            "downsample": "1s-avg",
                            "filters": [{"type": "wildcard",
                                         "tagk": "host",
                                         "filter": "*",
                                         "groupBy": True}]}]}
    px_body = _json.loads(_json.dumps(cq_body))
    px_body["queries"][0]["pixels"] = 150
    cq_f = reg.register(dict(cq_body, id="vizfull"), now_ms=end_ms)
    cq_p = reg.register(dict(px_body, id="vizpx"), now_ms=end_ms)
    sub_f = reg.subscribe(cq_f)
    sub_p = reg.subscribe(cq_p)
    snap_f = sub_f.queue.get(timeout=30)
    snap_p = sub_p.queue.get(timeout=30)

    out = {"config": "viz", "series": n_series, "groups": n_hosts,
           "points": n_series * span_s,
           "ingest_mpps": round(n_series * span_s / ingest_s / 1e6, 1),
           "pixels": 1500,
           "resp_bytes_full": full["resp_bytes"],
           "resp_bytes_px": px["resp_bytes"],
           "bytes_ratio": round(bytes_ratio, 1),
           "dps_full": full["dps"], "dps_px": px["dps"],
           "p50_full_ms": round(full["p50_ms"], 1),
           "p50_px_ms": round(px["p50_ms"], 1),
           "p50_ratio": round(p50_ratio, 2),
           "exec_p50_full_ms": round(full["exec_p50_ms"], 1),
           "exec_p50_px_ms": round(px["exec_p50_ms"], 1),
           "serialize_p50_full_ms": round(full["serialize_p50_ms"], 1),
           "serialize_p50_px_ms": round(px["serialize_p50_ms"], 1),
           "sse_snapshot_bytes_full": len(snap_f),
           "sse_snapshot_bytes_px": len(snap_p),
           "sse_frame_ratio": round(len(snap_f)
                                    / max(len(snap_p), 1), 1),
           "criterion_pass": bool(bytes_ratio >= 20.0
                                  and p50_ratio >= 2.0)}
    return out


def bench_cluster(repeats: int, n_hosts: int = 120,
                  span_s: int = 600) -> dict:
    """Sharded cluster tier config: 3 shard TSDs on real sockets
    behind a consistent-hash router, vs a single-node TSD holding the
    same points. Runs the whole measurement TWICE — once over the
    binary columnar wire (the default transport) and once pinned to
    per-request JSON HTTP (``tsd.cluster.wire.enable=false``) — so the
    record prices the transport change itself, then reports the wire
    run as primary with the JSON run alongside."""
    js = _bench_cluster_once(repeats, n_hosts, span_s, wire=False)
    wired = _bench_cluster_once(repeats, n_hosts, span_s, wire=True)
    out = dict(wired)
    out["json_transport"] = {k: js[k] for k in (
        "router_ingest_kpps", "read_p50_cluster_ms",
        "scatter_gather_overhead", "read_p50_degraded_ms")}
    out["wire_vs_json_ingest_speedup"] = round(
        wired["router_ingest_kpps"]
        / max(js["router_ingest_kpps"], 1e-3), 2)
    out["wire_vs_json_read_speedup"] = round(
        js["read_p50_cluster_ms"]
        / max(wired["read_p50_cluster_ms"], 1e-3), 2)
    out["router_ingest_vs_single"] = round(
        wired["router_ingest_kpps"]
        / max(wired["single_ingest_kpps"], 1e-3), 2)
    return out


def _bench_cluster_once(repeats: int, n_hosts: int, span_s: int,
                        wire: bool) -> dict:
    """One full cluster-vs-single measurement over one transport
    (the chaos battery in tests/test_cluster.py proves the values;
    this config prices the transport)."""
    import asyncio
    import json as _json
    import threading

    from opentsdb_tpu import TSDB, Config
    from opentsdb_tpu.tsd.http_api import HttpRequest, HttpRpcRouter
    from opentsdb_tpu.tsd.server import TSDServer

    peer_cfg = {"tsd.core.auto_create_metrics": "true",
                "tsd.tpu.warmup": "false"}

    class Peer:
        def __init__(self, name):
            self.name = name
            self.tsdb = TSDB(Config(**peer_cfg))
            self.loop = asyncio.new_event_loop()
            self.server = TSDServer(self.tsdb, host="127.0.0.1",
                                    port=0)
            started = threading.Event()

            def run():
                asyncio.set_event_loop(self.loop)
                self.loop.run_until_complete(self.server.start())
                started.set()
                self.loop.run_forever()

            self._thread = threading.Thread(target=run, daemon=True)
            self._thread.start()
            assert started.wait(30)
            self.port = (self.server._server.sockets[0]
                         .getsockname()[1])

        def _call(self, coro):
            return asyncio.run_coroutine_threadsafe(
                coro, self.loop).result(20)

        def kill(self):
            async def _close():
                srv = self.server._server
                if srv is not None:
                    srv.close()
                    await srv.wait_closed()
                    self.server._server = None
            self._call(_close())

        def stop(self):
            try:
                self._call(self.server.stop())
            except Exception:  # noqa: BLE001
                pass
            self.loop.call_soon_threadsafe(self.loop.stop)

    def req(method, path, body=None, **params):
        return HttpRequest(
            method=method, path=path,
            params={k: [str(v)] for k, v in params.items()},
            body=_json.dumps(body).encode()
            if body is not None else b"")

    peers = [Peer(f"s{i}") for i in range(3)]
    spec = ",".join(f"{p.name}=127.0.0.1:{p.port}" for p in peers)
    router = TSDB(Config(**{
        "tsd.cluster.role": "router", "tsd.cluster.peers": spec,
        "tsd.cluster.wire.enable": "true" if wire else "false",
        "tsd.query.cache.enable": "false",
        "tsd.tpu.warmup": "false"}))
    http = HttpRpcRouter(router)
    router.cluster.start()
    single = TSDB(Config(**{**peer_cfg,
                            "tsd.query.cache.enable": "false"}))
    single_http = HttpRpcRouter(single)

    points = [{"metric": "bench.cluster",
               "timestamp": BASE_S + i,
               "value": (h * 37 + i) % 1000,
               "tags": {"host": f"h{h:03d}"}}
              for h in range(n_hosts) for i in range(span_s)]
    batches = [points[i:i + 4000]
               for i in range(0, len(points), 4000)]

    def ingest(target):
        t0 = time.perf_counter()
        for b in batches:
            resp = target.handle(req("POST", "/api/put", b,
                                     summary="true"))
            assert resp.status == 200
            assert _json.loads(resp.body)["failed"] == 0
        return time.perf_counter() - t0

    router_ingest_s = ingest(http)
    single_ingest_s = ingest(single_http)

    qbody = {"start": BASE_MS - 1000,
             "end": BASE_MS + span_s * 1000,
             "queries": [{"metric": "bench.cluster",
                          "aggregator": "sum",
                          "downsample": "10s-sum",
                          "filters": [{"type": "wildcard",
                                       "tagk": "host", "filter": "*",
                                       "groupBy": True}]}]}

    def read_p50(target, reps):
        target.handle(req("POST", "/api/query", qbody))  # warm
        times = []
        body = b""
        for _ in range(max(reps, 3)):
            t0 = time.perf_counter()
            resp = target.handle(req("POST", "/api/query", qbody))
            times.append(time.perf_counter() - t0)
            assert resp.status == 200
            body = resp.body
        return _percentile(times, 50) * 1e3, body

    cluster_p50, cluster_body = read_p50(http, repeats)
    single_p50, single_body = read_p50(single_http, repeats)

    def rows(body):
        doc = _json.loads(body)
        if doc and isinstance(doc[-1], dict) and "shardsDegraded" \
                in doc[-1]:
            doc = doc[:-1]
        return sorted(((r["tags"].get("host", ""), r["dps"])
                       for r in doc))

    merged_identical = rows(cluster_body) == rows(single_body)

    # degraded reads: one shard killed, answers must stay 200 with
    # the marker — never a 5xx
    peers[1].kill()
    degraded_times, degraded_ok = [], True
    for _ in range(max(repeats, 3)):
        t0 = time.perf_counter()
        resp = http.handle(req("POST", "/api/query", qbody))
        degraded_times.append(time.perf_counter() - t0)
        doc = _json.loads(resp.body)
        degraded_ok &= (resp.status == 200 and bool(doc)
                        and isinstance(doc[-1], dict)
                        and doc[-1].get("shardsDegraded") == ["s1"])
    degraded_p50 = _percentile(degraded_times, 50) * 1e3

    if wire:  # the wire must actually have carried the traffic
        assert any(p.wire_connects > 0
                   for p in router.cluster.peers.values())
    out = {"config": "cluster", "shards": 3,
           "transport": "wire" if wire else "json",
           "series": n_hosts, "points": len(points),
           "router_ingest_kpps":
               round(len(points) / router_ingest_s / 1e3, 1),
           "single_ingest_kpps":
               round(len(points) / single_ingest_s / 1e3, 1),
           "read_p50_cluster_ms": round(cluster_p50, 1),
           "read_p50_single_ms": round(single_p50, 1),
           "scatter_gather_overhead":
               round(cluster_p50 / max(single_p50, 1e-3), 2),
           "read_p50_degraded_ms": round(degraded_p50, 1),
           "merged_identical_to_single_node": merged_identical,
           "degraded_always_200_with_marker": degraded_ok,
           "criterion_pass": bool(merged_identical and degraded_ok)}
    router.shutdown()
    single.shutdown()
    for p in peers:
        p.stop()
    return out


def bench_cluster_rf(repeats: int, n_hosts: int = 60,
                     span_s: int = 300) -> dict:
    """Replicated cluster config (``tsd.cluster.rf = 2``): two
    3-shard clusters ingest the same points at RF=1 and RF=2
    (interleaved batches — host noise on a shared box swings
    single-config timings far more than the effect under test), then
    reads interleave healthy passes, then one RF=2 replica dies and
    the read-fallback p50 is measured (answers must stay COMPLETE
    marker-less 200s). Finally the RF=1 cluster resizes online to 4
    shards and the cutover-window read overhead is recorded.
    Criteria: RF=2 write amplification ~2x (1.8-2.2), every
    one-dead-replica read complete + marker-less, every
    reshard-window read complete."""
    import asyncio
    import json as _json
    import threading

    from opentsdb_tpu import TSDB, Config
    from opentsdb_tpu.tsd.http_api import HttpRequest, HttpRpcRouter
    from opentsdb_tpu.tsd.server import TSDServer

    peer_cfg = {"tsd.core.auto_create_metrics": "true",
                "tsd.tpu.warmup": "false"}

    class Peer:
        def __init__(self, name):
            self.name = name
            self.tsdb = TSDB(Config(**peer_cfg))
            self.loop = asyncio.new_event_loop()
            self.server = TSDServer(self.tsdb, host="127.0.0.1",
                                    port=0)
            started = threading.Event()

            def run():
                asyncio.set_event_loop(self.loop)
                self.loop.run_until_complete(self.server.start())
                started.set()
                self.loop.run_forever()

            self._thread = threading.Thread(target=run, daemon=True)
            self._thread.start()
            assert started.wait(30)
            self.port = (self.server._server.sockets[0]
                         .getsockname()[1])

        def _call(self, coro):
            return asyncio.run_coroutine_threadsafe(
                coro, self.loop).result(20)

        def kill(self):
            async def _close():
                srv = self.server._server
                if srv is not None:
                    srv.close()
                    await srv.wait_closed()
                    self.server._server = None
            self._call(_close())

        def stop(self):
            try:
                self._call(self.server.stop())
            except Exception:  # noqa: BLE001
                pass
            self.loop.call_soon_threadsafe(self.loop.stop)

    def req(method, path, body=None, **params):
        return HttpRequest(
            method=method, path=path,
            params={k: [str(v)] for k, v in params.items()},
            body=_json.dumps(body).encode()
            if body is not None else b"")

    def mk_router(peers, rf):
        spec = ",".join(f"{p.name}=127.0.0.1:{p.port}"
                        for p in peers)
        t = TSDB(Config(**{
            "tsd.cluster.role": "router",
            "tsd.cluster.peers": spec,
            "tsd.cluster.rf": str(rf),
            "tsd.cluster.breaker.reset_timeout_ms": "300",
            "tsd.cluster.reshard.interval_ms": "3600000",
            "tsd.query.cache.enable": "false",
            "tsd.tpu.warmup": "false"}))
        t.cluster.start()
        return t, HttpRpcRouter(t)

    fleets = {1: [Peer(f"a{i}") for i in range(3)],
              2: [Peer(f"b{i}") for i in range(3)]}
    routers = {rf: mk_router(peers, rf)
               for rf, peers in fleets.items()}

    points = [{"metric": "bench.rf",
               "timestamp": BASE_S + i,
               "value": (h * 37 + i) % 1000,
               "tags": {"host": f"h{h:03d}"}}
              for h in range(n_hosts) for i in range(span_s)]
    batches = [points[i:i + 4000]
               for i in range(0, len(points), 4000)]

    ingest_s = {1: 0.0, 2: 0.0}
    for b in batches:  # interleaved per batch
        for rf in (1, 2):
            t0 = time.perf_counter()
            resp = routers[rf][1].handle(
                req("POST", "/api/put", b, summary="true"))
            ingest_s[rf] += time.perf_counter() - t0
            assert resp.status == 200
            assert _json.loads(resp.body)["failed"] == 0

    def delivered(rf):
        return sum(p.forwarded_points + p.spooled_points
                   for p in routers[rf][0].cluster.peers.values())

    amplification = round(delivered(2) / max(delivered(1), 1), 2)

    qbody = {"start": BASE_MS - 1000,
             "end": BASE_MS + span_s * 1000,
             "queries": [{"metric": "bench.rf",
                          "aggregator": "sum",
                          "downsample": "10s-sum",
                          "filters": [{"type": "wildcard",
                                       "tagk": "host", "filter": "*",
                                       "groupBy": True}]}]}

    def read_pass(rf):
        t0 = time.perf_counter()
        resp = routers[rf][1].handle(req("POST", "/api/query",
                                         qbody))
        dt = time.perf_counter() - t0
        assert resp.status == 200
        doc = _json.loads(resp.body)
        degraded = doc and isinstance(doc[-1], dict) and \
            "shardsDegraded" in doc[-1]
        return dt, degraded

    for rf in (1, 2):
        read_pass(rf)  # warm
    healthy = {1: [], 2: []}
    for _ in range(max(repeats, 5)):
        for rf in (1, 2):
            dt, degraded = read_pass(rf)
            assert not degraded
            healthy[rf].append(dt)

    # one RF=2 replica dies: reads must stay complete + marker-less
    fleets[2][1].kill()
    fallback_times, fallback_ok = [], True
    for _ in range(max(repeats, 5)):
        dt, degraded = read_pass(2)
        fallback_times.append(dt)
        fallback_ok &= not degraded
    fallbacks = routers[2][0].cluster.read_fallbacks

    # online reshard of the RF=1 cluster: 3 -> 4 shards
    joiner = Peer("a3")
    rt1, http1 = routers[1]
    resp = http1.handle(req(
        "POST", "/api/cluster/reshard",
        {"peers": rt1.config.get_string("tsd.cluster.peers", "")
         + f",a3=127.0.0.1:{joiner.port}"}))
    assert resp.status == 200, resp.body
    window_times, window_ok = [], True
    for _ in range(max(repeats, 5)):
        dt, degraded = read_pass(1)
        window_times.append(dt)
        window_ok &= not degraded
    while rt1.cluster.resharding:
        info = rt1.cluster.backfill_step()
        assert info.get("phase") != "blocked", info
    post_times = []
    for _ in range(max(repeats, 5)):
        dt, degraded = read_pass(1)
        assert not degraded
        post_times.append(dt)

    h1 = _percentile(healthy[1], 50) * 1e3
    h2 = _percentile(healthy[2], 50) * 1e3
    fb = _percentile(fallback_times, 50) * 1e3
    win = _percentile(window_times, 50) * 1e3
    post = _percentile(post_times, 50) * 1e3
    out = {"config": "cluster_rf", "shards": 3, "rf": 2,
           "series": n_hosts, "points": len(points),
           "write_amplification_rf2": amplification,
           "ingest_kpps_rf1":
               round(len(points) / ingest_s[1] / 1e3, 1),
           "ingest_kpps_rf2":
               round(len(points) / ingest_s[2] / 1e3, 1),
           "read_p50_rf1_ms": round(h1, 1),
           "read_p50_rf2_ms": round(h2, 1),
           "read_p50_rf2_one_dead_ms": round(fb, 1),
           "read_fallbacks": fallbacks,
           "one_dead_reads_complete_markerless": fallback_ok,
           "reshard_window_read_p50_ms": round(win, 1),
           "reshard_window_overhead":
               round(win / max(h1, 1e-3), 2),
           "post_reshard_read_p50_ms": round(post, 1),
           "reshard_window_reads_complete": window_ok,
           "criterion_pass": bool(
               1.8 <= amplification <= 2.2 and fallback_ok
               and window_ok)}
    for rf in (1, 2):
        routers[rf][0].shutdown()
    for peers in fleets.values():
        for p in peers:
            p.stop()
    joiner.stop()
    return out


def bench_multirouter(repeats: int, n_hosts: int = 60,
                      span_s: int = 300) -> dict:
    """Multi-router front door (ISSUE 16): TWO routers on real
    sockets over a shared 3-shard set, exchanging cache-invalidation
    deltas on the gossip bus (cluster/gossip.py). Prices what the
    single-router cluster config cannot: the gossip push round-trip,
    the write-on-A-coherent-read-on-B lag (THE multi-router number),
    the cached-read hit path with gossip healthy, and the
    conservative cache-BYPASSED read served while the sibling is
    unreachable (the degraded mode that replaces stale serves).
    tests/test_multirouter.py proves the values; this config prices
    the transport."""
    import asyncio
    import http.client
    import json as _json
    import socket
    import threading

    from opentsdb_tpu import TSDB, Config
    from opentsdb_tpu.tsd.server import TSDServer

    def free_port():
        with socket.socket() as s:
            s.bind(("127.0.0.1", 0))
            return s.getsockname()[1]

    class Node:
        def __init__(self, cfg, port=0):
            self.tsdb = TSDB(Config(**cfg))
            self.loop = asyncio.new_event_loop()
            self.server = TSDServer(self.tsdb, host="127.0.0.1",
                                    port=port)
            started = threading.Event()

            def run():
                asyncio.set_event_loop(self.loop)
                self.loop.run_until_complete(self.server.start())
                started.set()
                self.loop.run_forever()

            self._thread = threading.Thread(target=run, daemon=True)
            self._thread.start()
            assert started.wait(30)
            self.port = (self.server._server.sockets[0]
                         .getsockname()[1])

        def _call(self, coro):
            return asyncio.run_coroutine_threadsafe(
                coro, self.loop).result(20)

        def kill(self):
            async def _close():
                srv = self.server._server
                if srv is not None:
                    srv.close()
                    await srv.wait_closed()
                    self.server._server = None
            self._call(_close())

        def stop(self):
            try:
                self._call(self.server.stop())
            except Exception:  # noqa: BLE001
                pass
            self.loop.call_soon_threadsafe(self.loop.stop)

    def request(port, method, path, body=None):
        conn = http.client.HTTPConnection("127.0.0.1", port,
                                          timeout=30)
        try:
            data = (_json.dumps(body).encode()
                    if body is not None else None)
            conn.request(method, path, body=data)
            resp = conn.getresponse()
            return resp.status, resp.read()
        finally:
            conn.close()

    peer_cfg = {"tsd.core.auto_create_metrics": "true",
                "tsd.tpu.warmup": "false"}
    shards = [Node(peer_cfg) for _ in range(3)]
    spec = ",".join(f"s{i}=127.0.0.1:{p.port}"
                    for i, p in enumerate(shards))
    ports = [free_port(), free_port()]
    routers = [Node({
        "tsd.cluster.role": "router",
        "tsd.cluster.peers": spec,
        "tsd.cluster.routers": f"r{1 - i}=127.0.0.1:{ports[1 - i]}",
        "tsd.cluster.gossip.interval_ms": "50",
        "tsd.cluster.gossip.stale_ms": "2000",
        "tsd.tpu.warmup": "false"}, port=ports[i])
        for i in (0, 1)]

    points = [{"metric": "bench.mr",
               "timestamp": BASE_S + i,
               "value": (h * 37 + i) % 1000,
               "tags": {"host": f"h{h:03d}"}}
              for h in range(n_hosts) for i in range(span_s)]
    batches = [points[i:i + 4000]
               for i in range(0, len(points), 4000)]

    # LB-style alternating ingest over both front doors, then the
    # same batches through ONE door (idempotent rewrite): the ratio
    # prices what the second router costs/buys on the write path
    t0 = time.perf_counter()
    for k, b in enumerate(batches):
        st, body = request(routers[k % 2].port,
                           "POST", "/api/put?summary=true", b)
        assert st == 200 and _json.loads(body)["failed"] == 0
    lb_ingest_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    for b in batches:
        st, body = request(routers[0].port,
                           "POST", "/api/put?summary=true", b)
        assert st == 200 and _json.loads(body)["failed"] == 0
    one_ingest_s = time.perf_counter() - t0

    qbody = {"start": BASE_MS - 1000,
             "end": BASE_MS + span_s * 1000,
             "queries": [{"metric": "bench.mr",
                          "aggregator": "sum",
                          "downsample": "10s-sum",
                          "filters": [{"type": "wildcard",
                                       "tagk": "host", "filter": "*",
                                       "groupBy": True}]}]}

    def read_p50(port, reps):
        request(port, "POST", "/api/query", qbody)  # warm + cache
        times, body = [], b""
        for _ in range(max(reps, 3)):
            t1 = time.perf_counter()
            st, body = request(port, "POST", "/api/query", qbody)
            times.append(time.perf_counter() - t1)
            assert st == 200
        return _percentile(times, 50) * 1e3, body

    r0_p50, r0_body = read_p50(routers[0].port, repeats)
    r1_p50, r1_body = read_p50(routers[1].port, repeats)
    merged_identical = r0_body == r1_body

    # gossip push round-trip (one delta round to the sibling)
    bus0 = routers[0].tsdb.cluster.gossip
    push_times = []
    for _ in range(max(repeats, 5)):
        t1 = time.perf_counter()
        assert bus0.push_once() == 1
        push_times.append(time.perf_counter() - t1)
    push_p50 = _percentile(push_times, 50) * 1e3

    # write-on-B / coherent-read-on-A lag: the wall-clock from an
    # acked sibling write to the first r0 answer that contains it
    # (wake-on-write + one gossip push; polls are 1 ms)
    probe_q = {"start": BASE_MS - 1000,
               "end": BASE_MS + (span_s + 100) * 1000,
               "queries": [{"metric": "bench.mr.probe",
                            "aggregator": "sum"}]}
    lag_times, coherent = [], True
    for k in range(max(repeats, 5)):
        dp = [{"metric": "bench.mr.probe",
               "timestamp": BASE_S + span_s + k,
               "value": k + 1, "tags": {"host": "lb"}}]
        st, body = request(routers[1].port,
                           "POST", "/api/put?summary=true", dp)
        assert st == 200 and _json.loads(body)["failed"] == 0
        t1 = time.perf_counter()
        deadline = t1 + 10
        seen = False
        while time.perf_counter() < deadline:
            st, body = request(routers[0].port, "POST",
                               "/api/query", probe_q)
            if st == 200 and f'"{BASE_S + span_s + k}"' \
                    in body.decode():
                seen = True
                break
            time.sleep(0.001)
        coherent &= seen
        lag_times.append(time.perf_counter() - t1)
    lag_p50 = _percentile(lag_times, 50) * 1e3

    # sibling gone: the router degrades to cache-BYPASSED reads —
    # conservative exactness, never stale, never a 5xx
    routers[1].kill()
    deadline = time.monotonic() + 10
    while not bus0.degraded() and time.monotonic() < deadline:
        time.sleep(0.05)
    degraded_verdict = bus0.degraded()
    bypass_before = bus0.cache_bypasses
    degraded_times, degraded_ok = [], True
    for _ in range(max(repeats, 3)):
        t1 = time.perf_counter()
        st, body = request(routers[0].port, "POST", "/api/query",
                           qbody)
        degraded_times.append(time.perf_counter() - t1)
        degraded_ok &= (st == 200 and body == r0_body)
    degraded_p50 = _percentile(degraded_times, 50) * 1e3
    bypassed = bus0.cache_bypasses > bypass_before

    out = {"config": "multirouter", "routers": 2, "shards": 3,
           "series": n_hosts, "points": len(points),
           "lb_ingest_kpps":
               round(len(points) / lb_ingest_s / 1e3, 1),
           "single_door_ingest_kpps":
               round(len(points) / one_ingest_s / 1e3, 1),
           "read_p50_r0_ms": round(r0_p50, 1),
           "read_p50_r1_ms": round(r1_p50, 1),
           "gossip_push_p50_ms": round(push_p50, 2),
           "sibling_write_coherence_lag_p50_ms": round(lag_p50, 1),
           "read_p50_sibling_dead_bypassed_ms":
               round(degraded_p50, 1),
           "merged_identical_across_routers": merged_identical,
           "coherent_after_sibling_write": coherent,
           "degraded_reads_exact_200": degraded_ok,
           "degraded_verdict_raised": degraded_verdict,
           "cache_bypassed_while_degraded": bypassed,
           "criterion_pass": bool(
               merged_identical and coherent and degraded_ok
               and degraded_verdict and bypassed)}
    for r in routers:
        r.stop()
    for p in shards:
        p.stop()
    return out


def bench_control(repeats: int, n_series: int = 48,
                  span_s: int = 7200) -> dict:
    """Control-plane config. (1) Adaptive materialization: a hot
    decomposable dashboard shape is mined from the query-shape log
    and auto-registered as a standing continuous query; the repeat
    pull (served from the standing fold) must be >= 5x faster than
    the cold first-miss execution, with the result cache OFF so every
    non-served repeat pays the full execution. (2) Noisy-tenant
    isolation: a gold-weighted interactive tenant vs a bronze batch
    flood of closed-loop clients that honor Retry-After on a shed
    and pace requests with think time. The victim's p99 must stay
    within 1.5x of its solo baseline while the flood absorbs every
    tenant shed. Contended and solo passes are interleaved and
    compared best-of (the bench_obs idiom) to fight host noise."""
    import random
    import shutil
    import tempfile
    import threading

    from opentsdb_tpu import TSDB, Config
    from opentsdb_tpu.tsd.http_api import HttpRequest, HttpRpcRouter

    # -- part 1: miner-materialized repeat speedup ---------------------
    d = tempfile.mkdtemp(prefix="ctlbench-")
    now_s = int(time.time())
    t = TSDB(Config(**{
        "tsd.core.auto_create_metrics": "true",
        "tsd.storage.backend": "memory",
        "tsd.storage.data_dir": d,
        "tsd.storage.wal.enable": "false",
        "tsd.query.cache.enable": "false",
        "tsd.trace.enable": "true",
        "tsd.trace.sample": "1",
        "tsd.control.enable": "true",
        "tsd.control.materialize.min_score": "0",
        "tsd.tpu.warmup": "false",
    }))
    rng = np.random.default_rng(37)
    ts = np.arange(now_s - span_s, now_s, 1, dtype=np.int64)
    for i in range(n_series):
        t.add_points("ctl.dash", ts, rng.normal(100, 10, span_s),
                     {"host": f"h{i:03d}"})
    router = HttpRpcRouter(t)
    params = {"start": ["2h-ago"], "m": ["sum:1m-sum:ctl.dash"]}

    def pull() -> float:
        t0 = time.perf_counter()
        r = router.handle(HttpRequest("GET", "/api/query", params))
        assert r.status == 200, r.body
        return time.perf_counter() - t0

    n = max(repeats, 7)
    pull()                                   # warm compiles
    cold = [pull() for _ in range(n)]        # every miss re-executes
    rep = t.control.tick()
    materialized = rep.get("materialize", {}).get("registered", 0)
    hits0 = t.streaming.serve_hits
    warm = [pull() for _ in range(n)]
    serve_hits = t.streaming.serve_hits - hits0
    cold_p50 = _percentile(cold, 50) * 1e3
    warm_p50 = _percentile(warm, 50) * 1e3
    t.shutdown()
    shutil.rmtree(d, ignore_errors=True)

    # -- part 2: noisy-tenant isolation ---------------------------------
    # In-process: the bench replays the server's exact admission
    # sequence (try_admit -> started -> handle -> finished) per
    # request. End-to-end socket behaviour (503 + Retry-After, header
    # extraction) is covered by tests/test_control.py; over a
    # loopback socket this measurement would be dominated by the
    # single-threaded accept-loop churn of per-request connections,
    # which the governor does not control.
    max_inflight = 4
    tsdb = TSDB(Config(**{
        "tsd.core.auto_create_metrics": "true",
        "tsd.storage.backend": "memory",
        "tsd.query.cache.enable": "false",
        "tsd.control.enable": "true",
        "tsd.control.qos.enable": "true",
        "tsd.control.qos.weights": "victim:4,noisy:1",
        "tsd.query.admission.max_inflight": str(max_inflight),
        "tsd.query.admission.retry_after_s": "1",
        "tsd.tpu.warmup": "false",
    }))
    assert tsdb.control is not None
    governor = tsdb.control.qos
    nts = np.arange(now_s - 7200, now_s, 1, dtype=np.int64)
    for i in range(48):
        tsdb.add_points("nt.dense", nts,
                        rng.normal(100, 10, len(nts)),
                        {"host": f"h{i:02d}"})
    lts = np.arange(now_s - 120, now_s, 1, dtype=np.int64)
    tsdb.add_points("nt.light", lts,
                    rng.normal(100, 10, len(lts)), {"host": "h0"})
    nt_router = HttpRpcRouter(tsdb)
    victim_q = {"start": ["2h-ago"], "m": ["sum:1m-sum:nt.dense"]}
    noisy_q = {"start": ["2m-ago"], "m": ["sum:1m-sum:nt.light"]}

    def admit_and_run(tenant: str, q: dict) -> bool:
        shed = governor.try_admit(tenant, max_inflight)
        if shed is not None:
            return False
        governor.started(tenant)
        try:
            r = nt_router.handle(HttpRequest("GET", "/api/query", q))
            assert r.status == 200, r.body
        finally:
            governor.finished(tenant)
        return True

    def victim_pass(k: int) -> list[float]:
        times = []
        for _ in range(k):
            t0 = time.perf_counter()
            assert admit_and_run("victim", victim_q)
            times.append(time.perf_counter() - t0)
        return times

    n_victim = max(repeats * 30, 150)
    victim_pass(8)                           # warm compiles
    # 3 interleaved contended/solo cycles so both sides sample the
    # same host-noise epochs; compare best-of (the bench_obs idiom)
    solo_p99s: list[float] = []
    cont_p99s: list[float] = []
    for _ in range(3):
        stop = threading.Event()

        def noisy_flood():
            while not stop.is_set():
                admitted = admit_and_run("noisy", noisy_q)
                # closed-loop client: honor Retry-After on a tenant
                # shed (scaled down so the bench stays short),
                # think-time pacing otherwise; jittered to avoid a
                # synchronized retry herd
                base = 0.02 if admitted else 0.25
                time.sleep(base * (0.7 + 0.6 * random.random()))

        flood = [threading.Thread(target=noisy_flood, daemon=True)
                 for _ in range(4)]
        for th in flood:
            th.start()
        time.sleep(0.25)                     # flood reaches steady state
        try:
            cont_p99s.append(_percentile(victim_pass(n_victim), 99))
        finally:
            stop.set()
            for th in flood:
                th.join(10)
        solo_p99s.append(_percentile(victim_pass(n_victim), 99))
    qdoc = governor.describe()
    noisy_shed = qdoc["tenants"].get("noisy", {}).get("shed", 0)
    victim_shed = qdoc["tenants"].get("victim", {}).get("shed", 0)
    tsdb.shutdown()

    solo_p99 = min(solo_p99s) * 1e3
    cont_p99 = min(cont_p99s) * 1e3
    out = {
        "config": "control",
        "series": n_series, "span_s": span_s,
        "materialized": materialized,
        "repeat_serve_hits": serve_hits,
        "cold_miss_p50_ms": round(cold_p50, 2),
        "materialized_repeat_p50_ms": round(warm_p50, 2),
        "repeat_speedup": round(cold_p50 / max(warm_p50, 1e-6), 1),
        "victim_solo_p99_ms": round(solo_p99, 1),
        "victim_contended_p99_ms": round(cont_p99, 1),
        "victim_p99_ratio": round(cont_p99 / max(solo_p99, 1e-6), 2),
        "noisy_sheds": int(noisy_shed),
        "victim_sheds": int(victim_shed),
    }
    out["criterion_pass"] = bool(
        materialized >= 1 and serve_hits >= 1
        and out["repeat_speedup"] >= 5.0
        and out["victim_p99_ratio"] <= 1.5
        and noisy_shed > 0 and victim_shed == 0)
    return out


def bench_eventtime(repeats: int, n_users: int = 1_000_000,
                    n_sample: int = 20_000) -> dict:
    """Event-time layer at user scale: one session CQ keyed by a
    ``user`` tag with 1M distinct values (1M concurrent sessions in
    ONE columnar partial). (1) ingest tax — per-point write+fold
    throughput with the session CQ standing vs a zero-CQ control
    over the same 1M-series store, criterion <= 1.5x; (2) gap-close
    throughput — the completeness marker's watermark-driven
    open/closed sweep over all 1M session rows (one vectorized
    pass); (3) late-refold cost — folding an in-lateness batch into
    already-published buckets vs an equal at-the-front batch.

    Folds are timed deterministically on this thread (workers off,
    drain via the registry's own ``_drain_group``, no publish): the
    tap+fold pair IS the write-path cost a standing CQ adds — SSE
    publish is subscriber-driven and benched in ``live``."""
    from opentsdb_tpu import TSDB, Config
    from opentsdb_tpu.streaming.eventtime.watermark import (
        completeness_marker)

    end_ms = BASE_MS + 1800 * 1000

    def _mk():
        return TSDB(Config(**{
            "tsd.core.auto_create_metrics": "true",
            "tsd.tpu.warmup": "false",
            "tsd.streaming.workers.count": "0",
            "tsd.streaming.buffer_points": str(1 << 30),
            "tsd.streaming.workers.max_pending_points":
                str(1 << 30)}))

    def _drain(t):
        for g in t.streaming._partials:
            t.streaming._drain_group(g)

    def _preingest(t):
        # one point per user, event time swept monotonically across
        # 0..24m so the per-pass watermark commit never declares the
        # bulk late; drained every 100k to bound the pending buffer
        t0 = time.perf_counter()
        for u in range(n_users):
            t.add_point("evt.sess", BASE_S + (u * 1440) // n_users,
                        1.0, {"user": f"u{u:07d}"})
            if (u + 1) % 100_000 == 0:
                _drain(t)
        _drain(t)
        return time.perf_counter() - t0

    # sampled follow-up traffic: 20k distinct already-admitted users
    # (steady-state fold, no admission cost), event times at the
    # 25..30m front edge so nothing is late on first contact
    stride = max(n_users // n_sample, 1)
    sample_users = [f"u{(i * stride) % n_users:07d}"
                    for i in range(n_sample)]
    sample_ts = [BASE_S + 1500 + (i * 280) // n_sample
                 for i in range(n_sample)]

    def _ingest_pass(t) -> float:
        t0 = time.perf_counter()
        for u, ts in zip(sample_users, sample_ts):
            t.add_point("evt.sess", ts, 2.0, {"user": u})
        _drain(t)
        return time.perf_counter() - t0

    # --- zero-CQ control: same 1M-series store, no streaming tap
    t = _mk()
    setup_zero_s = _preingest(t)
    zero_s = min(_ingest_pass(t) for _ in range(max(repeats, 3)))
    t.shutdown()

    # --- session-CQ arm: register FIRST so every pre-ingest point
    # rides the live tap+fold path (1M admissions into user rows)
    t = _mk()
    cq = t.streaming.register(
        {"start": BASE_MS, "end": end_ms, "queries": [
            {"metric": "evt.sess", "aggregator": "none",
             "downsample": "1m-sum"}],
         "window": {"type": "session", "gap": "2m", "by": "user"},
         "watermark": {"allowedLateness": "5m"}},
        now_ms=end_ms)
    setup_cq_s = _preingest(t)
    cq_s = min(_ingest_pass(t) for _ in range(max(repeats, 3)))
    tax = cq_s / max(zero_s, 1e-9)

    part = t.streaming._partials[0]
    assert len(part._sids) == n_users, len(part._sids)

    # --- gap-close throughput: the marker's watermark sweep closes
    # sessions whose last bucket the watermark passed by > gap —
    # one vectorized pass over all 1M rows per pull
    marker = None
    sweep = []
    for _ in range(max(repeats, 5)):
        t0 = time.perf_counter()
        marker = completeness_marker(t.streaming, cq, end_ms)
        sweep.append(time.perf_counter() - t0)
    sweep_p50 = _percentile(sweep, 50)
    assert marker["sessionsClosed"] > n_users // 2, marker
    assert marker["sessionsOpen"] > 0, marker

    # --- late-refold cost: equal batches folded at the front edge
    # vs 4.5m behind the watermark (inside the 5m lateness horizon,
    # landing in already-published buckets)
    def _fold_batch(off_s: int) -> float:
        for i, u in enumerate(sample_users):
            t.add_point("evt.sess", BASE_S + off_s + i % 60, 3.0,
                        {"user": u})
        t0 = time.perf_counter()
        _drain(t)
        return time.perf_counter() - t0

    live_s = min(_fold_batch(1740) for _ in range(max(repeats, 3)))
    refold_before = part.late_refolded
    late_s = min(_fold_batch(1500) for _ in range(max(repeats, 3)))
    late_refolded = part.late_refolded - refold_before
    assert late_refolded > 0, "late batch never hit the refold path"
    t.shutdown()

    return {
        "config": "eventtime",
        "users": n_users,
        "sample_points": n_sample,
        "setup_zero_s": round(setup_zero_s, 1),
        "setup_cq_s": round(setup_cq_s, 1),
        "zero_cq_kpps": round(n_sample / zero_s / 1e3, 1),
        "session_cq_kpps": round(n_sample / cq_s / 1e3, 1),
        "ingest_tax": round(tax, 2),
        "gap_close_p50_ms": round(sweep_p50 * 1e3, 1),
        "gap_close_msessions_per_s": round(
            n_users / max(sweep_p50, 1e-9) / 1e6, 1),
        "sessions_open": marker["sessionsOpen"],
        "sessions_closed": marker["sessionsClosed"],
        "live_fold_us_per_point": round(live_s / n_sample * 1e6, 2),
        "late_refold_us_per_point": round(
            late_s / n_sample * 1e6, 2),
        "late_refold_ratio": round(late_s / max(live_s, 1e-9), 2),
        "late_refolded_points": int(late_refolded),
        "criterion_pass": bool(tax <= 1.5),
    }


def _serializer():
    from opentsdb_tpu.tsd.json_serializer import HttpJsonSerializer
    return HttpJsonSerializer()


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--cpu", action="store_true",
                    help="force CPU (debug; bench runs on TPU)")
    ap.add_argument("--configs", default="1,2,3,4")
    ap.add_argument("--repeats", type=int, default=5)
    ap.add_argument("--series3", type=int, default=1_000_000)
    args = ap.parse_args()
    if args.cpu:
        import os
        os.environ["JAX_PLATFORMS"] = "cpu"
    import jax
    if args.cpu:
        jax.config.update("jax_platforms", "cpu")
    devices = jax.devices()
    device = {"platform": devices[0].platform,
              "device_kind": devices[0].device_kind,
              "device_count": len(devices)}
    if not args.cpu and device["platform"] != "tpu":
        sys.exit(f"bench_e2e.py: no TPU (default backend is "
                 f"{device['platform']!r}); pass --cpu for a CPU "
                 f"debug run, whose rows are not device numbers")
    # a failed native build fails the benchmark here: no runner that
    # asks for the native store can fall back to the Python twin
    from opentsdb_tpu.native.store_backend import load_library
    load_library()

    runners = {1: bench_config1, 2: bench_config2,
               3: lambda r: bench_config3(r, args.series3),
               4: bench_config4, 5: bench_config5,
               "wal": bench_wal, "live": bench_live,
               "lifecycle": bench_lifecycle, "cold": bench_cold,
               "sketch": bench_sketch,
               "ingest": bench_ingest, "viz": bench_viz,
               "cluster": bench_cluster,
               "cluster_rf": bench_cluster_rf,
               "multirouter": bench_multirouter,
               "streamv2": bench_streamv2, "obs": bench_obs,
               "obs2": bench_obs2, "control": bench_control,
               "eventtime": bench_eventtime}
    out = []
    for c in ((int(x) if x.isdigit() else x)
              for x in args.configs.split(",")):
        t0 = time.perf_counter()
        res = runners[c](args.repeats)
        res["total_s"] = round(time.perf_counter() - t0, 1)
        res.update(device)
        out.append(res)
        print(json.dumps(res), flush=True)
    ns = [r for r in out if r.get("config") == 3]
    if ns:
        print(json.dumps({
            "metric": "p50 /api/query e2e latency, north-star config",
            "value": ns[0]["p50_ms"], "unit": "ms",
            "north_star_pass": ns[0]["north_star_pass"]}),
            file=sys.stderr)


if __name__ == "__main__":
    main()
