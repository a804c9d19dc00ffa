"""A window that moves reuses the metric's whole buckets from HBM
(PR 50): the ``metricgrid`` entry of a window that is not resident is
put together from per-bucket columns that are (``metriccol`` in
``query/device_cache.py``), the buckets the window cuts come from ONE
storage pass a request (``bucket_columns``) that also counts every
row's points, and the program that runs the tail assembles the grid.
The assembled operands are ``bucket_grid``'s bit for bit, whatever the
window; a write drops every column; a store without the pass builds
the window whole as before.
"""

import threading
from types import SimpleNamespace

import numpy as np
import pytest

from opentsdb_tpu import TSDB, Config
from opentsdb_tpu.native import store_backend
from opentsdb_tpu.obs import trace as trace_mod
from opentsdb_tpu.ops import downsample as ds_mod
from opentsdb_tpu.ops import shapes
from opentsdb_tpu.ops.pipeline import (PipelineSpec, execute_columns,
                                       pipeline_dtype)
from opentsdb_tpu.query import engine as engine_mod
from opentsdb_tpu.query.engine import GRID_STATS
from opentsdb_tpu.query.model import TSQuery

try:
    store_backend.load_library()
    HAVE_NATIVE = True
except store_backend.NativeBuildError:
    HAVE_NATIVE = False

pytestmark = pytest.mark.skipif(not HAVE_NATIVE,
                                reason="g++ not available")

BASE = 1356998400
BASE_MS = BASE * 1000
MIN = 60_000
HOSTS = 24
POINTS = 40            # a point every 20 s at the host's own second


def _tsdb(backend="native", **extra):
    return TSDB(Config(**{
        "tsd.core.auto_create_metrics": "true",
        "tsd.tpu.warmup": "false",
        "tsd.storage.backend": backend,
        # the small fleet would run its tail on the host, past the
        # HBM cache: place it on the device
        "tsd.query.host_tail_max_cells": "-1",
        "tsd.query.host_tail_max_cells_linear": "-1",
        "tsd.query.cache.enable": "false", **extra}))


def _seed(t):
    """A counter a host, each at its own second of the 20 s; hosts 3
    and 13 gappy (a run of two minutes missing and a third of the
    rest), host 5 with stored NaN (one whole minute of nothing else),
    host 7 without a point in any window asked (one, an hour later)."""
    rng = np.random.default_rng(50)
    for i in range(HOSTS):
        ts = BASE + i % 20 + np.arange(POINTS) * 20
        vals = np.cumsum(rng.integers(1, 50, POINTS)).astype(float) \
            + 1 / 3
        keep = np.ones(POINTS, bool)
        if i in (3, 13):
            keep = rng.random(POINTS) > 0.33
            keep[12:18] = False
        if i == 5:
            vals[rng.random(POINTS) < 0.2] = np.nan
            vals[6:9] = np.nan
        if i == 7:
            ts = ts + 3600
        t.add_points("m", ts[keep], vals[keep],
                     {"host": f"h{i:02d}", "dc": f"d{i % 3}"})


@pytest.fixture
def fleet():
    t = _tsdb()
    _seed(t)
    yield t
    t.shutdown()


def _sids(t):
    return t.store.series_ids_for_metric(t.uids.metrics.get_id("m"))


# windows of the 800 s the fleet holds, 1m buckets
WINDOWS = {
    "aligned_at_both_ends": (BASE_MS + 2 * MIN, BASE_MS + 9 * MIN - 1),
    "cut_at_the_start": (BASE_MS + 2 * MIN + 7_001, BASE_MS + 9 * MIN - 1),
    "cut_at_the_end": (BASE_MS + 2 * MIN, BASE_MS + 9 * MIN + 31_000),
    "cut_at_both": (BASE_MS + 61_234, BASE_MS + 11 * MIN + 59_998),
    "inside_one_bucket": (BASE_MS + 4 * MIN + 5_000,
                          BASE_MS + 4 * MIN + 47_000),
    "two_cut_buckets_alone": (BASE_MS + 4 * MIN + 30_000,
                              BASE_MS + 5 * MIN + 30_000),
    "one_whole_bucket": (BASE_MS + 4 * MIN, BASE_MS + 5 * MIN - 1),
    "before_the_data": (BASE_MS - 30 * MIN + 10_000, BASE_MS - 1),
    "after_the_data": (BASE_MS + 20 * MIN + 1, BASE_MS + 31 * MIN),
    "over_the_datas_end": (BASE_MS + 10 * MIN + 3, BASE_MS + 19 * MIN),
}


def _assembled(t, start_ms, end_ms, fn="avg", interval=MIN):
    """``(grid, mask, counts, wanted)``: the padded operands the
    engine's column path hands its tail for the whole metric over this
    window, as the program assembled them, and the buckets the storage
    pass was asked for."""
    engine = t.new_query()
    sids = _sids(t)
    metric_id = t.uids.metrics.get_id("m")
    if not isinstance(metric_id, int):
        metric_id = t.store.series(int(sids[0])).metric_id
    bucket_ts = ds_mod.fixed_bucket_edges(start_ms, end_ms, interval)
    asked = []
    real = t.store.bucket_columns

    def recorded(*args):
        asked.append(list(args[7]))
        return real(*args)

    t.store.bucket_columns = recorded
    try:
        meta = {}
        columns = engine._metric_columns(
            t.device_grid_cache, t.store, sids, metric_id,
            SimpleNamespace(start_ms=start_ms, end_ms=end_ms),
            bucket_ts, interval, fn, meta, lambda scan, n: None)
    finally:
        del t.store.bucket_columns
    assert len(asked) == 1     # ONE walk a request
    spec = PipelineSpec(num_series=len(sids), num_buckets=len(bucket_ts),
                        num_groups=1, ds_function="avg", agg_name="sum")
    _, _, grid, mask = execute_columns(
        columns, bucket_ts, np.zeros(len(sids), np.int32), spec)
    return np.asarray(grid), np.asarray(mask), meta["counts"], asked[0]


def _bucket_grid(t, start_ms, end_ms, fn="avg", interval=MIN):
    """What ``bucket_grid`` writes for the same window, and
    ``count_range``'s counts."""
    sids = _sids(t)
    bucket_ts = ds_mod.fixed_bucket_edges(start_ms, end_ms, interval)
    padded = (shapes.shape_bucket(len(sids)),
              shapes.shape_bucket(len(bucket_ts)))
    grid = np.full(padded, 12345.0, np.dtype(pipeline_dtype()))
    mask = np.ones(padded, np.bool_)
    t.store.bucket_grid(sids, start_ms, end_ms, int(bucket_ts[0]),
                        interval, len(bucket_ts), GRID_STATS[fn], grid,
                        mask)
    return grid, mask, t.store.count_range(sids, start_ms, end_ms)


def _same_bits(a, b):
    return a.dtype == b.dtype and a.shape == b.shape \
        and a.tobytes() == b.tobytes()


@pytest.mark.parametrize("fn", ["avg", "sum", "max", "mimmin", "count"])
@pytest.mark.parametrize("name", sorted(WINDOWS))
def test_the_assembled_grid_is_bucket_grids_bit_for_bit(fleet, name, fn):
    """Values, mask and per-row counts; over series with gaps, with
    stored NaN and with no point; built cold, and again from the
    columns the first build left resident."""
    start, end = WINDOWS[name]
    want = _bucket_grid(fleet, start, end, fn)
    if "the_data" in name and "over" not in name:
        assert not want[1].any() and not want[2].any()
    else:
        assert want[1].any() and not want[1].all()
    buckets = len(ds_mod.fixed_bucket_edges(start, end, MIN))
    whole = sum(1 for k in range(buckets)
                if start <= start // MIN * MIN + k * MIN
                and start // MIN * MIN + (k + 1) * MIN - 1 <= end)
    for built in (whole, 0):
        grid, mask, counts, wanted = _assembled(fleet, start, end, fn)
        assert _same_bits(grid, want[0]) and _same_bits(mask, want[1])
        assert counts.dtype == want[2].dtype
        np.testing.assert_array_equal(counts, want[2])
        # the cut buckets every time, the whole ones once
        assert len(wanted) == buckets - whole + built
    cols = [k for k in fleet.device_grid_cache._entries
            if k[0] == engine_mod.RESIDENT_COLUMN_KEY]
    assert len(cols) == whole and all(k[-2] == fn for k in cols)


def test_a_window_slid_over_the_data_reuses_columns_and_stays_exact(
        fleet):
    """Windows of other lengths and starts over the same buckets: each
    is ``bucket_grid``'s, whichever columns were there before it."""
    rng = np.random.default_rng(7)
    for _ in range(25):
        start = BASE_MS - 2 * MIN + int(rng.integers(0, 16 * MIN))
        end = start + int(rng.integers(1, 12 * MIN))
        grid, mask, counts, _ = _assembled(fleet, start, end)
        want = _bucket_grid(fleet, start, end)
        assert _same_bits(grid, want[0]) and _same_bits(mask, want[1])
        np.testing.assert_array_equal(counts, want[2])


# ---------------------------------------------------------------------
# through the engine
# ---------------------------------------------------------------------

GROUP_DC = {"type": "wildcard", "tagk": "dc", "filter": "*",
            "groupBy": True}


def _q(start_ms, end_ms, agg="sum", ds="1m-avg", rate=True):
    sub = {"metric": "m", "aggregator": agg, "downsample": ds,
           "filters": [GROUP_DC]}
    if rate:
        sub["rate"] = True
        sub["rateOptions"] = {"counter": True, "counterMax": 10000}
    return TSQuery.from_json({"start": start_ms, "end": end_ms,
                              "queries": [sub]}).validate()


def _dps(results):
    return [(r.tags, r.dps) for r in results]


def _kinds(t):
    return sorted(k[0] for k in t.device_grid_cache._entries)


def _traced(t, query):
    """The answer, and the request's spans by name."""
    ctx = t.tracer.start_request("query.http")
    with trace_mod.use(ctx):
        out = t.execute_query(query)
    t.tracer.finish(ctx)
    spans = {}
    for s in ctx.spans:
        spans.setdefault(s.name, []).append(s.tags)
    return out, spans


@pytest.mark.parametrize("agg, ds, rate", [
    ("sum", "1m-avg", True), ("max", "1m-max", False),
    ("p95", "1m-sum", False)], ids=["sum_rate", "max", "p95"])
def test_now_stepped_a_second_at_a_time_answers_as_a_fresh_engine(
        agg, ds, rate):
    """Ten minutes up to a "now" that crosses two bucket edges a
    second at a time: at every step the answer of an engine with
    nothing resident (no stale edge, the turn-over included) and of
    the Python store's whole-window build."""
    moving, fresh, twin = _tsdb(), _tsdb(), _tsdb("memory")
    for t in (moving, fresh, twin):
        _seed(t)
    cache = moving.device_grid_cache
    first_end = BASE_MS + 11 * MIN - 2_500
    turned = 0
    for step in range(110):
        end = first_end + step * 1000 + (step * 379) % 1000
        q = _q(end - 10 * MIN, end, agg, ds, rate)
        misses = cache.misses
        got = _dps(moving.execute_query(q))
        assert got and any(dps for _, dps in got)
        fresh.drop_caches()
        assert got == _dps(fresh.execute_query(q)), step
        assert got == _dps(twin.execute_query(q)), step
        # the window's own miss, and a column's when a bucket has
        # just become whole
        built = cache.misses - misses - 1
        assert built == (9 if step == 0 else built) and built in (0, 1, 9)
        turned += built == 1
    assert turned == 2
    assert engine_mod.RESIDENT_COLUMN_KEY not in _kinds(twin)
    moving.shutdown(), fresh.shutdown(), twin.shutdown()


def test_the_spans_and_counters_say_which_columns_hit(fleet):
    t = fleet
    t.tracer.sample = 1.0
    end = BASE_MS + 11 * MIN + 1_500
    _, first = _traced(t, _q(end - 10 * MIN, end))
    _, second = _traced(t, _q(end - 10 * MIN + 1000, end + 1000))
    _, again = _traced(t, _q(end - 10 * MIN + 1000, end + 1000))

    def stage(spans, name):
        return [s for s in spans["query.grid_build"]
                if s.get("stage") == name]

    assert [(s["hit"], s["built"], s["cut"])
            for s in stage(first, "columns")] == [(0, 9, 2)]
    assert [(s["hit"], s["built"], s["cut"])
            for s in stage(second, "columns")] == [(9, 0, 2)]
    assert not stage(again, "columns")
    assert [s["grid"] for s in stage(first, "cache_lookup")
            + stage(second, "cache_lookup")
            + stage(again, "cache_lookup")] \
        == ["resident_columns", "resident_columns", "resident_hit"]
    # the pass wrote eleven columns of 24 padded rows, then two
    assert [s["cells"] for s in stage(first, "alloc")
            + stage(second, "alloc")] == [11 * 24, 2 * 24]
    # ONE program a request: the columns' assembles the grid, which
    # the same window asked again finds as any resident grid
    assert [[p["path"] for p in spans["query.program"]]
            for spans in (first, second, again)] \
        == [["columns"], ["columns"], ["grid"]]
    assert t.tracer.grids == {
        "resident_built": 0, "resident_columns": 2, "resident_hit": 1,
        "selection": 0}
    grids = [e for k, e in t.device_grid_cache._entries.items()
             if k[0] == engine_mod.RESIDENT_GRID_KEY]
    assert len(grids) == 2
    for _, arrays, meta, *_ in grids:
        assert len(arrays) == 2 and arrays[0].shape == (24, 12)
        assert set(meta) == {"counts"}
    cache = t.device_grid_cache
    # 2 windows and 9 columns missed; 9 columns and a window hit
    assert (cache.misses, cache.hits) == (11, 10)


def test_a_write_between_two_requests_drops_the_columns(fleet):
    """And the acknowledged point is read back: into a bucket that lay
    whole in both windows, whose column the second request would
    otherwise have reused. Since PR 51 the columns of the buckets
    that end BEFORE the write's timestamp stay (the store says what
    the oldest timestamp written since their version is); the bucket
    it landed in and every later one are built again."""
    t = fleet
    fresh = _tsdb()
    _seed(fresh)
    cache = t.device_grid_cache
    end = BASE_MS + 11 * MIN + 1_500
    before = _dps(t.execute_query(_q(end - 10 * MIN, end, "max",
                                     "1m-max", False)))
    for db in (t, fresh):
        db.add_point("m", BASE + 6 * 60 + 30, 1e6,
                     {"host": "h01", "dc": "d1"})
    misses = cache.misses
    after = _dps(t.execute_query(_q(end - 10 * MIN + 1000, end + 1000,
                                    "max", "1m-max", False)))
    # the window, minute 6 and the four after it; minutes 2-5 stay
    assert cache.misses == misses + 1 + 5
    assert (cache.stale_kept, cache.stale_dropped) == (4, 5)
    assert after == _dps(fresh.execute_query(_q(
        end - 10 * MIN + 1000, end + 1000, "max", "1m-max", False)))
    row = dict(next(dps for tags, dps in after if tags == {"dc": "d1"}))
    assert row[BASE_MS + 6 * MIN] == 1e6
    assert dict(next(dps for tags, dps in before
                     if tags == {"dc": "d1"}))[BASE_MS + 6 * MIN] < 1e6
    # no column of the old version is left
    versions = {e[0] for k, e in cache._entries.items()
                if k[0] == engine_mod.RESIDENT_COLUMN_KEY}
    assert len(versions) == 1
    fresh.shutdown()


def test_two_threads_asking_different_windows_build_each_column_once(
        fleet):
    t = fleet
    fresh = _tsdb()
    _seed(fresh)
    engine = t.new_query()
    end = BASE_MS + 11 * MIN + 1_500
    queries = [_q(end - 10 * MIN + i * 1000, end + i * 1000)
               for i in range(2)]
    first_bucket = (end - 10 * MIN) // MIN * MIN
    built = []
    gate = threading.Barrier(2, timeout=30)
    real = t.store.bucket_columns

    def recorded(*args):
        whole = [int(args[3]) + k * MIN for k in args[7]
                 if args[1] <= args[3] + k * MIN
                 and args[3] + (k + 1) * MIN - 1 <= args[2]]
        built.append(whole)
        return real(*args)

    t.store.bucket_columns = recorded
    out = [None, None]

    def ask(i):
        gate.wait()
        out[i] = _dps(engine.run(queries[i]))

    threads = [threading.Thread(target=ask, args=(i,)) for i in (0, 1)]
    for th in threads:
        th.start()
    for th in threads:
        th.join(60)
        assert not th.is_alive()
    assert out == [_dps(fresh.execute_query(q)) for q in queries]
    # two walks, one of which built the nine whole buckets, once each
    assert sorted(map(len, built)) == [0, 9]
    assert max(built, key=len) \
        == [first_bucket + k * MIN for k in range(1, 10)]
    assert t.device_grid_cache._flights == {}
    fresh.shutdown()


def test_the_python_store_keeps_the_whole_window_build():
    t = _tsdb("memory")
    _seed(t)
    t.tracer.sample = 1.0
    assert not hasattr(t.store, "bucket_columns")
    end = BASE_MS + 11 * MIN + 1_500
    for i in range(2):
        _, spans = _traced(t, _q(end - 10 * MIN + i * 1000,
                                 end + i * 1000))
        assert [p["path"] for p in spans["query.program"]] == ["grid"]
        assert not [s for s in spans["query.grid_build"]
                    if s.get("stage") == "columns"]
    assert _kinds(t) == [engine_mod.RESIDENT_GRID_KEY] * 2
    assert t.tracer.grids["resident_built"] == 2
    assert t.tracer.grids["resident_columns"] == 0
    cache = t.device_grid_cache
    assert (cache.misses, cache.hits) == (2, 0)
    t.shutdown()


def test_a_window_of_more_buckets_than_columns_may_number_is_built_whole(
        fleet, monkeypatch):
    monkeypatch.setattr(engine_mod, "RESIDENT_COLUMNS_MAX_BUCKETS", 10)
    end = BASE_MS + 11 * MIN + 1_500
    want = _dps(fleet.execute_query(_q(end - 10 * MIN, end)))
    assert _kinds(fleet) == [engine_mod.RESIDENT_GRID_KEY]
    monkeypatch.setattr(engine_mod, "RESIDENT_COLUMNS_MAX_BUCKETS", 11)
    fleet.drop_caches()
    assert _dps(fleet.execute_query(_q(end - 10 * MIN, end))) == want
    assert _kinds(fleet).count(engine_mod.RESIDENT_COLUMN_KEY) == 9
