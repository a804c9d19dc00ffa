"""The padded grid is written once (PR 25): the native store's fused
``bucket_grid`` and the engine's ``fill_padded_grid`` (every other
store) both give, bit for bit, what the composition they replaced gave:
``bucket_reduce`` of the Python twin -> ``np.where`` fill -> pad through
``np.full`` -> ``astype`` (the upload's cast). And a grid query answers
the same bits through either path.
"""

import json

import jax
import numpy as np
import pytest

from opentsdb_tpu import TSDB, Config
from opentsdb_tpu.core.store import TimeSeriesStore
from opentsdb_tpu.native import store_backend
from opentsdb_tpu.ops.shapes import pad_2d_host
from opentsdb_tpu.query.engine import GRID_STATS, fill_padded_grid
from opentsdb_tpu.tsd.http_api import HttpRequest, HttpRpcRouter

try:
    store_backend.load_library()
    HAVE_NATIVE = True
except store_backend.NativeBuildError:
    HAVE_NATIVE = False

pytestmark = pytest.mark.skipif(not HAVE_NATIVE,
                                reason="g++ not available")

BASE_MS = 1356998400000
STEP = 10_000          # a point every 10 s
INTERVAL = 60_000      # 1m buckets


def _values(rng, n):
    # not representable in f32, of mixed sign and magnitude: a cast at
    # the wrong place (before the divide, say) shows
    return rng.normal(0.0, 1e3, n) + rng.integers(-5, 5, n) * 1e6 / 3.0


def scenario(name):
    """-> (series, window) where series is a list of (ts_ms, values)
    and window = (start_ms, end_ms, t0, interval_ms, nbuckets, s_pad,
    b_pad)."""
    rng = np.random.default_rng(sum(name.encode()))
    n, s, b = 72, 5, 12
    ts = BASE_MS + np.arange(n) * STEP
    series = [(ts, _values(rng, n)) for _ in range(s)]
    start, end, t0 = BASE_MS, BASE_MS + n * STEP - 1, BASE_MS
    s_pad, b_pad = 8, 16
    if name == "gappy_tenth":
        series = [(t[keep], v[keep]) for t, v in series
                  for keep in [rng.random(n) >= 0.1]]
        # and a run of whole buckets without a point
        series[1] = (series[1][0][30:], series[1][1][30:])
    elif name == "empty_series":
        series[2] = (ts[:0], np.zeros(0))
        series[4] = (ts + 10 * n * STEP, series[4][1])  # out of window
    elif name == "nan_values":
        for _, v in series:
            v[rng.random(n) < 0.3] = np.nan
        series[3][1][6:12] = np.nan   # one bucket of nothing but NaN
    elif name == "bucket_edge":
        # points exactly on every bucket's first millisecond, on the
        # window's last, and one past it
        edge = BASE_MS + np.arange(b + 1) * INTERVAL
        series = [(edge, _values(rng, b + 1)) for _ in range(s)]
        end = int(edge[-2])
        series.append((np.array([end, end + 1]), np.array([7.5, 9.5])))
        s_pad = 8
    elif name == "t0_before_start":
        # the first bucket starts before the window: its points before
        # start_ms stay out
        start = BASE_MS + 25_000
    elif name == "no_pad":
        series = series[:4]
        series[0] = (series[0][0][12:], series[0][1][12:])
        s_pad, b_pad = 4, b
    else:
        assert name in ("dense", "both_pads"), name
        if name == "both_pads":
            s_pad, b_pad = 16, 32
    return series, (start, end, t0, INTERVAL, b, s_pad, b_pad)


SCENARIOS = ("dense", "gappy_tenth", "empty_series", "nan_values",
             "bucket_edge", "t0_before_start", "no_pad", "both_pads")


def load(store, series):
    sids = []
    for i, (ts, vals) in enumerate(series):
        sid = store.get_or_create_series(1, [(1, i + 1)])
        if len(ts):
            store.append_many(sid, ts, vals)
        sids.append(sid)
    return np.asarray(sids, dtype=np.int64)


def replaced_composition(stat, sums, cnts, mins, maxs, s_pad, b_pad,
                         dtype):
    """``engine._grid_pipeline``'s fill and pads as they stood before
    PR 25, and ``put_grid``'s cast."""
    present = cnts > 0
    if stat == "sum":
        grid = np.where(present, sums, np.nan)
    elif stat == "count":
        grid = np.where(present, cnts, np.nan)
    elif stat == "avg":
        grid = np.where(present, sums / np.maximum(cnts, 1.0), np.nan)
    elif stat == "min":
        grid = np.where(present, mins, np.nan)
    else:
        grid = np.where(present, maxs, np.nan)
    grid = pad_2d_host(grid, s_pad, b_pad, np.nan)
    has_data = pad_2d_host(present, s_pad, b_pad, False)
    return grid.astype(dtype), has_data


def same_bits(a, b):
    return a.dtype == b.dtype and a.shape == b.shape \
        and a.tobytes() == b.tobytes()


@pytest.mark.parametrize("dtype", [np.float32, np.float64],
                         ids=["f32", "f64"])
@pytest.mark.parametrize("stat", ["sum", "count", "avg", "min", "max"])
@pytest.mark.parametrize("name", SCENARIOS)
def test_both_paths_write_the_bits_of_the_composition_they_replace(
        name, stat, dtype):
    series, (start, end, t0, interval, b, s_pad, b_pad) = scenario(name)
    twin = TimeSeriesStore(num_shards=4)
    native = store_backend.NativeTimeSeriesStore(num_shards=4)
    sids = load(twin, series)
    np.testing.assert_array_equal(load(native, series), sids)
    reduced = twin.bucket_reduce(sids, start, end, t0, interval, b,
                                 want_minmax=stat in ("min", "max"))
    want, want_mask = replaced_composition(stat, *reduced, s_pad, b_pad,
                                           dtype)
    assert want_mask.any() and not want_mask.all()

    def fresh():   # garbage, so a cell left unwritten shows
        return (np.full((s_pad, b_pad), 12345.0, dtype),
                np.ones((s_pad, b_pad), np.bool_))

    grid, has_data = fresh()
    num_points = native.bucket_grid(sids, start, end, t0, interval, b,
                                    stat, grid, has_data)
    assert num_points == int(reduced[1].sum())
    assert np.array_equal(grid, want, equal_nan=True)
    # NaN holes and all: not merely equal, the same bits
    assert same_bits(grid, want) and same_bits(has_data, want_mask)

    grid, has_data = fresh()
    fill_padded_grid(stat, *reduced, grid, has_data)
    assert np.array_equal(grid, want, equal_nan=True)
    assert same_bits(grid, want) and same_bits(has_data, want_mask)

    # and the native store's own bucket_reduce (it shares the walk)
    # still feeds the helper the same
    grid, has_data = fresh()
    fill_padded_grid(stat, *native.bucket_reduce(
        sids, start, end, t0, interval, b,
        want_minmax=stat in ("min", "max")), grid, has_data)
    assert same_bits(grid, want) and same_bits(has_data, want_mask)


def test_a_wide_grid_is_claimed_in_chunks_by_every_worker():
    """More rows than one claim (256), more workers than one, pad rows
    beyond the last chunk boundary: every row written exactly once."""
    rng = np.random.default_rng(25)
    s, b, s_pad, b_pad = 1500, 12, 2048, 16
    ts = BASE_MS + np.arange(72) * STEP
    native = store_backend.NativeTimeSeriesStore(
        num_shards=4, materialize_threads=8)
    twin = TimeSeriesStore(num_shards=4)
    series = []
    for _ in range(s):
        keep = rng.random(72) >= 0.1
        series.append((ts[keep], _values(rng, int(keep.sum()))))
    sids = load(twin, series)
    load(native, series)
    window = (sids, BASE_MS, BASE_MS + 72 * STEP - 1, BASE_MS, INTERVAL, b)
    reduced = twin.bucket_reduce(*window)
    want, want_mask = replaced_composition("avg", *reduced, s_pad, b_pad,
                                           np.float32)
    for _ in range(3):
        grid = np.full((s_pad, b_pad), 12345.0, np.float32)
        has_data = np.ones((s_pad, b_pad), np.bool_)
        assert native.bucket_grid(*window, "avg", grid, has_data) \
            == int(reduced[1].sum())
        assert same_bits(grid, want) and same_bits(has_data, want_mask)


# ---------------------------------------------------------------------
# the column pass (PR 50): bucket_grid's cells a bucket at a time
# ---------------------------------------------------------------------

WANTED = {"one": lambda b: [b // 2], "two": lambda b: [0, b - 1],
          "all": lambda b: list(range(b))}


@pytest.mark.parametrize("dtype", [np.float32, np.float64],
                         ids=["f32", "f64"])
@pytest.mark.parametrize("wanted", sorted(WANTED))
@pytest.mark.parametrize("fn", sorted(GRID_STATS))
@pytest.mark.parametrize("name", SCENARIOS)
def test_the_column_pass_writes_the_wanted_columns_of_that_grid(
        name, fn, wanted, dtype):
    """``bucket_columns`` against the twin's ``bucket_reduce`` (through
    the composition ``bucket_grid`` is held to above), for every
    downsample function the grid serves: column ``w`` of the pass is
    column ``wanted[w]`` of the grid, bit for bit, rows beyond the
    series NaN / False (``s_pad`` > series in every scenario but
    ``no_pad``), and the counts are ``count_range``'s."""
    series, (start, end, t0, interval, b, s_pad, _) = scenario(name)
    stat = GRID_STATS[fn]
    twin = TimeSeriesStore(num_shards=4)
    native = store_backend.NativeTimeSeriesStore(num_shards=4)
    sids = load(twin, series)
    load(native, series)
    reduced = twin.bucket_reduce(sids, start, end, t0, interval, b,
                                 want_minmax=stat in ("min", "max"))
    want, want_mask = replaced_composition(stat, *reduced, s_pad, b,
                                           dtype)
    which = WANTED[wanted](b)
    cols = np.full((len(which), s_pad), 12345.0, dtype)
    masks = np.ones((len(which), s_pad), np.bool_)
    counts = native.bucket_columns(sids, start, end, t0, interval, b,
                                   stat, which, cols, masks)
    assert same_bits(cols, np.ascontiguousarray(want[:, which].T))
    assert same_bits(masks, np.ascontiguousarray(want_mask[:, which].T))
    assert counts.dtype == np.int64
    np.testing.assert_array_equal(
        counts, twin.count_range(sids, start, end))
    np.testing.assert_array_equal(
        counts, native.count_range(sids, start, end))


def test_columns_of_many_rows_are_claimed_in_chunks_by_every_worker():
    """More rows than one claim (1,024), more workers than one, pad
    rows beyond the last chunk boundary, a cut bucket at each end."""
    rng = np.random.default_rng(50)
    s, b, s_pad = 2500, 12, 3072
    ts = BASE_MS + np.arange(72) * STEP
    native = store_backend.NativeTimeSeriesStore(
        num_shards=4, materialize_threads=8)
    series = []
    for _ in range(s):
        keep = rng.random(72) >= 0.1
        series.append((ts[keep], _values(rng, int(keep.sum()))))
    sids = load(native, series)
    window = (sids, BASE_MS + 25_000, BASE_MS + 72 * STEP - 15_001,
              BASE_MS, INTERVAL, b)
    grid = np.empty((s_pad, b), np.float32)
    has_data = np.empty((s_pad, b), np.bool_)
    native.bucket_grid(*window, "avg", grid, has_data)
    for which in ([0, b - 1], [0, 4, 5, b - 1], list(range(b))):
        cols = np.full((len(which), s_pad), 12345.0, np.float32)
        masks = np.ones((len(which), s_pad), np.bool_)
        counts = native.bucket_columns(*window, "avg", which, cols,
                                       masks)
        assert same_bits(cols, np.ascontiguousarray(grid[:, which].T))
        assert same_bits(masks,
                         np.ascontiguousarray(has_data[:, which].T))
        np.testing.assert_array_equal(
            counts, native.count_range(*window[:3]))


@pytest.mark.parametrize("seed", range(6))
def test_the_column_pass_finds_its_ranges_in_series_of_any_cadence(seed):
    """The pass begins each search where a series of even cadence
    would hold the key and widens from there: series whose points
    bunch, thin out a thousandfold, lie all on one side of the window
    or number one or two must give the ranges a halving search gives
    (``bucket_grid``'s and ``count_range``'s)."""
    rng = np.random.default_rng(500 + seed)
    native = store_backend.NativeTimeSeriesStore(num_shards=4)
    span = 3_600_000
    series = []
    for i in range(64):
        n = int(rng.choice([1, 2, 3, 40, 400]))
        kind = i % 4
        if kind == 0:       # gaps over three decades
            gaps = np.exp(rng.uniform(0, 7, n)).astype(np.int64) + 1
            ts = BASE_MS + np.cumsum(gaps) * 50
        elif kind == 1:     # nearly all in one minute, a few far off
            ts = BASE_MS + np.sort(np.concatenate([
                rng.integers(1_700_000, 1_760_000, max(n - 2, 1)),
                rng.integers(0, span, 2)]))
        elif kind == 2:     # even, at the series' own phase
            ts = BASE_MS + int(rng.integers(0, 60_000)) \
                + np.arange(n) * (span // n)
        else:               # all before or all after most windows
            ts = BASE_MS + (0 if i % 8 == 3 else span - 100_000) \
                + np.sort(rng.integers(0, 100_000, n))
        ts = np.unique(ts)
        series.append((ts, _values(rng, len(ts))))
    sids = load(native, series)
    for _ in range(12):
        interval = int(rng.choice([60_000, 300_000]))
        start = BASE_MS - 100_000 + int(rng.integers(0, span))
        end = start + int(rng.integers(0, span // 2))
        t0 = start // interval * interval
        b = (end - t0) // interval + 1
        grid = np.empty((64, b), np.float64)
        has_data = np.empty((64, b), np.bool_)
        native.bucket_grid(sids, start, end, t0, interval, b, "avg",
                           grid, has_data)
        which = sorted(set(rng.integers(0, b, 3).tolist()) | {0, b - 1})
        cols = np.empty((len(which), 64), np.float64)
        masks = np.empty((len(which), 64), np.bool_)
        counts = native.bucket_columns(sids, start, end, t0, interval, b,
                                       "avg", which, cols, masks)
        assert same_bits(cols, np.ascontiguousarray(grid[:, which].T))
        assert same_bits(masks,
                         np.ascontiguousarray(has_data[:, which].T))
        np.testing.assert_array_equal(
            counts, native.count_range(sids, start, end))


class TestColumnRefusals:

    @pytest.fixture
    def native(self):
        store = store_backend.NativeTimeSeriesStore(num_shards=4)
        load(store, scenario("dense")[0])
        return store

    WINDOW = (BASE_MS, BASE_MS + 719_999, BASE_MS, INTERVAL, 12)

    @staticmethod
    def buffers(n=2, s_pad=8):
        return (np.empty((n, s_pad), np.float32),
                np.empty((n, s_pad), np.bool_))

    def test_an_invalid_series_id_raises_as_bucket_grid_does(
            self, native):
        with pytest.raises(IndexError):
            native.bucket_columns(np.array([0, 99]), *self.WINDOW, "sum",
                                  [0, 11], *self.buffers())

    @pytest.mark.parametrize("wanted", [[12], [-1, 3], [3, 3], [5, 2]],
                             ids=["past_the_window", "negative",
                                  "twice", "falling"])
    def test_a_bucket_the_window_lacks_or_out_of_order_is_refused(
            self, native, wanted):
        with pytest.raises(IndexError):
            native.bucket_columns(np.arange(5), *self.WINDOW, "sum",
                                  wanted, *self.buffers(len(wanted)))

    @pytest.mark.parametrize("dims", [
        dict(interval_ms=0), dict(nbuckets=0), dict(s_pad=4),
        dict(fn=5), dict(nwanted=-1), dict(sid=99), dict(bucket=12)],
        ids=lambda d: next(iter(d)))
    def test_the_entry_itself_returns_minus_one(self, native, dims):
        """Straight at the library, past the wrapper's own checks."""
        lib = store_backend.load_library()
        sids = np.array([0, dims.get("sid", 1)], dtype=np.int64)
        wanted = np.array([0, dims.get("bucket", 11)], dtype=np.int64)
        cols, masks = self.buffers()
        counts = np.empty(2, np.int64)
        ptr = store_backend._ptr

        def call(**over):
            a = {"interval_ms": INTERVAL, "nbuckets": 12, "fn": 0,
                 "nwanted": 2, "s_pad": 8, **over}
            return lib.tss_bucket_columns(
                native._h, ptr(sids), 2, BASE_MS, BASE_MS + 719_999,
                BASE_MS, a["interval_ms"], a["nbuckets"], a["fn"],
                ptr(wanted), a["nwanted"], a["s_pad"], 0, ptr(cols),
                ptr(masks), ptr(counts), 2)

        over = {k: v for k, v in dims.items()
                if k not in ("sid", "bucket")}
        if "s_pad" in over:
            over["s_pad"] = 1     # fewer rows than series
        assert call(**over) == -1
        if not over:
            return
        sids[1], wanted[1] = 1, 11
        assert call() == 0

    @pytest.mark.parametrize("cols, masks", [
        (np.empty((2, 8), np.float16), np.empty((2, 8), np.bool_)),
        (np.empty((2, 8), np.float32), np.empty((2, 8), np.uint8)),
        (np.empty((2, 8), np.float32), np.empty((2, 16), np.bool_)),
        (np.empty((8, 2), np.float32).T, np.empty((2, 8), np.bool_)),
        (np.empty((2, 4), np.float32), np.empty((2, 4), np.bool_)),
        (np.empty((3, 8), np.float32), np.empty((3, 8), np.bool_)),
    ], ids=["f16", "mask_u8", "shapes_differ", "not_contiguous",
            "too_few_rows", "not_a_column_a_bucket"])
    def test_buffers_the_pass_cannot_write_are_refused(
            self, native, cols, masks):
        with pytest.raises(ValueError):
            native.bucket_columns(np.arange(5), *self.WINDOW, "sum",
                                  [0, 11], cols, masks)


class TestRefusals:

    @pytest.fixture
    def native(self):
        store = store_backend.NativeTimeSeriesStore(num_shards=4)
        load(store, scenario("dense")[0])
        return store

    WINDOW = (BASE_MS, BASE_MS + 719_999, BASE_MS, INTERVAL, 12)

    def test_an_invalid_series_id_raises_as_bucket_reduce_does(
            self, native):
        bad = np.array([0, 99], dtype=np.int64)
        with pytest.raises(IndexError):
            native.bucket_reduce(bad, *self.WINDOW)
        with pytest.raises(IndexError):
            native.bucket_grid(bad, *self.WINDOW, "sum",
                               np.empty((8, 16), np.float32),
                               np.empty((8, 16), np.bool_))

    @pytest.mark.parametrize("grid, has_data", [
        (np.empty((8, 16), np.float16), np.empty((8, 16), np.bool_)),
        (np.empty((8, 16), np.float32), np.empty((8, 16), np.uint8)),
        (np.empty((8, 16), np.float32), np.empty((8, 8), np.bool_)),
        (np.empty((16, 8), np.float32).T, np.empty((8, 16), np.bool_)),
        (np.empty((4, 16), np.float32), np.empty((4, 16), np.bool_)),
        (np.empty((8, 8), np.float32), np.empty((8, 8), np.bool_)),
    ], ids=["f16", "mask_u8", "shapes_differ", "not_contiguous",
            "too_few_rows", "too_few_columns"])
    def test_a_buffer_the_pass_cannot_write_is_refused(
            self, native, grid, has_data):
        with pytest.raises(ValueError):
            native.bucket_grid(np.arange(5), *self.WINDOW, "sum", grid,
                               has_data)

    def test_a_library_without_the_entry_fails_as_a_build_does(
            self, monkeypatch):
        """No silent fallback to the helper path."""
        class Stale:
            def __getattr__(self, name):
                if name == "tss_bucket_grid":
                    raise AttributeError(name)
                return lambda *a: None

        monkeypatch.setattr(store_backend, "_lib", None)
        monkeypatch.setattr(store_backend, "_build_error", None)
        monkeypatch.setattr(store_backend.ctypes, "CDLL",
                            lambda path: Stale())
        with pytest.raises(store_backend.NativeBuildError,
                           match="tss_bucket_grid"):
            store_backend.load_library()
        # negative-cached like a failed build
        with pytest.raises(store_backend.NativeBuildError):
            store_backend.load_library()


def test_every_grid_function_names_its_statistic():
    assert set(GRID_STATS.values()) == set(store_backend._GRID_FN_CODES)


# ---------------------------------------------------------------------
# through the engine
# ---------------------------------------------------------------------

def import_text(series=48, points=90):
    rng = np.random.default_rng(7)
    lines = []
    for h in range(series):
        total = 1e6 * h
        for i in range(points):
            if h % 10 == 3 and rng.random() < 0.1:
                continue                       # a gappy tenth
            total += float(rng.integers(0, 1000)) + 1 / 3
            if h == 5 and i == 40:
                total = 17.25                  # a counter reset
            lines.append(f"sys.grid {BASE_MS // 1000 + i * 10} {total!r}"
                         f" host=h{h} dc=d{h % 4} rack=r{h % 6}\n")
    return "".join(lines).encode()


QUERIES = {
    # the wide cell's shape class at small size
    "rate_counter_groupby": {
        "metric": "sys.grid", "aggregator": "sum", "rate": True,
        "rateOptions": {"counter": True, "counterMax": 10000000},
        "downsample": "5m-avg", "filters": [
            {"type": "wildcard", "tagk": "dc", "filter": "*",
             "groupBy": True},
            {"type": "not_literal_or", "tagk": "rack", "filter": "r2",
             "groupBy": False}]},
    # the min/max branch (the panels cell's)
    "max_1m_max": {
        "metric": "sys.grid", "aggregator": "max",
        "downsample": "1m-max", "filters": [
            {"type": "literal_or", "tagk": "host",
             "filter": "h1|h3|h13|h40", "groupBy": False}]},
    "min_1m_min_by_dc": {
        "metric": "sys.grid", "aggregator": "min",
        "downsample": "1m-min", "filters": [
            {"type": "wildcard", "tagk": "dc", "filter": "*",
             "groupBy": True}]},
    # the live cell's pair
    "sum_1m_avg": {
        "metric": "sys.grid", "aggregator": "sum",
        "downsample": "1m-avg", "filters": [
            {"type": "wildcard", "tagk": "dc", "filter": "*",
             "groupBy": True}]},
    "sum_1m_count_nan_fill": {
        "metric": "sys.grid", "aggregator": "sum",
        "downsample": "1m-count-nan"},
    "zimsum_1m_sum": {
        "metric": "sys.grid", "aggregator": "zimsum",
        "downsample": "1m-sum"},
}


def answer(backend, query, flags):
    tsdb = TSDB(Config(**{
        "tsd.core.auto_create_metrics": "true",
        "tsd.tpu.warmup": "false", "tsd.trace.sample": "1",
        "tsd.query.cache.enable": "false",
        "tsd.storage.backend": backend, **flags}))
    router = HttpRpcRouter(tsdb)
    try:
        written, errors = tsdb.import_buffer(import_text(),
                                             durable=False)
        assert written and not errors
        body = json.dumps({
            "start": BASE_MS, "end": BASE_MS + 900_000,
            "queries": [query]}).encode()
        resp = router.handle(HttpRequest(
            method="POST", path="/api/query", params={}, headers={},
            body=body))
        assert resp.status == 200, resp.body
        (root,) = json.loads(router.handle(HttpRequest(
            method="GET", path="/api/trace/"
            + resp.headers["X-TSD-Trace-Id"], params={}, headers={},
            body=b"")).body)["tree"]
        execute = next(c for c in root["children"]
                       if c["name"] == "query.execute")
        (build,) = [c["tags"] for c in execute["children"]
                    if c["name"] == "query.grid_build"
                    and "fused" in c["tags"]]
        (program,) = [c["tags"] for c in execute["children"]
                      if c["name"] == "query.program"]
        build["path"] = program["path"]
        modes = {r["tags"]["mode"]: r["value"] for r in json.loads(
            router.handle(HttpRequest(
                method="GET", path="/api/stats", params={}, headers={},
                body=b"")).body)
            if r["metric"] == "tsd.query.grid_build"}
        return resp.body, modes, build
    finally:
        tsdb.shutdown()


@pytest.mark.parametrize("x64", [False, True], ids=["f32", "f64"])
@pytest.mark.parametrize("flags", [
    {}, {"tsd.query.host_tail_max_cells_linear": "-1"}],
    ids=["host_tail", "device_tail"])
@pytest.mark.parametrize("name", sorted(QUERIES))
def test_a_grid_query_answers_the_same_bytes_through_either_path(
        name, flags, x64):
    """``tsd.storage.backend`` native (the fused entry) against memory
    (the helper), in the compute dtype of a default server (float32,
    x64 off) and of an x64 one."""
    with jax.enable_x64(x64):
        fused, fused_modes, fused_build = answer(
            "native", QUERIES[name], flags)
        host, host_modes, host_build = answer(
            "memory", QUERIES[name], flags)
    assert fused_modes == {"fused": 1, "host": 0}
    assert host_modes == {"fused": 0, "host": 1}
    if fused_build["path"] == "columns":
        # the metric's resident grid, put together from its per-bucket
        # columns (PR 50; tests/test_moving_window.py): the pass wrote
        # the window's buckets a column each and no pad column
        # (900 s: 4 buckets of 5m padded to 8, 16 of 1m to 16)
        buckets, padded = (4, 8) \
            if QUERIES[name]["downsample"].startswith("5m") else (16, 16)
        host_build = {**host_build,
                      "cells": host_build["cells"] // padded * buckets,
                      "bytes": host_build["bytes"] // padded * buckets}
    else:
        assert fused_build["path"] == host_build["path"] == "grid"
    assert fused_build == {**host_build, "stage": "alloc", "fused": True,
                           "path": fused_build["path"]}
    # a cell of the compute dtype and a byte of mask
    assert host_build["bytes"] == host_build["cells"] * (9 if x64 else 5)
    assert fused == host
    rows = json.loads(fused)
    assert rows and all(r["dps"] for r in rows)
