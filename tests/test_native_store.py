"""Native C++ store backend tests: behavioral parity with the Python
TimeSeriesStore, plus the end-to-end query path on top of it."""

import contextlib
import os

import numpy as np
import pytest

pytest.importorskip("ctypes")

from opentsdb_tpu.native import store_backend

BASE = 1356998400

try:
    store_backend.load_library()
    HAVE_NATIVE = True
except store_backend.NativeBuildError:
    HAVE_NATIVE = False

pytestmark = pytest.mark.skipif(not HAVE_NATIVE,
                                reason="g++ not available")


@pytest.fixture
def store():
    return store_backend.NativeTimeSeriesStore(num_shards=8)


class TestNativeStore:
    def test_series_identity(self, store):
        a = store.get_or_create_series(1, [(1, 1)])
        b = store.get_or_create_series(1, [(1, 2)])
        assert a != b
        assert store.get_or_create_series(1, [(1, 1)]) == a
        assert store.num_series() == 2

    def test_append_and_view(self, store):
        sid = store.get_or_create_series(1, [(1, 1)])
        for i in range(100):
            store.append(sid, i * 1000, float(i), i % 2 == 0)
        ts, vals, ints = store.series(sid).buffer.view_full()
        np.testing.assert_array_equal(ts, np.arange(100) * 1000)
        np.testing.assert_array_equal(vals, np.arange(100.0))
        assert ints[0] and not ints[1]
        assert store.points_written == 100

    def test_out_of_order_and_dupes(self, store):
        sid = store.get_or_create_series(1, [(1, 1)])
        for t, v in ((5000, 5.0), (1000, 1.0), (5000, 99.0),
                     (3000, 3.0)):
            store.append(sid, t, v)
        ts, vals = store.series(sid).buffer.view()
        np.testing.assert_array_equal(ts, [1000, 3000, 5000])
        np.testing.assert_array_equal(vals, [1.0, 3.0, 99.0])

    def test_append_many(self, store):
        sid = store.get_or_create_series(1, [(1, 1)])
        store.append_many(sid, np.arange(1000) * 1000,
                          np.arange(1000.0))
        assert len(store.series(sid).buffer) == 1000

    @pytest.mark.parametrize("backend", ["native", "python"])
    def test_bulk_series_creation(self, backend):
        from opentsdb_tpu.core.store import TimeSeriesStore
        store = (store_backend.NativeTimeSeriesStore(num_shards=8)
                 if backend == "native" else
                 TimeSeriesStore(num_shards=8))
        # pre-create one so the bulk path mixes hits and misses; also
        # include an in-batch duplicate (must resolve to one sid)
        pre = store.get_or_create_series(7, [(1, 3)])
        tags_list = [((1, 3),), ((1, 4),), ((2, 5), (1, 4)),
                     ((1, 4),), ((1, 6),)]
        sids = store.get_or_create_series_bulk(7, tags_list)
        assert sids[0] == pre
        assert sids[1] == sids[3]
        assert len(set(sids.tolist())) == 4
        # identity agrees with the scalar path, tag order normalized
        assert store.get_or_create_series(7, [(1, 4), (2, 5)]) == sids[2]
        # index sees every new series exactly once
        assert sorted(store.series_ids_for_metric(7).tolist()) == \
            sorted(set(sids.tolist()))
        # a second bulk call is all hits
        np.testing.assert_array_equal(
            store.get_or_create_series_bulk(7, tags_list), sids)

    def test_materialize_matches_python(self, store):
        from opentsdb_tpu.core.store import TimeSeriesStore
        pystore = TimeSeriesStore(num_shards=8)
        rng = np.random.default_rng(4)
        for s in range(20):
            nsid = store.get_or_create_series(1, [(1, s)])
            psid = pystore.get_or_create_series(1, [(1, s)])
            ts = np.sort(rng.choice(100_000, size=50, replace=False))
            vals = rng.normal(size=50)
            store.append_many(nsid, ts, vals)
            pystore.append_many(psid, ts, vals)
        nb = store.materialize(list(range(20)), 10_000, 90_000)
        pb = pystore.materialize(list(range(20)), 10_000, 90_000)
        np.testing.assert_array_equal(nb.series_idx, pb.series_idx)
        np.testing.assert_array_equal(nb.ts_ms, pb.ts_ms)
        np.testing.assert_array_equal(nb.values, pb.values)

    def test_materialize_empty(self, store):
        store.get_or_create_series(1, [(1, 1)])
        batch = store.materialize([0], 0, 1000)
        assert batch.num_points == 0

    def test_invalid_series_raises(self, store):
        with pytest.raises(IndexError):
            store.append(99, 1000, 1.0)

    def test_slice_range(self, store):
        sid = store.get_or_create_series(1, [(1, 1)])
        for i in range(10):
            store.append(sid, i * 1000, float(i))
        ts, vals = store.series(sid).buffer.slice_range(2000, 5000)
        np.testing.assert_array_equal(ts, [2000, 3000, 4000, 5000])


class TestNativeEndToEnd:
    def test_query_through_native_backend(self):
        from opentsdb_tpu import TSDB, Config
        from opentsdb_tpu.query.model import TSQuery, TSSubQuery
        tsdb = TSDB(Config(**{
            "tsd.core.auto_create_metrics": "true",
            "tsd.storage.backend": "native"}))
        assert type(tsdb.store).__name__ == "NativeTimeSeriesStore"
        for i in range(60):
            tsdb.add_point("m", BASE + i * 10, i, {"host": "a"})
            tsdb.add_point("m", BASE + i * 10, i * 2, {"host": "b"})
        tsq = TSQuery(start=str(BASE), end=str(BASE + 600), queries=[
            TSSubQuery(aggregator="sum", metric="m",
                       downsample="1m-avg")]).validate()
        results = tsdb.execute_query(tsq)
        vals = [v for _, v in results[0].dps]
        # per minute: avg(i..i+5) + avg(2i..2i+10) = 3 * avg(i..i+5)
        assert vals[0] == (sum(range(6)) / 6) * 3

    def test_fsck_on_native(self):
        from opentsdb_tpu import TSDB, Config
        from opentsdb_tpu.tools.fsck import run_fsck
        tsdb = TSDB(Config(**{
            "tsd.core.auto_create_metrics": "true",
            "tsd.storage.backend": "native"}))
        tsdb.add_point("m", BASE, 1, {"host": "a"})
        report = run_fsck(tsdb)
        # native buffers are opaque to the buffer-internals checks, but
        # UID resolution and the walk itself must work
        assert report.series_checked == 1


class TestConcurrency:
    """SURVEY.md §5.2: the reference has no sanitizers; host-side
    ingest/query concurrency needs explicit tests. The directory
    vector reallocates on growth, so concurrent create + read/write
    must be exercised."""

    def test_concurrent_create_write_read(self):
        import threading
        store = store_backend.NativeTimeSeriesStore(num_shards=8)
        stop = threading.Event()
        errors = []

        def creator():
            try:
                for i in range(2000):
                    store.get_or_create_series(1, [(1, i)])
            except Exception as e:  # noqa: BLE001
                errors.append(e)
            finally:
                stop.set()

        def writer():
            rng = np.random.default_rng(1)
            try:
                while not stop.is_set():
                    n = store.num_series()
                    if n == 0:
                        continue
                    sid = int(rng.integers(0, n))
                    store.append_many(
                        sid, np.arange(50, dtype=np.int64) * 1000,
                        rng.normal(size=50), False)
            except Exception as e:  # noqa: BLE001
                errors.append(e)

        def reader():
            try:
                while not stop.is_set():
                    n = store.num_series()
                    if n == 0:
                        continue
                    sids = np.arange(n, dtype=np.int64)
                    store.count_range(sids, 0, 10**15)
                    store.materialize(sids[: max(1, n // 2)], 0, 10**15)
            except Exception as e:  # noqa: BLE001
                errors.append(e)

        threads = ([threading.Thread(target=creator)]
                   + [threading.Thread(target=writer) for _ in range(2)]
                   + [threading.Thread(target=reader) for _ in range(2)])
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
            assert not t.is_alive(), "thread hung (deadlock?)"
        assert not errors, errors
        assert store.num_series() == 2000


# ---------------------------------------------------------------------
# the worker pool (PR 39): a parallel pass takes its helpers from one
# pool of parked threads, and no more of them than it has chunks
# ---------------------------------------------------------------------

POOL_ROWS = (1, 8, 255, 256, 257, 100_000)
POOL_CAPS = (1, 2, 13, 16)
HOUR_MS = 3_600_000

needs_cores = pytest.mark.skipif(
    (os.cpu_count() or 1) < 2,
    reason="a one-core host has no helpers to wake")


def _tasks():
    return len(os.listdir("/proc/self/task"))


@contextlib.contextmanager
def _cap(store, threads):
    """The shared store asked for ``threads`` participants a pass."""
    store.threads = threads
    try:
        yield
    finally:
        store.threads = 1


def _grid(store, sids, fn="avg", dtype=np.float32):
    """One bucket_grid call: (cells as bytes, mask as bytes, points).
    Bytes, because NaN cells must compare equal."""
    s_pad = -(-max(len(sids), 8) // 8) * 8
    grid = np.empty((s_pad, 16), dtype)
    mask = np.empty((s_pad, 16), np.bool_)
    n = store.bucket_grid(sids, 0, HOUR_MS - 1, 0, 300_000, 12, fn,
                          grid, mask)
    return grid.tobytes(), mask.tobytes(), n


def _pass(store, name, sids):
    if name == "bucket_grid":
        return _grid(store, sids) + _grid(store, sids, "max", np.float64)
    if name == "bucket_reduce":
        return tuple(a.tobytes() for a in store.bucket_reduce(
            sids, 0, HOUR_MS - 1, 0, 300_000, 12, want_minmax=True))
    batch = store.materialize(sids, 600_000, HOUR_MS - 600_001)
    padded = store.materialize_padded(sids, 600_000, HOUR_MS - 600_001)
    return (batch.series_idx.tobytes(), batch.ts_ms.tobytes(),
            batch.values.tobytes(), padded.values2d.tobytes(),
            padded.ts2d.tobytes(), padded.counts.tobytes())


@pytest.fixture(scope="module")
def pool_store():
    """100,000 series of up to 12 points in an hour: a tenth of the
    points dropped, some NaN, a few series empty; and what each pass
    answers on one thread."""
    n = max(POOL_ROWS)
    store = store_backend.NativeTimeSeriesStore(materialize_threads=1)
    sids = store.get_or_create_series_bulk(
        1, [((1, i),) for i in range(n)])
    rng = np.random.default_rng(39)
    keep = rng.random((n, 12)) > 0.1
    keep[::1000] = False
    row, col = np.nonzero(keep)
    vals = rng.normal(size=len(row)) * 100
    vals[rng.random(len(row)) < 0.01] = np.nan
    store.append_lines(sids[row], col * 300_000 + (row % 7) * 1000, vals,
                       np.zeros(len(row), np.uint8))
    single = {(name, rows): _pass(store, name, sids[:rows])
              for name in ("bucket_grid", "bucket_reduce", "materialize")
              for rows in POOL_ROWS}
    return store, sids, single


class TestWorkerPool:
    @pytest.mark.parametrize("cap", POOL_CAPS)
    @pytest.mark.parametrize("rows", POOL_ROWS)
    @pytest.mark.parametrize("name", ["bucket_grid", "bucket_reduce",
                                      "materialize"])
    def test_a_pass_answers_the_same_bytes_at_every_cap(
            self, pool_store, name, rows, cap):
        store, sids, single = pool_store
        with _cap(store, cap):
            assert _pass(store, name, sids[:rows]) == single[name, rows]

    @needs_cores
    def test_a_pass_of_one_chunk_stays_on_its_caller(self, pool_store):
        store, sids, _ = pool_store
        with _cap(store, 16):
            _grid(store, sids[:257])            # the pool is up
            before = store_backend.pool_stats()
            assert before["threads"] >= min(16, os.cpu_count()) - 1
            _grid(store, sids[:8])
            _grid(store, sids[:256])
            store.bucket_reduce(sids[:8], 0, HOUR_MS, 0, 300_000, 12)
            store.materialize(sids[:8], 0, HOUR_MS)  # count + fill
            inline = store_backend.pool_stats()
            assert inline == {"inline": before["inline"] + 5,
                              "pooled": before["pooled"],
                              "threads": before["threads"]}
            _grid(store, sids[:257])            # two chunks: one helper
            _grid(store, sids)
            pooled = store_backend.pool_stats()
            assert pooled == {"inline": inline["inline"],
                              "pooled": inline["pooled"] + 2,
                              "threads": inline["threads"]}

    @needs_cores
    def test_a_thousand_panels_create_no_thread(self, pool_store):
        store, sids, single = pool_store
        with _cap(store, 16):
            _grid(store, sids)                  # the pool is up
            tasks, made = _tasks(), store_backend.pool_stats()["threads"]
            for _ in range(1000):
                got = _grid(store, sids[:8])
            assert got == single["bucket_grid", 8][:3]
            _grid(store, sids)
            assert _tasks() == tasks
            assert store_backend.pool_stats()["threads"] == made

    def test_grids_at_once_beside_an_appender(self, pool_store):
        """A live request's two sub-queries scan at the same time while
        writers append: four callers over overlapping series share the
        pool, each gets the single-threaded answer, none hangs."""
        import threading
        store, sids, single = pool_store
        spans = [slice(0, 257), slice(100, 100_000), slice(0, 100_000),
                 slice(50_000, 50_008)]
        want = [_grid(store, sids[s]) for s in spans]
        stop = threading.Event()
        errors = []

        def caller(k):
            try:
                for _ in range(25):
                    if _grid(store, sids[spans[k]]) != want[k]:
                        errors.append(f"caller {k}: another answer")
                        return
            except Exception as e:  # noqa: BLE001
                errors.append(e)

        def appender():
            # past the hour the grids read: the answers stand
            try:
                t = HOUR_MS
                while not stop.is_set():
                    t += 1000
                    store.append_lines(
                        sids[:4096], np.full(4096, t), np.ones(4096),
                        np.zeros(4096, np.uint8))
            except Exception as e:  # noqa: BLE001
                errors.append(e)

        callers = [threading.Thread(target=caller, args=(k,))
                   for k in range(4)]
        writer = threading.Thread(target=appender)
        with _cap(store, 16):
            try:
                for t in callers + [writer]:
                    t.start()
                for t in callers:
                    t.join(timeout=120)
                    assert not t.is_alive(), "a pass hung"
            finally:
                stop.set()
                writer.join(timeout=60)
        assert not writer.is_alive(), "the appender hung"
        assert not errors, errors

    @needs_cores
    def test_the_pool_outlives_a_store(self, pool_store):
        import gc
        store, sids, single = pool_store
        other = store_backend.NativeTimeSeriesStore(
            materialize_threads=16)
        osids = other.get_or_create_series_bulk(
            1, [((1, i),) for i in range(600)])
        other.append_lines(osids, np.full(600, 1000), np.ones(600),
                           np.zeros(600, np.uint8))
        assert _grid(other, osids)[2] == 600    # pooled: three chunks
        made = store_backend.pool_stats()["threads"]
        del other, osids
        gc.collect()
        with _cap(store, 16):
            before = store_backend.pool_stats()["pooled"]
            assert _grid(store, sids) == \
                single["bucket_grid", 100_000][:3]
            after = store_backend.pool_stats()
            assert after["pooled"] == before + 1
            assert after["threads"] == made

    def test_an_import_of_a_few_lines_is_one_chunk(self):
        before = store_backend.pool_stats()
        parsed = store_backend.parse_import_buffer(
            b"m 1356998400 1 host=a\nm 1356998401 2 host=b\n",
            threads=16)
        assert parsed.num_groups == 2 and parsed.num_lines == 2
        after = store_backend.pool_stats()
        assert after["pooled"] == before["pooled"]
        assert after["inline"] == before["inline"] + 3  # three rounds
