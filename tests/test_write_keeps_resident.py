"""A write drops what it touched, not the store's every entry (PR 51).

The deployment ``fleet-1m-live`` at a size a test can hold: two
thousand series of the cell's own generator (``benchmark/gen.py``)
loaded as its loader loads them, a TSD served on a real socket, the
cell's own request (``benchmark/traffic/wide-ingest.json``) and
``/api/put`` bodies, every answer held to the cell's own judge
(``benchmark/reference.py``, float64) under the configuration's
limits, on both stores. The configuration's guarantee, "every point
loaded or acknowledged is in every later answer", against every way
an entry of the HBM cache could be wrongly kept:

(a) a put at the head, beyond the window: kept, no scan;
(b) a put inside a resident whole bucket: that column and the
    window's grid go, the older columns stay, the answer has the point;
(c) a put older than the window's start: dropped (only the OLDEST
    timestamp written since a version is known), exact;
(d) a delete, or what a lifecycle sweep does: everything, as ever;
(e) the first points of a new series: another key, as ever;
(f) a write that lands during a build: the entry is not trusted;
(g) more writes than the store's log holds between two look-ups:
    "everything";
(h) both stores answer ``oldest_written_since`` alike.
CPU only.
"""

from __future__ import annotations

import json
import os
import sys
import types

import jax
import numpy as np
import pytest

from opentsdb_tpu import TSDB, Config
from opentsdb_tpu.core.store import ALL, TimeSeriesStore, WrittenLog
from opentsdb_tpu.native.store_backend import NativeTimeSeriesStore
from opentsdb_tpu.query import engine as engine_mod

from test_ingest_under_query import Served, _exchange

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
BENCH = os.path.join(ROOT, "benchmark")
SMALL = {"series": 2000, "chunk_series": 500}
SEED = 2**31 + 51
#: the series the loader withholds, for (e) to bring in
NEW_HOST = SMALL["series"] - 1
GRID, COLUMN = engine_mod.RESIDENT_GRID_KEY, engine_mod.RESIDENT_COLUMN_KEY


def _load(rel: str):
    with open(os.path.join(ROOT, rel), encoding="utf-8") as fh:
        return json.load(fh)


@pytest.fixture(scope="module")
def cell():
    """The cell's configuration, generator, judge, traffic and the
    harness's comparison, as ``benchmark/run.py`` finds them; the
    deployment's text and values, made once."""
    for p in (BENCH, ROOT):
        if p not in sys.path:
            sys.path.append(p)
    import deploy
    import run
    import traffic
    config = _load("benchmark/configs/fleet-1m-live.json")
    gen = deploy.generator_of(config)
    data = gen.Data(dict(config["data"], **SMALL))
    chunks = [gen.chunk_lines(data, SEED, c) for c in range(data.chunks)]
    values = np.concatenate([v for _t, v, _n in chunks])
    host = data.tag_name("host", NEW_HOST).encode()
    text = b"".join(line for t, _v, _n in chunks
                    for line in t.splitlines(keepends=True)
                    if b"host=" + host not in line)
    held_back = values[NEW_HOST].copy()
    values[NEW_HOST] = np.nan
    return types.SimpleNamespace(
        config=config, data=data, text=text, values=values,
        held_back=held_back, judge=deploy.judge_of(config), run=run,
        traffic=traffic.Traffic(
            _load("benchmark/traffic/wide-ingest.json"), data, SEED,
            2.0))


@pytest.fixture(scope="module", autouse=True)
def float32():
    """The configuration's precision, for every thread (the server
    answers on its workers), put back afterwards."""
    was = jax.config.read("jax_enable_x64")
    jax.config.update("jax_enable_x64", False)
    yield
    jax.config.update("jax_enable_x64", was)


class Tsd:
    """A TSD on a real socket (``test_ingest_under_query.Served``),
    loaded with the deployment's history as the benchmark's loader
    loads it, every tail on the device's branch as at the cell's own
    size."""

    def __init__(self, cell, backend: str):
        self.cell = cell
        self.tsdb = TSDB(Config(**{
            "tsd.core.auto_create_metrics": "true",
            "tsd.storage.backend": backend,
            "tsd.tpu.warmup": "false", "tsd.trace.sample": "1",
            "tsd.query.host_tail_max_cells_linear": "-1"}))
        written, errors = self.tsdb.import_buffer(cell.text,
                                                  durable=False)
        assert not errors and written == int(
            (~np.isnan(cell.values)).sum())
        self.values = cell.values.copy()
        self.cache = self.tsdb.device_grid_cache
        self.store = self.tsdb.store
        self.by_column = hasattr(self.store, "bucket_columns")
        # every storage pass a query can make, counted
        self.passes = 0
        for name in ("bucket_columns", "bucket_grid", "bucket_reduce",
                     "count_range", "materialize",
                     "materialize_padded"):
            if hasattr(self.store, name):
                setattr(self.store, name,
                        self._counted(getattr(self.store, name)))
        self.served = Served(self.tsdb)
        self.port = self.served.port
        self.asked = 0

    def _counted(self, real):
        def counted(*args, **kwargs):
            self.passes += 1
            return real(*args, **kwargs)
        return counted

    def send(self, method: str, path: str, doc=None):
        conn = self.served.connect()
        try:
            return _exchange(conn, method, path, doc)
        finally:
            conn.close()

    def put(self, doc: list) -> None:
        """One ``/api/put`` body, acknowledged."""
        status, _h, raw = self.send("POST", "/api/put", doc)
        assert status == 204, raw[:300]

    def point(self, host: int, ts: int, value: float) -> dict:
        d = self.cell.data
        return {"metric": d.metric, "timestamp": ts, "value": value,
                "tags": {k: d.tag_name(k, int(d.tag_ids(
                    k, np.array([host]))[0])) for k in d.tags}}

    def ask(self):
        """The cell's next request, its answer held to the judge over
        ``self.values`` by the harness's own comparison; the spans of
        its trace by name."""
        request = self.cell.traffic.timed[self.asked]
        self.asked += 1
        status, headers, raw = self.send(
            request.method, request.path, request.doc)
        limits = self.cell.config["limits"]
        verdict = self.cell.run.check_answers(
            self.cell.judge.Reference(self.cell.data, self.values,
                                      limits),
            self.cell.data, [types.SimpleNamespace(
                error=None, status=status, body=raw, request=request)],
            limits, self.cell.judge)
        assert verdict["failed"] == 0, (verdict["notes"],
                                        verdict["numbers"])
        _s, _h, tree = self.send(
            "GET", "/api/trace/" + headers["X-TSD-Trace-Id"])
        spans: dict = {}

        def walk(node):
            spans.setdefault(node["name"], []).append(
                node.get("tags", {}))
            for child in node.get("children", ()):
                walk(child)

        walk(json.loads(tree)["tree"][0])
        return json.loads(raw), spans

    def counter(self, metric: str, **tags) -> float:
        raw = json.loads(self.send("GET", "/api/stats/raw")[2])
        return sum(r["value"] for r in raw["records"]
                   if r["metric"] == metric
                   and all(r["tags"].get(k) == v
                           for k, v in tags.items()))

    def kinds(self) -> list:
        return sorted(k[0] for k in self.cache._entries)

    def lookups(self, spans) -> list:
        return [(s.get("stage"), s.get("grid"), s.get("stale"))
                for s in spans["query.grid_build"]
                if s.get("stage") in ("cache_lookup", "columns")]

    def stop(self):
        self.served.stop()
        self.tsdb.shutdown()


@pytest.fixture(params=["native", "memory"])
def tsd(cell, request):
    t = Tsd(cell, request.param)
    yield t
    t.stop()


def _whole_buckets(cell) -> int:
    """The window is the hour less its last second: eleven 5-minute
    buckets lie whole inside it, the twelfth is cut."""
    d = cell.data
    assert (d.end + 1 - d.t0) // 300 == 12
    return 11


def test_a_put_at_the_head_keeps_what_is_resident(tsd, cell):
    """(a): the cell's own traffic. The body's points lie a second
    after the window's end."""
    _rows, first = tsd.ask()
    whole = _whole_buckets(cell) if tsd.by_column else 0
    assert tsd.kinds() == [COLUMN] * whole + [GRID]
    assert [s[2] for s in tsd.lookups(first)] \
        == ["none"] * (1 + tsd.by_column)
    seen = (tsd.cache.misses, tsd.cache.hits)
    kept = tsd.counter("tsd.query.residency", outcome="kept")
    dropped = tsd.counter("tsd.query.residency.dropped_bytes")
    body = cell.traffic.writes[0].doc
    assert body[0]["timestamp"] == cell.data.end + 1
    tsd.put(body)
    written, passes = tsd.store.points_written, tsd.passes
    _rows, spans = tsd.ask()
    # one look-up, the window's: it met a newer version and stayed
    assert tsd.lookups(spans) == [("cache_lookup", "resident_hit",
                                   "kept")]
    assert (tsd.cache.misses, tsd.cache.hits) == (seen[0], seen[1] + 1)
    assert tsd.counter("tsd.query.residency", outcome="kept") \
        == kept + 1
    assert tsd.counter("tsd.query.residency", outcome="dropped") == 0
    assert tsd.counter("tsd.query.residency.dropped_bytes") \
        == dropped == 0
    # no scan ran, nothing went up but the labels
    assert tsd.passes == passes
    assert not [s for s in spans["query.grid_build"]
                if s.get("stage") in ("alloc", "fill_pad", "columns")]
    assert "labels" in [s.get("stage") for s in spans["query.upload"]]
    assert [s["path"] for s in spans["query.program"]] == ["grid"]
    # the entry now carries the version the look-up read, so the
    # store's log need only reach back to an entry's last use
    (entry,) = [e for k, e in tsd.cache._entries.items()
                if k[0] == GRID]
    assert entry[0] == (written, 0)
    assert entry[4] == (cell.data.t0 * 1000, cell.data.end * 1000)
    _rows, again = tsd.ask()
    assert tsd.lookups(again) == [("cache_lookup", "resident_hit",
                                   "none")]
    # and the acknowledged points are read back
    back = cell.run.readback_request(cell.config, cell.traffic)
    status, _h, raw = tsd.send(back.method, back.path, back.doc)
    assert status == 200
    got = {r["tags"]["dc"]: r["dps"] for r in json.loads(raw)}
    hosts = [cell.data.tag_index("host", p["tags"]["host"])
             for p in body]
    for dc in {p["tags"]["dc"] for p in body}:
        want = sum(p["value"] for p in body if p["tags"]["dc"] == dc)
        assert got[dc][str(cell.data.end + 1)] == pytest.approx(
            want, rel=1e-6)
    assert len(hosts) == 50


def test_a_put_inside_a_resident_bucket_drops_that_column(tsd, cell):
    """(b): into the LAST whole bucket, so that every other column is
    older than the write and stays; then into a bucket in the middle,
    where the newer columns go too (the store keeps the oldest
    timestamp written, nothing finer)."""
    d = cell.data
    whole = _whole_buckets(cell)
    tsd.ask()
    host, point = 40, 10 * 5 + 2              # minute 52: bucket 10
    assert not tsd.store.oldest_written_since(tsd.store.points_written)
    tsd.put([tsd.point(host, d.t0 + point * 60, 7777.25)])
    tsd.values[host, point] = 7777.25
    seen = (tsd.cache.misses, tsd.cache.hits, tsd.cache.stale_kept,
            tsd.cache.stale_dropped)
    rows, spans = tsd.ask()                    # holds the point
    if tsd.by_column:
        assert tsd.lookups(spans) == [
            ("cache_lookup", "resident_columns", "dropped"),
            ("columns", None, "dropped")]
        (cols,) = [s for s in spans["query.grid_build"]
                   if s.get("stage") == "columns"]
        assert (cols["hit"], cols["built"], cols["cut"]) \
            == (whole - 1, 1, 1)
        assert tsd.cache.stale_kept == seen[2] + whole - 1
        assert tsd.cache.stale_dropped == seen[3] + 2
        assert tsd.cache.misses == seen[0] + 2
    else:
        assert tsd.lookups(spans) == [
            ("cache_lookup", "resident_built", "dropped")]
        assert tsd.cache.misses == seen[0] + 1
    # a bucket in the middle: the older columns stay, it and the
    # newer ones are built again
    point = 4 * 5 + 1                          # minute 21: bucket 4
    tsd.put([tsd.point(host, d.t0 + point * 60, 1234.5)])
    tsd.values[host, point] = 1234.5
    _rows, spans = tsd.ask()
    if tsd.by_column:
        (cols,) = [s for s in spans["query.grid_build"]
                   if s.get("stage") == "columns"]
        assert (cols["hit"], cols["built"], cols["cut"]) \
            == (4, whole - 4, 1)
    assert tsd.kinds() == [COLUMN] * (
        whole if tsd.by_column else 0) + [GRID]


def test_a_backfill_behind_the_window_drops_the_entry(tsd, cell):
    """(c): needlessly (no cell of the window changed), and exactly."""
    d = cell.data
    tsd.ask()
    tsd.put([tsd.point(7, d.t0 - 600, 5.0)])
    seen = (tsd.cache.misses, tsd.cache.stale_kept)
    _rows, spans = tsd.ask()
    assert [s[2] for s in tsd.lookups(spans)] \
        == ["dropped"] * (1 + tsd.by_column)
    built = 1 + _whole_buckets(cell) * tsd.by_column
    assert (tsd.cache.misses, tsd.cache.stale_kept) \
        == (seen[0] + built, seen[1])


@pytest.mark.parametrize("what", ["delete_range", "sweep"])
def test_a_delete_drops_everything_as_before(tsd, cell, what):
    """(d): ``mutation_epoch`` is no append: no log refines it."""
    d = cell.data
    tsd.ask()
    tsd.put(cell.traffic.writes[0].doc)       # kept, were it alone
    if what == "delete_range":
        uids = tsd.tsdb.uids
        sids = tsd.store.series_ids_for_metric(
            uids.metrics.get_id(d.metric))[:3]
        # the first quarter of an hour of three series
        assert tsd.store.delete_range(sids, d.t0 * 1000,
                                      (d.t0 + 899) * 1000)
        hostk = uids.tag_names.get_id("host")
        hosts = [d.tag_index("host", uids.tag_values.get_name(
            dict(tsd.store.series(int(s)).tags)[hostk])) for s in sids]
        tsd.values[hosts, :15] = np.nan
    else:
        # what a lifecycle sweep or an fsck repair does to the store
        tsd.store.mutation_epoch += 1
    seen = (tsd.cache.misses, tsd.cache.stale_kept)
    _rows, spans = tsd.ask()
    assert [s[2] for s in tsd.lookups(spans)] \
        == ["dropped"] * (1 + tsd.by_column)
    built = 1 + _whole_buckets(cell) * tsd.by_column
    assert (tsd.cache.misses, tsd.cache.stale_kept) \
        == (seen[0] + built, seen[1])


def test_a_new_series_changes_the_key_as_before(tsd, cell):
    """(e): the plan index's version (the metric's series count) is
    in both levels' keys: nothing of the old count is looked up."""
    d = cell.data
    tsd.ask()
    points = [3, 9, 14]
    tsd.put([tsd.point(NEW_HOST, d.t0 + p * 60,
                       float(cell.held_back[p])) for p in points])
    tsd.values[NEW_HOST, points] = cell.held_back[points]
    seen = (tsd.cache.misses, tsd.cache.stale_kept,
            tsd.cache.stale_dropped)
    _rows, spans = tsd.ask()
    assert [s[2] for s in tsd.lookups(spans)] \
        == ["none"] * (1 + tsd.by_column)
    built = 1 + _whole_buckets(cell) * tsd.by_column
    assert (tsd.cache.misses, tsd.cache.stale_kept,
            tsd.cache.stale_dropped) == (seen[0] + built, *seen[1:])


def test_a_write_during_a_build_is_not_trusted(tsd, cell):
    """(f): the storage pass has read a series when a point of it
    lands, inside the window: the entry is stamped with the version
    read BEFORE the build, the store's log has the point as written
    since, and the next look-up drops the entry."""
    d = cell.data
    host, point = 11, 33
    name = "bucket_columns" if tsd.by_column else "bucket_grid" \
        if hasattr(tsd.store, "bucket_grid") else "bucket_reduce"
    real = getattr(tsd.store, name)
    late = []

    def racing(*args, **kwargs):
        out = real(*args, **kwargs)
        if not late:
            p = tsd.point(host, d.t0 + point * 60, 4242.5)
            tsd.tsdb.add_point(p["metric"], p["timestamp"], p["value"],
                               p["tags"])
            late.append(tsd.store.points_written)
        return out

    setattr(tsd.store, name, racing)
    try:
        # the first answer is computed without the point: the request
        # began before the write was acknowledged
        request = cell.traffic.timed[0]
        status, _h, _raw = tsd.send(request.method, request.path,
                                    request.doc)
        assert status == 200 and late
    finally:
        setattr(tsd.store, name, real)
    (entry,) = [e for k, e in tsd.cache._entries.items()
                if k[0] == GRID]
    assert entry[0][0] < late[0]
    tsd.values[host, point] = 4242.5
    tsd.asked = 1
    _rows, spans = tsd.ask()                   # holds the point
    assert tsd.lookups(spans)[0][2] == "dropped"


def test_more_writes_than_the_log_holds_is_everything(tsd, cell):
    """(g): a timestamp an append, ascending, all beyond the window:
    each is an entry of the log, and the first of them is folded
    into the floor before the next look-up comes."""
    d = cell.data
    tsd.ask()
    version = tsd.store.points_written
    sid = int(tsd.store.series_ids_for_metric(
        tsd.tsdb.uids.metrics.get_id(d.metric))[5])
    # what the bulk load left in the log: an entry or two
    loaded = int(tsd.counter("tsd.storage.written_log.entries"))
    assert 1 <= loaded <= 3
    assert tsd.counter("tsd.storage.written_log.floor_version") == 0
    head = (d.end + 1) * 1000
    for i in range(WrittenLog.MAX_ENTRIES):
        tsd.store.append(sid, head + i, 1.0)
    # the load's entries are folded, these are all there
    assert tsd.store.oldest_written_since(version) == head
    assert tsd.counter("tsd.storage.written_log.floor_version") \
        == version
    tsd.store.append(sid, head + WrittenLog.MAX_ENTRIES, 1.0)
    assert tsd.store.oldest_written_since(version) == ALL
    assert tsd.store.oldest_written_since(version + 1) == head + 1
    assert tsd.counter("tsd.storage.written_log.entries") \
        == WrittenLog.MAX_ENTRIES
    assert tsd.counter("tsd.storage.written_log.floor_version") \
        == version + 1
    _rows, spans = tsd.ask()
    assert tsd.lookups(spans)[0][2] == "dropped"
    # a log long enough again: the next head write keeps the entry
    tsd.store.append(sid, head + WrittenLog.MAX_ENTRIES + 1, 1.0)
    _rows, spans = tsd.ask()
    assert tsd.lookups(spans) == [("cache_lookup", "resident_hit",
                                   "kept")]


# -- (h) the stores' word ---------------------------------------------


def _interleaving(seed: int, calls: int = 400):
    rng = np.random.default_rng(seed)
    for _ in range(calls):
        kind = int(rng.integers(3))
        if kind == 0:
            yield "append", (int(rng.integers(8)),
                             int(rng.integers(1000, 2000)), 1.0)
        elif kind == 1:
            ts = rng.integers(1000, 2000, int(rng.integers(1, 6)))
            yield "append_many", (int(rng.integers(8)), ts,
                                  np.ones(len(ts)))
        else:
            yield "append_grid", (
                np.arange(8), rng.permutation(np.arange(1000, 2000))[:4],
                rng.random((8, 4)), rng.random((8, 4)) < 0.25)


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_both_stores_answer_oldest_written_since_alike(seed):
    """(h): and what they answer is the minimum over every call since
    the version, kept by the test call by call."""
    stores = [TimeSeriesStore(), NativeTimeSeriesStore()]
    for st in stores:
        for i in range(8):
            st.get_or_create_series(1, [(1, i)])
        assert st.oldest_written_since(0) is None
    calls = []                  # (points_written after, oldest ts)
    for name, args in _interleaving(seed):
        for st in stores:
            getattr(st, name)(*args)
        written = stores[0].points_written
        assert stores[1].points_written == written
        if name == "append":
            oldest = args[1]
        elif name == "append_many":
            oldest = int(args[1].min())
        else:
            wrote = args[3].any(axis=0)
            oldest = int(args[1][wrote].min()) if wrote.any() else None
        if oldest is not None:
            calls.append((written, oldest))
        for version in {0, written, *(v for v, _ in calls[-9::3])}:
            want = min((ts for v, ts in calls if v > version),
                       default=None)
            assert [st.oldest_written_since(version)
                    for st in stores] == [want, want]
    # between two calls' versions: the later call's word
    assert all(st.oldest_written_since(calls[-1][0] - 1) == calls[-1][1]
               for st in stores)


def test_the_log_keeps_what_no_newer_call_undercuts():
    log = WrittenLog()
    for version, ts in [(1, 500), (2, 700), (3, 600), (4, 600),
                        (5, 900)]:
        with log.lock:
            log.note(version, ts)
    # 700 is undercut by 600, the second 600 stands for both
    assert (log._versions, log._oldest) == ([1, 4, 5], [500, 600, 900])
    assert [log.oldest_since(v) for v in range(6)] \
        == [500, 600, 600, 600, 900, None]
    with log.lock:
        log.note(6, 100)
    assert len(log) == 1 and log.oldest_since(0) == 100
