"""Histogram percentiles through the served path (PR 42).

``/api/query`` with ``percentiles`` as the benchmark's cell
``hist-200k.percentiles`` sends it: a TSD on a real socket over a store
of a thousand histogram series made by the cell's own generator
(``benchmark/generators/histogram_points.py``) and landed by its
loader's blobs through ``TSDB.add_histogram_batch``, answers in the
configuration's float32, and every answer is held to the cell's own
judge (``benchmark/references/histograms.py``, integers, loaded as the
harness loads it) under the configuration's limits, and without a
downsample to ``percentiles_from_counts`` over the generator's counts.
Beside it: the resident counts are dropped by a write, bounds that
disagree keep the host path, a merged total past 2**24 is answered
from the float64 arena, the columnar decode is the per-point decode
blob for blob, the arena grows where it stands, and the stages and
counters of the path are in ``/api/trace`` and ``/api/stats``. CPU
only.
"""

from __future__ import annotations

import asyncio
import base64
import http.client
import json
import os
import struct
import sys
import threading
import types

import jax
import numpy as np
import pytest

from opentsdb_tpu import TSDB, Config
from opentsdb_tpu.core.histogram import (HistogramArena, SimpleHistogram,
                                         SimpleHistogramCodec,
                                         decode_simple_run)
from opentsdb_tpu.query.histogram_engine import percentiles_from_counts
from opentsdb_tpu.tsd.server import TSDServer

ROOT = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..")
BENCH = os.path.abspath(os.path.join(ROOT, "benchmark"))
# every tag rule and the gappy tenth (series 900-999) are there
SMALL = {"series": 1000, "chunk_series": 250, "points": 20,
         "buckets": 16, "dcs": 10, "racks": 40}
SEED = 2**31 + 42


def _load(rel: str):
    with open(os.path.join(ROOT, rel), encoding="utf-8") as fh:
        return json.load(fh)


@pytest.fixture(scope="module")
def cell():
    """The cell's configuration with its generator, its judge and its
    loader, as ``benchmark/run.py`` finds them (``deploy.py``)."""
    for p in (BENCH, os.path.abspath(ROOT)):
        if p not in sys.path:
            sys.path.append(p)
    import deploy
    config = _load("benchmark/configs/hist-200k.json")
    from benchmark import hist_plugin
    return types.SimpleNamespace(
        config=config, generator=deploy.generator_of(config),
        judge=deploy.judge_of(config), loader=hist_plugin,
        spec=_load("benchmark/traffic/percentiles.json"))


def _data(cell):
    return cell.generator.Data(dict(cell.config["data"], **SMALL))


class Tsd:
    """A TSD serving on a real socket, its loop on a thread, loaded
    with the generator's chunks through the loader's blobs."""

    def __init__(self, cell, data, seed: int, **flags):
        self.tsdb = TSDB(Config(**{
            "tsd.core.auto_create_metrics": "true",
            "tsd.tpu.warmup": "false", "tsd.trace.sample": "1",
            "tsd.query.cache.enable": "false", **flags}))
        values = []
        self.points = 0
        for c in range(data.chunks):
            idx, counts, present = cell.generator.chunk_counts(
                data, seed, c)
            head = json.loads(cell.generator.frame(
                data, idx, counts, present).split(b"\n", 1)[0])
            written, errors = self.tsdb.add_histogram_batch(
                cell.loader.frame_points(self.tsdb, head, present,
                                         counts))
            assert not errors and written == int(present.sum())
            values.append(counts)
            self.points += written
        self.values = np.concatenate(values)
        self.loop = asyncio.new_event_loop()
        self.server = TSDServer(self.tsdb, host="127.0.0.1", port=0)
        started = threading.Event()

        def run():
            asyncio.set_event_loop(self.loop)
            self.loop.run_until_complete(self.server.start())
            started.set()
            self.loop.run_forever()

        threading.Thread(target=run, daemon=True).start()
        assert started.wait(30), "the TSD did not start"
        self.port = self.server._server.sockets[0].getsockname()[1]

    def ask(self, method: str, path: str, doc=None, status: int = 200):
        conn = http.client.HTTPConnection("127.0.0.1", self.port,
                                          timeout=120)
        try:
            conn.request(method, path,
                         body=None if doc is None else json.dumps(doc))
            resp = conn.getresponse()
            body = resp.read()
            assert resp.status == status, body[:300]
            return json.loads(body) if body else None, \
                dict(resp.getheaders())
        finally:
            conn.close()

    def counter(self, metric: str, **tags) -> float:
        raw, _ = self.ask("GET", "/api/stats/raw")
        return sum(r["value"] for r in raw["records"]
                   if r["metric"] == metric
                   and all(r["tags"].get(k) == v
                           for k, v in tags.items()))

    def stop(self):
        asyncio.run_coroutine_threadsafe(
            self.server.stop(), self.loop).result(20)
        self.loop.call_soon_threadsafe(self.loop.stop)
        self.tsdb.shutdown()


@pytest.fixture(scope="module")
def served(cell):
    """(the TSD, its deployment) in the configuration's float32; x64
    is set for every thread (the server answers on its workers) and
    put back afterwards."""
    was = jax.config.read("jax_enable_x64")
    jax.config.update("jax_enable_x64", False)
    data = _data(cell)
    tsd = Tsd(cell, data, SEED)
    yield tsd, data
    tsd.stop()
    jax.config.update("jax_enable_x64", was)


def _judge(cell, tsd, data):
    return cell.judge.Reference(data, tsd.values, cell.config["limits"])


FILTERS = {
    # (filters beside the group-by on dc, series selected of 1,000)
    "all": ([], 1000),
    "a-rack-out": ([{"type": "not_literal_or", "tagk": "rack",
                     "filter": "r0007", "groupBy": False}], 975),
    "two-dcs": ([{"type": "literal_or", "tagk": "dc",
                  "filter": "d03|d07|nosuch", "groupBy": True}], 200),
    "a-dc-emptied": ([{"type": "not_literal_or", "tagk": "dc",
                       "filter": "d09", "groupBy": True}], 900),
    "the-gappy-alone": ([{"type": "literal_or", "tagk": "fleet",
                          "filter": "b", "groupBy": False}], 200),
}


def _sub(data, qs, downsample, extra) -> dict:
    filters = list(extra)
    if not any(f["tagk"] == "dc" for f in filters):
        filters.insert(0, {"type": "wildcard", "tagk": "dc",
                           "filter": "*", "groupBy": True})
    sub = {"metric": data.metric, "aggregator": "sum",
           "percentiles": qs, "filters": filters}
    if downsample:
        sub["downsample"] = downsample
    return sub


def _held_to_the_judge(cell, ref, data, sub, rows) -> None:
    tagk, names, secs, cells = ref.answer(sub)
    assert len(rows) == len(names), (len(rows), len(names))
    got, stray = cell.judge.rows_to_grid(
        rows, tagk, names, data.t0, data.points * data.cadence_s // secs,
        secs, data.metric)
    verdict = cell.judge.compare(got, stray, cells)
    limits = cell.config["limits"]
    assert verdict.shape_errors == 0, verdict.note
    assert verdict.ok(limits["sum_rtol"], limits["rank_atol"]), \
        (verdict.rank_abs_err, verdict.note)
    # the next bucket's midpoint in one cell is not the judge's answer
    off = got.copy()
    at = tuple(np.argwhere(~np.isnan(off))[0])
    off[at] *= 1.15
    assert not cell.judge.compare(off, 0, cells).ok(
        limits["sum_rtol"], limits["rank_atol"])


@pytest.mark.parametrize("case", list(FILTERS))
@pytest.mark.parametrize("downsample", ["1m-sum", "5m-sum"])
@pytest.mark.parametrize("qs", [[50.0], [99.0], [99.9], [99.0, 99.9]],
                         ids=["p50", "p99", "p99.9", "p99+p99.9"])
def test_a_served_answer_is_the_judges(served, cell, qs, downsample,
                                       case):
    tsd, data = served
    extra, selected = FILTERS[case]
    sub = _sub(data, qs, downsample, extra)
    cell.judge.Reference.supports(sub, data)
    ref = _judge(cell, tsd, data)
    assert ref.selected(sub) == selected
    rows, _headers = tsd.ask("POST", "/api/query", {
        "start": data.t0 * 1000, "end": data.end * 1000,
        "queries": [sub]})
    _held_to_the_judge(cell, ref, data, sub, rows)
    if case == "a-dc-emptied":
        assert "d09" not in {r["tags"]["dc"] for r in rows}
    if case == "the-gappy-alone":
        # fleet b is series 100-199 and 900-999: the gappy tenth
        assert (~tsd.values[900:].any(axis=2)).sum() > 0


@pytest.mark.parametrize("q", [50.0, 99.0, 99.9])
def test_without_a_downsample_a_timestamp_is_a_bucket(served, q):
    """The distinct-timestamp path against ``percentiles_from_counts``
    over the generator's own counts, a group at a time."""
    tsd, data = served
    rows, _ = tsd.ask("POST", "/api/query", {
        "start": data.t0 * 1000, "end": data.end * 1000,
        "queries": [_sub(data, [q], None, [])]})
    assert len(rows) == data.dcs
    dc = data.tag_ids("dc", np.arange(data.series))
    bounds = np.asarray(data.bounds)
    for row in rows:
        assert row["metric"] == f"{data.metric}_pct_{q:g}"
        merged = tsd.values[dc == data.tag_index(
            "dc", row["tags"]["dc"])].sum(axis=0, dtype=np.int64)
        want = percentiles_from_counts(merged.astype(np.float64),
                                       bounds, [q])[0]
        got = [row["dps"][str(int(t))] for t in data.timestamps]
        np.testing.assert_allclose(got, want, rtol=1e-6)


def test_a_write_drops_the_resident_counts(served, cell):
    """Two requests either side of an acknowledged ``/api/histogram``
    write: the first reads resident counts, the write drops them
    (``_histogram_version``), and the new point shows."""
    tsd, data = served
    sub = _sub(data, [99.0], "5m-sum", [])
    body = {"start": data.t0 * 1000, "end": data.end * 1000,
            "queries": [sub]}
    tsd.ask("POST", "/api/query", body)
    uploaded = tsd.counter("tsd.query.histogram.upload_bytes")
    tsd.ask("POST", "/api/query", body)
    per_request = tsd.counter("tsd.query.histogram.upload_bytes") \
        - uploaded
    resident = tsd.counter("tsd.query.histogram.resident_bytes")
    # resident: a request uploads a label a row and a few vectors
    assert 0 < per_request < 8 * 1024 < resident
    # host 3 of dc d03 reports a minute in which everything was slow
    host = 3
    hist = SimpleHistogram(list(data.bounds))
    hist.counts = [0] * (data.buckets - 1) + [60_000]
    tags = {k: data.tag_name(k, int(data.tag_ids(k, np.array([host]))[0]))
            for k in data.tags}
    tsd.ask("POST", "/api/histogram", [{
        "metric": data.metric, "timestamp": int(data.timestamps[2]),
        "value": base64.b64encode(
            tsd.tsdb.histogram_manager.encode(hist)).decode(),
        "tags": tags}])
    rows, _ = tsd.ask("POST", "/api/query", body)
    # the stored point and the new one at one timestamp merge by SUM
    tsd.values = tsd.values.copy()
    tsd.values[host, 2, -1] += 60_000
    _held_to_the_judge(cell, _judge(cell, tsd, data), data, sub, rows)
    slow = next(r for r in rows if r["tags"]["dc"] == "d03")
    assert slow["dps"][str(data.t0)] == pytest.approx(
        (data.bounds[-2] + data.bounds[-1]) / 2, rel=1e-6)
    # the counts went up again, whole
    assert tsd.counter("tsd.query.histogram.upload_bytes") - uploaded \
        > resident
    assert tsd.counter("tsd.histogram.bulk_points") == tsd.points + 1


def test_the_stages_and_the_counters_of_a_request(served):
    tsd, data = served
    sub = _sub(data, [99.0, 99.9], "5m-sum",
               FILTERS["a-rack-out"][0])
    body = {"start": data.t0 * 1000, "end": data.end * 1000,
            "queries": [sub]}
    tsd.ask("POST", "/api/query", body)      # the counts are resident
    tails = tsd.counter("tsd.query.tail", **{"class": "histogram"},
                        placement="device", path="hist")
    merged = tsd.counter("tsd.query.histogram.points")
    _rows, headers = tsd.ask("POST", "/api/query", body)
    doc, _ = tsd.ask("GET", "/api/trace/" + headers["X-TSD-Trace-Id"])
    (root,) = doc["tree"]
    (execute,) = [c for c in root["children"]
                  if c["name"] == "query.execute"]
    assert [c["name"] for c in execute["children"]] == [
        "query.plan", "query.upload", "query.program",
        "query.download", "query.assemble"]
    plan, _up, program, _down, assemble = execute["children"]
    assert plan["tags"]["index"] == "hit"
    assert plan["tags"]["series"] == 975 and plan["tags"]["groups"] == 10
    assert program["tags"]["class"] == "histogram"
    assert program["tags"]["placement"] == "device"
    assert program["tags"]["path"] == "hist"
    # series, 5-minute buckets of 20 minutes + 1, datacentres + 1
    assert program["tags"]["shape"] == "1024x8x12"
    assert "compiled" not in program["tags"]
    assert assemble["tags"]["groups"] == 10
    assert tsd.counter("tsd.query.tail", **{"class": "histogram"},
                       placement="device", path="hist") == tails + 1
    # every stored point of the 975 series, and no other (series ids
    # are the generator's indices: it made them in order)
    (arena,) = tsd.tsdb._histogram_arenas.values()
    (sub_arena,) = arena.groups.values()
    stored = sub_arena.sid[:sub_arena.n]
    assert tsd.counter("tsd.query.histogram.points") - merged \
        == int((data.tag_ids("rack", stored) != 7).sum())
    assert tsd.counter("tsd.query.histogram.wide_counts") == 0
    assert tsd.counter("tsd.histogram.slow_points") == 0


def test_bounds_that_disagree_keep_the_host_path(cell):
    """A point of other bounds inside the window: every request of the
    window merges on the host, each timestamp under its own bounds."""
    was = jax.config.read("jax_enable_x64")
    jax.config.update("jax_enable_x64", False)
    data = cell.generator.Data({**cell.config["data"], **SMALL,
                                "series": 100, "chunk_series": 100})
    tsd = Tsd(cell, data, SEED)
    try:
        odd = SimpleHistogram([0.0, 5.0, 10.0])
        odd.counts = [0, 10]
        tsd.tsdb.add_histogram_point(
            data.metric, data.end + 1,
            tsd.tsdb.histogram_manager.encode(odd),
            {"host": "other", "dc": "d00", "rack": "r0000",
             "fleet": "a"})
        rows, _ = tsd.ask("POST", "/api/query", {
            "start": data.t0 * 1000, "end": (data.end + 1) * 1000,
            "queries": [_sub(data, [50.0], None, [])]})
        d00 = next(r for r in rows if r["tags"]["dc"] == "d00")
        assert d00["dps"][str(data.end + 1)] == 7.5
        assert len(d00["dps"]) == data.points + 1
        assert tsd.counter("tsd.query.tail",
                           **{"class": "histogram"}) == 0
    finally:
        tsd.stop()
        jax.config.update("jax_enable_x64", was)


def _blob(bounds, counts, under=0, over=0) -> bytes:
    hist = SimpleHistogram(bounds)
    hist.counts = list(counts)
    hist.underflow, hist.overflow = under, over
    return SimpleHistogramCodec().encode(hist)


@pytest.mark.parametrize("each", [1 << 22, 1 << 23, (1 << 24) + 2])
def test_a_merged_total_past_2_24_is_answered_in_float64(each):
    """Eight series of one point whose counts merge to more than
    float32 holds exactly: the program reports it, the request is
    answered from the float64 arena, and the counter says so."""
    tsdb = TSDB(Config(**{"tsd.core.auto_create_metrics": "true",
                          "tsd.tpu.warmup": "false"}))
    bounds = [0.0, 1.0, 2.0, 4.0, 8.0]
    rows = np.array([[each, 3, 1, 0], [1, each, 0, 5]] * 4,
                    dtype=np.int64)
    written, errors = tsdb.add_histogram_batch(
        [("wide.m", 1356998400, _blob(bounds, row), {"host": f"h{i}"})
         for i, row in enumerate(rows)])
    assert (written, errors) == (8, [])
    from opentsdb_tpu.query.model import TSQuery
    qs = [25.0, 50.0, 75.0, 99.99]
    out = tsdb.execute_query(TSQuery.from_json({
        "start": 1356998000, "end": 1356999000,
        "queries": [{"aggregator": "sum", "metric": "wide.m",
                     "percentiles": qs}]}).validate())
    want = percentiles_from_counts(
        rows.sum(axis=0, dtype=np.float64)[None], np.asarray(bounds), qs)
    assert [r.dps[0][1] for r in out] == want[:, 0].tolist()
    wide = rows.sum() >= 1 << 24
    assert tsdb.histogram_stats.wide_counts == int(wide)
    assert tsdb.histogram_stats.query_points == 8


# -- the write side --------------------------------------------------------

BOUNDS = [0.0, 1.0, 2.5, 7.0]


def _malformed():
    good = _blob(BOUNDS, [1, 2, 3])
    return {
        "empty": b"",
        "another-codec": b"\x07" + good[1:],
        "truncated": good[:-3],
        "over-long": good + b"\x00",
        "one-edge": b"\x01" + struct.pack(">H", 1)
        + struct.pack(">d", 1.0) + struct.pack(">QQ", 0, 0),
        "a-counter-past-int64": _blob(BOUNDS, [1, 2, 3],
                                      under=(1 << 63) + 5),
        "other-bounds": _blob([0.0, 1.0, 2.5, 9.0], [4, 5, 6]),
        "fewer-buckets": _blob([0.0, 1.0, 2.5], [4, 5]),
    }


def test_the_columnar_decode_is_the_per_point_decode():
    rng = np.random.default_rng(42)
    counts = rng.integers(0, 1 << 40, size=(50, 3))
    under, over = rng.integers(0, 9, size=(2, 50))
    blobs = [_blob(BOUNDS, c, u, o)
             for c, u, o in zip(counts, under.tolist(), over.tolist())]
    bounds, rows, got_under, got_over = decode_simple_run(blobs)
    codec = SimpleHistogramCodec()
    for i, blob in enumerate(blobs):
        one = codec.decode(blob)
        assert bounds == one.bounds_key()
        np.testing.assert_array_equal(rows[i], one.counts_array())
        assert (got_under[i], got_over[i]) == (one.underflow,
                                               one.overflow)
    for name, bad in _malformed().items():
        mixed = blobs[:3] + [bad] + blobs[3:6]
        assert decode_simple_run(mixed) is None, name
        if name not in ("other-bounds", "fewer-buckets"):
            assert decode_simple_run([bad]) is None, name


@pytest.mark.parametrize("bad", list(_malformed()))
def test_a_batch_lands_what_a_point_at_a_time_lands(bad):
    """One series' run with a blob in it that is no part of the run:
    the batch takes the per-point path for the series, lands and
    refuses what ``add_histogram_point`` lands and refuses, and says
    which path each point took."""
    rng = np.random.default_rng(7)
    points = [("b.m", 1356998400 + 60 * i,
               _blob(BOUNDS, rng.integers(0, 1000, 3)), {"host": "a"})
              for i in range(6)]
    points.insert(3, ("b.m", 1356998400 + 60 * 9, _malformed()[bad],
                      {"host": "a"}))
    clean = [("b.m", 1356998400 + 60 * i,
              _blob(BOUNDS, rng.integers(0, 1000, 3)), {"host": "b"})
             for i in range(5)]
    batch, single = (TSDB(Config(**{
        "tsd.core.auto_create_metrics": "true"})) for _ in range(2))
    failed = []
    written, errors = batch.add_histogram_batch(
        points + clean, on_error=lambda i, e: failed.append(i))
    refused = []
    for i, (metric, ts, blob, tags) in enumerate(points + clean):
        try:
            single.add_histogram_point(metric, ts, blob, tags)
        except Exception:  # noqa: BLE001 - whatever it refuses
            refused.append(i)
    assert failed == refused and len(errors) == len(refused)
    assert written == len(points) + len(clean) - len(refused)
    # host b's run went the columnar way, host a's a point at a time
    assert batch.histogram_stats.bulk_points == len(clean)
    assert batch.histogram_stats.slow_points == written - len(clean)
    assert single.histogram_stats.bulk_points == 0

    def stored(tsdb):
        (arena,) = tsdb._histogram_arenas.values()
        out = {}
        for key, sub in arena.groups.items():
            order = np.lexsort((sub.ts[:sub.n], sub.sid[:sub.n]))
            out[key] = [a[:sub.n][order].tolist() for a in (
                sub.ts, sub.sid, sub.rows, sub.under, sub.over)]
        return arena.total_points, out

    assert stored(batch) == stored(single)


def test_telnet_and_http_use_the_batch_entry(served):
    tsd, data = served
    blob = base64.b64encode(_blob(BOUNDS, [1, 2, 3])).decode()
    before = tsd.counter("tsd.histogram.bulk_points")
    from opentsdb_tpu.tsd.telnet import TelnetRouter
    handler = TelnetRouter(tsd.tsdb)
    assert handler._cmd_histogram(
        ["histogram", "tn.m", "1356998400", blob, "host=a"]) == ""
    said = handler._cmd_histogram(
        ["histogram", "tn.m", "-5", blob, "host=a"])
    assert said.startswith("histogram: ValueError: invalid timestamp")
    tsd.ask("POST", "/api/histogram", [{
        "metric": "tn.m", "timestamp": 1356998460, "value": blob,
        "tags": {"host": "a"}}])
    assert tsd.counter("tsd.histogram.bulk_points") == before + 2


def test_the_arena_grows_where_it_stands():
    """No second copy while it grows (6.1 GB of float64 at the
    deployment's size), unless a snapshot still reads the first."""
    sub = HistogramArena._Sub(tuple(BOUNDS), 3)
    block = np.arange(3000.0).reshape(1000, 3)
    stamps = np.arange(1000)
    copies = []
    real = np.empty

    def counting(shape, *a, **kw):
        copies.append(shape)
        return real(shape, *a, **kw)

    np.empty = counting
    try:
        for i in range(8):
            sub.append_many(stamps + 1000 * i, 1, block)
        assert not copies
        snap = sub.snapshot()
        for i in range(8, 24):
            sub.append_many(stamps + 1000 * i, 1, block)
        # the three arrays the snapshot reads were replaced, once
        assert len(copies) == 3
    finally:
        np.empty = real
    assert sub.n == 24_000 and len(snap[0]) == 8000
    np.testing.assert_array_equal(snap[0], np.arange(8000))
    np.testing.assert_array_equal(sub.ts[:sub.n], np.arange(24_000))
    np.testing.assert_array_equal(sub.rows[:sub.n],
                                  np.tile(block, (24, 1)))


# -- the deployment's files -------------------------------------------------

def test_the_traffic_excludes_a_rack_a_request_never_twice(cell):
    import traffic
    data = cell.generator.Data(cell.config["data"])
    t = traffic.Traffic(cell.spec, data, SEED, 51)
    assert len(t.warmup) == 3 and len(t.timed) == 4000 - 3
    assert not t.probes and not t.writes
    racks = [r.doc["queries"][0]["filters"][1]["filter"]
             for r in t.warmup + t.timed]
    assert len(set(racks)) == 4000
    sub = t.timed[0].doc["queries"][0]
    assert sub["percentiles"] == [99.0, 99.9]
    assert (sub["aggregator"], sub["downsample"]) == ("sum", "5m-sum")
    cell.judge.Reference.supports(sub, data)
    for other in (dict(sub, aggregator="max"),
                  dict(sub, downsample="5m-avg"),
                  dict(sub, downsample="7m-sum"),
                  {k: v for k, v in sub.items() if k != "percentiles"},
                  {k: v for k, v in sub.items() if k != "downsample"}):
        with pytest.raises(cell.judge.Unsupported):
            cell.judge.Reference.supports(other, data)


def test_the_counts_put_percentiles_in_different_buckets(cell):
    """p99 and p99.9 of different datacentres and of different
    five-minute buckets fall in different buckets of the histogram,
    and no count of one point passes uint16."""
    data = _data(cell)
    _idx, counts, present = cell.generator.chunk_counts(data, SEED, 0)
    assert counts.dtype == np.uint16
    assert not counts[~present].any() and counts[present].reshape(
        -1, data.buckets).any(axis=1).all()
    kept = counts.sum(axis=2)[present]
    assert 0.9 * data.observations < kept.mean() \
        < 1.1 * data.observations
    full = cell.generator.Data(dict(cell.config["data"], series=2000,
                                    chunk_series=2000))
    idx, counts, _present = cell.generator.chunk_counts(full, SEED, 0)
    dc = full.tag_ids("dc", idx)
    merged = np.stack([
        counts[dc == g].reshape(-1, 12, 5, full.buckets)
        .sum(axis=(0, 2), dtype=np.int64) for g in (0, 50, 99)])
    p99 = percentiles_from_counts(
        merged.reshape(-1, full.buckets).astype(np.float64),
        np.asarray(full.bounds), [99.0, 99.9]).reshape(2, 3, 12)
    assert len(set(p99[0, :, 0])) == 3       # by datacentre
    assert len(set(p99[0, 0])) >= 3          # by bucket of time
    assert (p99[1] > p99[0]).all()
    assert p99.max() < full.bounds[-2]       # never the last bucket
