"""A rollup tier's average through the served path (PR 48).

``/api/query`` with ``<n>h-avg`` as the benchmark's cell
``rollup-100k.month-avg`` sends it: a TSD on a real socket over the 1h
tier of a thousand series made by the cell's own generator
(``benchmark/generators/rollup_tiers.py``) and landed by its loader's
runs through ``TSDB.add_aggregate_batch``, answers in the
configuration's float32, and every answer is held to the cell's own
judge (``benchmark/references/rollup_avg.py``, float64, loaded as the
harness loads it) under the configuration's limits: ``1h-avg``,
``6h-avg`` and ``1d-avg`` (weighted), the gappy tenth, a series the
COUNT tier lacks, filters on both sides of
``RESIDENT_GRID_MIN_SHARE``. Beside it: the resident pair is dropped
by a write to either tier, the stages and counters of the path, and
the write side: ``add_aggregate_batch`` against the per-point entry
cell for cell, WAL replay of a batch, one fsync a ``/api/rollup`` body
before its answer, the errors a point under ``details``. CPU only.
"""

from __future__ import annotations

import asyncio
import http.client
import json
import os
import sys
import threading
import types

import jax
import numpy as np
import pytest

from opentsdb_tpu import TSDB, Config
from opentsdb_tpu.query import engine as engine_mod
from opentsdb_tpu.tsd.http_api import HttpRequest, HttpRpcRouter
from opentsdb_tpu.tsd.server import TSDServer
from opentsdb_tpu.tsd.telnet import TelnetRouter

ROOT = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..")
BENCH = os.path.abspath(os.path.join(ROOT, "benchmark"))
# every tag rule and the gappy tenth (series 900-999) are there; four
# days of hours, so a 1d-avg has four buckets of 24 cells
SMALL = {"series": 1000, "chunk_series": 250, "points": 96,
         "dcs": 10, "racks": 40}
SEED = 2**31 + 48
#: a series whose COUNT cells were never written
NO_COUNT = 123
BASE = 1356998400


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
    config = _load("benchmark/configs/rollup-100k.json")
    from benchmark import rollup_plugin
    return types.SimpleNamespace(
        config=config, generator=deploy.generator_of(config),
        judge=deploy.judge_of(config), loader=rollup_plugin,
        spec=_load("benchmark/traffic/month-avg.json"))


def _data(cell):
    return cell.generator.Data(dict(cell.config["data"], **SMALL))


class Tsd:
    """A TSD serving on a real socket, its loop on a thread, loaded
    with the generator's chunks through the loader's runs; series
    :data:`NO_COUNT` has its SUM cells alone."""

    def __init__(self, cell, data, seed: int, **flags):
        self.tsdb = TSDB(Config(**{
            "tsd.core.auto_create_metrics": "true",
            "tsd.rollups.enable": "true",
            "tsd.tpu.warmup": "false", "tsd.trace.sample": "1",
            "tsd.query.cache.enable": "false", **flags}))
        values = []
        self.points = 0
        for c in range(data.chunks):
            idx, sums, counts, present = cell.generator.chunk_cells(
                data, seed, c)
            head = json.loads(cell.generator.frame(
                data, idx, sums, counts, present).split(b"\n", 1)[0])
            runs = [r for r in cell.loader.frame_runs(
                head, present, sums, counts)
                if not (r[1] == "count"
                        and r[3]["host"] == data.tag_name("host",
                                                          NO_COUNT))]
            written, errors = self.tsdb.add_aggregate_batch(runs)
            assert not errors and written == sum(len(r[4]) for r in runs)
            values.append(cell.generator.as_values(sums, counts,
                                                   present))
            self.points += written
        self.values = np.concatenate(values, axis=1)
        self.values[1, NO_COUNT] = np.nan
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
    """(the TSD, its deployment) in the configuration's float32, every
    tail on the device's branch as at the cell's own size; x64 is set
    for every thread (the server answers on its workers) and put back
    afterwards."""
    was = jax.config.read("jax_enable_x64")
    jax.config.update("jax_enable_x64", False)
    data = _data(cell)
    tsd = Tsd(cell, data, SEED,
              **{"tsd.query.host_tail_max_cells_linear": "-1"})
    yield tsd, data
    tsd.stop()
    jax.config.update("jax_enable_x64", was)


def _judge(cell, tsd, data):
    return cell.judge.Reference(data, tsd.values, cell.config["limits"])


FILTERS = {
    # (filters beside the group-by on dc, series selected of 1,000,
    # whether the request reads the metric's resident pair)
    "all": ([], 1000, True),
    "two-racks-out": ([{"type": "not_literal_or", "tagk": "rack",
                        "filter": "r0007|r0031", "groupBy": False}],
                      950, True),
    "a-dc-emptied": ([{"type": "not_literal_or", "tagk": "dc",
                       "filter": "d09", "groupBy": True}], 900, True),
    "just-a-half": ([{"type": "literal_or", "tagk": "fleet",
                          "filter": "a|b|c", "groupBy": False}],
                        500, True),
    "two-dcs": ([{"type": "literal_or", "tagk": "dc",
                  "filter": "d03|d07|nosuch", "groupBy": True}],
                200, False),
    "the-gappy-alone": ([{"type": "literal_or", "tagk": "fleet",
                          "filter": "b", "groupBy": False}], 200,
                        False),
}


def _sub(data, downsample, extra, aggregator="sum") -> dict:
    filters = list(extra)
    if not any(f["tagk"] == "dc" for f in filters):
        filters.insert(0, {"type": "wildcard", "tagk": "dc",
                           "filter": "*", "groupBy": True})
    return {"metric": data.metric, "aggregator": aggregator,
            "downsample": downsample, "filters": filters}


def _body(data, sub) -> dict:
    return {"start": data.t0 * 1000, "end": data.end * 1000,
            "queries": [sub]}


def _held_to_the_judge(cell, ref, data, sub, rows) -> None:
    tagk, names, secs, cells = ref.answer(sub)
    got, stray = cell.judge.rows_to_grid(
        rows, tagk, names, data.t0, data.points * data.cadence_s // secs,
        secs, data.metric)
    verdict = cell.judge.compare(got, stray, cells)
    limits = cell.config["limits"]
    assert verdict.shape_errors == 0, verdict.note
    assert verdict.ok(limits["sum_rtol"], limits["rank_atol"]), \
        (verdict.sum_rel_err, verdict.rank_abs_err, verdict.note)
    # one cell a thousandth off is not the judge's answer
    off = got.copy()
    at = tuple(np.argwhere(~np.isnan(off))[0])
    off[at] *= 1.001
    assert not cell.judge.compare(off, 0, cells).ok(
        limits["sum_rtol"], limits["rank_atol"])


@pytest.mark.parametrize("case", list(FILTERS))
@pytest.mark.parametrize("downsample", ["1h-avg", "6h-avg", "1d-avg"])
def test_a_served_answer_is_the_judges(served, cell, downsample, case):
    tsd, data = served
    extra, selected, whole = FILTERS[case]
    sub = _sub(data, downsample, extra)
    cell.judge.Reference.supports(sub, data)
    ref = _judge(cell, tsd, data)
    assert ref.selected(sub) == selected
    kept = {k: tsd.counter("tsd.query.grid", source=k)
            for k in ("resident_hit", "resident_built", "selection")}
    rows, _headers = tsd.ask("POST", "/api/query", _body(data, sub))
    _held_to_the_judge(cell, ref, data, sub, rows)
    grown = {k for k, v in kept.items()
             if tsd.counter("tsd.query.grid", source=k) > v}
    assert grown <= ({"resident_hit", "resident_built"} if whole
                     else {"selection"}) and grown
    if case == "a-dc-emptied":
        assert "d09" not in {r["tags"]["dc"] for r in rows}
    if case == "the-gappy-alone":
        # fleet b is series 100-199 and 900-999: the gappy tenth,
        # with hours that have no cell and hours of a partial count
        assert np.isnan(tsd.values[0, 900:]).sum() > 0
        assert (tsd.values[1, 900:] < 360).sum() > 0


@pytest.mark.parametrize("aggregator", ["max", "min"])
def test_a_rank_aggregator_over_the_tier(served, cell, aggregator):
    tsd, data = served
    sub = _sub(data, "6h-avg", FILTERS["two-racks-out"][0], aggregator)
    rows, _ = tsd.ask("POST", "/api/query", _body(data, sub))
    _held_to_the_judge(cell, _judge(cell, tsd, data), data, sub, rows)


def test_a_days_average_weighs_each_hour_by_its_count(served, cell):
    """The judge's ``1d-avg`` of a gappy series is SUM over COUNT of
    the day, not the mean of its hours' averages: the two differ where
    an hour counted few points, and the served answer is the first."""
    tsd, data = served
    host = 950      # of the gappy tenth, with an hour of a partial count
    assert np.nanmin(tsd.values[1, host]) < 300
    sub = {"metric": data.metric, "aggregator": "sum",
           "downsample": "1d-avg", "filters": [{
               "type": "literal_or", "tagk": "host",
               "filter": data.tag_name("host", host), "groupBy": False}]}
    rows, _ = tsd.ask("POST", "/api/query", _body(data, sub))
    (row,) = rows
    sums = tsd.values[0, host].reshape(-1, 24)
    counts = tsd.values[1, host].reshape(-1, 24)
    weighted = np.nansum(sums, axis=1) / np.nansum(counts, axis=1)
    plain = np.nanmean(sums / counts, axis=1)
    got = np.array([row["dps"][str(data.t0 + 86400 * j)]
                    for j in range(4)])
    np.testing.assert_allclose(got, weighted, rtol=2e-7)
    assert np.abs(weighted - plain).max() > 1e3 * np.abs(
        got - weighted).max()


@pytest.mark.parametrize("case", ["two-racks-out", "a-dc-emptied",
                                  "just-a-half"])
def test_both_sides_of_the_share_give_one_answer(served, monkeypatch,
                                                 case):
    """The same request over the metric's resident pair and over a
    pair of its own rows (the share moved out of reach): the same
    rows, to the last bit but the order of a group's float32 sum."""
    tsd, data = served
    body = _body(data, _sub(data, "6h-avg", FILTERS[case][0]))
    whole, _ = tsd.ask("POST", "/api/query", body)
    built = tsd.counter("tsd.query.grid", source="selection")
    monkeypatch.setattr(engine_mod, "RESIDENT_GRID_MIN_SHARE", 2.0)
    own, _ = tsd.ask("POST", "/api/query", body)
    assert tsd.counter("tsd.query.grid", source="selection") == built + 1
    assert [r["tags"] for r in own] == [r["tags"] for r in whole]
    for a, b in zip(own, whole):
        assert list(a["dps"]) == list(b["dps"])
        np.testing.assert_allclose(list(a["dps"].values()),
                                   list(b["dps"].values()), rtol=2e-6)


@pytest.mark.parametrize("tier", ["count", "sum"])
def test_a_write_to_either_tier_drops_the_pair(served, cell, tier):
    """Two requests either side of an acknowledged ``/api/rollup``
    write: the first reads the resident pair and uploads its labels
    alone, the write drops the pair (the version rule of
    ``resident()``), and the next answer has the cell."""
    tsd, data = served
    sub = _sub(data, "1h-avg", FILTERS["two-racks-out"][0])
    body = _body(data, sub)
    tsd.ask("POST", "/api/query", body)
    uploaded = tsd.counter("tsd.query.rollup.upload_bytes")
    hits = tsd.counter("tsd.query.grid", source="resident_hit")
    tsd.ask("POST", "/api/query", body)
    per_request = tsd.counter("tsd.query.rollup.upload_bytes") - uploaded
    resident = tsd.counter("tsd.query.rollup.resident_bytes")
    assert tsd.counter("tsd.query.grid", source="resident_hit") \
        == hits + 1
    # one int32 label a row of the metric
    assert per_request == 4 * data.series
    # two float32 grids of 1,024 x 96, no mask, whatever else the
    # cache holds of other windows
    assert resident >= 2 * 1024 * 96 * 4
    # host 3 of dc d03: an hour that counted ten times its points
    host, hour = 3, 5 if tier == "count" else 6
    tags = {k: data.tag_name(k, int(data.tag_ids(k, np.array([host]))[0]))
            for k in data.tags}
    which = 1 if tier == "count" else 0
    value = float(tsd.values[which, host, hour]) * 10
    tsd.ask("POST", "/api/rollup", [{
        "metric": data.metric, "timestamp": int(data.timestamps[hour]),
        "value": value, "tags": tags, "interval": "1h",
        "aggregator": tier.upper()}])
    builds = tsd.counter("tsd.query.grid", source="resident_built")
    rows, _ = tsd.ask("POST", "/api/query", body)
    assert tsd.counter("tsd.query.grid", source="resident_built") \
        == builds + 1
    tsd.values = tsd.values.copy()
    tsd.values[which, host, hour] = value
    _held_to_the_judge(cell, _judge(cell, tsd, data), data, sub, rows)
    # both grids went up again, whole
    assert tsd.counter("tsd.query.rollup.upload_bytes") - uploaded \
        > 2 * 1024 * 96 * 4


def test_the_stages_and_the_counters_of_a_request(served):
    tsd, data = served
    body = _body(data, _sub(data, "1h-avg",
                            FILTERS["two-racks-out"][0]))
    tsd.ask("POST", "/api/query", body)      # the pair is resident
    tails = tsd.counter("tsd.query.tail", path="avg_div",
                        placement="device")
    from_tier = tsd.counter("tsd.query.rollup", source="tier")
    _rows, headers = tsd.ask("POST", "/api/query", body)
    doc, _ = tsd.ask("GET", "/api/trace/" + headers["X-TSD-Trace-Id"])
    (root,) = doc["tree"]
    (execute,) = [c for c in root["children"]
                  if c["name"] == "query.execute"]
    stages = [(c["name"], c.get("tags", {}).get("stage"))
              for c in execute["children"]]
    assert stages == [
        ("query.plan", None), ("query.grid_build", "cache_lookup"),
        ("query.scan", None), ("query.upload", "labels"),
        ("query.upload", None), ("query.program", None),
        ("query.download", None), ("query.assemble", None)]
    plan, lookup, scan, _labels, _up, program = execute["children"][:6]
    assert (plan["tags"]["source"], plan["tags"]["tier"]) \
        == ("tier", "1h")
    assert plan["tags"]["index"] == "hit"
    assert plan["tags"]["series"] == 950 and plan["tags"]["groups"] == 10
    assert lookup["tags"]["grid"] == "resident_hit"
    # the selection's cells of both tiers, as a build's scan says
    mine = data.tag_ids("rack", np.arange(data.series))
    mine = ~np.isin(mine, [7, 31])
    assert scan["tags"]["points"] == int(
        (~np.isnan(tsd.values[:, mine])).sum())
    assert program["tags"]["path"] == "avg_div"
    assert program["tags"]["placement"] == "device"
    # the metric's rows, hours, datacentres + 1, all padded
    assert program["tags"]["shape"] == "1024x96x12"
    assert "compiled" not in program["tags"]
    assert tsd.counter("tsd.query.tail", path="avg_div",
                       placement="device") == tails + 1
    assert tsd.counter("tsd.query.rollup", source="tier") \
        == from_tier + 1
    assert tsd.counter("tsd.query.rollup", source="fallback") == 0
    assert tsd.counter("tsd.rollup.slow_points") <= 2    # the writes'
    assert tsd.counter("tsd.rollup.batch_points") >= tsd.points


def test_an_empty_tier_falls_back_and_says_so(served):
    """A metric with raw points and no cell in the tier, asked with
    ``ROLLUP_FALLBACK``: answered from raw, counted as a fallback."""
    tsd, data = served
    for j in range(4):
        tsd.tsdb.add_point("raw.only", data.t0 + 600 * j, 1.0 + j,
                           {"host": "h0"})
    sub = {"metric": "raw.only", "aggregator": "sum",
           "downsample": "1h-avg", "rollupUsage": "ROLLUP_FALLBACK"}
    before = tsd.counter("tsd.query.rollup", source="fallback")
    rows, _ = tsd.ask("POST", "/api/query", _body(data, sub))
    assert rows[0]["dps"] == {str(data.t0): 2.5}
    assert tsd.counter("tsd.query.rollup", source="fallback") \
        == before + 1
    raw = tsd.counter("tsd.query.rollup", source="raw")
    tsd.ask("POST", "/api/query", _body(data, dict(sub,
                                                   downsample="10m-avg")))
    assert tsd.counter("tsd.query.rollup", source="raw") == raw + 1


# -- the write side --------------------------------------------------------

def _tsdb(tmp_path=None, **extra):
    conf = {"tsd.core.auto_create_metrics": "true",
            "tsd.rollups.enable": "true", **extra}
    if tmp_path is not None:
        conf["tsd.storage.data_dir"] = str(tmp_path)
    return TSDB(Config(**conf))


def _runs(n_series=6, n_cells=30):
    """Runs of three kinds of store: two tiers and the pre-aggregates
    (second and millisecond timestamps among them)."""
    rng = np.random.default_rng(7)
    runs = []
    for i in range(n_series):
        tags = {"host": f"h{i}", "dc": f"d{i % 2}"}
        ts = BASE + 3600 * np.arange(n_cells)
        keep = rng.random(n_cells) > 0.2
        if i == 2:
            ts = ts * 1000
        runs.append(("1h", "sum", "w.m", tags, ts[keep].tolist(),
                     (rng.random(n_cells) * 1e6)[keep].tolist()))
        runs.append(("1h", "COUNT", "w.m", tags, ts[keep].tolist(),
                     rng.integers(1, 360, n_cells)[keep].tolist()))
        runs.append(("1m", "max", "w.m", tags, ts[:5].tolist(),
                     rng.random(5).tolist()))
    runs.append((None, None, "w.m", {"dc": "d0"},
                 (BASE + np.arange(4)).tolist(), [1.0, 2.0, 3.0, 4.0],
                 "sum", True))
    runs.append(("1h", "sum", "w.m", {"dc": "d1"},
                 (BASE + 3600 * np.arange(4)).tolist(),
                 [5.0, 6.0, 7.0, 8.0], "sum", True))
    return runs


def _stores(t):
    rs = t.rollup_store
    return {"preagg": rs.preagg_store(),
            **{f"{iv}:{agg}": store
               for (iv, agg), store in sorted(rs._tiers.items())}}


def _cells(t) -> dict:
    """Every stored cell: {store: {series' tags: (timestamps, values)}}."""
    out = {}
    for name, store in _stores(t).items():
        mine = out[name] = {}
        for sid in range(store.num_series()):
            rec = store.series(sid)
            batch = store.materialize(np.array([sid]), 0, 1 << 60)
            mine[(rec.metric_id, tuple(sorted(rec.tags)))] = (
                batch.ts_ms.tolist(), batch.values.tolist())
    return out


@pytest.mark.parametrize("backend", ["native", "memory"])
def test_a_batch_lands_what_the_per_point_entry_lands(backend):
    runs = _runs()
    one = _tsdb(**{"tsd.storage.backend": backend})
    for run in runs:
        interval, agg, metric, tags, ts, values = run[:6]
        gb_agg, is_gb = run[6:] if len(run) > 6 else (None, False)
        for t, v in zip(ts, values):
            one.add_aggregate_point(metric, t, v, tags, is_gb, interval,
                                    agg, gb_agg)
    many = _tsdb(**{"tsd.storage.backend": backend})
    total = sum(len(r[4]) for r in runs)
    assert many.add_aggregate_batch(runs) == (total, [])
    assert _cells(many) == _cells(one)
    assert set(_stores(many)) == {"preagg", "1h:sum", "1h:count",
                                  "1m:max"}
    assert many.datapoints_added == one.datapoints_added == total
    stats = many.rollup_store.stats
    assert (stats.batch_points, stats.slow_points) == (total, 0)
    stats = one.rollup_store.stats
    assert (stats.batch_points, stats.slow_points) == (0, total)
    # a pre-aggregate carries the agg-tag, as the reference's
    keys = {many.uids.tag_names.get_name(k)
            for (_m, tags) in _cells(many)["preagg"] for k, _v in tags}
    assert keys == {"dc", many.agg_tag_key}


@pytest.mark.parametrize("kill", [False, True],
                         ids=["shutdown", "killed"])
def test_a_batch_is_replayed_from_the_wal(tmp_path, kill):
    runs = _runs()
    t = _tsdb(tmp_path, **{"tsd.storage.backend": "memory"})
    total = sum(len(r[4]) for r in runs)
    assert t.add_aggregate_batch(runs) == (total, [])
    want = _cells(t)
    if kill:
        # no flush, no snapshot: what the log holds is what survives
        import shutil
        shutil.copytree(tmp_path, tmp_path.parent / "copy")
        again = _tsdb(tmp_path.parent / "copy",
                      **{"tsd.storage.backend": "memory"})
    else:
        t.shutdown()
        again = _tsdb(tmp_path, **{"tsd.storage.backend": "memory"})
    got = _cells(again)
    assert {k: sorted(v.values()) for k, v in got.items()} \
        == {k: sorted(v.values()) for k, v in want.items()}
    again.shutdown()


def _request(method, path, doc):
    path, _, query = path.partition("?")
    return HttpRequest(method=method, path=path,
                       params={k: [""] for k in query.split("&") if k},
                       body=json.dumps(doc).encode())


def _rollup_body(n_series=5, n_cells=8):
    return [{"metric": "b.m", "timestamp": BASE + 3600 * j,
             "value": 10.0 * i + j, "tags": {"host": f"h{i}"},
             "interval": "1h", "aggregator": agg}
            for i in range(n_series) for j in range(n_cells)
            for agg in ("SUM", "COUNT")]


def test_a_body_is_answered_after_its_one_fsync(tmp_path):
    """``/api/rollup`` under a WAL (``fsync`` = ``always``): a body of
    80 cells in 10 runs is ONE group-committed fsync (it was one a
    cell), made before the answer: what was acknowledged is in the log
    of a TSD that is killed right after."""
    t = _tsdb(tmp_path, **{"tsd.storage.backend": "memory"})
    router = HttpRpcRouter(t)
    t.faults.arm("wal.fsync")       # a pure counter, never fails
    before = t.faults._sites["wal.fsync"].calls
    body = _rollup_body()
    resp = router.handle(_request("POST", "/api/rollup?summary", body))
    assert resp.status == 200, resp.body
    assert json.loads(resp.body) == {"success": len(body), "failed": 0}
    assert t.faults._sites["wal.fsync"].calls - before == 1
    assert t.wal.sync_lag() == 0
    stats = t.rollup_store.stats
    assert (stats.batch_points, stats.slow_points) == (len(body), 0)
    # killed: no shutdown, no flush
    import shutil
    shutil.copytree(tmp_path, tmp_path.parent / "killed")
    again = _tsdb(tmp_path.parent / "killed",
                  **{"tsd.storage.backend": "memory"})
    assert {k: sorted(v.values()) for k, v in _cells(again).items()} \
        == {k: sorted(v.values()) for k, v in _cells(t).items()}
    assert sum(len(ts) for store in _cells(again).values()
               for ts, _v in store.values()) == len(body)
    again.shutdown()
    t.shutdown()


BAD = {
    "no-such-tier": ({"interval": "7m"}, "no rollup tier"),
    "no-such-aggregator": ({"aggregator": "p99"},
                           "unsupported rollup aggregator"),
    "no-aggregator": ({"aggregator": None},
                      "missing rollup aggregator"),
    "a-bad-tag": ({"tags": {"ho st": "x"}}, "Invalid tag name"),
    "no-tags": ({"tags": {}}, "tag"),
    "a-bad-value": ({"value": "1_0"}, ""),
    "no-timestamp": ({"timestamp": "soon"}, "invalid literal"),
}


@pytest.mark.parametrize("case", list(BAD))
def test_a_bad_cell_fails_alone_with_its_own_error(case):
    """One bad cell among good ones of the same and of other series:
    the good ones land, the error is the bad cell's under ``details``
    and the one the per-point entry raises."""
    t = _tsdb()
    router = HttpRpcRouter(t)
    change, message = BAD[case]
    body = _rollup_body(3, 4)
    bad = dict(body[5], **change)
    body.insert(6, bad)
    resp = router.handle(_request("POST", "/api/rollup?details", body))
    assert resp.status == 400
    doc = json.loads(resp.body)
    assert (doc["success"], doc["failed"]) == (len(body) - 1, 1)
    (err,) = doc["errors"]
    assert err["datapoint"] == bad and message in err["error"]
    assert sum(len(ts) for store in _cells(t).values()
               for ts, _v in store.values()) == len(body) - 1
    if case not in ("a-bad-value", "no-timestamp"):
        with pytest.raises(Exception) as raised:
            t.add_aggregate_point(
                bad["metric"], bad["timestamp"], bad["value"],
                bad["tags"], False, bad["interval"], bad["aggregator"])
        assert str(raised.value) == err["error"]
    # without details the body is refused, with the error in the text
    t2 = _tsdb()
    resp = HttpRpcRouter(t2).handle(_request("POST", "/api/rollup",
                                             body))
    assert resp.status == 400 and b"errors" in resp.body


def test_rollups_switched_off_fail_every_cell():
    t = TSDB(Config(**{"tsd.core.auto_create_metrics": "true"}))
    written, errors = t.add_aggregate_batch(_runs(2, 3))
    assert written == 0 and len(errors) == sum(
        len(r[4]) for r in _runs(2, 3))
    assert all("rollups are not enabled" in e for e in errors)
    with pytest.raises(RuntimeError, match="rollups are not enabled"):
        t.add_aggregate_point("m", BASE, 1.0, {"h": "a"}, False, "1h",
                              "sum")


def test_the_telnet_line_goes_through_the_batch_entry():
    t = _tsdb()
    rpc = TelnetRouter(t)
    assert rpc.execute(f"rollup 1h:sum t.m {BASE} 42.5 host=a") == ""
    assert rpc.execute(f"rollup 1h:sum:max t.m {BASE} 7 host=a") == ""
    assert rpc.execute(f"rollup sum t.m {BASE} 9 host=a") == ""
    assert "no rollup tier" in rpc.execute(
        f"rollup 9h:sum t.m {BASE} 1 host=a")
    cells = _cells(t)
    assert [len(s) for s in (cells["1h:sum"], cells["preagg"])] == [2, 1]
    assert t.rollup_store.stats.slow_points == 3


def test_a_batch_is_one_ingest_root():
    """Outside a request a batch roots an ``ingest.rollup`` trace that
    says how many cells it landed."""
    t = _tsdb(**{"tsd.trace.sample": "1"})
    runs = _runs(2, 5)
    t.add_aggregate_batch(runs)
    (trace,) = [d for d in t.tracer.recent(limit=10)
                if d["name"] == "ingest.rollup"]
    root = t.tracer.get(trace["traceId"]).root
    assert root.tags["points"] == sum(len(r[4]) for r in runs)
