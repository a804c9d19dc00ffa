"""The filters that match a stored NAME, through the served path (PR 40).

``host=wildcard(h00123*)`` as the benchmark's cell
``fleet-1m.wildcard-lookup`` sends it, and its four siblings that
``FilterEvaluator.apply`` resolves against the plan index's table of
the key's names (``iwildcard``, ``regexp``, ``iliteral_or``,
``not_iliteral_or``; PR 41, before it a walk of the names): a
TSD on a real socket, over a store of ten thousand series made by the
cell's own generator (``benchmark/generators/pattern_draws.py``),
answers in the configuration's float32, and every answer is held to
the cell's own judge (``benchmark/references/patterns.py``, loaded as
the harness loads it) under the configuration's limits, on two seeds,
with and without a group-by on ``dc``. The judge's matcher is held to
a character-by-character one, the generator's patterns to one size of
selection, the configuration to ``fleet-1m``'s store, and the table to
its stage (``query.filter_resolve``) and its counter
(``tsd.query.filter.names_read``). CPU only.
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
from opentsdb_tpu.tsd.server import TSDServer

ROOT = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..")
BENCH = os.path.abspath(os.path.join(ROOT, "benchmark"))
SERIES = 10_000
SEEDS = (40, 2**31 + 40)


def _load(rel: str):
    with open(os.path.join(ROOT, rel), encoding="utf-8") as fh:
        return json.load(fh)


@pytest.fixture(scope="module")
def cell():
    """The cell's configuration with its generator and its judge, as
    ``benchmark/run.py`` finds them (``deploy.py``)."""
    if BENCH not in sys.path:
        sys.path.append(BENCH)
    import deploy
    import gen
    import traffic
    config = _load("benchmark/configs/fleet-1m-wildcard.json")
    return types.SimpleNamespace(
        config=config, gen=gen, traffic=traffic,
        generator=deploy.generator_of(config),
        judge=deploy.judge_of(config),
        spec=_load("benchmark/traffic/wildcard-lookup.json"))


def _data(cell, series: int):
    return cell.generator.Data(dict(
        cell.config["data"], series=series,
        chunk_series=max(series // 4, 1)))


class Tsd:
    """A TSD serving on a real socket, its loop on a thread, loaded
    with the generator's import text."""

    def __init__(self, cell, data, seed: int):
        self.tsdb = TSDB(Config(**{
            "tsd.core.auto_create_metrics": "true",
            "tsd.tpu.warmup": "false", "tsd.trace.sample": "1",
            "tsd.query.cache.enable": "false"}))
        values, points = [], 0
        for c in range(data.chunks):
            text, vals, pts = cell.gen.chunk_lines(data, seed, c)
            written, errors = self.tsdb.import_buffer(text,
                                                      durable=False)
            assert not errors and written == pts
            values.append(vals)
            points += pts
        self.values = np.concatenate(values)
        assert points == int((~np.isnan(self.values)).sum())
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

    def ask(self, method: str, path: str, doc=None):
        conn = http.client.HTTPConnection("127.0.0.1", self.port,
                                          timeout=120)
        try:
            conn.request(method, path,
                         body=None if doc is None else json.dumps(doc))
            resp = conn.getresponse()
            body = resp.read()
            assert resp.status == 200, body[:300]
            return json.loads(body), dict(resp.getheaders())
        finally:
            conn.close()

    def stop(self):
        asyncio.run_coroutine_threadsafe(
            self.server.stop(), self.loop).result(20)
        self.loop.call_soon_threadsafe(self.loop.stop)
        self.tsdb.shutdown()


@pytest.fixture(scope="module", params=SEEDS)
def served(request, cell):
    """(the TSD, its deployment, the judge over its values) in the
    configuration's float32; x64 is set for every thread (the server
    answers on its workers) and put back afterwards."""
    was = jax.config.read("jax_enable_x64")
    jax.config.update("jax_enable_x64", False)
    data = _data(cell, SERIES)
    tsd = Tsd(cell, data, request.param)
    ref = cell.judge.Reference(data, tsd.values, cell.config["limits"])
    yield tsd, data, ref
    tsd.stop()
    jax.config.update("jax_enable_x64", was)


# (case, filter type, expression, hosts selected of 10,000)
FILTERS = [
    ("prefix", "wildcard", "h00012*", 100),
    ("suffix", "wildcard", "*07", 100),
    ("infix", "wildcard", "*0012*", 111),
    ("two-part", "wildcard", "h0001*3", 100),
    ("a-cell-prefix", "wildcard", "h0001234*", 1),
    ("a-cell-suffix", "wildcard", "*4321", 1),
    ("iwildcard", "iwildcard", "H00034*", 100),
    ("regexp", "regexp", r"h0+12[0-4]\d$", 50),
    ("iliteral_or", "iliteral_or", "H0000123|h0004567|H0009999|nosuch",
     3),
    ("not_iliteral_or", "not_iliteral_or", "H0000123|h0004567", 9998),
]


def _sub(data, kind: str, expr: str, by_dc: bool) -> dict:
    filters = [{"type": kind, "tagk": "host", "filter": expr,
                "groupBy": False}]
    if by_dc:
        filters.append({"type": "wildcard", "tagk": "dc", "filter": "*",
                        "groupBy": True})
    return {"metric": data.metric, "aggregator": "sum",
            "downsample": "1m-avg", "filters": filters}


@pytest.mark.parametrize("by_dc", [False, True], ids=["one-line", "by-dc"])
@pytest.mark.parametrize("case, kind, expr, hosts", FILTERS,
                         ids=[f[0] for f in FILTERS])
def test_a_name_filter_selects_what_the_judge_selects(
        served, cell, case, kind, expr, hosts, by_dc):
    tsd, data, ref = served
    sub = _sub(data, kind, expr, by_dc)
    cell.judge.Reference.supports(sub, data)
    assert ref.selected(sub) == hosts
    rows, _headers = tsd.ask("POST", "/api/query", {
        "start": data.t0 * 1000, "end": data.end * 1000,
        "queries": [sub]})
    tagk, names, secs, cells = ref.answer(sub)
    assert (tagk, secs) == ("dc" if by_dc else "", 60)
    assert len(rows) == len(names) and cells.emitted.any(axis=1).all()
    got, stray = cell.judge.rows_to_grid(
        rows, tagk, names, data.t0, data.points, secs, data.metric)
    verdict = cell.judge.compare(got, stray, cells)
    limits = cell.config["limits"]
    assert verdict.shape_errors == 0, verdict.note
    assert verdict.ok(limits["sum_rtol"], limits["rank_atol"]), \
        (verdict.sum_rel_err, verdict.note)
    # a cell a thousandth off (one host of a thousand) is not the
    # judge's answer
    off = got.copy()
    at = tuple(np.argwhere(~np.isnan(off))[0])
    off[at] *= 1.001
    assert not cell.judge.compare(off, 0, cells).ok(
        limits["sum_rtol"], limits["rank_atol"])


# -- the judge's matcher --------------------------------------------------

def by_character(pattern: str, name: str) -> bool:
    """``*`` any run of characters, every other character itself: one
    character of the pattern at a time."""
    if not pattern:
        return not name
    if pattern[0] == "*":
        return any(by_character(pattern[1:], name[i:])
                   for i in range(len(name) + 1))
    return bool(name) and pattern[0] == name[0] \
        and by_character(pattern[1:], name[1:])


@pytest.mark.parametrize("fold", [False, True], ids=["wildcard",
                                                      "iwildcard"])
@pytest.mark.parametrize("seed", [1, 2, 3, 4])
def test_the_judges_matcher_is_the_character_by_character_one(
        cell, seed, fold):
    rng = np.random.default_rng([seed, 40])
    letters = list("ab?[]Ab.")

    def word(lo: int, hi: int, extra: str = "") -> str:
        return "".join(rng.choice(letters + list(extra),
                                  size=int(rng.integers(lo, hi))))

    import patterns                  # loaded by deploy under its stem
    names = sorted({word(0, 7) for _ in range(400)} | {"h[01]x", "webx12",
                                                        "web?12", ""})
    table = patterns.Names(names)
    seen = 0
    drawn = [word(1, 6, extra="***") for _ in range(150)] \
        + ["web?1*", "h[01]*", "*", "**", "a*", "*a", "a*b", "*a*b*"]
    for pattern in drawn:
        if "*" not in pattern:
            pattern += "*"
        want = [by_character(pattern.lower() if fold else pattern,
                             n.lower() if fold else n) for n in names]
        assert table.glob(pattern, fold=fold).tolist() == want, pattern
        seen += sum(want)
    assert seen > 200                # the draws are not all misses
    # the two forms the source's glob would read otherwise
    assert table.glob("web?1*").tolist() == [n == "web?12" for n in names]
    assert table.glob("h[01]*").tolist() == [n == "h[01]x" for n in names]


# -- the generator's patterns ---------------------------------------------

@pytest.mark.parametrize("series, count, hosts, prefix, suffix", [
    (1_000, 2_000, 1, "h0000999*", "*999"),
    (10_000, 20_000, 1, "h0009999*", "*9999"),
    (100_000, 20_000, 10, "h009999*", "*9999"),
    (1_000_000, 20_000, 100, "h09999*", "*9999")])
def test_every_pattern_selects_the_same_number_of_hosts(
        cell, series, count, hosts, prefix, suffix):
    data = _data(cell, series)
    key = "host~pattern"
    assert data.tag_count(key) == count
    assert data.hosts_per_pattern == hosts
    assert data.tag_name(key, count // 2 - 1) == prefix
    assert data.tag_name(key, count - 1) == suffix
    drawn = [data.tag_name(key, i) for i in range(count)]
    assert len(set(drawn)) == count
    assert all(p.count("*") == 1 and (p[0] == "*") != (p[-1] == "*")
               for p in drawn)
    # no series carries the key: it is the traffic's draw alone
    assert key not in data.tags
    with pytest.raises(KeyError):
        data.tag_ids(key, np.arange(3))
    import patterns                  # loaded by deploy under its stem
    names = patterns.Names([data.tag_name("host", i)
                            for i in range(series)])
    step = 1 if series <= 10_000 else 97
    some = sorted(set(range(0, count, step))
                  | {0, count // 2 - 1, count // 2, count - 1})
    for i in some:
        assert int(names.glob(drawn[i]).sum()) == hosts, drawn[i]
    with pytest.raises(ValueError, match="power of ten"):
        _data(cell, 4000)


@pytest.mark.parametrize("seed", SEEDS)
def test_the_traffic_draws_distinct_patterns_of_both_forms(cell, seed):
    data = cell.generator.Data(cell.config["data"])
    t = cell.traffic.Traffic(cell.spec, data, seed, 51)
    assert len(t.warmup) == 3 and len(t.timed) == 16_000 - 3
    assert len(t.probes) == 3 and not t.writes
    drawn = [r.doc["queries"][0]["filters"][0]["filter"]
             for r in t.warmup + t.timed]
    assert len(set(drawn)) == 16_000
    prefixes = sum(p.endswith("*") for p in drawn)
    assert 7_600 <= prefixes <= 8_400
    sub = t.timed[0].doc["queries"][0]
    assert sub["aggregator"] == "sum" and sub["downsample"] == "1m-avg"
    (only,) = sub["filters"]
    assert only == {"type": "wildcard", "tagk": "host",
                    "filter": only["filter"], "groupBy": False}
    assert only["filter"] in drawn
    # the judge takes every template before the server starts
    for req in (t.timed[0], t.probes[0]):
        for q in req.doc["queries"]:
            cell.judge.Reference.supports(q, data)


@pytest.mark.parametrize("key", ["data", "server", "limits", "precision"])
def test_the_store_is_fleet_1ms_key_for_key(cell, key):
    assert cell.config[key] == _load("benchmark/configs/fleet-1m.json")[key]
    assert cell.config["reduced"] == []


# -- the stage and the counter --------------------------------------------

def _names_read(tsd) -> int:
    rows, _ = tsd.ask("GET", "/api/stats")
    (row,) = [r for r in rows
              if r["metric"] == "tsd.query.filter.names_read"]
    return row["value"]


def _stage_count(tsd) -> int:
    raw, _ = tsd.ask("GET", "/api/stats/raw")
    return sum(h["count"] for h in raw["histograms"]
               if h["name"] == "tsd_stage_latency_ms"
               and h["labels"].get("stage") == "query.filter_resolve")


def _table(state: str, read: int, matched: int) -> dict:
    return {"way": "table", "table": state, "names_read": read,
            "matched": matched}


# (case, filters, the stage's tags on the first request after a value
# was renamed, and on the second)
@pytest.mark.parametrize("case, filters, cold, warm", [
    ("table", [("host", "wildcard", "h00012*")],
     [_table("built", SERIES, 100)], [_table("hit", 0, 100)]),
    ("ids", [("host", "literal_or", "h0000007|h0001234|nosuch")],
     [{"way": "ids", "names_read": 0, "matched": 2}],
     [{"way": "ids", "names_read": 0, "matched": 2}]),
    ("presence", [("dc", "wildcard", "*")], [], []),
    ("two-tables-and-an-id",
     [("host", "iwildcard", "*07"), ("dc", "regexp", "d0[0-7]"),
      ("rack", "not_literal_or", "r0007")],
     [_table("built", SERIES, 100), _table("built", 100, 8),
      {"way": "ids", "names_read": 0, "matched": 1}],
     [_table("hit", 0, 100), _table("hit", 0, 8),
      {"way": "ids", "names_read": 0, "matched": 1}])],
    ids=lambda v: v if isinstance(v, str) else "")
def test_resolving_a_filter_is_a_stage_and_the_names_a_counter(
        served, case, filters, cold, warm):
    tsd, data, _ref = served
    # there and back: the names are what they were, the dictionary's
    # generation is not, and no table of before it is trusted
    tagv = tsd.tsdb.uids.tag_values
    tagv.rename("d00", "elsewhere")
    tagv.rename("elsewhere", "d00")
    for spans in (cold, warm):
        before, stages = _names_read(tsd), _stage_count(tsd)
        _rows, headers = tsd.ask("POST", "/api/query", {
            "start": data.t0 * 1000, "end": data.end * 1000,
            "queries": [{
                "metric": data.metric, "aggregator": "sum",
                "downsample": "1m-avg", "filters": [
                    {"type": kind, "tagk": tagk, "filter": expr,
                     "groupBy": False}
                    for tagk, kind, expr in filters]}]})
        doc, _ = tsd.ask("GET",
                         "/api/trace/" + headers["X-TSD-Trace-Id"])
        (root,) = doc["tree"]
        (execute,) = [c for c in root["children"]
                      if c["name"] == "query.execute"]
        (plan,) = [c for c in execute["children"]
                   if c["name"] == "query.plan"]
        found = [c for c in plan.get("children", ())
                 if c["name"] == "query.filter_resolve"]
        # children of the plan, one a filter that became tagv ids, in
        # the order the filters were evaluated
        assert [c["tags"] for c in found] == spans
        assert all(c["durationMs"] <= plan["durationMs"] for c in found)
        read = sum(s["names_read"] for s in spans)
        assert plan["tags"]["names_read"] == read
        assert _names_read(tsd) - before == read
        assert _stage_count(tsd) - stages == len(spans)
        assert "resolve_walk" not in plan["tags"]
