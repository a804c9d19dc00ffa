"""The percentile over the fleet through the served path (PR 34).

BASELINE config 4's request as the benchmark's cell
``fleet-1m.rank-p95`` sends it (``p95:5m-avg`` grouped by ``dc``, one
rack left out, some of the series gappy), at a series count a test can
hold and for four of the six legacy percentiles: a TSD on a real
socket answers, and every answer is held to the independent oracle
(``tests/oracle.py``: plain Python, the position ``h = p (n + 1)`` of
commons-math's LEGACY estimation). Groups of one and of two members
are among them: there ``h >= n`` and the percentile is the maximum
(``h < 1`` cannot happen from ``p50`` upwards). In the configuration's
float32 the answers lie within its ``rank_atol``, the float32 rounding
of one value under 10,000 and of the interpolation; in the suite's
float64 they are the oracle's to 1e-9. One request of each class of
group stage leaves ``class=rank`` or ``class=linear`` on its
``query.program`` span and moves ``tsd.query.tail{class}`` by one.
Since PR 44 a third server runs float32 with its tails forced to the
device's branch (as ``tests/test_device_cache.py`` forces it), where
the rank group stage selects by counting: the same answers, and the
span's ``rank=select|sort`` and ``tsd.query.rank{method}`` say which
lowering ran (a host-placed tail and float64 keep the sort). Since PR
49 every program's span says ``carry=unrolled|loop``, the form its
nearest-present carry takes along its padded buckets, and
``tsd.query.carry{form}`` counts it.
CPU only.
"""

from __future__ import annotations

import asyncio
import http.client
import json
import os
import threading

import jax
import numpy as np
import pytest

from opentsdb_tpu import TSDB, Config
from opentsdb_tpu.tsd.server import TSDServer

from oracle import run_oracle

T0 = 1356998400
CADENCE, POINTS = 60, 60           # an hour: 12 buckets of 5 minutes
END = T0 + POINTS * CADENCE - 1
# groups of unequal size, two of them the smallest there are
DC_SIZES = (1, 2, 37, 60, 90, 110)
SERIES = sum(DC_SIZES)
RACKS, SKIP_RACK = 20, 3
METRIC = "fleet.cpu"
with open(os.path.join(os.path.dirname(__file__), "..", "benchmark",
                       "configs", "fleet-1m-rank.json"),
          encoding="utf-8") as _fh:
    RANK_ATOL = json.load(_fh)["limits"]["rank_atol"]


def _dc_of() -> np.ndarray:
    return np.repeat(np.arange(len(DC_SIZES)), DC_SIZES)


def _rack_of() -> np.ndarray:
    rack = np.arange(SERIES) % RACKS
    # the groups of one and two keep their members
    rack[:3] = SKIP_RACK + 1
    return rack


def _values(seed: int) -> np.ndarray:
    """[series, points] in cents between 1,000.00 and 10,000.00; a
    fifth of the series gappy: single points, a block of seven (a
    whole bucket gone: interpolated at the merge) and, for some, the
    first or the last ten minutes (no value there, real or
    interpolated: the member does not count)."""
    rng = np.random.default_rng(seed)
    vals = rng.integers(100000, 1000000, (SERIES, POINTS)) / 100.0
    for i in np.flatnonzero(rng.random(SERIES) < 0.2):
        vals[i, rng.random(POINTS) < 0.03] = np.nan
        at = int(rng.integers(5, POINTS - 12))
        vals[i, at:at + 7] = np.nan
        if i % 3 == 0:
            vals[i, :10] = np.nan
        if i % 3 == 1:
            vals[i, -10:] = np.nan
    return vals


class Tsd:
    """A TSD serving on a real socket, its loop on a thread."""

    def __init__(self, vals: np.ndarray, on_device: bool = False):
        placed = {"tsd.query.host_tail_max_cells": "-1",
                  "tsd.query.host_tail_max_cells_linear": "-1"} \
            if on_device else {}
        self.tsdb = TSDB(Config(**{
            "tsd.core.auto_create_metrics": "true",
            "tsd.tpu.warmup": "false", "tsd.trace.sample": "1",
            "tsd.query.cache.enable": "false", **placed}))
        dc, rack = _dc_of(), _rack_of()
        lines = []
        for i in range(SERIES):
            tags = f"host=h{i:04d} dc=dc{dc[i]} rack=r{rack[i]}"
            for j in np.flatnonzero(~np.isnan(vals[i])).tolist():
                lines.append(f"{METRIC} {T0 + j * CADENCE} "
                             f"{vals[i, j]:.2f} {tags}\n")
        written, errors = self.tsdb.import_buffer(
            "".join(lines).encode(), durable=False)
        assert not errors and written == int((~np.isnan(vals)).sum())
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


@pytest.fixture(scope="module",
                params=["float32", "float64", "float32-device"])
def served(request):
    """(the TSD, its values, the tolerance) in the configuration's
    precision and in the suite's, and in the configuration's with the
    tails placed as the chip's are; x64 is set for every thread (the
    server answers on its workers) and put back afterwards."""
    was = jax.config.read("jax_enable_x64")
    jax.config.update("jax_enable_x64", request.param == "float64")
    vals = _values(seed=34)
    tsd = Tsd(vals, on_device=request.param.endswith("-device"))
    # what a rank program of this server has to say of itself
    tsd.rank_method = "select" if request.param == "float32-device" \
        else "sort"
    yield tsd, vals, 1e-9 if request.param == "float64" else RANK_ATOL
    tsd.stop()
    jax.config.update("jax_enable_x64", was)


def _query(agg: str, downsample: str = "5m-avg") -> dict:
    return {"start": T0 * 1000, "end": END * 1000, "queries": [{
        "metric": METRIC, "aggregator": agg, "downsample": downsample,
        "filters": [
            {"type": "wildcard", "tagk": "dc", "filter": "*",
             "groupBy": True},
            {"type": "not_literal_or", "tagk": "rack",
             "filter": f"r{SKIP_RACK}", "groupBy": False}]}]}


@pytest.mark.parametrize("agg", ["p50", "p95", "p99", "p999"])
def test_a_percentile_by_dc_is_the_oracles(served, agg):
    tsd, vals, tol = served
    rows, _headers = tsd.ask("POST", "/api/query", _query(agg))
    got = {r["tags"]["dc"]: {int(t): v for t, v in r["dps"].items()}
           for r in rows}
    assert sorted(got) == [f"dc{d}" for d in range(len(DC_SIZES))]
    dc, rack = _dc_of(), _rack_of()
    ts_ms = (T0 + np.arange(POINTS) * CADENCE) * 1000
    for d, size in enumerate(DC_SIZES):
        members = []
        for i in np.flatnonzero((dc == d) & (rack != SKIP_RACK)):
            keep = ~np.isnan(vals[i])
            members.append((ts_ms[keep], vals[i][keep]))
        # the excluded rack thins every group but the two smallest
        assert len(members) == size if size < 3 \
            else 0 < len(members) < size
        want = run_oracle(members, agg, 300_000, "avg", T0 * 1000,
                          END * 1000)
        mine = got[f"dc{d}"]
        assert set(mine) == {t // 1000 for t in want} and want
        for t, v in want.items():
            assert abs(mine[t // 1000] - v) <= tol, (agg, d, t)
        if size <= 2:
            # h = p (n + 1) >= n from p95 upwards: the maximum
            top = run_oracle(members, "max", 300_000, "avg",
                             T0 * 1000, END * 1000)
            assert (want == top) == (agg != "p50" or size == 1)


def _counted(tsd, metric: str, tag: str) -> dict:
    rows, _ = tsd.ask("GET", "/api/stats")
    out: dict = {}
    for r in rows:
        if r["metric"] == metric:
            out[r["tags"][tag]] = out.get(r["tags"][tag], 0) + r["value"]
    return out


def _program_of(tsd, agg: str, downsample: str = "5m-avg") -> dict:
    """The ``query.program`` span of one request for ``agg``."""
    _rows, headers = tsd.ask("POST", "/api/query",
                             _query(agg, downsample))
    doc, _ = tsd.ask("GET", "/api/trace/" + headers["X-TSD-Trace-Id"])
    (root,) = doc["tree"]

    def nodes(node, name):
        found = [node] if node["name"] == name else []
        for child in node.get("children", ()):
            found += nodes(child, name)
        return found

    (program,) = nodes(root, "query.program")
    return program


@pytest.mark.parametrize("agg, cls", [("p95", "rank"), ("sum", "linear")])
def test_a_request_names_its_class_of_group_stage(served, agg, cls):
    tsd, _vals, _tol = served
    before = {"rank": 0, "linear": 0,
              **_counted(tsd, "tsd.query.tail", "class")}
    program = _program_of(tsd, agg)
    assert program["tags"]["class"] == cls
    assert program["tags"]["path"] == "grid"
    assert program["tags"]["placement"] == (
        "device" if tsd.rank_method == "select" else "host")
    after = _counted(tsd, "tsd.query.tail", "class")
    other = "linear" if cls == "rank" else "rank"
    assert after[cls] == before[cls] + 1
    assert after.get(other, 0) == before[other]


@pytest.mark.parametrize("agg", ["p99", "median", "ep95r7", "sum"])
def test_a_rank_program_says_how_it_read_its_ranks(served, agg):
    """``rank=select`` where the group stage counted (float32 on the
    device's branch), ``rank=sort`` where it sorted (a host-placed
    tail; float64), no tag on a linear program; the counter moves by
    one a sub-query, the other method's not at all."""
    tsd, _vals, _tol = served
    before = _counted(tsd, "tsd.query.rank", "method")
    assert set(before) == {"select", "sort"}
    program = _program_of(tsd, agg)
    after = _counted(tsd, "tsd.query.rank", "method")
    if agg == "sum":
        assert "rank" not in program["tags"]
        assert after == before
        return
    assert program["tags"]["rank"] == tsd.rank_method
    other = "sort" if tsd.rank_method == "select" else "select"
    assert after[tsd.rank_method] == before[tsd.rank_method] + 1
    assert after[other] == before[other]


@pytest.mark.parametrize("downsample, buckets, form", [
    ("5m-avg", 12, "unrolled"), ("5s-avg", 768, "loop")])
def test_a_program_says_which_form_its_carry_takes(served, downsample,
                                                   buckets, form):
    """An hour in 12 buckets sweeps with every step written out; in
    720 (padded to 768) it runs the steps as a loop:
    ``ops.interp.carry_form`` of the padded bucket count, the
    predicate the jitted fill applies. The counter moves by one a
    program, the other form's not at all."""
    from opentsdb_tpu.ops.interp import carry_form
    tsd, _vals, _tol = served
    before = _counted(tsd, "tsd.query.carry", "form")
    assert set(before) == {"unrolled", "loop"}
    program = _program_of(tsd, "sum", downsample)
    assert program["tags"]["shape"].split("x")[1] == str(buckets)
    assert program["tags"]["carry"] == form == carry_form(buckets)
    after = _counted(tsd, "tsd.query.carry", "form")
    other = "loop" if form == "unrolled" else "unrolled"
    assert after[form] == before[form] + 1
    assert after[other] == before[other]
