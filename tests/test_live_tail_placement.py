"""The live dashboard's request wherever its tail runs (PR 32).

BASELINE config 2's request as the benchmark's live cells send it
(``sum:1m-avg`` and ``max:1m-avg`` in one request, grouped by ``dc``,
one rack left out, a tenth of the series gappy), at a series count a
test can hold, answered with the tail placed by the default rule (the
crossover measured on the chip: 65,536 padded cells) and with the rule
switched off, in the configuration's float32. Every answer against the
independent oracle (``tests/oracle.py``) inside the limits
``benchmark/configs/live-100k.json`` states, and each sub-query's
``query.program`` span tagged with where it ran. CPU only: "the
device" is JAX's default CPU device, the host tail the one pinned by
``PipelineSpec.host`` (segment lowering).
"""

from __future__ import annotations

import json
import os

import jax
import numpy as np
import pytest

from opentsdb_tpu import TSDB, Config
from opentsdb_tpu.tsd.http_api import HttpRequest, HttpRpcRouter

from oracle import run_oracle

T0 = 1356998400
CADENCE, POINTS = 30, 120          # an hour: 60 buckets of a minute
END = T0 + POINTS * CADENCE - 1
DCS, RACKS = 5, 40
METRIC = "live.load"
with open(os.path.join(os.path.dirname(__file__), "..", "benchmark",
                       "configs", "live-100k.json"),
          encoding="utf-8") as _fh:
    LIMITS = json.load(_fh)["limits"]
DEVICE_EVERYWHERE = {"tsd.query.host_tail_max_cells_linear": "-1"}


@pytest.fixture
def float32():
    """x64 off for every thread (the sub-queries fan out), as the
    configuration runs; the suite's own setting back afterwards."""
    was = jax.config.read("jax_enable_x64")
    jax.config.update("jax_enable_x64", False)
    yield
    jax.config.update("jax_enable_x64", was)


def _values(series: int, seed: int) -> np.ndarray:
    """[series, points] in cents between 1,000.00 and 10,000.00; a
    tenth of the series gappy (single points and blocks of six)."""
    rng = np.random.default_rng(seed)
    vals = rng.integers(100000, 1000000, (series, POINTS)) / 100.0
    gappy = np.flatnonzero(rng.random(series) < 0.1)
    for i in gappy:
        vals[i, rng.random(POINTS) < 0.02] = np.nan
        at = int(rng.integers(0, POINTS - 6))
        vals[i, at:at + 6] = np.nan
    return vals


def _load(tsdb: TSDB, vals: np.ndarray) -> None:
    lines = []
    for i in range(len(vals)):
        tags = f"host=h{i:05d} dc=dc{i % DCS} rack=r{i % RACKS}"
        for j in np.flatnonzero(~np.isnan(vals[i])).tolist():
            lines.append(f"{METRIC} {T0 + j * CADENCE} "
                         f"{vals[i, j]:.2f} {tags}\n")
    written, errors = tsdb.import_buffer("".join(lines).encode(),
                                         durable=False)
    assert not errors and written == int((~np.isnan(vals)).sum())


def _sub(agg: str, skip_rack: int) -> dict:
    return {"metric": METRIC, "aggregator": agg, "downsample": "1m-avg",
            "filters": [
                {"type": "wildcard", "tagk": "dc", "filter": "*",
                 "groupBy": True},
                {"type": "not_literal_or", "tagk": "rack",
                 "filter": f"r{skip_rack}", "groupBy": False}]}


def _ask(router, method: str, path: str, body: bytes = b""):
    return router.handle(HttpRequest(method=method, path=path,
                                     params={}, headers={}, body=body))


def _nodes(node: dict, name: str) -> list[dict]:
    found = [node] if node["name"] == name else []
    for child in node.get("children", ()):
        found += _nodes(child, name)
    return found


def _check(rows: list, agg: str, vals: np.ndarray,
           skip_rack: int) -> None:
    got = {r["tags"]["dc"]: {int(t): v for t, v in r["dps"].items()}
           for r in rows}
    assert sorted(got) == [f"dc{d}" for d in range(DCS)]
    ts_ms = (T0 + np.arange(POINTS) * CADENCE) * 1000
    for dc in range(DCS):
        members = []
        for i in range(dc, len(vals), DCS):
            if i % RACKS == skip_rack:
                continue
            keep = ~np.isnan(vals[i])
            members.append((ts_ms[keep], vals[i][keep]))
        want = run_oracle(members, agg, 60_000, "avg", T0 * 1000,
                          END * 1000)
        mine = got[f"dc{dc}"]
        assert set(mine) == {t // 1000 for t in want}
        for t, v in want.items():
            if agg == "sum":
                # the benchmark's sum_rel_err: beyond the value's own
                # rounding, over the sum of the members' magnitudes
                # (all positive here, so the sum itself)
                tol = LIMITS["sum_rtol"] * abs(v) + LIMITS["value_atol"]
            else:
                tol = LIMITS["rank_atol"]
            assert abs(mine[t // 1000] - v) <= tol, (agg, dc, t)


@pytest.mark.parametrize("series, flags, placement, shape", [
    # 975 selected series -> 1,024 x 64 padded cells: at the crossover,
    # the last class the host wins
    (1000, {}, "host", "1024x64x8"),
    (1000, DEVICE_EVERYWHERE, "device", "1024x64x8"),
    # 1,170 selected -> 1,280 x 64: the chip's by the default rule, as
    # the live cells' 114,688 x 64 is
    (1200, {}, "device", "1280x64x8"),
], ids=["crossover-default", "crossover-forced", "above-default"])
def test_the_live_request_is_exact_wherever_its_tail_runs(
        float32, series, flags, placement, shape):
    tsdb = TSDB(Config(**{
        "tsd.core.auto_create_metrics": "true",
        "tsd.tpu.warmup": "false", "tsd.trace.sample": "1",
        "tsd.query.cache.enable": "false", **flags}))
    router = HttpRpcRouter(tsdb)
    try:
        vals = _values(series, seed=series)
        _load(tsdb, vals)
        skip_rack = 7
        body = json.dumps({
            "start": T0 * 1000, "end": END * 1000,
            "queries": [_sub("sum", skip_rack),
                        _sub("max", skip_rack)]}).encode()
        resp = _ask(router, "POST", "/api/query", body)
        assert resp.status == 200, resp.body[:300]
        rows = json.loads(resp.body)
        assert len(rows) == 2 * DCS
        _check(rows[:DCS], "sum", vals, skip_rack)
        _check(rows[DCS:], "max", vals, skip_rack)
        (root,) = json.loads(_ask(
            router, "GET", "/api/trace/"
            + resp.headers["X-TSD-Trace-Id"]).body)["tree"]
        programs = _nodes(root, "query.program")
        # on the chip both run over the metric's resident grid, which
        # the first puts together from per-bucket columns (PR 50); the
        # second finds the columns, or the grid the first's program
        # has assembled from them by then
        paths = [p["tags"]["path"] for p in programs]
        assert paths in ([["grid"] * 2] if placement == "host" else
                         [["columns"] * 2, ["columns", "grid"],
                          ["grid", "columns"]])
        assert [(p["tags"]["placement"], p["tags"]["shape"])
                for p in programs] == [(placement, shape)] * 2
        # float32: a cell of four bytes and a byte of mask
        for build in _nodes(root, "query.grid_build"):
            if "fused" in build["tags"]:
                assert build["tags"]["bytes"] \
                    == build["tags"]["cells"] * 5
        tails = {(r["tags"]["path"], r["tags"]["placement"]): r["value"]
                 for r in json.loads(
                     _ask(router, "GET", "/api/stats").body)
                 if r["metric"] == "tsd.query.tail"}
        assert tails == {(path, placement): paths.count(path)
                         for path in set(paths)}
        # a device-placed tail goes through the HBM grid cache, a
        # host-placed one never does
        cache = tsdb.device_grid_cache
        assert (cache.hits + cache.misses > 0) == (placement == "device")
    finally:
        tsdb.shutdown()
