"""The store that is written while it is read (PR 31): a TSD served
on a real socket, writer threads posting ``/api/put`` bodies at the
head of the store while a reader sends the dashboard's group-by, every
answer against the independent oracle (``tests/oracle.py``).

What the deployment ``live-100k-ingest`` promises, at a size a test
can hold: answers over the loaded history are exact whatever has been
acknowledged when they are computed; every acknowledged point is in a
later answer; a body answered 204 is in the WAL before the answer (a
store dropped without a close gives every acknowledged point back);
and a put body is one ``ingest.put`` root whose stages and counters
the benchmark's per-layer readers take their numbers from.
"""

from __future__ import annotations

import asyncio
import http.client
import json
import shutil
import threading

import numpy as np
import pytest

from opentsdb_tpu import TSDB, Config

from oracle import run_oracle

T0 = 1356998400
CADENCE = 10
POINTS = 36                        # six minutes of history
END = T0 + POINTS * CADENCE - 1    # the queries' range ends here
HEAD = END + 1                     # the writers' first timestamp
SERIES, DCS, RACKS = 240, 6, 40
PER_BODY = 60
METRIC = "live.load"


def _tags(i: int) -> dict:
    return {"host": f"h{i:04d}", "dc": f"dc{i % DCS}",
            "rack": f"r{i % RACKS}"}


def _history(seed: int) -> np.ndarray:
    """[series, points] values in cents, a point in 50 dropped."""
    rng = np.random.default_rng(seed)
    vals = rng.integers(1000, 100000, size=(SERIES, POINTS)) / 100.0
    vals[rng.random((SERIES, POINTS)) < 0.02] = np.nan
    return vals


def _tsdb(data_dir, **extra) -> TSDB:
    return TSDB(Config(**{
        "tsd.core.auto_create_metrics": "true",
        "tsd.tpu.warmup": "false",
        "tsd.trace.sample": "1",
        "tsd.storage.data_dir": str(data_dir), **extra}))


class Served:
    """A TSD on an ephemeral port, its loop in a thread of its own."""

    def __init__(self, tsdb: TSDB):
        from opentsdb_tpu.tsd.server import TSDServer
        self.tsdb = tsdb
        self.server = TSDServer(tsdb, host="127.0.0.1", port=0)
        self.loop = asyncio.new_event_loop()
        started = threading.Event()

        async def run():
            await self.server.start()
            started.set()
            await self.server.serve_forever()

        self.thread = threading.Thread(
            target=lambda: self.loop.run_until_complete(run()),
            daemon=True)
        self.thread.start()
        assert started.wait(30)
        self.port = self.server._server.sockets[0].getsockname()[1]

    def connect(self) -> http.client.HTTPConnection:
        return http.client.HTTPConnection("127.0.0.1", self.port,
                                          timeout=60)

    def stop(self) -> None:
        self.loop.call_soon_threadsafe(self.server.request_shutdown)
        self.thread.join(timeout=30)
        self.loop.close()


def _exchange(conn, method: str, path: str, doc=None):
    body = json.dumps(doc).encode() if doc is not None else None
    conn.request(method, path, body=body,
                 headers={"Content-Type": "application/json"})
    resp = conn.getresponse()
    return resp.status, dict(resp.getheaders()), resp.read()


@pytest.fixture
def served(tmp_path):
    s = Served(_tsdb(tmp_path / "data"))
    yield s
    s.stop()
    s.tsdb.shutdown()


def _load(tsdb: TSDB, vals: np.ndarray) -> None:
    """The history, bulk-loaded without a WAL as the benchmark's
    loader does."""
    lines = []
    for i in range(SERIES):
        tags = " ".join(f"{k}={v}" for k, v in _tags(i).items())
        for j in np.flatnonzero(~np.isnan(vals[i])).tolist():
            lines.append(f"{METRIC} {T0 + j * CADENCE} "
                         f"{vals[i, j]:.2f} {tags}\n")
    written, errors = tsdb.import_buffer("".join(lines).encode(),
                                         durable=False)
    assert not errors and written == int((~np.isnan(vals)).sum())


def _body(block: int, step: int, cents: np.ndarray) -> list[dict]:
    ids = range(block * PER_BODY, (block + 1) * PER_BODY)
    return [{"metric": METRIC, "timestamp": HEAD + step * CADENCE,
             "value": int(c) / 100.0, "tags": _tags(i)}
            for i, c in zip(ids, cents)]


def _group_by(agg: str, ds: str, skip_rack: int | None) -> dict:
    filters = [{"type": "wildcard", "tagk": "dc", "filter": "*",
                "groupBy": True}]
    if skip_rack is not None:
        filters.append({"type": "not_literal_or", "tagk": "rack",
                        "filter": f"r{skip_rack}", "groupBy": False})
    return {"metric": METRIC, "aggregator": agg, "downsample": ds,
            "filters": filters}


def _check(rows: list, sub: dict, vals: np.ndarray, start: int,
           end: int, interval_s: int, skip_rack, cadence: int = CADENCE,
           **rate) -> None:
    """One sub-query's rows against the oracle over ``vals``
    ([series, steps] from ``start`` at the cadence, NaN where absent);
    ``skip_rack`` a rack or several left out; ``rate``: the oracle's
    ``rate_kwargs`` of a sub-query that asks a rate."""
    got = {r["tags"]["dc"]: {int(t): v for t, v in r["dps"].items()}
           for r in rows}
    ts_ms = (start + np.arange(vals.shape[1]) * cadence) * 1000
    skipped = () if skip_rack is None else np.atleast_1d(skip_rack)
    want_groups = 0
    for dc in range(DCS):
        members = []
        for i in range(dc, SERIES, DCS):
            if i % RACKS in skipped:
                continue
            keep = ~np.isnan(vals[i])
            if keep.any():
                members.append((ts_ms[keep], vals[i][keep]))
        want = run_oracle(members, sub["aggregator"],
                          interval_s * 1000, "avg", start * 1000,
                          end * 1000, rate=bool(rate),
                          rate_kwargs=rate) if members else {}
        want = {t // 1000: v for t, v in want.items()
                if not np.isnan(v)}
        if not want:
            assert f"dc{dc}" not in got
            continue
        want_groups += 1
        mine = got[f"dc{dc}"]
        assert set(mine) == set(want), (dc, sorted(
            set(mine) ^ set(want))[:5])
        for t, v in want.items():
            assert mine[t] == pytest.approx(v, rel=1e-9, abs=1e-9), \
                (sub["aggregator"], dc, t)
    assert len(got) == want_groups


def _ask_history(conn, vals: np.ndarray, skip_rack: int) -> None:
    """The benchmark's request: sum and max in one, a rack left out."""
    subs = [_group_by(agg, "1m-avg", skip_rack)
            for agg in ("sum", "max")]
    status, _h, raw = _exchange(conn, "POST", "/api/query", {
        "start": T0 * 1000, "end": END * 1000, "queries": subs})
    assert status == 200, raw[:300]
    rows = json.loads(raw)
    by_agg = {"sum": rows[:len(rows) // 2], "max": rows[len(rows) // 2:]}
    for sub in subs:
        _check(by_agg[sub["aggregator"]], sub, vals, T0, END, 60,
               skip_rack)


def _stats(conn) -> dict:
    _s, _h, raw = _exchange(conn, "GET", "/api/stats/raw")
    doc = json.loads(raw)
    out = {}
    for r in doc["records"]:
        out[r["metric"]] = out.get(r["metric"], 0) + r["value"]
    for h in doc["histograms"]:
        if h["name"] == "tsd_stage_latency_ms":
            out["stage:" + h["labels"]["stage"]] = h["count"]
    return out


@pytest.mark.parametrize("writers", [1, 4])
def test_answers_stay_exact_and_acknowledged_points_are_read_back(
        served, writers):
    vals = _history(seed=writers)
    _load(served.tsdb, vals)
    reader = served.connect()
    _ask_history(reader, vals, skip_rack=0)      # compiles the shapes
    before = _stats(reader)
    blocks = SERIES // PER_BODY
    min_steps, max_steps, want_answers = 4, 400, 3
    rng = np.random.default_rng(7)
    cents = rng.integers(1000, 100000,
                         size=(max_steps, blocks, PER_BODY))
    acked: list[tuple[int, int]] = []         # (block, step), any order
    answers = [0]
    failures: list[str] = []
    go = threading.Barrier(writers + 1)

    def writer(k: int) -> None:
        conn = served.connect()
        try:
            go.wait(30)
            step = 0
            while step < min_steps or (answers[0] < want_answers
                                       and step < max_steps):
                for block in range(k, blocks, writers):
                    status, _h, raw = _exchange(
                        conn, "POST", "/api/put",
                        _body(block, step, cents[step, block]))
                    if status != 204:
                        failures.append(f"{status}: {raw[:200]!r}")
                        return
                    acked.append((block, step))
                step += 1
        except Exception as e:  # noqa: BLE001 - reported by the test
            failures.append(repr(e))
        finally:
            conn.close()

    threads = [threading.Thread(target=writer, args=(k,))
               for k in range(writers)]
    for th in threads:
        th.start()
    go.wait(30)
    rack = 1
    while any(th.is_alive() for th in threads):
        # exact over the loaded history, whatever has been
        # acknowledged when the answer is computed
        _ask_history(reader, vals, skip_rack=rack % RACKS)
        rack += 1
        if any(th.is_alive() for th in threads):
            answers[0] += 1
    for th in threads:
        th.join(60)
    assert not failures, failures[:3]
    assert answers[0] >= want_answers, \
        "no answer was computed while the writers appended"
    _ask_history(reader, vals, skip_rack=rack % RACKS)
    # the written span, asked again: the oracle over what was
    # acknowledged, and nothing else
    steps = max(step for _b, step in acked) + 1
    assert len(set(acked)) == len(acked) >= min_steps * blocks
    written = np.full((SERIES, steps), np.nan)
    for block, step in acked:
        written[block * PER_BODY:(block + 1) * PER_BODY, step] = \
            cents[step, block] / 100.0
    last = HEAD + steps * CADENCE - 1
    for agg in ("sum", "max"):
        sub = _group_by(agg, f"{CADENCE}s-avg", None)
        status, _h, raw = _exchange(reader, "POST", "/api/query", {
            "start": HEAD * 1000, "end": last * 1000,
            "queries": [sub]})
        assert status == 200, raw[:300]
        _check(json.loads(raw), sub, written, HEAD, last, CADENCE,
               None)
    # the counters the benchmark reads moved by what was sent
    after = _stats(reader)
    assert after["tsd.datapoints.added"] \
        - before["tsd.datapoints.added"] == len(acked) * PER_BODY
    assert after["stage:ingest.put"] \
        - before.get("stage:ingest.put", 0) == len(acked)
    syncs = after["tsd.wal.group_syncs"] \
        - before.get("tsd.wal.group_syncs", 0)
    assert 1 <= syncs <= len(acked)
    assert after["tsd.storage.series.count"] \
        == before["tsd.storage.series.count"]    # appends only
    reader.close()


# -- the 1M-shaped cell at small size (fleet-1m-live.wide-ingest) -----

FLEET_CADENCE = 60
FLEET_POINTS = 60                       # the hour
FLEET_END = T0 + FLEET_POINTS * FLEET_CADENCE - 1
FLEET_HEAD = FLEET_END + 1
FLEET_PER_BODY = 20


def _fleet_request(racks: tuple) -> dict:
    """The north-star request: two racks left out, so every request
    is distinct."""
    sub = _group_by("sum", "5m-avg", None)
    sub["filters"].append({
        "type": "not_literal_or", "tagk": "rack",
        "filter": "|".join(f"r{r}" for r in racks), "groupBy": False})
    sub.update(rate=True, rateOptions={"counter": True,
                                       "counterMax": 10000})
    return sub


def _ask_fleet(conn, vals: np.ndarray, racks: tuple) -> None:
    sub = _fleet_request(racks)
    status, _h, raw = _exchange(conn, "POST", "/api/query", {
        "start": T0 * 1000, "end": FLEET_END * 1000, "queries": [sub]})
    assert status == 200, raw[:300]
    _check(json.loads(raw), sub, vals, T0, FLEET_END, 300, racks,
           FLEET_CADENCE, counter=True, counter_max=10000)


@pytest.mark.parametrize("backend", ["native", "memory"])
def test_head_writes_beside_the_north_star_request_keep_its_grid(
        tmp_path, backend):
    """``fleet-1m-live.wide-ingest`` at small size: the hour of a
    counter a minute apart, ``sum:5m-avg:rate`` by dc with two racks
    left out, asked back to back while four writers post bodies of one
    new point a series at the head of the store, a second after the
    window's end. Every answer is the oracle's, every acknowledged
    point is read back, and behind the first request no write costs a
    reader its window: the metric's grid is built once (PR 51: the
    store says where the writes since the entry's version landed)."""
    s = Served(_tsdb(tmp_path / "data", **{
        "tsd.storage.backend": backend,
        # the small grid's tail on the device's branch, as at 1M
        "tsd.query.host_tail_max_cells_linear": "-1"}))
    try:
        rng = np.random.default_rng(51)
        vals = rng.integers(1000, 999900,
                            size=(SERIES, FLEET_POINTS)) / 100.0
        vals[rng.random(vals.shape) < 0.02] = np.nan
        lines = []
        for i in range(SERIES):
            tags = " ".join(f"{k}={v}" for k, v in _tags(i).items())
            for j in np.flatnonzero(~np.isnan(vals[i])).tolist():
                lines.append(f"{METRIC} {T0 + j * FLEET_CADENCE} "
                             f"{vals[i, j]:.2f} {tags}\n")
        written, errors = s.tsdb.import_buffer("".join(lines).encode(),
                                               durable=False)
        assert not errors and written == int((~np.isnan(vals)).sum())
        reader = s.connect()
        _ask_fleet(reader, vals, (0, 1))      # builds the grid, once
        cache = s.tsdb.device_grid_cache
        built = cache.misses
        writers, blocks = 4, SERIES // FLEET_PER_BODY
        cents = rng.integers(1000, 999900, size=(blocks,
                                                 FLEET_PER_BODY))
        acked: list[int] = []
        failures: list[str] = []
        answers = [0]
        go = threading.Barrier(writers + 1)

        def writer(k: int) -> None:
            conn = s.connect()
            try:
                go.wait(30)
                for block in range(k, blocks, writers):
                    ids = range(block * FLEET_PER_BODY,
                                (block + 1) * FLEET_PER_BODY)
                    status, _h, raw = _exchange(conn, "POST", "/api/put", [
                        {"metric": METRIC, "timestamp": FLEET_HEAD,
                         "value": int(c) / 100.0, "tags": _tags(i)}
                        for i, c in zip(ids, cents[block])])
                    if status != 204:
                        failures.append(f"{status}: {raw[:200]!r}")
                        return
                    acked.append(block)
                    # a request between two bodies of this writer
                    seen = answers[0]
                    while answers[0] == seen and block + writers \
                            < blocks and not failures:
                        threading.Event().wait(0.002)
            except Exception as e:  # noqa: BLE001 - reported below
                failures.append(repr(e))
            finally:
                conn.close()

        threads = [threading.Thread(target=writer, args=(k,))
                   for k in range(writers)]
        for th in threads:
            th.start()
        go.wait(30)
        pair = 0
        try:
            while any(th.is_alive() for th in threads):
                pair += 1
                _ask_fleet(reader, vals, (pair % RACKS,
                                          (pair * 7 + 3) % RACKS))
                answers[0] += 1
        except BaseException as e:
            failures.append(repr(e))
            raise
        finally:
            for th in threads:
                th.join(60)
        assert not failures, failures[:3]
        assert sorted(acked) == list(range(blocks))
        assert answers[0] >= blocks // writers - 1
        _ask_fleet(reader, vals, (2, 9))
        # no look-up missed after the first request's: every one that
        # met a newer version kept the window's grid
        assert cache.misses == built
        assert cache.stale_dropped == 0
        assert cache.stale_kept >= blocks // writers - 1
        # every acknowledged point, read back
        sub = _group_by("sum", f"{FLEET_CADENCE}s-avg", None)
        status, _h, raw = _exchange(reader, "POST", "/api/query", {
            "start": FLEET_HEAD * 1000,
            "end": (FLEET_HEAD + FLEET_CADENCE - 1) * 1000,
            "queries": [sub]})
        assert status == 200, raw[:300]
        _check(json.loads(raw), sub, (cents / 100.0).reshape(-1, 1),
               FLEET_HEAD, FLEET_HEAD + FLEET_CADENCE - 1,
               FLEET_CADENCE, None, FLEET_CADENCE)
        reader.close()
    finally:
        s.stop()
        s.tsdb.shutdown()


def _reopened_points(copy_dir) -> dict:
    """{(host, ts): value} of a store opened on a copy of a data
    directory whose owner never closed it."""
    t = _tsdb(copy_dir)
    try:
        from opentsdb_tpu.query.model import TSQuery
        q = TSQuery.from_json({
            "start": HEAD * 1000, "end": (HEAD + 3600) * 1000,
            "queries": [{"metric": METRIC, "aggregator": "none"}]
        }).validate()
        out = {}
        for r in t.execute_query(q):
            for ts, v in r.dps:
                out[(r.tags["host"], int(ts) // 1000)] = float(v)
        return out
    finally:
        t.shutdown()


@pytest.mark.parametrize("writers", [1, 3])
def test_an_acknowledged_body_is_in_the_wal_of_a_store_never_closed(
        served, tmp_path, writers):
    """204 means durable: the data directory is copied the instant a
    body is acknowledged, while the store stays open, and a store
    opened on the copy holds every point acknowledged until then."""
    rng = np.random.default_rng(11)
    steps = 3
    cents = rng.integers(1000, 100000,
                         size=(steps, writers, PER_BODY))
    assert served.tsdb.wal is not None
    assert served.tsdb.config.get_string(
        "tsd.storage.wal.fsync", "always") == "always"
    sent: dict = {}
    lock = threading.Lock()
    for step in range(steps):
        go = threading.Barrier(writers)
        failures: list[str] = []

        def post(k: int) -> None:
            conn = served.connect()
            try:
                body = _body(k, step, cents[step, k])
                go.wait(30)
                status, _h, raw = _exchange(conn, "POST", "/api/put",
                                            body)
                if status != 204:
                    failures.append(f"{status}: {raw[:200]!r}")
                    return
                with lock:
                    for dp in body:
                        sent[(dp["tags"]["host"], dp["timestamp"])] \
                            = dp["value"]
            finally:
                conn.close()

        threads = [threading.Thread(target=post, args=(k,))
                   for k in range(writers)]
        for th in threads:
            th.start()
        for th in threads:
            th.join(60)
        assert not failures, failures
        # no close, no flush of ours: what a kill would leave
        copy = tmp_path / f"copy{step}"
        shutil.copytree(tmp_path / "data", copy)
        assert _reopened_points(copy) == sent
    assert len(sent) == steps * writers * PER_BODY


def _walk(node: dict):
    yield node
    for child in node.get("children", ()):
        yield from _walk(child)


PUTS = [
    # (bodies in order as (hosts, points a host), new series each makes)
    pytest.param([(5, 1)], [5], id="five-new-series"),
    pytest.param([(5, 1), (5, 1)], [5, 0], id="again-no-new-series"),
    pytest.param([(3, 4), (6, 2)], [3, 3], id="several-points-a-series"),
]


@pytest.mark.parametrize("bodies, new_series", PUTS)
def test_a_put_body_is_one_root_with_its_stages_and_counters(
        served, bodies, new_series):
    conn = served.connect()
    ts = HEAD
    for (hosts, per_host), made in zip(bodies, new_series):
        doc = []
        for i in range(hosts):
            for j in range(per_host):
                doc.append({"metric": METRIC,
                            "timestamp": ts + j * CADENCE,
                            "value": i + j / 100.0, "tags": _tags(i)})
        ts += per_host * CADENCE
        before = _stats(conn)
        status, headers, raw = _exchange(conn, "POST", "/api/put", doc)
        assert status == 204 and raw == b""
        after = _stats(conn)
        moved = {k: after.get(k, 0) - before.get(k, 0) for k in (
            "tsd.datapoints.added", "tsd.wal.group_syncs",
            "tsd.storage.series.count",
            "stage:ingest.put", "stage:ingest.decode",
            "stage:store.scatter", "stage:wal.commit_wait")}
        assert moved == {
            "tsd.datapoints.added": hosts * per_host,
            "tsd.wal.group_syncs": 1,       # one fsync a body
            "tsd.storage.series.count": made,
            "stage:ingest.put": 1, "stage:ingest.decode": 1,
            "stage:store.scatter": 1, "stage:wal.commit_wait": 1}
        _s, _h, raw = _exchange(
            conn, "GET", "/api/trace/" + headers["X-TSD-Trace-Id"])
        root = json.loads(raw)["tree"][0]
        assert root["name"] == "ingest.put"
        under = {n["name"]: n for n in _walk(root) if n is not root}
        assert {"ingest.decode", "store.scatter",
                "wal.commit_wait"} <= set(under)
        assert under["ingest.decode"]["tags"]["points"] \
            == hosts * per_host
        assert under["store.scatter"]["tags"]["groups"] == hosts
    conn.close()
