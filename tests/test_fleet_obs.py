"""Fleet observability battery (``-m obs``).

- OpenMetrics exposition: ``GET /metrics`` parses under a STRICT
  in-test OpenMetrics parser (HELP/TYPE before samples, label
  escaping round-trip, cumulative bucket monotonicity, counter
  ``_total`` suffixes, terminal ``# EOF``)
- histogram bucket-merge property: fleet merge over split
  observations == a single-node oracle holding the concatenation,
  percentiles BIT-equal
- continuous sampling profiler: per-role folded stacks, the
  ``/api/profile`` collapsed/json surfaces, thread provably joined on
  shutdown (this module runs under BOTH runtime witnesses)
- SLO burn-rate: objective math, the /api/health ``slo`` section and
  the ``tsd_slo_burn_rate`` gauges at /metrics
- query-shape read surface: ``GET /api/stats/query_shapes`` top-N
  mined from query_shapes.jsonl
- fleet aggregation on a LIVE 2-shard cluster: counters sum,
  histograms bucket-sum exactly (vs a local merge of the per-shard
  raw snapshots), dead shard => 200 with degraded marker + survivor-
  only counters, ``/api/cluster/status`` progress doc, router
  ``/api/health`` fleet section
- dirty-debt AGE: a week-old divergence is distinguishable from a
  seconds-old blip
"""

from __future__ import annotations

import asyncio
import json
import re
import threading
import time

import numpy as np
import pytest

from opentsdb_tpu import TSDB, Config
from opentsdb_tpu.cluster.replica import DirtyTracker
from opentsdb_tpu.obs.slo import SloTracker
from opentsdb_tpu.stats.stats import (Histogram,
                                      merge_histogram_snapshots,
                                      percentiles_from_buckets)
from opentsdb_tpu.tsd.http_api import HttpRequest, HttpRpcRouter

pytestmark = pytest.mark.obs

BASE = 1356998400
BASE_MS = BASE * 1000


@pytest.fixture(autouse=True, scope="module")
def _witnessed(lock_witness, leak_witness):
    """Profiler + fleet scatter threads run under BOTH witnesses:
    lock-order cycles and leaked threads/fds fail the module at
    teardown with allocation stacks."""
    return lock_witness


def mk_tsdb(**cfg):
    return TSDB(Config(**{
        "tsd.core.auto_create_metrics": "true",
        "tsd.storage.backend": "memory",
        "tsd.tpu.warmup": "false",
        **cfg,
    }))


def req(method, path, body=None, **params):
    return HttpRequest(
        method=method, path=path,
        params={k: [str(v)] for k, v in params.items()},
        body=json.dumps(body).encode() if body is not None else b"")


def put_body(metric="sys.fleet", n=10, host="a"):
    return [{"metric": metric, "timestamp": BASE + i, "value": i,
             "tags": {"host": host}} for i in range(n)]


def query_body(metric="sys.fleet", ds="10s-sum"):
    q = {"start": BASE_MS - 10_000, "end": BASE_MS + 600_000,
         "queries": [{"metric": metric, "aggregator": "sum"}]}
    if ds:
        q["queries"][0]["downsample"] = ds
    return q


# ---------------------------------------------------------------------------
# a strict OpenMetrics parser (the test's own, so the contract is
# checked against the spec, not against the renderer)
# ---------------------------------------------------------------------------

_SAMPLE_RE = re.compile(
    r"^([a-zA-Z_:][a-zA-Z0-9_:]*)(\{.*\})? (\S+)$")
_NAME_RE = re.compile(r"^[a-zA-Z_:][a-zA-Z0-9_:]*$")


def _parse_labels(raw: str) -> dict:
    """Parse `{k="v",...}` honoring \\\\, \\" and \\n escapes."""
    assert raw.startswith("{") and raw.endswith("}"), raw
    body = raw[1:-1]
    labels = {}
    i = 0
    while i < len(body):
        eq = body.index("=", i)
        key = body[i:eq]
        assert _NAME_RE.match(key), f"bad label name {key!r}"
        assert body[eq + 1] == '"', raw
        j = eq + 2
        val = []
        while True:
            c = body[j]
            if c == "\\":
                nxt = body[j + 1]
                assert nxt in ("\\", '"', "n"), \
                    f"bad escape \\{nxt} in {raw!r}"
                val.append({"\\": "\\", '"': '"', "n": "\n"}[nxt])
                j += 2
            elif c == '"':
                break
            else:
                assert c != "\n"
                val.append(c)
                j += 1
        labels[key] = "".join(val)
        i = j + 1
        if i < len(body):
            assert body[i] == ",", raw
            i += 1
    return labels


def parse_openmetrics(text: str) -> dict:
    """Validate + parse one exposition document. Returns
    {family: {"type": t, "samples": [(name, labels, value)]}}."""
    assert text.endswith("# EOF\n"), "missing # EOF terminator"
    families: dict = {}
    current = None
    declared: set = set()
    for line in text[:-len("# EOF\n")].splitlines():
        assert line, "blank line in exposition"
        if line.startswith("# HELP "):
            fam = line.split(" ", 3)[2]
            assert _NAME_RE.match(fam), fam
            assert fam not in declared, f"family {fam} re-declared"
            current = fam
            continue
        if line.startswith("# TYPE "):
            _, _, fam, kind = line.split(" ", 3)
            assert fam == current, \
                f"TYPE {fam} without adjacent HELP ({current})"
            assert kind in ("counter", "gauge", "histogram"), kind
            declared.add(fam)
            families[fam] = {"type": kind, "samples": []}
            continue
        assert not line.startswith("#"), f"stray comment {line!r}"
        m = _SAMPLE_RE.match(line)
        assert m, f"unparseable sample line {line!r}"
        name, raw_labels, raw_val = m.groups()
        labels = _parse_labels(raw_labels) if raw_labels else {}
        value = float(raw_val)
        # the sample must belong to the family being exposed
        fam = current
        assert fam is not None and fam in families, line
        kind = families[fam]["type"]
        if kind == "counter":
            assert name == fam + "_total", \
                f"counter sample {name} must end _total"
            assert value >= 0
        elif kind == "gauge":
            assert name == fam, line
        else:
            assert name in (fam + "_bucket", fam + "_sum",
                            fam + "_count"), line
        families[fam]["samples"].append((name, labels, value))
    # histogram family invariants: per label-subset, cumulative
    # monotone buckets, increasing le, +Inf == _count
    for fam, doc in families.items():
        if doc["type"] != "histogram":
            continue
        series: dict = {}
        for name, labels, value in doc["samples"]:
            key = tuple(sorted((k, v) for k, v in labels.items()
                               if k != "le"))
            series.setdefault(key, {"buckets": [], "sum": None,
                                    "count": None})
            if name.endswith("_bucket"):
                series[key]["buckets"].append((labels["le"], value))
            elif name.endswith("_sum"):
                series[key]["sum"] = value
            else:
                series[key]["count"] = value
        for key, s in series.items():
            assert s["buckets"], (fam, key)
            assert s["sum"] is not None and s["count"] is not None
            les = [le for le, _v in s["buckets"]]
            assert les[-1] == "+Inf", les
            bounds = [float(le) for le in les[:-1]]
            assert bounds == sorted(bounds) and \
                len(set(bounds)) == len(bounds), les
            counts = [v for _le, v in s["buckets"]]
            assert counts == sorted(counts), \
                f"non-monotone buckets {fam}{key}"
            assert counts[-1] == s["count"]
    return families


# ---------------------------------------------------------------------------
# OpenMetrics exposition
# ---------------------------------------------------------------------------

class TestOpenMetrics:
    def _served(self):
        tsdb = mk_tsdb()
        router = HttpRpcRouter(tsdb)
        r = router.handle(req("POST", "/api/put",
                              put_body(n=25)))
        assert r.status == 204, r.body
        r = router.handle(req("POST", "/api/query",
                              query_body()))
        assert r.status == 200, r.body
        # request-level histograms are fed by the socket server;
        # direct-handler tests feed them explicitly
        for ms in (0.4, 2.2, 7.9, 55.0, 900.0, 20000.0):
            tsdb.stats.latency_query.add(ms)
        tsdb.stats.latency_put.add(1.5)
        return tsdb, router

    def test_document_parses_strict(self):
        tsdb, router = self._served()
        try:
            resp = router.handle(req("GET", "/metrics"))
            assert resp.status == 200
            assert resp.content_type.startswith(
                "application/openmetrics-text")
            fams = parse_openmetrics(resp.body.decode())
            # counters, gauges and histograms all present
            assert fams["tsd_datapoints_added"]["type"] == "counter"
            total = [v for n, _l, v in
                     fams["tsd_datapoints_added"]["samples"]
                     if n.endswith("_total")]
            assert total == [25.0]
            assert fams["tsd_request_latency_ms"]["type"] \
                == "histogram"
            assert fams["tsd_uptime_seconds"]["type"] == "gauge"
            # SLO burn gauges rode the record stream
            assert fams["tsd_slo_burn_rate"]["type"] == "gauge"
        finally:
            tsdb.shutdown()

    def test_histogram_samples_are_exact(self):
        tsdb, router = self._served()
        try:
            fams = parse_openmetrics(router.handle(
                req("GET", "/metrics")).body.decode())
            doc = fams["tsd_request_latency_ms"]
            q = {le: v for (n, labels, v) in doc["samples"]
                 for le in [labels.get("le")]
                 if labels.get("op") == "query"
                 and n.endswith("_bucket")}
            # 6 query observations: 0.4 <= 1; 2.2 <= 3; 7.9 <= 8;
            # 55 <= 55... ladder has 55; 900 <= 1000; 20000 -> +Inf
            assert q["1"] == 1
            assert q["3"] == 2
            assert q["8"] == 3
            assert q["55"] == 4
            assert q["1000"] == 5
            assert q["+Inf"] == 6
            sums = [v for (n, labels, v) in doc["samples"]
                    if labels.get("op") == "query"
                    and n.endswith("_sum")]
            assert sums == [pytest.approx(
                0.4 + 2.2 + 7.9 + 55.0 + 900.0 + 20000.0)]
        finally:
            tsdb.shutdown()

    def test_label_escaping_round_trip(self):
        tsdb, router = self._served()
        try:
            hostile = 'quo"te\\back\nline'
            tsdb.hook_errors[hostile] = 3
            fams = parse_openmetrics(router.handle(
                req("GET", "/metrics")).body.decode())
            rows = {labels.get("hook"): v for (_n, labels, v)
                    in fams["tsd_hooks_errors"]["samples"]}
            assert rows[hostile] == 3.0
        finally:
            tsdb.shutdown()

    def test_get_only(self):
        tsdb, router = self._served()
        try:
            assert router.handle(
                req("POST", "/metrics")).status == 405
        finally:
            tsdb.shutdown()


# ---------------------------------------------------------------------------
# histogram bucket-merge property
# ---------------------------------------------------------------------------

class TestHistogramMerge:
    PCTS = [50.0, 95.0, 99.0, 99.9]

    def test_fleet_merge_equals_concatenation_oracle(self):
        rng = np.random.default_rng(7)
        obs = np.concatenate([
            rng.gamma(2.0, 30.0, size=2000),      # ms-scale body
            rng.uniform(5000, 30000, size=50),    # tail + overflow
        ])
        oracle = Histogram(16000, 2, 1)
        parts = [Histogram(16000, 2, 1) for _ in range(3)]
        for i, v in enumerate(obs):
            oracle.add(float(v))
            parts[i % 3].add(float(v))
        merged = merge_histogram_snapshots(
            [h.snapshot() for h in parts])
        osnap = oracle.snapshot()
        assert merged["buckets"] == osnap["buckets"]
        assert merged["count"] == osnap["count"]
        assert merged["sum"] == pytest.approx(osnap["sum"])
        got = percentiles_from_buckets(
            merged["bounds"], merged["buckets"], merged["count"],
            self.PCTS)
        want = oracle.percentile_many(self.PCTS)
        assert got == want  # BIT-equal, not approx

    def test_merge_order_invariant(self):
        rng = np.random.default_rng(11)
        parts = [Histogram(16000, 2, 1) for _ in range(4)]
        for v in rng.gamma(2.0, 40.0, size=500):
            parts[rng.integers(4)].add(float(v))
        snaps = [h.snapshot() for h in parts]
        a = merge_histogram_snapshots(snaps)
        b = merge_histogram_snapshots(list(reversed(snaps)))
        # bucket counts and count are integers — exactly invariant;
        # the float sum agrees to the usual reassociation ulp
        assert a["buckets"] == b["buckets"]
        assert a["count"] == b["count"]
        assert a["sum"] == pytest.approx(b["sum"])

    def test_mismatched_bounds_refuse(self):
        a = Histogram(16000, 2, 1)
        b = Histogram(1000, 2, 10)
        assert merge_histogram_snapshots(
            [a.snapshot(), b.snapshot()]) is None
        assert merge_histogram_snapshots([]) is None


# ---------------------------------------------------------------------------
# continuous sampling profiler
# ---------------------------------------------------------------------------

class TestProfiler:
    def test_roles_and_collapsed_output(self):
        tsdb = mk_tsdb(**{"tsd.profile.hz": "100"})
        stop = threading.Event()

        def busy():
            x = 0
            while not stop.is_set():
                x += sum(i * i for i in range(500))

        worker = threading.Thread(target=busy, name="tsd-query-w0",
                                  daemon=True)
        worker.start()
        try:
            prof = tsdb.profiler
            # deterministic: drive samples by hand, no loop needed
            for i in range(5):
                prof.sample_once(now_s=1000 + i)
            rep = prof.report(seconds=60, now_s=1004)
            assert "query" in rep, rep.keys()
            # only the stacks that hold `busy`: the role also counts
            # any pool thread an earlier module left alive
            assert sum(n for s, n in rep["query"].items()
                       if "busy" in s) == 5
            text = prof.collapsed(seconds=60, now_s=1004)
            line = next(ln for ln in text.splitlines()
                        if ln.startswith("query;"))
            stack, n = line.rsplit(" ", 1)
            assert int(n) >= 1
            assert ";" in stack
        finally:
            stop.set()
            worker.join(5)
            tsdb.shutdown()

    def test_http_surface_and_ring_window(self):
        tsdb = mk_tsdb(**{"tsd.profile.hz": "100",
                          "tsd.profile.ring_s": "5"})
        router = HttpRpcRouter(tsdb)
        stop = threading.Event()

        def busy():
            x = 0
            while not stop.is_set():
                x += sum(i * i for i in range(500))

        worker = threading.Thread(target=busy, name="tsd-query-w1",
                                  daemon=True)
        worker.start()
        try:
            prof = tsdb.profiler
            for i in range(8):   # 8s of activity into a 5s ring
                prof.sample_once(now_s=2000 + i)
            # the ring kept only the trailing 5s: the always-running
            # worker contributed exactly one stack per retained second
            full = prof.report(seconds=999, now_s=2007)
            assert sum(n for s, n in full["query"].items()
                       if "busy" in s) == 5
            resp = router.handle(req("GET", "/api/profile",
                                     seconds=60))
            assert resp.status == 200
            assert resp.content_type.startswith("text/plain")
            resp = router.handle(req("GET", "/api/profile",
                                     format="json"))
            doc = json.loads(resp.body)
            assert doc["hz"] == 100.0
            assert "roles" in doc and doc["profiler"]["samples"] == 8
            assert router.handle(req(
                "GET", "/api/profile", format="nope")).status == 400
        finally:
            stop.set()
            worker.join(5)
            tsdb.shutdown()

    def test_loop_starts_and_joins(self):
        tsdb = mk_tsdb(**{"tsd.profile.hz": "200"})
        try:
            prof = tsdb.profiler
            prof.start()
            deadline = time.monotonic() + 10
            while prof.samples < 3 and time.monotonic() < deadline:
                time.sleep(0.01)
            assert prof.samples >= 3
            assert prof.running
        finally:
            tsdb.shutdown()
        # joined, not abandoned (the module-level leak witness
        # additionally proves convergence at teardown)
        assert tsdb.profiler._thread is None
        assert not tsdb.profiler.running

    def test_disabled_is_a_clean_400(self):
        tsdb = mk_tsdb(**{"tsd.profile.enable": "false"})
        router = HttpRpcRouter(tsdb)
        try:
            resp = router.handle(req("GET", "/api/profile"))
            assert resp.status == 400
            assert b"tsd.profile.enable" in resp.body
            prof = tsdb.profiler
            prof.start()   # no-op
            assert not prof.running
        finally:
            tsdb.shutdown()


# ---------------------------------------------------------------------------
# SLO burn-rate
# ---------------------------------------------------------------------------

class TestSlo:
    def test_burn_math(self):
        slo = SloTracker(Config(**{
            "tsd.tpu.warmup": "false",
            "tsd.slo.windows": "60,3600",
            "tsd.slo.query.latency_ms": "10",
            "tsd.slo.query.latency_objective": "0.99",
            "tsd.slo.query.availability_objective": "0.999",
        }))
        now = 10_000.0
        for i in range(100):
            slo.record("query", 5.0 if i < 90 else 50.0,
                       errored=(i >= 98), now_s=now)
        rates = slo.burn_rates(now_s=now)["query"]
        # 10% slow against a 1% budget; 2% errored against 0.1%
        assert rates["latency"]["1m"] == pytest.approx(10.0)
        assert rates["availability"]["1m"] == pytest.approx(20.0)
        # same events inside the hour window
        assert rates["latency"]["1h"] == pytest.approx(10.0)
        # an idle window reports 0 burn, not a flap
        assert slo.burn_rates(now_s=now + 7200)["query"][
            "latency"]["1m"] == 0.0

    def test_window_expiry(self):
        slo = SloTracker(Config(**{
            "tsd.tpu.warmup": "false", "tsd.slo.windows": "60",
            "tsd.slo.query.latency_ms": "1",
        }))
        slo.record("query", 100.0, errored=False, now_s=1000.0)
        assert slo.burn_rates(now_s=1005.0)["query"][
            "latency"]["1m"] > 0
        assert slo.burn_rates(now_s=1100.0)["query"][
            "latency"]["1m"] == 0.0

    def test_served_requests_feed_burn(self):
        tsdb = mk_tsdb(**{
            # a 0ms latency objective: every real query violates it
            "tsd.slo.query.latency_ms": "0",
        })
        router = HttpRpcRouter(tsdb)
        try:
            router.handle(req("POST", "/api/put", put_body()))
            for _ in range(3):
                r = router.handle(req("POST", "/api/query",
                                      query_body()))
                assert r.status == 200
            health = json.loads(router.handle(
                req("GET", "/api/health")).body)
            slo_doc = health["slo"]
            assert slo_doc["enabled"]
            burn = slo_doc["burn_rates"]["query"]["latency"]
            assert max(burn.values()) > 0, slo_doc
            # availability untouched: those queries answered 200
            assert max(slo_doc["burn_rates"]["query"][
                "availability"].values()) == 0.0
            fams = parse_openmetrics(router.handle(
                req("GET", "/metrics")).body.decode())
            rows = {tuple(sorted(labels.items())): v
                    for _n, labels, v
                    in fams["tsd_slo_burn_rate"]["samples"]}
            assert any(v > 0 for k, v in rows.items()
                       if ("endpoint", "query") in k
                       and ("slo", "latency") in k), rows
        finally:
            tsdb.shutdown()


# ---------------------------------------------------------------------------
# query-shape read surface
# ---------------------------------------------------------------------------

class TestQueryShapes:
    def test_top_n_summary(self, tmp_path):
        tsdb = mk_tsdb(**{
            "tsd.storage.data_dir": str(tmp_path / "d"),
            "tsd.trace.sample": "1",
        })
        router = HttpRpcRouter(tsdb)
        try:
            router.handle(req("POST", "/api/put", put_body()))
            for _ in range(3):   # shape A x3 (miss, hit, hit)
                assert router.handle(req(
                    "POST", "/api/query",
                    query_body(ds="10s-sum"))).status == 200
            assert router.handle(req(                # shape B x1
                "POST", "/api/query",
                query_body(ds="30s-avg"))).status == 200
            resp = router.handle(req("GET",
                                     "/api/stats/query_shapes"))
            assert resp.status == 200
            doc = json.loads(resp.body)
            assert doc["distinctShapes"] == 2
            top = doc["shapes"][0]
            assert top["count"] == 3
            assert top["metrics"] == "sys.fleet"
            assert top["downsample"] == "10s-sum"
            outcomes = top["cacheOutcomes"]
            assert outcomes.get("miss", 0) == 1
            assert outcomes.get("hit", 0) == 2, outcomes
            assert top["durationMs"]["p50"] >= 0
            assert "query.execute" in top["stagesMs"]
            # limit is honored
            doc = json.loads(router.handle(req(
                "GET", "/api/stats/query_shapes",
                limit=1)).body)
            assert len(doc["shapes"]) == 1
        finally:
            tsdb.shutdown()

    def test_disabled_is_a_clean_400(self):
        tsdb = mk_tsdb()   # no data_dir => no shape log
        router = HttpRpcRouter(tsdb)
        try:
            resp = router.handle(req("GET",
                                     "/api/stats/query_shapes"))
            assert resp.status == 400
        finally:
            tsdb.shutdown()


# ---------------------------------------------------------------------------
# dirty-debt age
# ---------------------------------------------------------------------------

class TestDirtyDebtAge:
    def test_age_distinguishes_old_debt(self, tmp_path):
        d = DirtyTracker(str(tmp_path))
        now_ms = int(time.time() * 1000)
        week_old = now_ms - 7 * 86400 * 1000
        d.mark("s0", ["m.old"], week_old)
        d.mark("s1", ["m.new"], now_ms - 2000)
        info = d.health_info()
        assert info["entries"] == 2
        assert info["ages"]["s0"]["age_s"] == pytest.approx(
            7 * 86400, rel=0.01)
        assert info["ages"]["s1"]["age_s"] < 60
        assert info["oldest_age_s"] == info["ages"]["s0"]["age_s"]
        a = d.age_info("s0", now_ms)
        assert a["oldest_ms"] == week_old
        # cleared debt has no age
        d.clear("s0")
        assert d.age_info("s0", now_ms) == {
            "entries": 0, "oldest_ms": 0, "age_s": 0.0}


# ---------------------------------------------------------------------------
# fleet aggregation over a live 2-shard cluster
# ---------------------------------------------------------------------------

PEER_CFG = {
    "tsd.core.auto_create_metrics": "true",
    "tsd.tpu.warmup": "false",
}


class MiniPeer:
    """One shard TSD on a real socket (the LivePeer shape from
    test_cluster, trimmed to start/kill/stop)."""

    def __init__(self, name: str):
        from opentsdb_tpu.tsd.server import TSDServer
        self.name = name
        self.tsdb = TSDB(Config(**PEER_CFG))
        self.loop = asyncio.new_event_loop()
        self.server = TSDServer(self.tsdb, host="127.0.0.1", port=0)
        started = threading.Event()

        def run():
            asyncio.set_event_loop(self.loop)
            self.loop.run_until_complete(self.server.start())
            started.set()
            self.loop.run_forever()

        self._thread = threading.Thread(target=run, daemon=True)
        self._thread.start()
        assert started.wait(30), f"peer {name} did not start"
        self.port = self.server._server.sockets[0].getsockname()[1]

    def kill(self):
        async def _close():
            srv = self.server._server
            if srv is not None:
                srv.close()
                await srv.wait_closed()
                self.server._server = None
        asyncio.run_coroutine_threadsafe(_close(),
                                         self.loop).result(15)

    def stop(self):
        if self.loop.is_closed():
            return
        try:
            asyncio.run_coroutine_threadsafe(
                self.server.stop(), self.loop).result(20)
        except Exception:  # noqa: BLE001 - already dead is fine
            pass
        self.loop.call_soon_threadsafe(self.loop.stop)
        self._thread.join(timeout=10)
        if not self._thread.is_alive():
            try:
                self.loop.close()
            except Exception:  # noqa: BLE001 - teardown best-effort
                pass


@pytest.fixture(scope="class")
def fleet2(request):
    peers = [MiniPeer(f"s{i}") for i in range(2)]
    spec = ",".join(f"s{i}=127.0.0.1:{p.port}"
                    for i, p in enumerate(peers))
    tsdb = TSDB(Config(**{
        "tsd.cluster.role": "router",
        "tsd.cluster.peers": spec,
        "tsd.cluster.spool.replay_interval_ms": "100",
        # the chaos test needs the NEXT health poll to see the kill
        "tsd.cluster.fleet_health_ttl_ms": "0",
        "tsd.tpu.warmup": "false",
    }))
    http = HttpRpcRouter(tsdb)
    tsdb.cluster.start()
    # 12 hosts spread across both shards
    pts = []
    for h in range(12):
        for i in range(10):
            pts.append({"metric": "c.fleet", "timestamp": BASE + i,
                        "value": h + i,
                        "tags": {"host": f"h{h:02d}"}})
    resp = http.handle(req("POST", "/api/put", pts, summary="true"))
    assert resp.status == 200 and \
        json.loads(resp.body)["failed"] == 0
    # feed each shard's request histograms through its REAL socket
    # server (puts above already did; add queries for latency_query)
    request.cls.peers = peers
    request.cls.tsdb = tsdb
    request.cls.http = http
    request.cls.n_points = len(pts)
    yield
    tsdb.shutdown()
    for p in peers:
        p.stop()


@pytest.mark.usefixtures("fleet2")
class TestFleetAggregation:
    peers: list
    tsdb: TSDB
    http: HttpRpcRouter
    n_points: int

    def test_fleet_requires_router(self):
        lone = mk_tsdb()
        r = HttpRpcRouter(lone)
        try:
            assert r.handle(req("GET",
                                "/api/stats/fleet")).status == 400
        finally:
            lone.shutdown()

    def test_counters_sum_across_shards(self):
        resp = self.http.handle(req("GET", "/api/stats/fleet"))
        assert resp.status == 200
        doc = json.loads(resp.body)
        assert doc["shardsDegraded"] == []
        assert doc["nodes"] == {"s0": "ok", "s1": "ok"}
        assert doc["counters"]["tsd.datapoints.added"] \
            == self.n_points
        # every shard holds a non-empty share (the ring spread)
        per_node = {p.name: p.tsdb.datapoints_added
                    for p in self.peers}
        assert all(v > 0 for v in per_node.values()), per_node

    def test_gauges_listed_per_node_with_min_max(self):
        doc = json.loads(self.http.handle(
            req("GET", "/api/stats/fleet")).body)
        up = doc["gauges"]["tsd.uptime.seconds"]
        assert set(up["nodes"]) == {"s0", "s1"}
        assert up["min"] <= up["max"]

    def test_histograms_bucket_sum_exact(self):
        # drive a few queries through the real sockets so shard-side
        # latency_query histograms hold data
        tsq = query_body("c.fleet")
        for _ in range(3):
            r = self.http.handle(req("POST", "/api/query", tsq))
            assert r.status == 200, r.body
        doc = json.loads(self.http.handle(
            req("GET", "/api/stats/fleet")).body)
        key = "tsd_request_latency_ms{op=put}"
        assert key in doc["histograms"], list(doc["histograms"])
        fleet_h = doc["histograms"][key]
        # oracle: merge the shards' raw snapshots in-process
        snaps = []
        for p in self.peers:
            raw = json.loads(p.server.http_router.handle(
                req("GET", "/api/stats/raw")).body)
            snaps.extend(h for h in raw["histograms"]
                         if h["labels"] == {"op": "put"})
        merged = merge_histogram_snapshots(snaps)
        assert merged is not None
        want = percentiles_from_buckets(
            merged["bounds"], merged["buckets"], merged["count"],
            [50.0, 95.0, 99.0, 99.9])
        assert [fleet_h["p50"], fleet_h["p95"], fleet_h["p99"],
                fleet_h["p999"]] == want   # bit-equal
        assert fleet_h["count"] == merged["count"]
        assert sorted(fleet_h["nodes"]) == ["s0", "s1"]

    def test_cluster_status_progress_doc(self):
        resp = self.http.handle(req("GET", "/api/cluster/status"))
        assert resp.status == 200
        doc = json.loads(resp.body)
        assert doc["epoch"] == 0
        assert set(doc["peers"]) == {"s0", "s1"}
        for p in doc["peers"].values():
            assert p["spool_pending_records"] == 0
            assert p["dirty_oldest_age_s"] == 0.0
        assert doc["spool_backlog_records"] == 0
        assert doc["reshard"]["active"] is False
        assert "retire" in doc

    def test_server_feeds_slo_at_response_time(self):
        # forwarded puts reached the shards through their REAL socket
        # servers — the server-side SLO feed must have counted them
        assert all(p.tsdb.slo.events > 0 for p in self.peers), \
            [p.tsdb.slo.events for p in self.peers]

    def test_router_health_fleet_section(self):
        health = json.loads(self.http.handle(
            req("GET", "/api/health")).body)
        fleet = health["cluster"]["fleet"]
        assert fleet["shards"] == 2
        assert fleet["ok"] == 2 and fleet["degraded"] == []
        assert fleet["nodes"]["s0"]["status"] == "ok"

    def test_health_fleet_ttl_cache(self):
        # /api/health is a probe surface: within the TTL the fleet
        # section must be served from cache, not re-scattered
        self.tsdb.config.override_config(
            "tsd.cluster.fleet_health_ttl_ms", "60000")
        try:
            a = self.tsdb.cluster.fleet_health()
            b = self.tsdb.cluster.fleet_health()
            assert b is a
        finally:
            self.tsdb.config.override_config(
                "tsd.cluster.fleet_health_ttl_ms", "0")
            self.tsdb.cluster._fleet_health_cache = (None, 0.0)

    def test_zz_dead_shard_degrades_never_5xx(self):
        # zz: runs last in the class — it kills s1 for good
        self.peers[1].kill()
        resp = self.http.handle(req("GET", "/api/stats/fleet"))
        assert resp.status == 200
        doc = json.loads(resp.body)
        assert doc["shardsDegraded"] == ["s1"]
        assert doc["nodes"]["s1"] == "degraded"
        # counters come from the SURVIVOR only
        assert doc["counters"]["tsd.datapoints.added"] \
            == self.peers[0].tsdb.datapoints_added
        # a put while s1 is dead spools; /api/cluster/status shows
        # the backlog + a drain ETA
        r = self.http.handle(req("POST", "/api/put", [
            {"metric": "c.fleet", "timestamp": BASE + 500,
             "value": 1, "tags": {"host": f"h{h:02d}"}}
            for h in range(12)]))
        assert r.status == 204, r.body
        status = json.loads(self.http.handle(
            req("GET", "/api/cluster/status")).body)
        s1 = status["peers"]["s1"]
        assert s1["spool_pending_records"] > 0
        assert s1["spool_drain_eta_s"] > 0
        assert status["spool_backlog_records"] \
            == s1["spool_pending_records"]
        # health fleet section marks the dead shard, still 200
        health = json.loads(self.http.handle(
            req("GET", "/api/health")).body)
        fleet = health["cluster"]["fleet"]
        assert fleet["degraded"] == ["s1"]
        assert fleet["nodes"]["s1"]["status"] == "unreachable"
        assert "fleet_shards_degraded" in health["causes"]
