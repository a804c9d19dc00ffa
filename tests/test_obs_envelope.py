"""The whole request inside the program (PR 36): ``query.receive``
and ``query.respond`` close the stretch between the socket and
``query.http``, the process reports its threads' CPU time by pool
(``tsd.runtime.thread_cpu_ms``) and its page faults, ``Tracer.finish``
times itself, and a histogram folds
its observations into the quantile sketch a batch at a time with
nothing exported changing by a bit. CPU only.
"""

import asyncio
import bisect
import json
import socket
import threading
import time

import numpy as np
import pytest

from opentsdb_tpu import TSDB, Config
from opentsdb_tpu.obs import trace as trace_mod
from opentsdb_tpu.obs.trace import Tracer
from opentsdb_tpu.stats import stats as stats_mod
from opentsdb_tpu.stats.stats import (Histogram, StatsCollector,
                                      StatsCollectorRegistry)
from opentsdb_tpu.tsd.http_api import HttpRequest
from opentsdb_tpu.tsd.server import TSDServer

pytestmark = pytest.mark.obs

BASE = 1356998400
ENVELOPE = ("query.receive", "query.admission", "query.http",
            "query.respond")
QUERY = json.dumps({
    "start": BASE * 1000, "end": (BASE + 600) * 1000, "queries": [{
        "metric": "sys.stage", "aggregator": "sum",
        "downsample": "1m-avg", "filters": [
            {"type": "wildcard", "tagk": "dc", "filter": "*",
             "groupBy": True}]}]}).encode()


def mk_tsdb(**cfg):
    tsdb = TSDB(Config(**{
        "tsd.core.auto_create_metrics": "true",
        "tsd.tpu.warmup": "false", "tsd.trace.sample": "1",
        "tsd.query.cache.enable": "false", **cfg}))
    text = "".join(
        f"sys.stage {BASE + i * 10} {i + h} host=h{h} dc=d{h % 4}\n"
        for h in range(16) for i in range(60)).encode()
    tsdb.import_buffer(text, durable=False)
    return tsdb


def mk_tracer():
    stats = StatsCollectorRegistry()
    return Tracer(Config(**{"tsd.tpu.warmup": "false",
                            "tsd.trace.sample": "1"}),
                  stats=stats), stats


def records(provider) -> dict:
    """{(metric, stage or ""): value} of one provider's records."""
    collector = StatsCollector("tsd")
    provider.collect_stats(collector)
    return {(name, tags.get("stage", "")): value
            for name, value, tags in collector.records}




class Clocks:
    """A scripted ``trace._now``, in milliseconds."""

    def __init__(self, monkeypatch):
        self.ms = 0.0
        monkeypatch.setattr(trace_mod, "_now", lambda: self.ms / 1e3)

    def spin(self, ms):
        self.ms += ms


# ---------------------------------------------------------------------
# (a) the envelope of a served query, over a real socket
# ---------------------------------------------------------------------

class Served:
    """A TSD on a socket and one keep-alive client connection."""

    def __init__(self):
        self.tsdb = mk_tsdb()
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
        port = self.server._server.sockets[0].getsockname()[1]
        self.sock = socket.create_connection(("127.0.0.1", port),
                                             timeout=120)
        self.sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)

    def ask(self, method, path, body=b"", status="200"):
        """(client's latency in ms, headers, body)."""
        head = f"{method} {path} HTTP/1.1\r\nHost: t\r\n" \
               f"Content-Length: {len(body)}\r\n\r\n".encode()
        t0 = time.monotonic()
        self.sock.sendall(head + body)
        buf = b""
        while b"\r\n\r\n" not in buf:
            buf += self.sock.recv(65536)
        head, _, rest = buf.partition(b"\r\n\r\n")
        lines = head.decode("latin-1").split("\r\n")
        assert lines[0].split()[1] == status, (lines[0], rest[:300])
        headers = {k.strip().lower(): v.strip() for k, _, v in
                   (ln.partition(":") for ln in lines[1:])}
        while len(rest) < int(headers["content-length"]):
            rest += self.sock.recv(65536)
        return (time.monotonic() - t0) * 1000.0, headers, rest

    def spans_of(self, trace_id):
        _, _, body = self.ask("GET", f"/api/trace/{trace_id}")
        return {s["name"]: s for s in json.loads(body)["spans"]}

    def stop(self):
        self.sock.close()
        asyncio.run_coroutine_threadsafe(
            self.server.stop(), self.loop).result(20)
        self.loop.call_soon_threadsafe(self.loop.stop)
        self.tsdb.shutdown()


@pytest.fixture(scope="module")
def served():
    s = Served()
    try:
        yield s
    finally:
        s.stop()


def test_a_served_query_is_four_stages_end_to_end(served, monkeypatch):
    router = served.server.http_router
    loop_thread = None
    ran = []                 # (thread, ms) of each _handle_query_run
    inner = router._handle_query_run

    def timed(request):
        t0 = time.monotonic()
        try:
            return inner(request)
        finally:
            ran.append((threading.get_ident(),
                        (time.monotonic() - t0) * 1000.0))

    monkeypatch.setattr(router, "_handle_query_run", timed)
    loop_thread = asyncio.run_coroutine_threadsafe(
        _ident(), served.loop).result(10)
    served.ask("POST", "/api/query", QUERY)          # compiles
    gaps, slack = [], []
    for _ in range(12):
        del ran[:]
        client_ms, headers, _body = served.ask("POST", "/api/query",
                                               QUERY)
        spans = served.spans_of(headers["x-tsd-trace-id"])
        assert set(ENVELOPE) <= set(spans)
        iv = [(spans[n]["startMs"],
               spans[n]["startMs"] + spans[n]["durationMs"])
              for n in ENVELOPE]
        # in order, and no two overlap (the JSON rounds to a
        # microsecond)
        for (_, end), (start, _) in zip(iv, iv[1:]):
            assert start >= end - 0.002
        # receive ends where admission begins, admission where the
        # root begins; between the root and the respond lies
        # Tracer.finish alone
        assert iv[1][0] == pytest.approx(iv[0][1], abs=0.002)
        assert iv[2][0] == pytest.approx(iv[1][1], abs=0.002)
        total = sum(end - start for start, end in iv)
        gaps.append(client_ms - total)
        # the root is what it was: begun in the worker just before
        # the handler runs, ended at its return
        (thread, run_ms), = ran
        assert thread != loop_thread
        assert spans["query.http"]["durationMs"] >= run_ms - 0.002
        slack.append(spans["query.http"]["durationMs"] - run_ms)
        for name in ("query.receive", "query.admission",
                     "query.respond"):
            assert spans[name]["parentId"] \
                == spans["query.http"]["spanId"]
    # what no stage names is the client's own share, the kernel's and
    # Tracer.finish: under a millisecond at this size (and the four
    # never add up to more than the client saw, but for the server's
    # last stamp, taken after the bytes have gone)
    assert -1.0 < min(gaps) < 1.0
    assert min(slack) < 0.5
    # every request fed all four histograms, retained or not
    stages = served.tsdb.stats.stage_latency
    n = stages["query.http"].count
    assert n >= 13
    for name in ENVELOPE:
        assert stages[name].count == n, name
    _, _, raw = served.ask("GET", "/api/stats/raw")
    fams = {(h["name"], h["labels"].get("stage"))
            for h in json.loads(raw)["histograms"]}
    for name in ENVELOPE:
        assert ("tsd_stage_latency_ms", name) in fams
    # the root's self time has a histogram for http.self_ms to read
    assert ("tsd_stage_self_ms", "query.http") in fams


async def _ident():
    return threading.get_ident()


def test_a_direct_handle_call_records_neither_new_span(served):
    tsdb = served.tsdb
    served.ask("POST", "/api/query", QUERY)      # all four have fired
    # query.respond is fed after the last drain, so the client may hold
    # the answer first: the connection's next answer is behind it
    served.ask("GET", "/api/version")
    before = {n: tsdb.stats.stage_latency[n].count for n in ENVELOPE}
    resp = served.server.http_router.handle(HttpRequest(
        method="POST", path="/api/query", params={}, headers={},
        body=QUERY))
    assert resp.status == 200
    after = {n: tsdb.stats.stage_latency[n].count for n in ENVELOPE}
    assert after["query.http"] == before["query.http"] + 1
    for name in ("query.receive", "query.admission", "query.respond"):
        assert after[name] == before[name], name
    data = tsdb.tracer.get(resp.headers["X-TSD-Trace-Id"])
    assert not {"query.receive", "query.respond"} \
        & {s.name for s in data.spans}


def test_the_respond_span_needs_a_finished_root():
    tracer, stats = mk_tracer()
    tracer.record_respond(None, 1.0)
    ctx = tracer.start_request("query.http")
    tracer.record_respond(ctx, 1.0)      # a 504: the worker still runs
    assert "query.respond" not in stats.stage_latency
    tracer.finish(ctx)
    tracer.record_respond(ctx, ctx.finished_at + 0.004)
    assert stats.stage_latency["query.respond"].sum \
        == pytest.approx(4.0)
    (span,) = [s for s in tracer.get(ctx.trace_id).spans
               if s.name == "query.respond"]
    assert span.parent_id == ctx.root_span_id
    assert span.duration_ms == pytest.approx(4.0)


# ---------------------------------------------------------------------
# (b) what the threads ran: the kernel's account, read with the stats
# ---------------------------------------------------------------------

@pytest.mark.parametrize("name, group", [
    ("tsd-query_3", "tsd-query"), ("tsd-subq_12", "tsd-subq"),
    ("asyncio_0", "asyncio"), ("Thread-7 (attempt)", "Thread"),
    ("Thread-7", "Thread"), ("MainThread", "MainThread"),
    ("tsd-telemetry", "tsd-telemetry"), ("7", "7")])
def test_a_pools_threads_are_one_group(name, group):
    assert trace_mod.thread_group(name) == group


def _in_thread(name, fn):
    """Run ``fn`` in a thread called ``name``; the records read while
    it is alive, just before and just after ``fn``."""
    got, go = [], threading.Event()

    def body():
        got.append(trace_mod.thread_cpu_ms())
        fn()
        got.append(trace_mod.thread_cpu_ms())
        go.wait(10)

    t = threading.Thread(target=body, name=name)
    t.start()
    try:
        while len(got) < 2 and t.is_alive():
            time.sleep(0.01)
    finally:
        go.set()
        t.join(10)
    return got


needs_procfs = pytest.mark.skipif(
    not trace_mod.thread_cpu_ms(), reason="no /proc/self/task")


@needs_procfs
def test_a_spinning_thread_is_charged_and_a_sleeping_one_is_not():
    def spin():
        end = time.monotonic() + 0.4
        while time.monotonic() < end:
            pass

    before, after = _in_thread("tsd-query_0", spin)
    ran = after["tsd-query"] - before.get("tsd-query", 0.0)
    # the kernel's tick is 10 ms; another test's threads may hold the
    # interpreter for some of the 400
    assert 100.0 <= ran <= 450.0
    before, after = _in_thread("tsd-subq_0", lambda: time.sleep(0.4))
    assert after["tsd-subq"] - before.get("tsd-subq", 0.0) <= 50.0
    # a group's threads add up, and no thread counts twice
    assert set(after) >= {"MainThread", "tsd-subq"}


def test_thread_cpu_is_read_from_the_task_files(tmp_path, monkeypatch):
    """Fields 14 and 15 counted from the name's last bracket (a name
    may hold spaces and brackets); a thread that ended since, one not
    started yet and a torn file are left out."""
    class T:
        def __init__(self, name, native_id):
            self.name, self.native_id = name, native_id

    def stat(tid, comm, utime, stime):
        d = tmp_path / str(tid)
        d.mkdir()
        (d / "stat").write_text(
            f"{tid} ({comm}) S 1 1 1 0 -1 4194368 55 0 0 0 "
            f"{utime} {stime} 0 0 20 0 9 0 100 1 1\n")

    stat(11, "python3", 120, 30)
    stat(12, "a b) (c", 7, 3)
    stat(13, "python3", 1, 1)
    (tmp_path / "14").mkdir()
    (tmp_path / "14" / "stat").write_text("14 (python3) S 1")
    monkeypatch.setattr(threading, "enumerate", lambda: [
        T("tsd-query_0", 11), T("tsd-query_1", 12), T("MainThread", 13),
        T("tsd-subq_0", 14), T("asyncio_0", 15), T("Thread-1", None)])
    tick = trace_mod._TICK_MS
    assert trace_mod.thread_cpu_ms(str(tmp_path)) == {
        "tsd-query": pytest.approx(160 * tick),
        "MainThread": pytest.approx(2 * tick)}
    # no procfs: nothing, and no error
    assert trace_mod.thread_cpu_ms(str(tmp_path / "none")) == {}


@needs_procfs
def test_the_thread_cpu_records_are_served(served):
    """The query pool's threads are a group at ``/api/stats/raw``, and
    a query's work is charged to it (the loop's to MainThread's group
    or the test's loop thread's)."""
    def pool_ms():
        _, _, raw = served.ask("GET", "/api/stats/raw")
        return {r["tags"]["thread"]: r["value"]
                for r in json.loads(raw)["records"]
                if r["metric"] == "tsd.runtime.thread_cpu_ms"}

    served.ask("POST", "/api/query", QUERY)
    before = pool_ms()
    assert "tsd-query" in before
    # monotone while the pool lives, and it grows with the work: the
    # tick is 10 ms, so ask until two have been charged
    deadline = time.monotonic() + 60
    while time.monotonic() < deadline:
        served.ask("POST", "/api/query", QUERY)
        after = pool_ms()
        assert after["tsd-query"] >= before["tsd-query"]
        if after["tsd-query"] >= before["tsd-query"] + 20.0:
            break
    else:
        pytest.fail("no CPU time was charged to the query pool")
    # no span carries a CPU time of its own any more
    _, headers, _ = served.ask("POST", "/api/query", QUERY)
    assert not any("cpuMs" in s for s in served.spans_of(
        headers["x-tsd-trace-id"]).values())


def test_a_timed_out_query_records_no_respond(monkeypatch):
    """Its worker may finish before the 504 is written: the answer
    written is not the worker's, so no ``query.respond`` is timed over
    it (and a shed query had no worker at all)."""
    s = Served()
    try:
        s.ask("POST", "/api/query", QUERY)           # compiles
        s.server.query_timeout_ms = 150
        s.ask("GET", "/api/version")
        stages = s.tsdb.stats.stage_latency
        before = {n: stages[n].count for n in ENVELOPE}
        inner = s.server.http_router._handle_query_run
        done = threading.Event()

        def slow(request):
            time.sleep(0.4)
            try:
                return inner(request)
            finally:
                done.set()

        monkeypatch.setattr(s.server.http_router, "_handle_query_run",
                            slow)
        # hold the loop between the timeout and the write until the
        # worker has finished its root: the window the review named
        write = s.server._write_response

        async def late(*args, **kwargs):
            await asyncio.get_running_loop().run_in_executor(
                None, done.wait, 30)
            for _ in range(200):
                if stages["query.http"].count > before["query.http"]:
                    break
                await asyncio.sleep(0.01)
            return await write(*args, **kwargs)

        monkeypatch.setattr(s.server, "_write_response", late)
        _, _, body = s.ask("POST", "/api/query", QUERY, status="504")
        assert b"timeout" in body.lower()
        monkeypatch.setattr(s.server, "_write_response", write)
        s.ask("GET", "/api/version")
        after = {n: stages[n].count for n in ENVELOPE}
        assert after["query.http"] == before["query.http"] + 1
        assert after["query.respond"] == before["query.respond"]
    finally:
        s.stop()


# ---------------------------------------------------------------------
# (c) the histogram folds its sketch a batch at a time
# ---------------------------------------------------------------------

class OneByOne(Histogram):
    """``Histogram.add`` as it was: the sketch fed a value at a
    time."""

    def add(self, value):
        idx = bisect.bisect_left(self.bounds, value)
        with self._lock:
            self.buckets[min(idx, len(self.buckets) - 1)] += 1
            self.count += 1
            self.sum += value
            self._sketch.add(value)


def latencies(n, seed=36):
    """Stage latencies in ms: sums of multiples of 1/64 are exact in
    float64, so a sum does not depend on the order of its terms."""
    rng = np.random.default_rng(seed)
    vals = np.round(rng.lognormal(1.0, 2.0, n) * 64.0) / 64.0
    vals[rng.random(n) < 0.02] = 0.0
    vals[rng.random(n) < 0.01] = 20000.0        # past the last bound
    return [float(v) for v in vals]


LIMIT = stats_mod._SKETCH_FOLD_AT


@pytest.mark.parametrize("n", [0, 1, LIMIT - 1, LIMIT, LIMIT + 1,
                               2 * LIMIT - 1, 2 * LIMIT, 3000])
def test_a_batched_histogram_exports_what_one_by_one_did(n):
    vals = latencies(n)
    new, old = Histogram(16000, 2, 1), OneByOne(16000, 2, 1)
    for v in vals:
        new.add(v)
        old.add(v)
    assert len(new._pending) == n % LIMIT
    # percentiles first: they must not need the fold
    assert new.percentiles() == old.percentiles()
    assert new.percentile_many([10, 50, 99.9]) \
        == old.percentile_many([10, 50, 99.9])
    a, b = new.snapshot(), old.snapshot()
    assert a == b          # bounds, buckets, count, sum, base64 sketch
    assert a["count"] == n and not new._pending
    # and a snapshot in the middle changes nothing after it
    for v in vals[:300]:
        new.add(v)
        old.add(v)
    assert new.snapshot() == old.snapshot()


@pytest.mark.parametrize("n", [LIMIT - 1, LIMIT + 1, 3000])
def test_two_threads_feed_one_histogram(n):
    vals = latencies(n, seed=37)
    new, old = Histogram(16000, 2, 1), OneByOne(16000, 2, 1)
    for v in vals:
        old.add(v)
    go = threading.Barrier(2, timeout=10)

    def feed(part):
        go.wait()
        for i, v in enumerate(part):
            new.add(v)
            if i % 97 == 0:
                new.snapshot()       # a reader in between

    threads = [threading.Thread(target=feed, args=(vals[k::2],))
               for k in range(2)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(30)
    assert new.snapshot() == old.snapshot()
    assert new.percentiles() == old.percentiles()


def test_the_fleet_merge_sees_pending_observations():
    from opentsdb_tpu.cluster.fleet import merge_fleet
    from opentsdb_tpu.sketch.ddsketch import DDSketch
    from opentsdb_tpu.stats.stats import LATENCY_PCTS
    parts = (latencies(100), latencies(7, 38))   # both under the limit
    docs, whole = {}, DDSketch()
    for i, vals in enumerate(parts):
        reg = StatsCollectorRegistry()
        for v in vals:
            reg.observe_stage("query.plan", v)
            whole.add(v)
        docs[f"n{i}"] = {"records": [], "histograms": [
            {"name": name, "labels": labels, **h.snapshot()}
            for name, labels, h in reg.histograms()]}
    (hist,) = [h for key, h in merge_fleet(docs)["histograms"].items()
               if "query.plan" in key]
    assert hist["count"] == 107 and hist["merge"] == "buckets"
    assert hist["sketch"] == {
        label: whole.quantile(q) for label, q in LATENCY_PCTS}


# ---------------------------------------------------------------------
# (d) the host's memory, as a counter
# ---------------------------------------------------------------------

def test_minor_faults_are_monotone_and_grow_with_fresh_pages():
    first = records(trace_mod.RUNTIME)
    assert first["tsd.runtime.major_faults", ""] >= 0
    again = records(trace_mod.RUNTIME)
    assert again["tsd.runtime.minor_faults", ""] \
        >= first["tsd.runtime.minor_faults", ""] > 0
    fresh = np.ones(64 << 20, dtype=np.uint8)   # 16,384 pages of 4 KB
    after = records(trace_mod.RUNTIME)
    assert fresh[-1] == 1
    # with 2 MB transparent huge pages that is 32 faults, not 16,384
    assert after["tsd.runtime.minor_faults", ""] \
        >= again["tsd.runtime.minor_faults", ""] + 16


# ---------------------------------------------------------------------
# (e) what tracing costs
# ---------------------------------------------------------------------

def test_finish_times_itself_on_the_spans_clock(monkeypatch):
    clocks = Clocks(monkeypatch)
    tracer, stats = mk_tracer()
    for _ in range(3):
        ctx = tracer.start_request("query.http")
        with trace_mod.use(ctx):
            with trace_mod.trace_span("query.execute"):
                with trace_mod.trace_span("query.plan"):
                    clocks.spin(1.0)
        real = stats.observe_stage

        def slow(stage, ms, real=real):
            clocks.spin(0.25)         # an observation costs 0.25 ms
            real(stage, ms)

        monkeypatch.setattr(stats, "observe_stage", slow)
        tracer.finish(ctx)
        monkeypatch.setattr(stats, "observe_stage", real)
        # the root ended where finish began, not where it ended
        assert tracer.get(ctx.trace_id).root.duration_ms \
            == pytest.approx(1.0)
        assert ctx.finished_at == pytest.approx(clocks.ms / 1e3)
    got = records(tracer)
    # three stage observations a request, and two self times (the
    # root's and execute's)
    assert got["tsd.trace.finish_ms", ""] == pytest.approx(3 * 0.75)
    assert got["tsd.trace.observations", ""] == 3 * 5
    adds = sum(h.count for h in stats.stage_latency.values()) \
        + sum(h.count for h in stats.stage_self.values())
    assert adds == 15


def test_finish_costs_well_under_a_millisecond_a_request():
    """Sixteen spans a request, as a panel makes: one by one through
    the sketch that was 0.8 ms of observations alone on this sandbox's
    CPU (a ratio only; the chip's host is read by
    ``trace.finish_ms_per_query``)."""
    tracer, _ = mk_tracer()
    stages = ("query.plan", "query.scan", "query.grid_build",
              "query.upload", "query.program", "query.download",
              "query.assemble")
    best = None
    for _batch in range(5):
        before = records(tracer)["tsd.trace.finish_ms", ""]
        for _ in range(40):
            ctx = tracer.start_request("query.http")
            with trace_mod.use(ctx):
                for _sub in range(2):
                    with trace_mod.trace_span("query.execute"):
                        for name in stages:
                            with trace_mod.trace_span(name):
                                pass
            tracer.finish(ctx)
        per = (records(tracer)["tsd.trace.finish_ms", ""] - before) / 40
        best = per if best is None else min(best, per)
    assert 0.0 < best < 0.4
