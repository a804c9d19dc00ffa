"""HTTP-surface robustness sweep: every route x a battery of junk
inputs must answer with a STRUCTURED 4xx/2xx — never a 500 and never
an unhandled exception (ref: BadRequestException discipline across
``test/tsd/Test*Rpc.java``; RpcHandler turns user errors into 400s).

A 500 is only legitimate for genuine server faults, so any junk input
that produces one is a bug: the reference's HTTP layer wraps all
parse/validation failures in BadRequestException.
"""

from __future__ import annotations

import json

import pytest

from opentsdb_tpu import TSDB, Config
from opentsdb_tpu.tsd.http_api import HttpRequest, HttpRpcRouter

BASE = 1356998400


@pytest.fixture(scope="module")
def router():
    t = TSDB(Config(**{"tsd.core.auto_create_metrics": "true",
                       "tsd.rollups.enable": "true",
                       "tsd.http.query.allow_delete": "true"}))
    t.add_point("r.m", BASE + 30, 1.0, {"host": "a"})
    return HttpRpcRouter(t)


ROUTES = ["query", "query/last", "query/exp", "query/gexp", "suggest",
          "annotation/bulk",
          "search/lookup", "uid/assign", "uid/uidmeta", "uid/tsmeta",
          "uid/rename", "annotation", "annotations", "tree",
          "tree/rule", "tree/branch", "tree/test", "put", "rollup",
          "histogram", "aggregators", "config", "config/filters",
          "dropcaches", "serializers", "stats", "stats/query",
          "stats/jvm", "stats/threads", "stats/region_clients",
          "version"]

JUNK_BODIES = [
    b"", b"not json", b"{", b"[1,2,", b"null", b"42", b'"str"',
    b"[]", b"{}", b'{"a":', b"\x00\x01\x02",
    # element-shape junk: arrays of scalars, wrong-typed fields
    b"[1]", b'["x"]', b"[null]", b"[true, {}]",
    json.dumps({"backScan": None, "max": [], "limit": False,
                "treeId": True, "tsuids": 5, "queries": "x",
                "metric": 0, "tags": 3}).encode(),
    json.dumps({"tsuids": "ABCDEF", "global": 0,
                "startTime": [], "endTime": {}}).encode(),
    json.dumps({"metric": 5, "timestamp": "x", "value": {},
                "tags": 7}).encode(),
    json.dumps([{"deeply": {"nested": [1, {"junk": None}]}}]).encode(),
]

JUNK_PARAMS = [
    {},
    {"start": ["never-ago"]},
    {"start": ["1h-ago"], "m": ["sum"]},
    {"start": ["1h-ago"], "m": ["sum:nosuch.metric{bad"]},
    {"treeid": ["notanint"]},
    {"uid": ["ZZZZ"], "type": ["metric"]},
    {"type": ["nosuchtype"], "q": ["x"]},
    {"tsuids": ["nothex!"]},
    {"exp": ["scale(sum:r.m"]},
    {"serializer": ["nosuch"]},
    {"max": ["notanint"], "type": ["metrics"], "q": [""]},
]

ACCEPTABLE = set(range(200, 500)) - {500}


@pytest.mark.parametrize("route", ROUTES)
@pytest.mark.parametrize("method", ["GET", "POST", "DELETE", "PUT"])
def test_junk_never_500s(router, route, method):
    for body in (JUNK_BODIES if method in ("POST", "PUT")
                 else [b""]):
        for params in JUNK_PARAMS:
            resp = router.handle(HttpRequest(
                method, f"/api/{route}", params, {}, body))
            assert resp.status != 500, (
                route, method, body[:30], params, resp.body[:200])
            assert 200 <= resp.status < 500, (route, method,
                                              resp.status)
            if resp.status >= 400 and resp.body:
                # errors are structured (ref: {"error":{code,message}})
                err = json.loads(resp.body)
                assert "error" in err, (route, resp.body[:100])


def test_unknown_route_404(router):
    resp = router.handle(HttpRequest("GET", "/api/nosuch", {}, {},
                                     b""))
    assert resp.status == 404


def test_server_faults_still_500(router, monkeypatch):
    """A genuine internal fault (not user input) must still surface
    as a 500 — the sweep above must not be satisfied by swallowing
    everything."""
    def boom(*a, **k):
        raise RuntimeError("internal fault")
    monkeypatch.setattr(router.tsdb, "execute_query", boom)
    monkeypatch.setattr(router.tsdb, "new_query", boom)
    resp = router.handle(HttpRequest(
        "GET", "/api/query",
        {"start": ["1h-ago"], "m": ["sum:r.m"]}, {}, b""))
    assert resp.status == 500


class TestTelnetRobustness:
    """Telnet verb sweep: junk lines answer with an error string (or
    the documented silent success), never raise out of the router
    (ref: the telnet RPC error write-back, PutDataPointRpc:158)."""

    @pytest.fixture(scope="class")
    def tel(self):
        from opentsdb_tpu.tsd.telnet import TelnetRouter
        t = TSDB(Config(**{"tsd.core.auto_create_metrics": "true",
                           "tsd.rollups.enable": "true"}))
        return TelnetRouter(t)

    LINES = [
        "", " ", "nosuchcmd a b", "put", "put m", "put m ts",
        "put m 1356998400", "put m 1356998400 1",
        "put m notatime 1 host=a", "put m 1356998400 xx host=a",
        "put m 1356998400 1 nothostpair", "put m 1356998400 1 =",
        "put m 1356998400 1 host=", "put m 1356998400 1 =v",
        "put \x00\x01 1356998400 1 host=a",
        "put m -1 1 host=a", "put m 99999999999999999999 1 host=a",
        "rollup", "rollup 1m", "rollup bad:spec:extra:parts m 1 1 h=a",
        "rollup 1m:sum m notatime 1 host=a",
        "histogram", "histogram m", "histogram m 1356998400",
        "histogram m 1356998400 nothex host=a",
        "stats extra args here", "version extra",
        "dropcaches noise", "help unknown",
    ]

    @pytest.mark.parametrize("line", LINES, ids=[repr(x) for x in LINES])
    def test_junk_lines_never_raise(self, tel, line):
        from opentsdb_tpu.tsd.telnet import (TelnetCloseConnection,
                                             TelnetServerShutdown)
        try:
            out = tel.execute(line)
        except (TelnetCloseConnection, TelnetServerShutdown):
            return  # exit/diediedie control flow is fine
        assert isinstance(out, str)
        words = line.split()
        if words and words[0] in ("put", "rollup", "histogram") and \
                len(words) < 5:
            assert out.startswith(words[0]), (line, out)

    def test_good_put_still_silent(self, tel):
        assert tel.execute("put t.m 1356998400 1 host=a") == ""


class TestStaticPathTraversal:
    """/s must never serve files outside the static root
    (ref: StaticFileRpc.java staticroot containment)."""

    TRAVERSALS = ["/s/../../../etc/passwd", "/s/..%2f..%2fetc/passwd",
                  "/s/subdir/../../../../etc/hostname",
                  "/s//etc/passwd", "/s/%2e%2e/%2e%2e/etc/passwd",
                  "/s/....//....//etc/passwd"]

    @pytest.mark.parametrize("path", TRAVERSALS)
    def test_router_rejects(self, router, path):
        resp = router.handle(HttpRequest("GET", path, {}, {}, b""))
        assert resp.status == 404
        assert b"root:" not in (resp.body or b"")

    def test_valid_static_serves(self, router):
        resp = router.handle(HttpRequest("GET", "/s/index.html", {},
                                         {}, b""))
        assert resp.status == 200 and b"<!DOCTYPE html>" in resp.body


@pytest.mark.robustness
class TestOverloadShedding:
    """Admission-control + connection-flood sweep over REAL sockets:
    past the configured thresholds the server sheds with a structured
    503 + ``Retry-After`` — never a 500, never a silent close, never a
    hang — and /api/health accounts for every shed decision."""

    BASE_CFG = {
        "tsd.core.auto_create_metrics": "true",
        "tsd.tpu.warmup": "false",
        "tsd.tpu.platform": "cpu",
    }

    @staticmethod
    async def _start(tsdb):
        from opentsdb_tpu.tsd.server import TSDServer
        server = TSDServer(tsdb, host="127.0.0.1", port=0)
        await server.start()
        return server, server._server.sockets[0].getsockname()[1]

    @staticmethod
    async def _fetch(port, path):
        import asyncio
        reader, writer = await asyncio.open_connection("127.0.0.1",
                                                       port)
        writer.write(f"GET {path} HTTP/1.0\r\n\r\n".encode())
        await writer.drain()
        raw = await asyncio.wait_for(reader.read(), 15)
        writer.close()
        head, _, body = raw.partition(b"\r\n\r\n")
        lines = head.decode("latin-1").split("\r\n")
        status = int(lines[0].split(" ")[1])
        headers = {}
        for ln in lines[1:]:
            k, _, v = ln.partition(":")
            headers[k.strip().lower()] = v.strip()
        return status, headers, body

    def test_query_flood_sheds_structured_503(self):
        import asyncio
        import time as _t
        from opentsdb_tpu import TSDB, Config
        tsdb = TSDB(Config(**self.BASE_CFG, **{
            "tsd.query.admission.max_inflight": "1",
            "tsd.query.admission.retry_after_s": "2"}))
        tsdb.add_point("o.m", BASE + 30, 1.0, {"host": "a"})

        async def scenario():
            server, port = await self._start(tsdb)
            try:
                orig = server.http_router.handle

                def slow_handle(request):
                    if "query" in request.path:
                        _t.sleep(0.5)
                    return orig(request)

                server.http_router.handle = slow_handle
                results = await asyncio.gather(*[
                    self._fetch(port,
                                "/api/query?start=1h-ago&m=sum:o.m")
                    for _ in range(5)])
                statuses = [s for s, _, _ in results]
                assert 500 not in statuses
                assert statuses.count(200) >= 1   # someone was served
                sheds = [(s, h, b) for s, h, b in results if s == 503]
                assert sheds                      # someone was shed
                for s, h, b in sheds:
                    assert h.get("retry-after") == "2"
                    err = json.loads(b)["error"]
                    assert err["code"] == 503
                    assert "overloaded" in err["message"]
                # writes and admin endpoints are never shed
                st, _, _ = await self._fetch(port, "/api/version")
                assert st == 200
                st, _, body = await self._fetch(port, "/api/health")
                assert st == 200
                health = json.loads(body)
                assert health["admission"]["shed_total"] == len(sheds)
                assert health["admission"]["shed"]["inflight"] \
                    == len(sheds)
            finally:
                await server.stop()

        asyncio.run(scenario())

    def test_connection_flood_structured_refusal(self):
        import asyncio
        from opentsdb_tpu import TSDB, Config
        tsdb = TSDB(Config(**self.BASE_CFG, **{
            "tsd.core.connections.limit": "2"}))

        async def scenario():
            server, port = await self._start(tsdb)
            try:
                held = []
                for _ in range(2):
                    held.append(await asyncio.open_connection(
                        "127.0.0.1", port))
                # the third connection is refused with a STRUCTURED
                # body before the close, not a silent reset
                reader, writer = await asyncio.open_connection(
                    "127.0.0.1", port)
                raw = await asyncio.wait_for(reader.read(), 10)
                writer.close()
                assert b"503" in raw.split(b"\r\n", 1)[0]
                body = raw.partition(b"\r\n\r\n")[2]
                err = json.loads(body)["error"]
                assert err["code"] == 503
                assert "Connection limit" in err["message"]
                assert tsdb.config  # server still alive
                # the refusal shows up in stats AND health
                collector = tsdb.stats.collect()
                refused = [v for n, v, _ in collector.records
                           if n == "tsd.connections.refused"]
                assert refused and refused[0] >= 1
                for _, w in held:
                    w.close()
                await asyncio.sleep(0.1)
                st, _, body = await self._fetch(port, "/api/health")
                assert st == 200
                assert json.loads(body)["connections"]["refused"] >= 1
            finally:
                await server.stop()

        asyncio.run(scenario())

    def test_armed_fault_sweep_never_500s(self):
        """Overload sweep with faults armed everywhere at once: WAL
        fsync down, device pipeline failing — puts stay acknowledged
        (degraded durability), queries answer from the host fallback,
        health reports every degradation, and NOTHING 500s or hangs."""
        import asyncio
        from opentsdb_tpu import TSDB, Config
        tsdb = TSDB(Config(**self.BASE_CFG, **{
            "tsd.query.host_tail_max_cells": "-1",
            "tsd.query.host_tail_max_cells_linear": "-1",
            "tsd.query.breaker.failure_threshold": "1",
            "tsd.storage.wal.retry.attempts": "2",
            "tsd.storage.wal.retry.base_ms": "1",
            "tsd.faults.wal.fsync_error_rate": "1.0",
            "tsd.faults.device.compile_error_rate": "1.0"},
            **{"tsd.storage.data_dir": ""}))
        tsdb.add_point("o.m", BASE + 30, 1.0, {"host": "a"})

        async def scenario():
            server, port = await self._start(tsdb)
            try:
                window = f"start={BASE * 1000}&end={(BASE + 60) * 1000}"
                paths = [
                    f"/api/query?{window}&m=sum:o.m",
                    f"/api/query?{window}&m=max:o.m",
                    "/api/health", "/api/version", "/api/stats",
                ]
                for path in paths:
                    status, _, _ = await self._fetch(port, path)
                    assert status == 200, (path, status)
                assert tsdb.device_breaker.state == "open"
                _, _, body = await self._fetch(port, "/api/health")
                health = json.loads(body)
                assert health["status"] == "degraded"
                assert "breaker:device.pipeline" in health["causes"]
                assert health["faults"]["armed"]
            finally:
                await server.stop()

        asyncio.run(scenario())


@pytest.mark.robustness
class TestBreakerTripFallbackRecovery:
    """Breaker lifecycle through the HTTP router: trip on injected
    device failures (clients still get 200s from the host fallback),
    serve degraded while open, recover through the half-open probe."""

    def test_full_lifecycle(self):
        t = TSDB(Config(**{
            "tsd.core.auto_create_metrics": "true",
            "tsd.tpu.warmup": "false",
            "tsd.query.host_tail_max_cells": "-1",
            "tsd.query.host_tail_max_cells_linear": "-1",
            "tsd.query.breaker.failure_threshold": "2",
            "tsd.query.breaker.reset_timeout_ms": "60000",
            # repeats must reach the device each time, not the
            # serve-path result cache in front of the breaker
            "tsd.query.cache.enable": "false",
            "tsd.faults.device.compile_error_count": "2"}))
        for i in range(20):
            t.add_point("b.m", BASE + i * 10, float(i), {"host": "a"})
        router = HttpRpcRouter(t)

        def q():
            return router.handle(HttpRequest(
                "GET", "/api/query",
                {"start": [str(BASE * 1000)],
                 "end": [str((BASE + 3600) * 1000)],
                 "m": ["sum:b.m"]}, {}, b""))

        def health():
            return json.loads(router.handle(HttpRequest(
                "GET", "/api/health", {}, {}, b"")).body)

        # trip: both injected failures answered by the host fallback
        assert q().status == 200
        assert q().status == 200
        assert t.device_breaker.state == "open"
        assert health()["breakers"]["device.pipeline"]["fallbacks"] == 2
        # degraded serving while open
        assert q().status == 200
        assert health()["status"] == "degraded"
        # recovery: past the reset window the probe runs on the device
        # (fault exhausted) and closes the breaker
        t.device_breaker._opened_at -= 61
        t.drop_caches()
        assert q().status == 200
        assert t.device_breaker.state == "closed"
        assert health()["status"] == "ok"


class TestApiVersionNegotiation:
    """(ref: HttpQuery.apiVersion, MAX_API_VERSION=1 — unknown
    versions are a 400, not silently treated as v1)."""

    def test_v1_and_unversioned_ok(self, router):
        for path in ("/api/version", "/api/v1/version"):
            assert router.handle(HttpRequest("GET", path, {}, {},
                                             b"")).status == 200

    @pytest.mark.parametrize("ver", ["v2", "v9", "v0", "v999"])
    def test_unsupported_version_400(self, router, ver):
        resp = router.handle(HttpRequest(
            "GET", f"/api/{ver}/version", {}, {}, b""))
        assert resp.status == 400
        assert b"API version" in resp.body

    def test_non_ascii_version_digits_not_accepted(self, router):
        # str.isdigit() is true for non-ASCII digits; the matcher is
        # ASCII-only so these fall through to (and 404 as) unknown
        # endpoints rather than parsing as versions
        for seg in ("v\u00b2", "v\u0661"):
            resp = router.handle(HttpRequest(
                "GET", f"/api/{seg}/version", {}, {}, b""))
            assert resp.status == 404, (seg, resp.status)


class TestSiblingPrefixStaticContainment:
    """Static containment must compare with a trailing separator: a
    SIBLING directory sharing the root's name prefix (static_private
    next to static) defeats a bare startswith check (RFC-agnostic
    path-traversal hardening)."""

    @pytest.fixture()
    def sibling_router(self, tmp_path):
        root = tmp_path / "static"
        root.mkdir()
        (root / "ok.txt").write_text("public")
        sibling = tmp_path / "static_private"
        sibling.mkdir()
        (sibling / "secret.txt").write_text("SECRET")
        t = TSDB(Config(**{"tsd.http.staticroot": str(root)}))
        return HttpRpcRouter(t)

    def test_sibling_prefix_dir_is_404(self, sibling_router):
        resp = sibling_router.handle(HttpRequest(
            "GET", "/s/../static_private/secret.txt", {}, {}, b""))
        assert resp.status == 404
        assert b"SECRET" not in (resp.body or b"")

    def test_root_files_still_serve(self, sibling_router):
        resp = sibling_router.handle(HttpRequest(
            "GET", "/s/ok.txt", {}, {}, b""))
        assert resp.status == 200 and resp.body == b"public"


@pytest.mark.robustness
class TestTransferEncodingFraming:
    """RFC 7230 §3.3.3: a Transfer-Encoding whose FINAL coding is not
    chunked leaves the body length unknowable — the server must answer
    400 and close instead of falling through to Content-Length
    framing (request-smuggling precondition)."""

    @staticmethod
    async def _raw_request(port, raw: bytes):
        import asyncio
        reader, writer = await asyncio.open_connection("127.0.0.1",
                                                       port)
        writer.write(raw)
        await writer.drain()
        data = await asyncio.wait_for(reader.read(), 15)
        writer.close()
        return data

    def _run(self, raw: bytes, cfg=None):
        import asyncio
        from opentsdb_tpu import TSDB, Config
        from opentsdb_tpu.tsd.server import TSDServer
        tsdb = TSDB(Config(**{
            "tsd.core.auto_create_metrics": "true",
            "tsd.tpu.warmup": "false", "tsd.tpu.platform": "cpu",
            **(cfg or {})}))

        async def scenario():
            server = TSDServer(tsdb, host="127.0.0.1", port=0)
            await server.start()
            try:
                port = server._server.sockets[0].getsockname()[1]
                return await self._raw_request(port, raw)
            finally:
                await server.stop()

        return asyncio.run(scenario())

    def test_non_chunked_final_coding_400_and_close(self):
        raw = (b"POST /api/put HTTP/1.1\r\n"
               b"Host: x\r\nTransfer-Encoding: gzip\r\n"
               b"Content-Length: 5\r\n\r\nhello")
        data = self._run(raw)
        head = data.split(b"\r\n", 1)[0]
        assert b"400" in head
        # the connection was closed (read() returned EOF after the
        # response) and the refusal names the framing problem
        assert b"Transfer-Encoding" in data
        assert b"Connection: close" in data

    def test_gzip_then_chunked_still_allowed_when_enabled(self):
        # final coding chunked: legal per RFC 7230; the server already
        # dechunks (it does not decompress, but framing is sound)
        body = b"5\r\nhello\r\n0\r\n\r\n"
        raw = (b"POST /api/put HTTP/1.1\r\n"
               b"Host: x\r\nConnection: close\r\n"
               b"Transfer-Encoding: chunked\r\n\r\n" + body)
        data = self._run(raw, {
            "tsd.http.request_enable_chunked": "true"})
        head = data.split(b"\r\n", 1)[0]
        # "hello" is not valid JSON -> a 400 from the HANDLER, but the
        # framing was accepted (not the TE refusal)
        assert b"400" in head
        assert b"Transfer-Encoding" not in data
