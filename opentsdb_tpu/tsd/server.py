"""The TSD network server (ref: ``src/tsd/PipelineFactory.java:44``,
``src/tools/TSDMain.java:48``).

One asyncio server on one port speaking both HTTP and the telnet line
protocol, distinguished by sniffing the first bytes of a connection
exactly like the reference's ``DetectHttpOrRpc`` handler
(PipelineFactory.java:134-171): if the first token looks like an HTTP
method, the connection is HTTP (with keep-alive); otherwise each line
is a telnet command. Connection counting mirrors
``ConnectionManager.java:37``; optional auth wraps the first exchange
(AuthenticationChannelHandler.java:50).
"""

from __future__ import annotations

import asyncio
import concurrent.futures
import json
import logging
import re
import threading
import time
import urllib.parse

from opentsdb_tpu.auth.simple import AuthStatus
from opentsdb_tpu.tsd.http_api import HttpRequest, HttpResponse, \
    HttpRpcRouter
from opentsdb_tpu.tsd.telnet import (TelnetCloseConnection, TelnetRouter,
                                     TelnetServerShutdown)

LOG = logging.getLogger("tsd.server")

_HTTP_METHODS = (b"GET ", b"POST", b"PUT ", b"DELE", b"HEAD", b"OPTI",
                 b"PATC")


def _api_endpoint(path: str) -> str:
    """The path's first endpoint segment with the ``/api[/vN]``
    prefix stripped (ASCII-only version match, agreeing with
    HttpRpcRouter._dispatch's parse)."""
    parts = [p for p in path.split("/") if p]
    if parts and parts[0] == "api":
        parts = parts[1:]
        if parts and re.fullmatch(r"v[0-9]+", parts[0]):
            parts = parts[1:]
    return parts[0] if parts else ""


def _structured_error(status: int, message: str,
                      details: str = "") -> HttpResponse:
    """A PR-1-shaped structured error body for the server framing
    layer, which answers before any serializer is bound (the
    serializer-owning twin is ``format_error``). Built by json.dumps
    so the shape can never drift from what operators alert on."""
    doc: dict = {"error": {"code": status, "message": message}}
    if details:
        doc["error"]["details"] = details
    return HttpResponse(status, json.dumps(doc).encode())


def _is_query_path(path: str) -> bool:
    """True for the endpoints ``tsd.query.timeout`` governs — the data
    query surface only (ref: the reference expires *queries*, not
    writes; a timed-out /api/put would 504 while the write still
    commits, making client retries duplicate side effects)."""
    return _api_endpoint(path) in ("query", "q")


def _is_put_path(path: str) -> bool:
    """The write front door (``/api/put``) — feeds latency_put."""
    return _api_endpoint(path) == "put"


class IdleTimeout(Exception):
    """A connection sat idle past ``tsd.core.socket.timeout``."""


class ConnectionManager:
    """(ref: src/tsd/ConnectionManager.java:37)"""

    def __init__(self, max_connections: int = 0):
        self.max_connections = max_connections
        self.open_connections = 0
        self.total_connections = 0
        self.rejected_connections = 0
        self.exceptions_unknown = 0
        self.idle_closed = 0

    def accept(self) -> bool:
        if self.max_connections and \
                self.open_connections >= self.max_connections:
            self.rejected_connections += 1
            return False
        self.open_connections += 1
        self.total_connections += 1
        return True

    def release(self) -> None:
        self.open_connections -= 1

    def collect_stats(self, collector) -> None:
        collector.record("connectionmgr.connections",
                         self.open_connections, type="open")
        collector.record("connectionmgr.connections",
                         self.total_connections, type="total")
        collector.record("connectionmgr.exceptions",
                         self.rejected_connections, type="rejected")
        collector.record("connectionmgr.connections", self.idle_closed,
                         type="idle_closed")
        # handler errors (the reference's ConnectionManager exports
        # exceptions_unknown; this counter was bumped but never
        # exported until tsdlint's counter-export pass flagged it)
        collector.record("connectionmgr.exceptions",
                         self.exceptions_unknown, type="unknown")
        # refusal counter under its own name so dashboards can alert
        # on it without parsing the connectionmgr.exceptions tag
        collector.record("connections.refused",
                         self.rejected_connections)


class AdmissionController:
    """Query-surface load shedding (the graceful twin of the hard
    ``tsd.core.connections.limit`` refusal): once in-flight queries or
    the worker-pool queue depth cross their thresholds, new queries
    are answered with a structured 503 + ``Retry-After`` instead of
    queueing without bound. Writes and admin endpoints are never shed
    — during overload, operators still need /api/health and clients
    still need their puts acknowledged."""

    CAUSES = ("inflight", "queue")

    def __init__(self, max_inflight: int = 0, max_queue: int = 0,
                 retry_after_s: int = 1):
        self.max_inflight = max_inflight
        self.max_queue = max_queue
        self.retry_after_s = max(retry_after_s, 1)
        # started() runs on the event loop, finished() on the worker
        # thread (a timed-out query's asyncio future is cancelled
        # while the thread keeps running — only the THREAD finishing
        # frees the slot, or retrying clients would be admitted onto
        # an already-saturated pool)
        self._lock = threading.Lock()
        self.inflight = 0
        self.shed_counts = {cause: 0 for cause in self.CAUSES}

    def try_admit(self, queue_depth: int) -> str | None:
        """The shed cause, or None when admitted (caller must then
        pair the admit with :meth:`started`)."""
        with self._lock:
            if self.max_inflight and self.inflight >= self.max_inflight:
                self.shed_counts["inflight"] += 1
                return "inflight"
            if self.max_queue and queue_depth >= self.max_queue:
                self.shed_counts["queue"] += 1
                return "queue"
            return None

    def started(self) -> None:
        with self._lock:
            self.inflight += 1

    def finished(self) -> None:
        with self._lock:
            self.inflight -= 1

    @property
    def total_shed(self) -> int:
        return sum(self.shed_counts.values())

    def collect_stats(self, collector) -> None:
        collector.record("admission.inflight", self.inflight)
        for cause, n in self.shed_counts.items():
            collector.record("admission.shed", n, cause=cause)

    def health_info(self, queue_depth: int) -> dict:
        return {
            "inflight_queries": self.inflight,
            "queue_depth": queue_depth,
            "max_inflight": self.max_inflight,
            "max_queue": self.max_queue,
            "retry_after_s": self.retry_after_s,
            "shed": dict(self.shed_counts),
            "shed_total": self.total_shed,
        }


class TSDServer:
    """(ref: TSDMain.java:71)"""

    def __init__(self, tsdb, host: str | None = None,
                 port: int | None = None):
        self.tsdb = tsdb
        self.host = host or tsdb.config.get_string("tsd.network.bind",
                                                   "0.0.0.0")
        self.port = port if port is not None else \
            tsdb.config.get_int("tsd.network.port", 4242)
        self.http_router = HttpRpcRouter(tsdb)
        self.http_router.server = self
        self.telnet_router = TelnetRouter(tsdb, self)
        self.connections = ConnectionManager(
            tsdb.config.get_int("tsd.core.connections.limit", 0))
        tsdb.stats.register(self.connections)
        # query admission control (load shedding): structured 503 +
        # Retry-After once in-flight queries / queue depth cross the
        # configured thresholds (0 = unlimited, the old behavior)
        self.admission = AdmissionController(
            max_inflight=tsdb.config.get_int(
                "tsd.query.admission.max_inflight"),
            max_queue=tsdb.config.get_int(
                "tsd.query.admission.max_queue"),
            retry_after_s=tsdb.config.get_int(
                "tsd.query.admission.retry_after_s"))
        tsdb.stats.register(self.admission)
        # canned refusal for over-limit connections: a structured 503
        # beats a silent close (the reference just drops the channel,
        # ConnectionManager.java:87 — clients saw a reset and could
        # not tell overload from outage)
        refusal_body = json.dumps({"error": {
            "code": 503, "message": "Connection limit exceeded",
            "details": "tsd.core.connections.limit reached; "
                       "retry later"}}).encode()
        self._refusal_bytes = (
            b"HTTP/1.1 503 Service Unavailable\r\n"
            b"Content-Type: application/json; charset=UTF-8\r\n"
            b"Retry-After: " +
            str(self.admission.retry_after_s).encode() +
            b"\r\nContent-Length: " + str(len(refusal_body)).encode() +
            b"\r\nConnection: close\r\n\r\n" + refusal_body)
        self._server: asyncio.AbstractServer | None = None
        self._shutdown = asyncio.Event()
        self._loop: asyncio.AbstractEventLoop | None = None
        self.cors_domains = [
            d.strip() for d in tsdb.config.get_string(
                "tsd.http.request.cors_domains", "").split(",")
            if d.strip()]
        # ms; 0 = no limit (ref: tsd.query.timeout expiring queries)
        self.query_timeout_ms = tsdb.config.get_int("tsd.query.timeout",
                                                    0)
        # queries run on their own bounded pool so abandoned (timed-out)
        # query threads can't starve puts and admin endpoints
        self._query_pool = concurrent.futures.ThreadPoolExecutor(
            max_workers=tsdb.config.get_int("tsd.query.workers", 8),
            thread_name_prefix="tsd-query")
        # idle-connection reaper (ref: PipelineFactory.java:169 installs
        # an IdleStateHandler with tsd.core.socket.timeout seconds of
        # all-idle): every await on the client — reads AND backpressure
        # drains — carries this deadline, so a stalled or wedged client
        # cannot hold a connection (or a streaming worker) forever.
        # 0 (the reference default) disables reaping.
        self.socket_timeout_s = tsdb.config.get_int(
            "tsd.core.socket.timeout", 0)

    async def _on_client(self, coro):
        """Await a client-facing read/drain under the idle deadline."""
        if self.socket_timeout_s <= 0:
            return await coro
        try:
            return await asyncio.wait_for(coro, self.socket_timeout_s)
        except asyncio.TimeoutError:
            self.connections.idle_closed += 1
            raise IdleTimeout() from None

    async def _refuse(self, reader, writer, response,
                      version="HTTP/1.1"):
        """Answer an early protocol error and drain briefly before the
        connection closes: closing with unread request-body bytes in
        the kernel buffer sends RST, which can destroy the response
        in flight (the client then sees a dropped connection instead
        of the 4xx)."""
        await self._write_response(writer, response, version, False)
        try:
            for _ in range(16):
                chunk = await asyncio.wait_for(reader.read(65536), 0.2)
                if not chunk:
                    break
        except (asyncio.TimeoutError, ConnectionError):
            pass

    async def _read_chunked(self, reader, buffer: bytes,
                            max_bytes: int):
        """Dechunk a Transfer-Encoding: chunked request body
        (ref: Netty's HttpChunkAggregator behind
        tsd.http.request_enable_chunked). Returns (body, remainder)
        or (None, b"") on a malformed/oversized stream (the caller
        drops the connection — framing is unrecoverable)."""
        body = bytearray()
        buffer = bytearray(buffer)  # immutable += is quadratic
        while True:
            while b"\r\n" not in buffer:
                if len(buffer) > 8192:
                    # a size line is a few hex digits; a stream that
                    # never sends CRLF is hostile, don't buffer it
                    return None, b"", "framing"
                chunk = await self._on_client(reader.read(65536))
                if not chunk:
                    return None, b"", "framing"
                buffer += chunk
            size_line, _, rest = bytes(buffer).partition(b"\r\n")
            buffer = bytearray(rest)
            # chunk extensions after ';' are ignored per RFC 9112;
            # strict ASCII hex only — python's int() leniency
            # (underscores, signs, unicode digits) is a framing-
            # disagreement / request-smuggling precondition
            hex_part = size_line.split(b";")[0].strip()
            if not re.fullmatch(rb"[0-9A-Fa-f]{1,16}", hex_part):
                return None, b"", "framing"
            size = int(hex_part, 16)
            if len(body) + size > max_bytes:
                # framing is still intact here: the caller can answer
                # 413 like the Content-Length path does
                return None, b"", "too_large"
            if size == 0:
                # terminal chunk: consume optional trailer fields up
                # to the blank line so keep-alive framing stays in
                # sync (ref: RFC 9112 trailer section)
                while b"\r\n" not in buffer or not (
                        buffer.startswith(b"\r\n")
                        or b"\r\n\r\n" in buffer):
                    if len(buffer) > 8192:
                        return None, b"", "framing"
                    chunk = await self._on_client(reader.read(65536))
                    if not chunk:
                        return None, b"", "framing"
                    buffer += chunk
                if buffer.startswith(b"\r\n"):
                    del buffer[:2]
                else:
                    buffer = bytearray(
                        bytes(buffer).split(b"\r\n\r\n", 1)[1])
                return bytes(body), bytes(buffer), ""
            while len(buffer) < size + 2:  # data + trailing CRLF
                chunk = await self._on_client(reader.read(65536))
                if not chunk:
                    return None, b"", "framing"
                buffer += chunk
            if buffer[size:size + 2] != b"\r\n":
                # declared size disagrees with actual framing: fail
                # fast instead of splicing attacker-chosen bytes
                return None, b"", "framing"
            body += buffer[:size]
            del buffer[:size + 2]

    # ------------------------------------------------------------------

    async def start(self) -> None:
        self._loop = asyncio.get_running_loop()
        self._server = await asyncio.start_server(
            self._handle_connection, self.host, self.port,
            backlog=self.tsdb.config.get_int("tsd.network.backlog", 3072),
            reuse_address=self.tsdb.config.get_bool(
                "tsd.network.reuse_address", True))
        # pre-compile the common query shape buckets in the background
        # so first queries of each class run warm (tsd.tpu.warmup)
        from opentsdb_tpu.tsd.warmup import start_warmup_thread
        self._warmup_thread = start_warmup_thread(self.tsdb)
        # the data-lifecycle sweeper (retention / demotion /
        # compaction, opentsdb_tpu/lifecycle/) runs on its own
        # background thread; no-op when tsd.lifecycle.enable is off
        # or tsd.lifecycle.interval_s <= 0 (manual sweeps only, via
        # POST /api/lifecycle/sweep). Stopped by TSDB.shutdown.
        lifecycle = self.tsdb.lifecycle
        if lifecycle is not None:
            lifecycle.start()
        # cluster router (opentsdb_tpu/cluster/): a tsd.cluster.role =
        # router TSD owns the shard map. Instantiating it here (the
        # TSDB property is lazy) validates tsd.cluster.peers at
        # startup instead of on the first request, and starts the
        # spool replay thread so handoff drains even with no traffic.
        # Stopped by TSDB.shutdown.
        cluster = self.tsdb.cluster
        if cluster is not None:
            cluster.start()
        # streaming fold workers (opentsdb_tpu/streaming/workers.py):
        # the registry is lazy and the pool self-starts on first
        # hand-off, but a serving TSD pays worker-thread creation at
        # startup, not inside the first ingest burst that crosses the
        # drain threshold. Stopped by TSDB.shutdown ->
        # ContinuousQueryRegistry.shutdown.
        streaming = self.tsdb.streaming
        if streaming is not None and streaming.workers.enabled:
            streaming.workers.start()
        # self-driving control plane (opentsdb_tpu/control/): shape
        # mining, tenant QoS refresh, placement assessment on one
        # background loop. No-op unless tsd.control.enable; stopped
        # FIRST by TSDB.shutdown (it steers the other subsystems).
        control = self.tsdb.control
        if control is not None:
            control.start()
        # self-telemetry pump (obs/telemetry.py): no-op unless
        # tsd.stats.self_interval > 0. Stopped by TSDB.shutdown.
        self.tsdb.telemetry.start()
        # continuous sampling profiler (obs/profiler.py): the
        # always-on low-rate ring behind GET /api/profile — the last
        # tsd.profile.ring_s seconds of per-role stack samples are
        # queryable after the fact. No-op when tsd.profile.enable is
        # off or hz <= 0. Stopped (joined) by TSDB.shutdown.
        self.tsdb.profiler.start()
        addr = self._server.sockets[0].getsockname()
        LOG.info("Ready to serve on %s:%s", addr[0], addr[1])

    async def serve_forever(self) -> None:
        if self._server is None:
            await self.start()
        await self._shutdown.wait()
        await self.stop()

    async def stop(self) -> None:
        # signal the warmup thread to stop between compiles; joined
        # AFTER the listener closes (a thread mid-JIT at interpreter
        # teardown can crash inside XLA, but new connections must stop
        # being accepted immediately)
        stop_ev = getattr(self.tsdb, "_warmup_stop", None)
        if stop_ev is not None:
            stop_ev.set()
        if self._server is not None:
            self._server.close()
            try:
                # wait_closed (3.12+) waits for every live handler:
                # a keep-alive client that never disconnects must not
                # wedge shutdown forever
                await asyncio.wait_for(self._server.wait_closed(), 10)
            except asyncio.TimeoutError:
                LOG.warning("connections still open after 10s; "
                            "forcing shutdown")
            self._server = None
        # cluster wire sessions poll the listener and self-terminate,
        # but a caller that stops the loop right after this return
        # would abandon them mid-poll (and leak their sockets):
        # cancel deterministically instead of racing the poll
        sessions = list(getattr(self, "_wire_sessions", ()))
        for t in sessions:
            t.cancel()
        if sessions:
            await asyncio.gather(*sessions, return_exceptions=True)
        th = getattr(self, "_warmup_thread", None)
        if th is not None and th.is_alive():
            await asyncio.get_event_loop().run_in_executor(
                None, th.join, 30)
        self._query_pool.shutdown(wait=False)
        self.tsdb.shutdown()

    def request_shutdown(self) -> None:
        # callable from executor threads (HTTP diediedie runs on the
        # request worker pool): asyncio.Event.set is not thread-safe
        if self._loop is not None and self._loop.is_running():
            self._loop.call_soon_threadsafe(self._shutdown.set)
        else:
            self._shutdown.set()

    # ------------------------------------------------------------------

    def query_queue_depth(self) -> int:
        """Pending (unstarted) tasks in the query worker pool.
        ``_work_queue`` is a private CPython attribute; report 0 if a
        future runtime hides it — admission then falls back to the
        in-flight limit alone instead of 500ing every query."""
        queue = getattr(self._query_pool, "_work_queue", None)
        try:
            return queue.qsize() if queue is not None else 0
        except Exception:  # noqa: BLE001 - runtime-specific queue
            return 0

    async def _handle_connection(self, reader: asyncio.StreamReader,
                                 writer: asyncio.StreamWriter) -> None:
        if not self.connections.accept():
            # shed with a structured body; the protocol is unknown at
            # this point (nothing read yet) so speak HTTP — a telnet
            # client sees one junk line before the close, an HTTP
            # client sees a proper 503 + Retry-After
            try:
                writer.write(self._refusal_bytes)
                await asyncio.wait_for(writer.drain(), 1)
            except Exception:  # noqa: BLE001
                # tsdlint: allow[swallow] best-effort refusal body on
                # an over-limit connection; the close below is the
                # real answer and the refusal is already counted
                pass
            writer.close()
            return
        try:
            # protocol sniff (ref: DetectHttpOrRpc.decode :134)
            first = await self._on_client(reader.read(4))
            if not first:
                return
            if first in _HTTP_METHODS or first[:3] == b"GET":
                await self._serve_http(first, reader, writer)
            elif first == b"TSDW":
                await self._serve_wire(reader, writer)
            else:
                await self._serve_telnet(first, reader, writer)
        except (ConnectionResetError, asyncio.IncompleteReadError):
            pass
        except IdleTimeout:
            LOG.info("closing idle connection (tsd.core.socket.timeout="
                     "%ds)", self.socket_timeout_s)
        except TelnetServerShutdown:
            writer.write(b"Cleanup complete, shutting down.\n")
            await writer.drain()
            self.request_shutdown()
        except Exception:  # noqa: BLE001
            LOG.exception("connection handler error")
            self.connections.exceptions_unknown += 1
        finally:
            self.connections.release()
            try:
                writer.close()
                await writer.wait_closed()
            except Exception:  # noqa: BLE001
                # tsdlint: allow[swallow] teardown race on an already-
                # reset connection; the handler's real errors were
                # logged and counted above
                pass

    # -- cluster wire --------------------------------------------------

    async def _serve_wire(self, reader, writer) -> None:
        """Binary columnar cluster wire session (router sniffed in by
        the ``TSDW`` magic). Frames are read directly — NOT through
        ``_on_client`` — because a persistent pipelined link is idle
        between deliveries by design; its lifetime is bounded by the
        session's listener watchdog (and ``stop()``'s deterministic
        cancel) instead of the idle reaper."""
        from opentsdb_tpu.cluster import wire as wire_mod
        sessions = getattr(self, "_wire_sessions", None)
        if sessions is None:
            sessions = self._wire_sessions = set()
        task = asyncio.current_task()
        sessions.add(task)
        try:
            await wire_mod.serve_wire(self, reader, writer)
        finally:
            sessions.discard(task)

    # -- telnet --------------------------------------------------------

    async def _serve_telnet(self, first: bytes, reader, writer) -> None:
        buffer = first
        authed = self.tsdb.authentication is None
        auth_state = None
        while True:
            if buffer.find(b"\n") < 0:
                chunk = await self._on_client(reader.read(65536))
                if not chunk:
                    break
                buffer += chunk
                continue
            # drain EVERY complete line already buffered: a pipelined
            # put burst decodes as ONE columnar batch (one WAL write +
            # one group-committed fsync) instead of one command — and
            # one fsync — per loop turn (TelnetRouter.execute_lines)
            raw, _, buffer = buffer.rpartition(b"\n")
            lines = [ln.rstrip(b"\r").decode("utf-8", "replace")
                     for ln in raw.split(b"\n")]
            idx = 0
            while not authed and idx < len(lines):
                # first exchange must be auth
                # (ref: AuthenticationChannelHandler.java:50)
                words = lines[idx].split()
                idx += 1
                if words and words[0] == "auth":
                    state = self.tsdb.authentication.authenticate_telnet(
                        words)
                    if state.status == AuthStatus.SUCCESS:
                        authed = True
                        auth_state = state
                        writer.write(b"auth_success\n")
                    else:
                        writer.write(b"auth_fail\n")
                else:
                    writer.write(b"auth_fail\n")
                await self._on_client(writer.drain())
            if idx >= len(lines):
                continue
            responses, deferred = self.telnet_router.execute_lines(
                lines[idx:], auth=auth_state)
            if responses:
                writer.write("\n".join(responses).encode() + b"\n")
                await self._on_client(writer.drain())
            if isinstance(deferred, TelnetCloseConnection):
                return
            if deferred is not None:
                raise deferred

    # -- http ----------------------------------------------------------

    async def _serve_http(self, first: bytes, reader, writer) -> None:
        buffer = first
        keep_alive = True
        while keep_alive:
            # the instant this request's first bytes are in the buffer
            # (query.receive runs from here to received_at): now when
            # a pipelined or sniffed request already sits there, else
            # the return of the first read that brings any
            first_byte_at = time.monotonic() if buffer else 0.0
            # read until end of headers
            while b"\r\n\r\n" not in buffer:
                chunk = await self._on_client(reader.read(65536))
                if not chunk:
                    return
                if not first_byte_at:
                    first_byte_at = time.monotonic()
                buffer += chunk
            head, _, buffer = buffer.partition(b"\r\n\r\n")
            lines = head.decode("latin-1").split("\r\n")
            try:
                method, target, version = lines[0].split(" ", 2)
            except ValueError:
                return
            headers = {}
            for hline in lines[1:]:
                name, _, val = hline.partition(":")
                headers[name.strip().lower()] = val.strip()
            max_chunk = self.tsdb.config.get_int(
                "tsd.http.request.max_chunk", 1048576)
            te_tokens = [t.strip() for t in
                         headers.get("transfer-encoding", "")
                         .lower().split(",") if t.strip()]
            if te_tokens and te_tokens[-1] != "chunked":
                # RFC 7230 §3.3.3: when Transfer-Encoding is present
                # and its FINAL coding is not chunked, the body length
                # is unknowable — falling through to Content-Length
                # framing is a request-smuggling precondition behind
                # intermediaries. 400 and close; the connection's
                # framing cannot be resynchronized.
                await self._refuse(
                    reader, writer, HttpResponse(
                        400, b'{"error":{"code":400,"message":'
                        b'"Unsupported Transfer-Encoding: final '
                        b'coding must be chunked"}}'))
                return
            if te_tokens:
                # final coding is chunked (anything else was refused
                # above). (ref: tsd.http.request_enable_chunked —
                # default off, HttpQuery rejects chunked with a 400)
                # the reference's dotted spelling, with the old
                # underscore form as a legacy alias (either enables)
                if not (self.tsdb.config.get_bool(
                            "tsd.http.request.enable_chunked", False)
                        or self.tsdb.config.get_bool(
                            "tsd.http.request_enable_chunked",
                            False)):
                    await self._refuse(
                        reader, writer, HttpResponse(
                            400, b'{"error":{"code":400,"message":'
                            b'"Chunked request not supported; set '
                            b'tsd.http.request.enable_chunked"}}'))
                    return
                body, buffer, err = await self._read_chunked(
                    reader, buffer, max_chunk * 64)
                if body is None:
                    if err == "too_large":
                        # framing intact: answer like the
                        # Content-Length path instead of a silent drop
                        await self._refuse(
                            reader, writer,
                            HttpResponse(413, b"content too large"))
                    return
            else:
                cl = headers.get("content-length", "0")
                if not re.fullmatch(r"[0-9]{1,18}", cl):
                    cl = None
                try:
                    length = int(cl)
                except (TypeError, ValueError):
                    await self._refuse(
                        reader, writer, HttpResponse(
                            400, b'{"error":{"code":400,"message":'
                            b'"Invalid Content-Length"}}'))
                    return
                if length > max_chunk * 64 or length < 0:
                    await self._refuse(
                        reader, writer,
                        HttpResponse(413, b"content too large"))
                    return
                while len(buffer) < length:
                    chunk = await self._on_client(reader.read(65536))
                    if not chunk:
                        return
                    buffer += chunk
                body, buffer = buffer[:length], buffer[length:]
            parsed = urllib.parse.urlsplit(target)
            params = urllib.parse.parse_qs(parsed.query,
                                           keep_blank_values=True)
            peer = writer.get_extra_info("peername")
            keep_alive = (version == "HTTP/1.1" and
                          headers.get("connection", "").lower() != "close")
            t0 = time.monotonic()
            request = HttpRequest(
                method=method.upper(), path=parsed.path, params=params,
                headers=headers, body=body,
                remote=f"{peer[0]}:{peer[1]}" if peer else "",
                received_at=t0, first_byte_at=first_byte_at)
            # is_query: a query path; answered: its worker's response
            # is the one written (not a shed's or a timeout's)
            is_query = answered = False
            if method.upper() == "OPTIONS":
                # preflight bypasses auth — browsers never attach
                # Authorization to OPTIONS
                response = self._cors_preflight(request)
            elif self.tsdb.authentication is not None and \
                    (auth_state := self.tsdb.authentication
                     .authenticate_http(headers)).status \
                    != AuthStatus.SUCCESS:
                # first-exchange auth, HTTP flavor (ref:
                # AuthenticationChannelHandler.java:50)
                response = HttpResponse(
                    401, b'{"error":{"code":401,"message":'
                    b'"Authentication required"}}',
                    headers={"WWW-Authenticate":
                             'Basic realm="opentsdb"'})
            else:
                if self.tsdb.authentication is not None:
                    request.auth = auth_state
                is_query = _is_query_path(
                    urllib.parse.unquote(parsed.path))
                # tenant identity rides the admission seam: the raw
                # _control read keeps the uncontrolled TSD at one
                # attribute load per request (streaming-tap idiom)
                ctl = self.tsdb._control
                governor = ctl.qos if ctl is not None else None
                tenant = None
                if is_query and governor is not None:
                    try:
                        tenant = governor.tenant_of(headers)
                    except Exception:  # tsdlint: allow[swallow] identity extraction can never refuse a query; the request rides untenanted
                        tenant = None
                shed_cause = self.admission.try_admit(
                    self.query_queue_depth()) if is_query else None
                if shed_cause is None and tenant is not None:
                    # weighted fair share of the SAME in-flight
                    # budget: one tenant at its share sheds (cause
                    # "tenant") while under-share tenants admit
                    try:
                        shed_cause = governor.try_admit(
                            tenant, self.admission.max_inflight)
                    except Exception:  # tsdlint: allow[swallow] QoS bookkeeping must degrade to plain global admission, never to a 500
                        shed_cause = None
                if shed_cause is not None:
                    response = self._overload_response(shed_cause)
                    LOG.warning("shedding query %s (%s; %d in flight)",
                                parsed.path, shed_cause,
                                self.admission.inflight)
                else:
                    if is_query:
                        # the slot is freed by the WORKER finishing,
                        # not the response: a 504'd query still holds
                        # its thread (see AdmissionController)
                        self.admission.started()
                        if tenant is not None:
                            governor.started(tenant)

                        def tracked(req=request, _tenant=tenant,
                                    _gov=governor):
                            if _tenant is not None:
                                # bound for the worker's duration so
                                # the result-cache insert gate can
                                # bill bytes to the right tenant
                                _gov.bind(_tenant)
                            try:
                                return self.http_router.handle(req)
                            finally:
                                if _tenant is not None:
                                    _gov.unbind()
                                    _gov.finished(_tenant)
                                self.admission.finished()

                        fut = asyncio.get_event_loop() \
                            .run_in_executor(self._query_pool, tracked)
                    else:
                        fut = asyncio.get_event_loop().run_in_executor(
                            None, self.http_router.handle, request)
                    answered = is_query
                    if is_query and self.query_timeout_ms > 0:
                        try:
                            response = await asyncio.wait_for(
                                fut, self.query_timeout_ms / 1000.0)
                        except asyncio.TimeoutError:
                            # the worker thread finishes in the
                            # background; the client gets the
                            # reference's expiry error
                            response = _structured_error(
                                504, "Query timeout exceeded "
                                f"({self.query_timeout_ms}ms)")
                            # the worker may yet finish its root
                            # before this answer is written: what is
                            # written is not its response
                            answered = False
                    else:
                        response = await fut
                # request-level latency histograms (exported with
                # percentiles at /api/stats + /api/health): queries
                # and puts each feed their own histogram — mixing
                # them buried put latency in the query distribution
                # and left latency_put empty since the seed
                elapsed_ms = (time.monotonic() - t0) * 1000
                is_put = not is_query and _is_put_path(
                    urllib.parse.unquote(parsed.path))
                if is_query:
                    self.tsdb.stats.latency_query.add(elapsed_ms)
                elif is_put:
                    self.tsdb.stats.latency_put.add(elapsed_ms)
                # SLO feed at RESPONSE time, from receipt: admission
                # sheds and query timeouts — responses built right
                # here, never entering HttpRpcRouter.handle — burn
                # the availability budget like any other 5xx, and
                # the recorded latency includes the queue wait (the
                # handler gates its own feed on received_at, so a
                # 504'd query's still-running worker records
                # nothing)
                slo = self.tsdb.slo
                if slo.enabled and (is_query or is_put):
                    slo.record("query" if is_query else "put",
                               elapsed_ms, response.status >= 500)
                if tenant is not None:
                    # per-tenant SLO burn attribution — the control
                    # loop's QoS tick turns this into shed priority
                    try:
                        governor.record(tenant, elapsed_ms,
                                        response.status >= 500)
                    except Exception:  # tsdlint: allow[swallow] attribution is observability; a broken governor must not fail a served response
                        pass
            self._apply_cors(request, response)
            await self._apply_gzip(request, response)
            if getattr(response, "close_connection", False):
                keep_alive = False
            # streamed serialization must honor the query timeout too:
            # the handler returned promptly with a lazy generator, so
            # the clock keeps running through the chunk writes. SSE
            # push streams (continuous queries) are exempt — they are
            # long-lived BY DESIGN and carry their own shedding +
            # lifetime bounds (tsd.streaming.*).
            is_sse = (response.content_type or "").startswith(
                "text/event-stream")
            deadline = (t0 + self.query_timeout_ms / 1000.0
                        if is_query and self.query_timeout_ms > 0
                        and not is_sse
                        and response.body_iter is not None else None)
            await self._write_response(writer, response, version,
                                       keep_alive, deadline=deadline)
            if answered:
                # the served query's last stage, from the worker's
                # return to the last byte drained; a shed query had
                # no worker, and a timed-out one's answer is not its
                # worker's
                self.tsdb.tracer.record_respond(
                    request.traced, time.monotonic())

    def _overload_response(self, cause: str) -> HttpResponse:
        """Structured load-shed answer (503 + Retry-After), one
        counter per cause so operators can tell WHICH limit sheds."""
        message = {
            "inflight": "too many in-flight queries",
            "queue": "query queue is full",
            "tenant": "tenant is over its fair in-flight share",
        }.get(cause, cause)
        body = json.dumps({"error": {
            "code": 503,
            "message": f"Service overloaded: {message}",
            "details": f"shed cause: {cause}; retry after "
                       f"{self.admission.retry_after_s}s"}}).encode()
        return HttpResponse(
            503, body,
            headers={"Retry-After":
                     str(self.admission.retry_after_s)})

    def _cors_preflight(self, request: HttpRequest) -> HttpResponse:
        """(ref: RpcHandler CORS handling :46)"""
        origin = request.headers.get("origin", "")
        if not self.cors_domains:
            return HttpResponse(405, b"")
        resp = HttpResponse(200, b"")
        resp.headers["Access-Control-Allow-Methods"] = \
            "GET, POST, PUT, DELETE"
        resp.headers["Access-Control-Allow-Headers"] = \
            self.tsdb.config.get_string("tsd.http.request.cors_headers",
                                        "")
        return resp

    def _apply_cors(self, request: HttpRequest,
                    response: HttpResponse) -> None:
        origin = request.headers.get("origin", "")
        if not origin or not self.cors_domains:
            return
        if "*" in self.cors_domains or origin in self.cors_domains:
            response.headers["Access-Control-Allow-Origin"] = origin

    # responses below this size aren't worth the deflate round trip
    _GZIP_MIN_BYTES = 1024

    async def _apply_gzip(self, request: HttpRequest,
                          response: HttpResponse) -> None:
        """Compress large response bodies when the client advertises
        gzip support (ref: the reference's Netty HttpContentCompressor
        in PipelineFactory — responses compress per Accept-Encoding).
        The deflate runs on a worker thread: compressing a multi-MB
        body inline would stall every connection on the event loop.
        Streamed responses compress incrementally per chunk — the
        biggest responses are exactly the ones that need it."""
        if "Content-Encoding" in response.headers:
            return
        if (response.content_type or "").startswith(
                "text/event-stream"):
            # SSE must not buffer: zlib without per-chunk sync flushes
            # would hold every event in the compressor until KBs
            # accumulate — a browser EventSource would see nothing
            return
        accept = request.headers.get("accept-encoding", "")
        if "gzip" not in accept.lower():
            return
        if response.body_iter is not None:
            import zlib
            inner = response.body_iter

            def gz_iter():
                co = zlib.compressobj(6, zlib.DEFLATED, 31)  # gzip hdr
                for chunk in inner:
                    out = co.compress(chunk)
                    if out:
                        yield out
                yield co.flush()

            response.body_iter = gz_iter()
            response.headers["Content-Encoding"] = "gzip"
            response.headers["Vary"] = "Accept-Encoding"
            return
        if len(response.body) < self._GZIP_MIN_BYTES:
            return
        import gzip as _gzip
        response.body = await asyncio.get_event_loop().run_in_executor(
            None, lambda: _gzip.compress(response.body,
                                         compresslevel=6))
        response.headers["Content-Encoding"] = "gzip"
        # shared caches must key on the encoding
        response.headers["Vary"] = "Accept-Encoding"

    async def _write_response(self, writer, response: HttpResponse,
                              version: str, keep_alive: bool,
                              deadline: float | None = None) -> None:
        reason = {200: "OK", 204: "No Content", 304: "Not Modified",
                  400: "Bad Request",
                  401: "Unauthorized", 403: "Forbidden",
                  404: "Not Found", 405: "Method Not Allowed",
                  413: "Request Entity Too Large",
                  429: "Too Many Requests", 500:
                  "Internal Server Error",
                  501: "Not Implemented",
                  503: "Service Unavailable",
                  504: "Gateway Timeout"}.get(response.status,
                                              "Unknown")
        loop = asyncio.get_event_loop()
        if response.body_iter is not None and version != "HTTP/1.1":
            if (response.content_type or "").startswith(
                    "text/event-stream"):
                # an SSE generator is unbounded by design — joining it
                # would pin a worker thread and memory forever. SSE
                # needs chunked TE, so non-1.1 clients get a clean
                # error instead.
                try:
                    response.body_iter.close()
                except Exception:  # noqa: BLE001
                    # tsdlint: allow[swallow] generator close on the
                    # refused-SSE path; the 400 below is the answer
                    pass
                response = HttpResponse(
                    400, b'{"error":{"code":400,"message":'
                    b'"Event streams require HTTP/1.1"}}',
                    close_connection=True)
                keep_alive = False
            else:
                # chunked TE needs 1.1; older clients get one body
                # (joined on a worker thread — serialization is CPU
                # work)
                response.body = await loop.run_in_executor(
                    None, lambda: b"".join(response.body_iter))
                response.body_iter = None
        head = [f"{version} {response.status} {reason}"]
        if response.body_iter is not None:
            head.append("Transfer-Encoding: chunked")
            head.append(f"Content-Type: {response.content_type}")
        else:
            head.append(f"Content-Length: {len(response.body)}")
            if response.body:
                head.append(f"Content-Type: {response.content_type}")
        head.append("Connection: " +
                    ("keep-alive" if keep_alive else "close"))
        for k, v in response.headers.items():
            head.append(f"{k}: {v}")
        writer.write("\r\n".join(head).encode("latin-1") + b"\r\n\r\n")
        if response.body_iter is not None:
            # stream bounded chunks; the generator (CPU-heavy JSON
            # serialization) advances on a worker thread so other
            # connections keep being served, and drain applies
            # backpressure so a slow client never forces the whole
            # body into memory
            it = iter(response.body_iter)
            sentinel = object()
            while True:
                if deadline is not None and \
                        time.monotonic() > deadline:
                    # past the query timeout mid-stream: abort the
                    # connection (headers are sent; an unterminated
                    # chunked body is the truncation signal)
                    LOG.warning("query stream exceeded "
                                "tsd.query.timeout; aborting")
                    raise ConnectionResetError("stream timeout")
                chunk = await loop.run_in_executor(
                    None, next, it, sentinel)
                if chunk is sentinel:
                    break
                if not chunk:
                    continue
                writer.write(f"{len(chunk):x}\r\n".encode()
                             + chunk + b"\r\n")
                await self._on_client(writer.drain())
            writer.write(b"0\r\n\r\n")
        else:
            writer.write(response.body)
        await self._on_client(writer.drain())
