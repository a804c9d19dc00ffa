"""The load generator: one thread of one process without JAX, asyncio
over keep-alive HTTP/1.1 connections, asleep on its sockets between
events. It times every request on its own clock (``time.perf_counter``)
from the first byte sent (closed loop) or from the instant the request
was due (open loop) to the last byte read, keeps every answer, and
judges none: answers are compared after the window.
"""

from __future__ import annotations

import asyncio
import time


class Result:
    __slots__ = ("request", "due", "sent", "done", "status", "body",
                 "error")

    def __init__(self, request):
        self.request = request
        self.due = self.sent = self.done = 0.0
        self.status = 0
        self.body = b""
        self.error = ""

    @property
    def latency_ms(self) -> float:
        return (self.done - self.due) * 1000.0

    @property
    def late_ms(self) -> float:
        return (self.sent - self.due) * 1000.0


class Connection:
    def __init__(self, port: int):
        self.port = port
        self.reader = self.writer = None

    async def open(self) -> None:
        self.reader, self.writer = await asyncio.open_connection(
            "127.0.0.1", self.port)

    async def close(self) -> None:
        if self.writer is not None:
            self.writer.close()
            try:
                await self.writer.wait_closed()
            except OSError:
                pass
        self.reader = self.writer = None

    async def exchange(self, method: str, path: str, body: bytes):
        """(status, body bytes) of one request on this connection."""
        if self.writer is None:
            await self.open()
        head = (f"{method} {path} HTTP/1.1\r\nHost: 127.0.0.1\r\n"
                f"Content-Type: application/json\r\n"
                f"Content-Length: {len(body)}\r\n\r\n").encode()
        self.writer.write(head + body)
        await self.writer.drain()
        line = await self.reader.readline()
        if not line:
            raise ConnectionError("closed before a status line")
        status = int(line.split()[1])
        length, chunked, close = 0, False, False
        while True:
            h = await self.reader.readline()
            if h in (b"\r\n", b"\n", b""):
                break
            k, _, v = h.decode("latin-1").partition(":")
            k, v = k.strip().lower(), v.strip().lower()
            if k == "content-length":
                length = int(v)
            elif k == "transfer-encoding" and "chunked" in v:
                chunked = True
            elif k == "connection" and v == "close":
                close = True
        if chunked:
            parts = []
            while True:
                n = int((await self.reader.readline()).strip() or b"0",
                        16)
                if n == 0:
                    await self.reader.readline()
                    break
                parts.append(await self.reader.readexactly(n))
                await self.reader.readline()
            payload = b"".join(parts)
        else:
            payload = await self.reader.readexactly(length) \
                if length else b""
        if close:
            await self.close()
        return status, payload


async def _one(conn: Connection, res: Result, timeout_s: float) -> None:
    req = res.request
    res.sent = time.perf_counter()
    try:
        res.status, res.body = await asyncio.wait_for(
            conn.exchange(req.method, req.path, req.body), timeout_s)
    except (asyncio.TimeoutError, OSError, ValueError, IndexError,
            asyncio.IncompleteReadError) as e:
        res.error = f"{type(e).__name__}: {e}"
        await conn.close()
    res.done = time.perf_counter()


async def _closed(port, requests, clients, seconds, timeout_s, t0):
    """``clients`` callers share the list, each sending its next
    request when its last is answered, until the window closes."""
    results: list[Result] = []
    it = iter(requests)

    async def client():
        conn = Connection(port)
        try:
            while time.perf_counter() - t0 < seconds:
                req = next(it, None)
                if req is None:
                    raise RuntimeError(
                        "the traffic list ran out inside the window")
                res = Result(req)
                results.append(res)
                res.due = time.perf_counter()
                await _one(conn, res, timeout_s)
        finally:
            await conn.close()

    await asyncio.gather(*(client() for _ in range(clients)))
    return results


async def _open(port, requests, clients, timeout_s, t0):
    """Arrivals on the schedule in ``request.due_s``, whatever the
    server does; a request waits only for a free connection, and that
    wait counts as lateness and as latency."""
    idle: asyncio.Queue = asyncio.Queue()
    conns = [Connection(port) for _ in range(clients)]
    for c in conns:
        await c.open()
        idle.put_nowait(c)
    results: list[Result] = []
    tasks = []

    async def run(conn, res):
        await _one(conn, res, timeout_s)
        idle.put_nowait(conn)

    for req in requests:
        res = Result(req)
        res.due = t0 + req.due_s
        results.append(res)
        delay = res.due - time.perf_counter()
        if delay > 0:
            await asyncio.sleep(delay)
        conn = await idle.get()
        tasks.append(asyncio.ensure_future(run(conn, res)))
    await asyncio.gather(*tasks)
    for c in conns:
        await c.close()
    return results


async def drive(port: int, traffic, seconds: float, side=None):
    """Run the window. ``side(t0)`` is an optional coroutine that runs
    beside it (the traced run's profiler calls). Returns (results, what
    ``side`` returned, window start on the perf_counter clock); the
    results of the traffic's scheduled writes, where it has any, are
    left in ``traffic.write_results``."""
    t0 = time.perf_counter()
    extra = asyncio.ensure_future(side(t0)) if side else None
    writes = asyncio.ensure_future(_open(
        port, traffic.writes, traffic.write_clients, traffic.timeout_s,
        t0)) if traffic.writes else None
    if traffic.loop == "closed":
        results = await _closed(port, traffic.timed, traffic.clients,
                                seconds, traffic.timeout_s, t0)
    else:
        results = await _open(port, traffic.timed, traffic.clients,
                              traffic.timeout_s, t0)
    traffic.write_results = (await writes) if writes is not None else []
    return results, (await extra) if extra is not None else None, t0


async def send_all(port: int, requests, timeout_s: float,
                   clients: int = 1) -> list[Result]:
    """Warm-up and probes: every request once, ``clients`` at a time,
    untimed by any schedule."""
    results = [Result(r) for r in requests]
    it = iter(results)

    async def client():
        conn = Connection(port)
        try:
            for res in it:
                res.due = time.perf_counter()
                await _one(conn, res, timeout_s)
        finally:
            await conn.close()

    await asyncio.gather(*(client() for _ in range(max(1, clients))))
    return results
