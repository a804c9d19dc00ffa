"""What the benchmark puts inside the TSD process, through the
program's own plugin slots (``opentsdb_tpu/plugins.py``): nothing of
the served path, only what can be done from inside alone.

``Loader`` (``tsd.rpc.plugin``) reads ``tsdb import`` text from the
process's standard input into the store through ``TSDB.import_buffer``
before the server binds its socket: the bulk load of a deployment's
history, without a WAL as the reference's batch import
(``setDurable(false)``), and without the snapshot a separate
``tsdb import`` would have to write and the server read again.

``Bench`` (``tsd.http.rpc.plugin``, served under ``/plugin/bench``)
reports what only the process holding the chip can see (devices, peak
device memory, JAX's compilation counters) and starts and stops
``jax.profiler`` around a stretch of the measured window. It does
nothing until asked.
"""

from __future__ import annotations

import json
import sys
import threading
import time

from opentsdb_tpu.plugins import HttpRpcPlugin, RpcPlugin
from opentsdb_tpu.tsd.http_api import HttpError, HttpResponse

_BLOCK = 64 << 20


class Loader(RpcPlugin):
    def initialize(self, tsdb) -> None:
        t0 = time.monotonic()
        total = 0
        errors: list[str] = []
        stdin = sys.stdin.buffer
        tail = b""
        while True:
            block = stdin.read(_BLOCK)
            if not block:
                buf, tail = tail, b""
                if not buf:
                    break
            else:
                block = tail + block
                cut = block.rfind(b"\n")
                if cut < 0:
                    tail = block
                    continue
                buf, tail = block[:cut + 1], block[cut + 1:]
            written, errs = tsdb.import_buffer(buf, durable=False)
            total += written
            errors += errs[:10]
        # the harness waits for this line and checks the count
        print(f"benchmark-loader: imported {total} data points in "
              f"{time.monotonic() - t0:.1f}s, {len(errors)} errors "
              f"{errors[:3]}", flush=True)
        if errors:
            raise RuntimeError(f"benchmark load failed: {errors[:3]}")


class Bench(HttpRpcPlugin):
    _EVENTS = ("/jax/compilation_cache/compile_requests_use_cache",
               "/jax/compilation_cache/cache_hits",
               "/jax/compilation_cache/cache_misses")

    def initialize(self, tsdb) -> None:
        self._lock = threading.Lock()
        self._counts = dict.fromkeys(self._EVENTS, 0)
        self._tracing = False
        import jax.monitoring
        jax.monitoring.register_event_listener(self._on_event)

    def _on_event(self, event: str, **_kw) -> None:
        if event in self._counts:
            with self._lock:
                self._counts[event] += 1

    def path(self) -> str:
        return "bench"

    def execute(self, tsdb, request) -> HttpResponse:
        op = request.param("op", "state")
        if op == "state":
            return _json(self._state())
        if op == "trace_start":
            return _json(self._trace_start(request.param("dir")))
        if op == "trace_stop":
            return _json(self._trace_stop())
        raise HttpError(400, f"unknown op {op!r}")

    def _state(self) -> dict:
        import jax
        devices = jax.devices()
        mem = []
        for d in devices:
            stats = d.memory_stats() or {}
            mem.append({"id": d.id,
                        "peak_bytes_in_use":
                        stats.get("peak_bytes_in_use"),
                        "bytes_in_use": stats.get("bytes_in_use"),
                        "bytes_limit": stats.get("bytes_limit")})
        with self._lock:
            counts = {k.rsplit("/", 1)[1]: v
                      for k, v in self._counts.items()}
        return {"platform": devices[0].platform,
                "kind": devices[0].device_kind, "count": len(devices),
                "memory": mem, "compile": counts,
                "tracing": self._tracing}

    def _trace_start(self, where: str | None) -> dict:
        import jax
        if not where:
            raise HttpError(400, "trace_start needs dir=")
        with self._lock:
            if self._tracing:
                raise HttpError(400, "a trace is already running")
            self._tracing = True
        opts = jax.profiler.ProfileOptions()
        # device operations are what the reduction reads; host events
        # and Python frames make the trace large (133 MB for 20 s with
        # host_tracer_level 1) and the host slow, and /api/profile
        # names what the host did
        opts.python_tracer_level = 0
        opts.host_tracer_level = 0
        t0 = time.monotonic()
        jax.profiler.start_trace(where, profiler_options=opts)
        return {"started": True, "wall_time_ns": time.time_ns(),
                "start_s": time.monotonic() - t0}

    def _trace_stop(self) -> dict:
        import jax
        with self._lock:
            if not self._tracing:
                raise HttpError(400, "no trace is running")
        t0 = time.monotonic()
        wall = time.time_ns()
        jax.profiler.stop_trace()
        with self._lock:
            self._tracing = False
        return {"stopped": True, "wall_time_ns": wall,
                "stop_s": time.monotonic() - t0}


def _json(doc: dict) -> HttpResponse:
    return HttpResponse(200, json.dumps(doc).encode())
