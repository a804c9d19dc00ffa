"""Per-request distributed tracing: cheap sampled span records.

Design constraints, in priority order:

1. **Overhead is first-class.** With ``tsd.trace.enable = false`` (or
   outside a traced request) every instrumentation site costs one
   thread-local read returning ``None``. With tracing on, a span is
   two ``time.monotonic()`` calls, one small object and one
   lock-guarded list append, and :meth:`Tracer.finish` times itself
   (``tsd.trace.finish_ms``) — spans wrap request-scoped *stages*
   (decode, WAL commit wait, plan, execute, serialize), never
   per-point work. Sampling (``tsd.trace.sample`` = keep 1 in N
   request roots) gates only *retention*: every request still records
   its spans so the slow-request log can keep ANY slow trace at full
   fidelity, and the per-stage latency histograms see every request,
   not just the sampled ones.
2. **One trace spans the cluster.** The router stamps an
   ``X-TSD-Trace`` header (``trace_id:parent_span_id:sampled``) on
   every shard scatter / write forward; the shard roots its own
   subtree under the router's per-peer span and honors the router's
   sampling decision, so ``GET /api/trace/<id>`` on the router can
   stitch the full tree from every surviving shard's ring. Span ids
   carry a per-context random nonce so ids from different nodes never
   collide in a stitched tree.
3. **Slow traces are never lost.** ``tsd.query.slowlog.threshold_ms``
   forces retention of any query root past the threshold (plus a WARN
   logring entry carrying the trace id) regardless of sampling, into
   a separate bounded slow ring so a burst of normal traffic cannot
   evict the evidence.

Span names form a CLOSED registry (:data:`KNOWN_SPANS`, the
``faults.KNOWN_SITES`` idiom): starting an unregistered name raises,
and tsdlint's ``trace-sites`` pass enforces it statically (plus
reports registered-but-never-started names as stale).

Spans NEST: a span begun without an explicit ``parent=`` hangs off the
innermost span still open on the same thread in the same context, so
``query.plan`` .. ``query.assemble`` are children of ``query.execute``
and :meth:`Tracer.finish` can compute each parent's SELF time
(duration minus the union of its children): what no child names.

A served query is four intervals that touch: ``query.receive`` and
``query.admission`` recorded from the socket server's stamps, the root
(begun in the worker, ended where :meth:`Tracer.finish` begins) and
``query.respond`` (:meth:`Tracer.record_respond`: from the end of
``finish`` to the response's last drain).

Beside the tracer sits the process's :data:`RUNTIME`: the
device-occupancy clock (:class:`DeviceClock`, on the spans' own
clock, so every idle millisecond lands on a host stage), JAX's
compile events, the collector's pauses, the process's page faults,
its threads' CPU time and the start-up phases.

The query-shape log is the explicit precursor to workload-adaptive
summaries (ROADMAP item 5 / Storyboard): each committed ``query.http``
trace appends one JSONL line — metric, filters, downsample, pixel
budget, cache outcome, per-stage breakdown — to a bounded rotating
file in ``data_dir`` for offline mining.
"""

from __future__ import annotations

import contextlib
import gc
import json
import logging
import os
import re
import resource
import secrets
import sys
import threading
import time
from collections import deque
from typing import Any

LOG = logging.getLogger("obs.trace")

# ---------------------------------------------------------------------------
# span-name registry
# ---------------------------------------------------------------------------
# Every span name started anywhere — roots and stages — must resolve
# here. tsdlint's ``trace-sites`` pass enforces it statically (an
# unregistered literal is a finding; a registered name never started
# is reported stale) and :meth:`TraceContext.begin` enforces it at
# runtime, so a typo'd stage name fails the first test that crosses it
# instead of silently recording an orphan stage.

KNOWN_SPANS: frozenset[str] = frozenset({
    # request roots
    "ingest.put",            # HTTP /api/put body
    "ingest.telnet",         # one telnet put burst
    "query.http",            # /api/query
    # background roots
    "lifecycle.sweep",       # lifecycle/manager.py sweep
    "streaming.drain",       # streaming/workers.py off-path fold drain
    "cluster.spool.replay",  # cluster/router.py spool catch-up drain
    "cluster.replica.repair",  # cluster/router.py anti-entropy pass
    "cluster.reshard.backfill",  # cluster/reshard.py moved-key copy
    "cluster.retire",        # cluster/retire.py stale-copy delete
    "cluster.gossip.push",   # cluster/gossip.py sibling push round
    "cluster.read_repair",   # cluster/router.py staged-hint drain
    "telemetry.pump",        # obs/telemetry.py self-stats ingest
    "control.loop",          # control/plane.py one control tick
    "ingest.import",         # core/tsdb.py import_buffer outside a request
    "ingest.rollup",         # core/tsdb.py add_aggregate_batch, likewise
    # ingest stages
    "ingest.decode",         # body parse + validate + series grouping
    "ingest.resolve",        # import_buffer: UIDs + series per distinct key
    "store.scatter",         # columnar store appends (+ inline taps)
    "wal.commit_wait",       # WAL group-commit fsync wait
    "stream.tap",            # continuous-query ingest tap
    # query stages
    "query.receive",         # first byte in the buffer -> request parsed
    "query.admission",       # admission + worker-queue wait
    "query.streaming_lookup",  # CQ registry try_serve
    "query.plan",            # store/tier selection, filters, groups
    "query.filter_resolve",  # one value filter -> its tagv ids (in plan)
    "sketch.fold",           # lifecycle/manager.py demote-time
                             # quantile-sketch fold (fifth stat column)
    "query.execute",         # scan + device pipeline (parent stage)
    "query.scan",            # storage read (the QueryStat scan timer)
    "query.grid_build",      # host NumPy between scan and upload
    "query.upload",          # operand casts + device_put (host side)
    "query.program",         # jit call until its outputs are ready
    "query.download",        # np.asarray of the results
    "query.assemble",        # result assembly incl. pixel reduce
    "query.serialize",       # response body serialization
    "query.respond",         # handler's return -> last byte written
    # cluster stages
    "cluster.scatter",       # router read fan-out (parent stage)
    "cluster.peer",          # one shard's scatter leg (error = degraded)
    "cluster.merge",         # cross-shard partial merge
    "cluster.forward",       # one shard's write-forward leg
    "cluster.spool.append",  # durable handoff of one write batch
    "cluster.wire.connect",  # binary wire negotiation (cluster/wire.py)
    "cluster.cq",            # one federated-CQ shard exchange
    "cluster.cq.pump",       # one merged cross-shard delta drain

    # background stages
    "coldstore.spill",       # lifecycle sweep's disk spill phase
})

#: wire header carrying trace identity across the cluster tier
TRACE_HEADER = "x-tsd-trace"

# id generation: trace/span ids need UNIQUENESS (across restarts and
# across cluster nodes, so stitched trees never alias), not
# unpredictability — os.urandom per request cost ~50us/trace, an
# order of magnitude over the rest of the tracer combined. One random
# process nonce + a counter gives both properties at ~1us.
_PROC_NONCE = secrets.token_hex(4)
_id_lock = threading.Lock()
_id_counter = 0


def _next_id() -> str:
    global _id_counter
    with _id_lock:
        _id_counter += 1
        n = _id_counter
    return f"{_PROC_NONCE}{n:08x}"


# one clock for spans, contexts and the occupancy clock (a test
# scripts a sequence by replacing it)
_now = time.monotonic


# ---------------------------------------------------------------------------
# the process's runtime: device occupancy, compiles, collector, start-up
# ---------------------------------------------------------------------------

class DeviceClock:
    """When is a program in flight on the device? ``enter``/``exit``
    bracket every device-placed ``query.program``; ``occupied_ms`` is
    the cumulative time with at least one in flight, on the spans' own
    clock, so a span that samples it at begin and finish knows how
    much of its interval the device was occupied. Occupancy runs from
    dispatch to ready: it contains the transfer and the launch, not
    only the kernels."""

    def __init__(self):
        self._lock = threading.Lock()
        self._inflight = 0
        self._since = 0.0
        self._occupied_s = 0.0
        self.dispatches = 0

    def enter(self) -> None:
        with self._lock:
            if self._inflight == 0:
                self._since = _now()
            self._inflight += 1
            self.dispatches += 1

    def exit(self) -> None:
        with self._lock:
            self._inflight -= 1
            if self._inflight == 0:
                self._occupied_s += _now() - self._since

    def occupied_ms(self) -> float:
        with self._lock:
            open_s = _now() - self._since if self._inflight else 0.0
            return (self._occupied_s + open_s) * 1000.0


_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_CACHE_EVENTS = {"/jax/compilation_cache/cache_hits": "cache_hits",
                 "/jax/compilation_cache/cache_misses": "cache_misses"}


_TASKS = "/proc/self/task"
_TICK_MS = 1000.0 / os.sysconf("SC_CLK_TCK")
# a pool's or the library's numbering of a thread's name:
# "tsd-query_3", "asyncio_0", "Thread-7 (attempt)"
_THREAD_NUMBER = re.compile(r"[-_ ]?\d+( \(.*\))?$")


def thread_group(name: str) -> str:
    """A thread's name without its number: the threads of one pool
    are one group (``tsd-query_3`` -> ``tsd-query``)."""
    return _THREAD_NUMBER.sub("", name) or name


def thread_cpu_ms(tasks: str = _TASKS) -> dict[str, float]:
    """CPU time (user + system, ms) the kernel has charged to each
    live Python thread since it started, summed by
    :func:`thread_group`: ``/proc/self/task/<tid>/stat``, whose ticks
    the kernel counts whether or not anybody asks, so a request's path
    reads no clock for it. Beside the stage histograms' sums it says
    how much of a pool's requests its threads ran. Empty where there
    is no procfs; a thread that ended takes its time with it."""
    out: dict[str, float] = {}
    for t in threading.enumerate():
        try:
            with open(f"{tasks}/{t.native_id}/stat", "rb") as f:
                # the name (field 2) may hold spaces and brackets:
                # count the fields from its last bracket on
                fields = f.read().rpartition(b")")[2].split()
            ticks = int(fields[11]) + int(fields[12])  # utime, stime
        except (OSError, ValueError, IndexError):
            continue        # not started yet, or ended since
        group = thread_group(t.name)
        out[group] = out.get(group, 0.0) + ticks * _TICK_MS
    return out


class ProcessRuntime:
    """What only the process as a whole has: one device (so one
    occupancy clock), JAX's compile events, the collector's pauses,
    the kernel's account of the process (page faults, CPU time by
    thread) and the start-up phases. Hooks are installed once, by the
    first :class:`Tracer`; every tracer of the process exports the
    same numbers (``tsd.device.*``, ``tsd.runtime.*``,
    ``tsd.startup.*``)."""

    def __init__(self):
        self.clock = DeviceClock()
        self._lock = threading.Lock()
        self._installed = False
        # every compile JAX was asked for, fresh or from the
        # persistent cache (the event wraps compile_or_get_cached)
        self.compiles = 0
        self.compile_ms = 0.0
        self.cache_hits = 0
        self.cache_misses = 0
        # collector pauses, by generation (written by the one thread
        # that collects, under the GIL)
        self.gc_pause_ms = [0.0, 0.0, 0.0]
        self.gc_collections = [0, 0, 0]
        self.gc_max_pause_ms = 0.0
        self._gc_t0 = 0.0
        # {phase: seconds}, in the order the phases ran
        # tsdlint: allow[unbounded-growth] keyed by the start-up
        # phases the code names (tools/cli.py, TSDB.__init__): seven
        self.startup: dict[str, float] = {}

    def install(self) -> None:
        with self._lock:
            if self._installed:
                return
            self._installed = True
        import jax.monitoring
        jax.monitoring.register_event_duration_secs_listener(
            self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)
        gc.callbacks.append(self._on_gc)

    def _on_duration(self, event: str, secs: float, **_kw) -> None:
        if event == _COMPILE_EVENT:
            with self._lock:
                self.compiles += 1
                self.compile_ms += secs * 1000.0

    def _on_event(self, event: str, **_kw) -> None:
        field = _CACHE_EVENTS.get(event)
        if field is not None:
            with self._lock:
                setattr(self, field, getattr(self, field) + 1)

    def _on_gc(self, phase: str, info: dict) -> None:
        if phase == "start":
            self._gc_t0 = _now()
            return
        ms = (_now() - self._gc_t0) * 1000.0
        gen = min(int(info.get("generation", 2)), 2)
        self.gc_pause_ms[gen] += ms
        self.gc_collections[gen] += 1
        if ms > self.gc_max_pause_ms:
            self.gc_max_pause_ms = ms

    @contextlib.contextmanager
    def phase(self, name: str):
        """Time one start-up phase into :attr:`startup`."""
        t0 = _now()
        try:
            yield
        finally:
            self.startup[name] = self.startup.get(name, 0.0) \
                + _now() - t0

    def collect_stats(self, collector) -> None:
        clock = self.clock
        collector.record("device.occupied_ms", clock.occupied_ms())
        collector.record("device.dispatches", clock.dispatches)
        collector.record("device.compiles", self.compiles)
        collector.record("device.compile_ms", self.compile_ms)
        collector.record("device.compile_cache_hits", self.cache_hits)
        collector.record("device.compile_cache_misses",
                         self.cache_misses)
        for gen in range(3):
            collector.record("runtime.gc_pause_ms",
                             self.gc_pause_ms[gen], gen=str(gen))
            collector.record("runtime.gc_collections",
                             self.gc_collections[gen], gen=str(gen))
        collector.record("runtime.gc_max_pause_ms",
                         self.gc_max_pause_ms)
        # pages the kernel had to map (minor) or read (major) for this
        # process since it started: the allocator giving a request's
        # large temporaries back and mapping them fresh shows here and
        # in no stage. Read when stats are collected, never on a
        # request's path.
        ru = resource.getrusage(resource.RUSAGE_SELF)
        collector.record("runtime.minor_faults", ru.ru_minflt)
        collector.record("runtime.major_faults", ru.ru_majflt)
        for group, ms in sorted(thread_cpu_ms().items()):
            collector.record("runtime.thread_cpu_ms", ms, thread=group)
        for name, secs in list(self.startup.items()):
            collector.record("startup.phase_s", secs, phase=name)


#: the one runtime of this process
RUNTIME = ProcessRuntime()


def parse_trace_header(value: str) -> tuple[str, str, bool] | None:
    """``trace_id:parent_span_id:sampled_flag`` -> parts, or None on
    anything malformed (a hostile header must never 500 a write)."""
    if not value or len(value) > 128:
        return None
    parts = value.split(":")
    if len(parts) != 3:
        return None
    trace_id, parent, flag = parts
    if not (1 <= len(trace_id) <= 32 and trace_id.isalnum()):
        return None
    if len(parent) > 32 or not all(
            c.isalnum() or c == "-" for c in parent):
        return None
    return trace_id, parent, flag == "1"


# ---------------------------------------------------------------------------
# thread-local current context
# ---------------------------------------------------------------------------

_local = threading.local()


def _open_spans() -> list:
    """This thread's stack of open spans, innermost last."""
    stack = getattr(_local, "open", None)
    if stack is None:
        stack = _local.open = []
    return stack


def _annotate(name: str, trace_id: str, tags: dict):
    """Put a query stage on the device trace's timeline too: with a
    ``jax.profiler`` capture live (and its host tracer on) the span
    appears there under its own name, request id and sub index as
    arguments; with none live this is a flag check. Only where JAX is
    already loaded."""
    jax = sys.modules.get("jax")
    if jax is None:
        return None
    args = {"sub": tags["sub"]} if "sub" in tags else {}
    ann = jax.profiler.TraceAnnotation(name, trace_id=trace_id, **args)
    ann.__enter__()
    return ann


def current() -> "TraceContext | None":
    """The active request's trace context on THIS thread, or None.
    Deep layers (WAL, engine, router) read this instead of threading
    a context parameter through every signature."""
    return getattr(_local, "ctx", None)


@contextlib.contextmanager
def use(ctx: "TraceContext | None"):
    """Bind ``ctx`` as the thread's current trace context for the
    scope (None is a no-op bind — instrumentation sees no context).
    Fan-out workers re-bind the parent's context so sub-query spans
    land in the right trace."""
    prev = getattr(_local, "ctx", None)
    _local.ctx = ctx
    try:
        yield ctx
    finally:
        _local.ctx = prev


def trace_begin(name: str, ctx: "TraceContext | None" = None,
                parent: str | None = None, **tags) -> "SpanHandle | None":
    """Open a span on the current (or given) context; None when
    untraced — pair with :func:`trace_end`. For straight-line regions
    with early exits prefer :func:`trace_span`."""
    c = ctx if ctx is not None else getattr(_local, "ctx", None)
    if c is None:
        return None
    return c.begin(name, parent=parent, **tags)


def trace_end(handle: "SpanHandle | None",
              error: BaseException | None = None) -> None:
    if handle is not None:
        if error is not None:
            handle.set_error(error)
        handle.finish()


@contextlib.contextmanager
def trace_span(name: str, ctx: "TraceContext | None" = None, **tags):
    """Span context manager: exceptions mark the span ``error`` and
    propagate."""
    h = trace_begin(name, ctx=ctx, **tags)
    try:
        yield h
    except BaseException as exc:
        trace_end(h, error=exc)
        raise
    else:
        trace_end(h)


def record_span(ctx: "TraceContext | None", name: str,
                start_mono: float, end_mono: float, **tags) -> None:
    """Record an already-timed span (e.g. the admission/queue wait,
    whose start predates the context)."""
    if ctx is None:
        return
    ctx.record(name, start_mono, end_mono, **tags)


# ---------------------------------------------------------------------------
# span model
# ---------------------------------------------------------------------------

class SpanRecord:
    """One finished span. Immutable once appended to its context."""

    __slots__ = ("span_id", "parent_id", "name", "start_ms",
                 "duration_ms", "status", "error", "tags",
                 "occupied_ms")

    def __init__(self, span_id: str, parent_id: str, name: str,
                 start_ms: float, duration_ms: float,
                 status: str = "ok", error: str = "",
                 tags: dict | None = None, occupied_ms: float = 0.0):
        self.span_id = span_id
        self.parent_id = parent_id
        self.name = name
        self.start_ms = start_ms
        self.duration_ms = duration_ms
        self.status = status
        self.error = error
        self.tags = tags or {}
        # how much of the interval a program was in flight on the
        # device (RUNTIME.clock sampled at begin and finish)
        self.occupied_ms = occupied_ms

    def to_json(self) -> dict[str, Any]:
        doc: dict[str, Any] = {
            "spanId": self.span_id, "parentId": self.parent_id,
            "name": self.name,
            "startMs": round(self.start_ms, 3),
            "durationMs": round(self.duration_ms, 3),
            "status": self.status,
        }
        if self.error:
            doc["error"] = self.error
        if self.tags:
            doc["tags"] = self.tags
        if self.occupied_ms:
            doc["deviceOccupiedMs"] = round(self.occupied_ms, 3)
        return doc

    @classmethod
    def from_json(cls, doc: dict) -> "SpanRecord":
        return cls(str(doc.get("spanId", "")),
                   str(doc.get("parentId", "")),
                   str(doc.get("name", "?")),
                   float(doc.get("startMs", 0.0)),
                   float(doc.get("durationMs", 0.0)),
                   str(doc.get("status", "ok")),
                   str(doc.get("error", "")),
                   doc.get("tags") or {},
                   float(doc.get("deviceOccupiedMs", 0.0)))


class SpanHandle:
    """An OPEN span: carry tags, then :meth:`finish` to record."""

    __slots__ = ("_ctx", "span_id", "parent_id", "name", "tags",
                 "_t0", "status", "error", "_done", "_occ0", "_stack",
                 "_annotation")

    def __init__(self, ctx: "TraceContext", span_id: str,
                 parent_id: str, name: str, tags: dict):
        self._ctx = ctx
        self.span_id = span_id
        self.parent_id = parent_id
        self.name = name
        self.tags = tags
        self.status = "ok"
        self.error = ""
        self._done = False
        self._stack = None        # the thread's open-span stack
        self._annotation = None   # jax.profiler.TraceAnnotation
        self._occ0 = RUNTIME.clock.occupied_ms()
        self._t0 = _now()

    def tag(self, **tags) -> None:
        self.tags.update(tags)

    def set_error(self, exc: BaseException | str) -> None:
        self.status = "error"
        self.error = (f"{type(exc).__name__}: {exc}"
                      if isinstance(exc, BaseException) else str(exc))

    def finish(self) -> float:
        """Record the span; returns its duration in ms (a caller that
        also keeps a stat uses the span as its timer)."""
        if self._done:
            return 0.0
        self._done = True
        t1 = _now()
        occupied = RUNTIME.clock.occupied_ms() - self._occ0
        if self._annotation is not None:
            self._annotation.__exit__(None, None, None)
        stack = self._stack
        if stack is not None and self in stack:
            # and whatever was opened inside and abandoned on an
            # error path: a finished span has no open children
            del stack[stack.index(self):]
        self._ctx._append(self, self._t0, t1, occupied)
        return (t1 - self._t0) * 1000.0

    def __enter__(self) -> "SpanHandle":
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        if exc is not None:
            self.set_error(exc)
        self.finish()
        return False


class TraceContext:
    """One request's (or background root's) in-flight trace."""

    __slots__ = ("tracer", "trace_id", "root_name", "remote",
                 "sampled", "forced", "parent_id", "root_span_id",
                 "start_epoch_ms", "_t0", "_lock", "spans",
                 "_next_span", "_nonce", "finished", "committed",
                 "slow", "error", "tags", "dropped_spans", "_gc2_ms0",
                 "finished_at")

    def __init__(self, tracer: "Tracer", trace_id: str,
                 root_name: str, sampled: bool, forced: bool,
                 parent_id: str = "", remote: str = ""):
        self.tracer = tracer
        self.trace_id = trace_id
        self.root_name = root_name
        self.remote = remote
        self.sampled = sampled
        self.forced = forced
        self.parent_id = parent_id
        # per-context nonce keeps span ids globally unique so a
        # stitched cross-node tree can never alias parent links
        self._nonce = _next_id()
        self.root_span_id = f"{self._nonce}-0"
        self.start_epoch_ms = time.time() * 1000.0
        self._t0 = _now()
        self._lock = threading.Lock()
        # tsdlint: allow[unbounded-growth] capped by the tracer's
        # tsd.trace.max_spans (overflow counted in spans_dropped),
        # and the context dies with its request
        self.spans: list[SpanRecord] = []
        self._next_span = 0
        self.finished = False
        self.committed = False
        self.slow = False
        self.error = ""
        self.tags: dict[str, Any] = {}
        self.dropped_spans = 0
        self._gc2_ms0 = RUNTIME.gc_pause_ms[2]
        # the instant Tracer.finish was done with it (0.0 until
        # then): where the server's query.respond begins
        self.finished_at = 0.0

    # -- span surface --------------------------------------------------

    def begin(self, name: str, parent: str | None = None,
              push: bool = True, **tags) -> SpanHandle | None:
        if name not in KNOWN_SPANS:
            raise ValueError(
                f"unknown span name {name!r}; register it in "
                f"obs/trace.py KNOWN_SPANS")
        with self._lock:
            if self.finished or \
                    len(self.spans) >= self.tracer.max_spans:
                self.dropped_spans += 1
                return None
            self._next_span += 1
            sid = f"{self._nonce}-{self._next_span}"
        # no explicit parent: the innermost span still open on THIS
        # thread in THIS context, else the root
        stack = _open_spans()
        while stack and (stack[-1]._done or stack[-1]._ctx.finished):
            stack.pop()
        if parent is None:
            parent = next((h.span_id for h in reversed(stack)
                           if h._ctx is self), self.root_span_id)
        h = SpanHandle(self, sid, parent, name, tags)
        if push:
            h._stack = stack
            stack.append(h)
            if name.startswith("query."):
                h._annotation = _annotate(name, self.trace_id, tags)
        return h

    def record(self, name: str, start_mono: float, end_mono: float,
               **tags) -> None:
        """Append an already-timed span (see :func:`record_span`)."""
        h = self.begin(name, push=False, **tags)
        if h is None:
            return
        self._append(h, start_mono, end_mono, 0.0)

    def _append(self, h: SpanHandle, t0: float, t1: float,
                occupied_ms: float) -> None:
        rec = SpanRecord(
            h.span_id, h.parent_id, h.name,
            self.start_epoch_ms + (t0 - self._t0) * 1000.0,
            (t1 - t0) * 1000.0, h.status, h.error, h.tags,
            max(occupied_ms, 0.0))
        with self._lock:
            if self.finished:
                self.dropped_spans += 1
                return
            self.spans.append(rec)

    # -- root surface --------------------------------------------------

    def tag(self, **tags) -> None:
        self.tags.update(tags)

    def set_error(self, exc: BaseException | str) -> None:
        self.error = (f"{type(exc).__name__}: {exc}"
                      if isinstance(exc, BaseException) else str(exc))


class TraceData:
    """One committed trace in the ring."""

    __slots__ = ("trace_id", "root", "spans", "slow")

    def __init__(self, trace_id: str, root: SpanRecord,
                 spans: tuple, slow: bool):
        self.trace_id = trace_id
        self.root = root
        self.spans = spans  # root first
        self.slow = slow

    def summary(self) -> dict[str, Any]:
        return {
            "traceId": self.trace_id,
            "name": self.root.name,
            "startMs": round(self.root.start_ms, 3),
            "durationMs": round(self.root.duration_ms, 3),
            "status": self.root.status,
            "error": self.root.error,
            "spans": len(self.spans),
            "slow": self.slow,
        }


def build_tree(spans: list[SpanRecord]) -> list[dict[str, Any]]:
    """Nest flat span records by parent id; orphans (parent not in
    the set — e.g. a shard subtree whose router leg was evicted)
    become additional roots so no span is ever silently dropped."""
    nodes = {s.span_id: dict(s.to_json(), children=[]) for s in spans}
    roots: list[dict] = []
    for s in spans:
        node = nodes[s.span_id]
        parent = nodes.get(s.parent_id)
        if parent is not None and parent is not node:
            parent["children"].append(node)
        else:
            roots.append(node)
    def _sort(n):
        n["children"].sort(key=lambda c: c["startMs"])
        for c in n["children"]:
            _sort(c)
    for r in roots:
        _sort(r)
    roots.sort(key=lambda n: n["startMs"])
    return roots


# ---------------------------------------------------------------------------
# the tracer
# ---------------------------------------------------------------------------

class Tracer:
    """Owns the sampling decision, the bounded trace rings, the
    slow-request log and the query-shape log. One per TSDB."""

    def __init__(self, config, data_dir: str = "", stats=None):
        self.enabled = config.get_bool("tsd.trace.enable", True)
        # the X-TSD-Trace header is honored ONLY in shard role — it
        # is the router→shard propagation channel, not a client
        # surface: an external client sending forged headers to a
        # standalone/router TSD could otherwise bypass sampling
        # (per-request shape-log writes, ring churn) and overwrite
        # the very trace ids an operator is investigating
        self.accept_headers = config.get_string(
            "tsd.cluster.role", "") == "shard"
        self.sample_n = max(config.get_int("tsd.trace.sample", 64), 1)
        self.max_spans = max(
            config.get_int("tsd.trace.max_spans", 512), 16)
        self.slow_ms = config.get_float(
            "tsd.query.slowlog.threshold_ms", 0.0)
        self.stats = stats  # StatsCollectorRegistry (stage histograms)
        RUNTIME.install()
        self._lock = threading.Lock()
        # per stage: the idle part of its SELF time (thread-ms: two
        # fan-out workers idle at once count twice)
        # tsdlint: allow[unbounded-growth] keyed by span name: the
        # closed KNOWN_SPANS registry
        self.idle_stage_ms: dict[str, float] = {}
        # what finish() itself cost, and the histogram observations
        # it made
        self.finish_ms = 0.0
        self.observations = 0
        # programs dispatched, by (path, placement, class)
        # tsdlint: allow[unbounded-growth] keyed by run_staged's
        # tags: the seven paths its callers name x two placements x
        # two classes of group stage (rank | linear)
        self.tails: dict[tuple[str, str, str], int] = {}
        # the rank class's programs, by how their group stage reads
        # its order statistics: "select" (counting, the device's
        # lowering) or "sort" (one sort of the grid)
        self.ranks = {"select": 0, "sort": 0}
        # programs by the form their nearest-present carry takes
        # along the buckets: "unrolled" (every step written out) or
        # "loop" (ops.interp.carry_form of the padded bucket count)
        self.carries = {"unrolled": 0, "loop": 0}
        # grids built, by who wrote the padded grid: "fused" (the
        # store's own pass) or "host" (fill_padded_grid)
        self.grid_builds = {"fused": 0, "host": 0}
        # HBM cache look-ups of grid sub-queries, by what answered:
        # "resident_hit" (the metric's resident grid was there),
        # "resident_built" (this request built it whole),
        # "resident_columns" (this request put it together from the
        # metric's per-bucket columns) or "selection" (a grid of the
        # request's own rows, keyed by their digest)
        self.grids = {"resident_hit": 0, "resident_built": 0,
                      "resident_columns": 0, "selection": 0}
        # plan stages, by what the engine's plan index did for them:
        # "hit" (planned from the cached index), "built" (built it
        # first), "bypass" (a selection that is not a whole metric)
        self.plans = {"hit": 0, "built": 0, "bypass": 0}
        # plan stages, by where tier selection sent them: "raw", a
        # rollup "tier", or raw as the "fallback" of a tier that
        # holds nothing of the metric (the span's tag ``source``)
        self.rollups = {"raw": 0, "tier": 0, "fallback": 0}
        # filters evaluated, by how each became a series mask: "ids"
        # (the UIDs of the exact names it holds), "table" (its
        # predicate over the plan index's table of the key's names,
        # all at once), "walk" (its predicate over the name of every
        # distinct value of its key, read one by one), "presence"
        # (the key's column alone: *, .*, not_key)
        self.filters = {"ids": 0, "table": 0, "walk": 0, "presence": 0}
        # the "table" ones, by what the table cost them: "hit" (it
        # was there, of the dictionary's generation) or "built" (this
        # filter read the key's names to make it)
        self.filter_tables = {"hit": 0, "built": 0}
        # names of stored tag values those filters read from the UID
        # dictionary (the ``query.filter_resolve`` spans'
        # ``names_read``, summed)
        self.filter_names_read = 0
        # assemble stages, by where the groups' common and aggregated
        # tags were read: "index" (the plan index's cached layout),
        # "small" (a sort of the request's own rows, few enough of an
        # index's that this is the cheaper way) or "matrix" (the same
        # sort because there was no index to read)
        self.assembles = {"index": 0, "small": 0, "matrix": 0}
        self._ring: deque[TraceData] = deque(
            maxlen=max(config.get_int("tsd.trace.ring", 256), 1))
        self._slow_ring: deque[TraceData] = deque(
            maxlen=max(config.get_int("tsd.trace.slow_ring", 64), 1))
        self._index: dict[str, TraceData] = {}
        self._root_count = 0
        # counters (exported via collect_stats + /api/health)
        self.traces_started = 0
        self.traces_committed = 0
        self.traces_sampled_out = 0
        self.slow_traces = 0
        self.spans_dropped = 0
        # query-shape log: bounded JSONL ring file in data_dir
        self.shape_path = ""
        if data_dir and config.get_bool("tsd.trace.shapes.enable",
                                        True):
            self.shape_path = os.path.join(data_dir,
                                           "query_shapes.jsonl")
        self.shape_max_bytes = max(
            config.get_int("tsd.trace.shapes.max_kb", 1024), 1) * 1024
        self._shape_lock = threading.Lock()
        self._shape_fh = None
        self.shape_lines = 0
        self.shape_errors = 0

    # -- root creation -------------------------------------------------

    def _sample_next(self) -> bool:
        """Deterministic 1-in-N retention: the 1st, (N+1)th, ... roots
        are kept — a counter, not a coin flip, so trace batteries (and
        the bench) reproduce exactly."""
        with self._lock:
            self._root_count += 1
            return (self._root_count - 1) % self.sample_n == 0

    def start_request(self, name: str, request=None,
                      remote: str = "") -> TraceContext | None:
        """Root a request trace, honoring an ``X-TSD-Trace`` header
        when present (cluster propagation: the upstream router made
        the sampling decision and this node's subtree must exist iff
        the router's tree does). Returns None when tracing is off."""
        if not self.enabled:
            return None
        if name not in KNOWN_SPANS:
            raise ValueError(
                f"unknown span name {name!r}; register it in "
                f"obs/trace.py KNOWN_SPANS")
        trace_id = parent_id = ""
        forced = False
        headers = getattr(request, "headers", None) \
            if self.accept_headers else None
        if headers:
            parsed = parse_trace_header(
                headers.get(TRACE_HEADER, ""))
            if parsed is not None:
                trace_id, parent_id, forced = parsed
        if trace_id:
            sampled = forced
        else:
            trace_id = _next_id()
            sampled = self._sample_next()
        ctx = TraceContext(
            self, trace_id, name, sampled, forced,
            parent_id=parent_id,
            remote=remote or getattr(request, "remote", ""))
        with self._lock:
            self.traces_started += 1
        # the read of the request and the admission/queue wait
        # predate this context: synthesize them from the server's
        # stamps so the trace shows where a loaded TSD's queries
        # actually wait (a direct handle() call has neither stamp)
        received = getattr(request, "received_at", 0.0)
        if received and name == "query.http":
            first = getattr(request, "first_byte_at", 0.0)
            if first:
                record_span(ctx, "query.receive", first, received)
            record_span(ctx, "query.admission", received, ctx._t0)
        return ctx

    def start_background(self, name: str, sample: bool = False,
                         **tags) -> TraceContext | None:
        """Root a background trace (sweep, spill, drain, replay).
        ``sample=True`` applies the 1-in-N retention (for
        high-frequency roots like fold drains); the default retains
        every occurrence — background roots are rare and are exactly
        what an operator goes looking for."""
        if not self.enabled:
            return None
        if name not in KNOWN_SPANS:
            raise ValueError(
                f"unknown span name {name!r}; register it in "
                f"obs/trace.py KNOWN_SPANS")
        sampled = self._sample_next() if sample else True
        ctx = TraceContext(self, _next_id(), name, sampled, False)
        if tags:
            ctx.tag(**tags)
        with self._lock:
            self.traces_started += 1
        return ctx

    def header_for(self, ctx: TraceContext,
                   span: SpanHandle | None = None) -> str:
        """The ``X-TSD-Trace`` value a downstream hop should carry:
        the hop's subtree hangs off ``span`` (this node's per-peer
        span) and inherits the retention decision.

        With a slowlog configured, QUERY hops always propagate
        flag=1: slow-retention is decided at finish, AFTER the shards
        already chose whether to keep their subtrees — without this a
        slow-but-unsampled router trace would commit locally and
        stitch an empty tree, losing exactly the evidence the
        slowlog exists for. Shard rings are bounded, so the cost is
        churn, not growth."""
        parent = span.span_id if span is not None else \
            ctx.root_span_id
        keep = ctx.sampled or ctx.forced or \
            (self.slow_ms > 0 and ctx.root_name.startswith("query"))
        return f"{ctx.trace_id}:{parent}:{'1' if keep else '0'}"

    # -- finish / commit -----------------------------------------------

    def finish(self, ctx: TraceContext | None) -> bool:
        """Close a root: feed the stage histograms, decide retention
        (sampled | propagated-sampled | slow | error), commit to the
        ring(s). Returns whether the trace was retained."""
        if ctx is None:
            return False
        with ctx._lock:
            if ctx.finished:
                return ctx.committed
            ctx.finished = True
            spans = list(ctx.spans)
            dropped = ctx.dropped_spans
        # the root ends here, and what follows is the tracer's own
        # bookkeeping, timed into tsd.trace.finish_ms
        t_end = _now()
        duration_ms = (t_end - ctx._t0) * 1000.0
        gc_ms = RUNTIME.gc_pause_ms[2] - ctx._gc2_ms0
        if gc_ms > 0:
            # a full collection ran inside this root
            ctx.tags["gc_ms"] = round(gc_ms, 1)
        root = SpanRecord(
            ctx.root_span_id, ctx.parent_id, ctx.root_name,
            ctx.start_epoch_ms, duration_ms,
            "error" if ctx.error else "ok", ctx.error, dict(ctx.tags),
            # the root never sampled the clock: what its children saw
            sum(s.occupied_ms for s in spans
                if s.parent_id == ctx.root_span_id))
        # per-stage latency histograms see EVERY traced request —
        # sampling gates only ring retention, so /api/stats
        # percentiles are not biased toward the sampled subset
        stats = self.stats
        observed = 0
        if stats is not None:
            stats.observe_stage(root.name, duration_ms)
            for s in spans:
                stats.observe_stage(s.name, s.duration_ms)
            observed = 1 + len(spans)
        observed += self._account_self_time(root, spans)
        slow = (self.slow_ms > 0 and duration_ms >= self.slow_ms
                and ctx.root_name.startswith("query"))
        commit = ctx.sampled or ctx.forced or slow or bool(ctx.error)
        data = TraceData(ctx.trace_id, root,
                         tuple([root] + spans), slow)
        with self._lock:
            self.spans_dropped += dropped
            if not commit:
                self.traces_sampled_out += 1
            else:
                self.traces_committed += 1
                if slow:
                    self.slow_traces += 1
                existing = self._index.get(ctx.trace_id)
                if existing is not None:
                    # a shard can serve SEVERAL legs of one trace
                    # (per-sub retries, hedged duplicates): merge the
                    # new leg's spans instead of last-write-wins,
                    # which silently lost every earlier leg's subtree
                    # from the stitched tree
                    data = TraceData(
                        ctx.trace_id, existing.root,
                        existing.spans + data.spans,
                        existing.slow or slow)
                    self._index[ctx.trace_id] = data
                    for ring in (self._ring, self._slow_ring):
                        for i, d in enumerate(ring):
                            if d is existing:
                                ring[i] = data
                                break
                        else:
                            continue
                        break
                else:
                    ring = self._slow_ring if slow else self._ring
                    if len(ring) == ring.maxlen:
                        evicted = ring[0]
                        if self._index.get(evicted.trace_id) \
                                is evicted:
                            del self._index[evicted.trace_id]
                    ring.append(data)
                    self._index[ctx.trace_id] = data
        ctx.slow = slow
        ctx.committed = commit
        if slow:
            # the WARN lands in the /logs ring; the trace id is the
            # cross-reference into /api/trace/<id>
            LOG.warning(
                "slow query trace=%s %.1fms >= slowlog threshold "
                "%.0fms (remote=%s, retained at full fidelity)",
                ctx.trace_id, duration_ms, self.slow_ms, ctx.remote)
        if commit and ctx.root_name == "query.http" and \
                self.shape_path:
            self._write_shape(ctx, root, spans)
        done = ctx.finished_at = _now()
        with self._lock:
            self.finish_ms += (done - t_end) * 1000.0
            self.observations += observed
        return commit

    def record_respond(self, ctx: TraceContext | None,
                       end_mono: float) -> None:
        """The last stage of a served query, which no handler sees:
        from the instant :meth:`finish` was done with the root (in
        the worker) to ``end_mono``, the server's stamp after the
        response's last ``drain`` -- the wake-up of the event loop,
        the latency / SLO / tenant feeds, CORS, gzip, the write. Fed
        to the stage histograms for every request; a retained trace
        gets the span under its root LATE: the trace was committed
        (ring, shape log, a router's stitch) before the response was
        written, so a reader that is quick enough sees the tree
        without it, and a window's last request may feed the
        histogram after the window's last snapshot. The server calls
        this only where the worker's response is the one it wrote
        (not for a shed query or one that timed out)."""
        if ctx is None or not ctx.finished_at:
            return
        ms = (end_mono - ctx.finished_at) * 1000.0
        if self.stats is not None:
            self.stats.observe_stage("query.respond", ms)
        if not ctx.committed:
            return
        rec = SpanRecord(
            f"{ctx._nonce}-respond", ctx.root_span_id, "query.respond",
            ctx.start_epoch_ms + (ctx.finished_at - ctx._t0) * 1000.0,
            ms)
        with self._lock:
            data = self._index.get(ctx.trace_id)
            if data is not None:
                data.spans += (rec,)

    def _account_self_time(self, root: SpanRecord,
                           spans: list[SpanRecord]) -> int:
        """Each span's SELF time (its duration minus the union of its
        children's intervals) feeds ``tsd_stage_self_ms`` where the
        span has children; the part of it with no program in flight
        on the device adds to ``idle_stage_ms`` of its stage; every
        ``query.program`` counts in ``tails`` (and by its tag ``rank``
        in ``ranks``, by ``carry`` in ``carries``), every
        ``query.grid_build``
        that built a grid (tag ``fused``) in ``grid_builds`` and that
        looked one up (tag ``grid``) in ``grids``, every
        ``query.plan`` that reached its filters (tag ``index``) in
        ``plans`` and its filters (tags ``resolve_<way>``) in
        ``filters``, every ``query.plan`` by its tag ``source`` in
        ``rollups``, every ``query.filter_resolve``'s ``names_read``
        in ``filter_names_read`` and its tag ``table`` in
        ``filter_tables``, every ``query.assemble`` by its tag
        ``tags`` in ``assembles``. Returns the histogram observations
        made."""
        kids: dict[str, list[SpanRecord]] = {}
        for s in spans:
            kids.setdefault(s.parent_id, []).append(s)
        idle: dict[str, float] = {}
        observed = 0
        tails = []
        ranks = []
        carries = []
        builds = []
        grids = []
        plans = []
        rollups = []
        filters = []
        names_read = 0
        tables = []
        assembles = []
        for s in [root] + spans:
            self_ms, occupied = s.duration_ms, s.occupied_ms
            mine = kids.get(s.span_id)
            if mine:
                lo, hi = s.start_ms, s.start_ms + s.duration_ms
                covered, edge = 0.0, lo
                for c in sorted(mine, key=lambda c: c.start_ms):
                    a = max(c.start_ms, edge)
                    b = min(c.start_ms + c.duration_ms, hi)
                    if b > a:
                        covered += b - a
                        edge = b
                self_ms = max(self_ms - covered, 0.0)
                occupied -= sum(c.occupied_ms for c in mine)
                if self.stats is not None:
                    self.stats.observe_stage_self(s.name, self_ms)
                    observed += 1
            idle[s.name] = idle.get(s.name, 0.0) + self_ms \
                - min(max(occupied, 0.0), self_ms)
            if s.name == "query.program":
                tails.append((str(s.tags.get("path", "?")),
                              str(s.tags.get("placement", "?")),
                              str(s.tags.get("class", "?"))))
                if s.tags.get("rank") in self.ranks:
                    ranks.append(s.tags["rank"])
                if s.tags.get("carry") in self.carries:
                    carries.append(s.tags["carry"])
            elif s.name == "query.grid_build":
                if "fused" in s.tags:
                    builds.append("fused" if s.tags["fused"]
                                  else "host")
                elif s.tags.get("grid") in self.grids:
                    grids.append(s.tags["grid"])
            elif s.name == "query.plan":
                if s.tags.get("source") in self.rollups:
                    rollups.append(s.tags["source"])
                if s.tags.get("index") in self.plans:
                    plans.append(s.tags["index"])
                    filters += [(way, s.tags["resolve_" + way])
                                for way in self.filters
                                if "resolve_" + way in s.tags]
            elif s.name == "query.filter_resolve":
                names_read += s.tags.get("names_read", 0)
                if s.tags.get("table") in self.filter_tables:
                    tables.append(s.tags["table"])
            elif s.name == "query.assemble" and s.tags.get("tags") \
                    in self.assembles:
                assembles.append(s.tags["tags"])
        with self._lock:
            for name, ms in idle.items():
                self.idle_stage_ms[name] = \
                    self.idle_stage_ms.get(name, 0.0) + ms
            for key in tails:
                self.tails[key] = self.tails.get(key, 0) + 1
            for method in ranks:
                self.ranks[method] += 1
            for form in carries:
                self.carries[form] += 1
            for mode in builds:
                self.grid_builds[mode] += 1
            for source in grids:
                self.grids[source] += 1
            for state in plans:
                self.plans[state] += 1
            for source in rollups:
                self.rollups[source] += 1
            for way, n in filters:
                self.filters[way] += n
            self.filter_names_read += names_read
            for state in tables:
                self.filter_tables[state] += 1
            for way in assembles:
                self.assembles[way] += 1
        return observed

    # -- retrieval -----------------------------------------------------

    def get(self, trace_id: str) -> TraceData | None:
        with self._lock:
            return self._index.get(trace_id)

    def recent(self, status: str = "", min_duration_ms: float = 0.0,
               slow_only: bool = False, limit: int = 50
               ) -> list[dict[str, Any]]:
        with self._lock:
            items = list(self._slow_ring) if slow_only else \
                list(self._ring) + list(self._slow_ring)
        items.sort(key=lambda d: d.root.start_ms, reverse=True)
        out = []
        for d in items:
            if status and d.root.status != status:
                continue
            if d.root.duration_ms < min_duration_ms:
                continue
            out.append(d.summary())
            if len(out) >= max(limit, 1):
                break
        return out

    # -- query-shape log -----------------------------------------------

    def _write_shape(self, ctx: TraceContext, root: SpanRecord,
                     spans: list[SpanRecord]) -> None:
        stages: dict[str, float] = {}
        for s in spans:
            stages[s.name] = round(
                stages.get(s.name, 0.0) + s.duration_ms, 3)
        line = json.dumps({
            "ts": round(root.start_ms / 1000.0, 3),
            "traceId": ctx.trace_id,
            "durationMs": round(root.duration_ms, 3),
            "status": root.status,
            "slow": ctx.slow,
            **{k: v for k, v in root.tags.items()},
            "stages": stages,
        }) + "\n"
        try:
            with self._shape_lock:
                fh = self._shape_fh
                if fh is None:
                    fh = self._shape_fh = open(self.shape_path, "a",
                                               encoding="utf-8")
                fh.write(line)
                fh.flush()
                if fh.tell() >= self.shape_max_bytes:
                    # bounded ring: one rotation generation keeps the
                    # most recent window without unbounded growth
                    fh.close()
                    self._shape_fh = None
                    os.replace(self.shape_path,
                               self.shape_path + ".1")
                self.shape_lines += 1
        except OSError:
            # mining data must never fail (or slow) a served query
            self.shape_errors += 1

    def close(self) -> None:
        with self._shape_lock:
            if self._shape_fh is not None:
                try:
                    self._shape_fh.close()
                except OSError:  # pragma: no cover - teardown race
                    LOG.warning("query-shape log close failed")
                self._shape_fh = None

    # -- observability about the observer ------------------------------

    def collect_stats(self, collector) -> None:
        collector.record("trace.started", self.traces_started)
        collector.record("trace.committed", self.traces_committed)
        collector.record("trace.sampled_out", self.traces_sampled_out)
        collector.record("trace.slow", self.slow_traces)
        collector.record("trace.spans_dropped", self.spans_dropped)
        collector.record("trace.shape_lines", self.shape_lines)
        collector.record("trace.shape_errors", self.shape_errors)
        RUNTIME.collect_stats(collector)
        with self._lock:
            collector.record("trace.finish_ms", self.finish_ms)
            collector.record("trace.observations", self.observations)
            idle = sorted(self.idle_stage_ms.items())
            tails = sorted(self.tails.items())
            ranks = sorted(self.ranks.items())
            carries = sorted(self.carries.items())
            builds = sorted(self.grid_builds.items())
            grids = sorted(self.grids.items())
            plans = sorted(self.plans.items())
            rollups = sorted(self.rollups.items())
            filters = sorted(self.filters.items())
            names_read = self.filter_names_read
            tables = sorted(self.filter_tables.items())
            assembles = sorted(self.assembles.items())
        for stage, ms in idle:
            collector.record("device.idle_stage_ms", ms, stage=stage)
        for (path, placement, cls), n in tails:
            collector.record("query.tail", n, path=path,
                             placement=placement, **{"class": cls})
        for method, n in ranks:
            collector.record("query.rank", n, method=method)
        for form, n in carries:
            collector.record("query.carry", n, form=form)
        for mode, n in builds:
            collector.record("query.grid_build", n, mode=mode)
        for source, n in grids:
            collector.record("query.grid", n, source=source)
        for state, n in plans:
            collector.record("query.plan", n, index=state)
        for source, n in rollups:
            collector.record("query.rollup", n, source=source)
        for way, n in filters:
            collector.record("query.filter", n, resolve=way)
        collector.record("query.filter.names_read", names_read)
        for state, n in tables:
            collector.record("query.filter.table", n, state=state)
        for way, n in assembles:
            collector.record("query.assemble", n, tags=way)

    def health_info(self) -> dict[str, Any]:
        with self._lock:
            ring_len = len(self._ring)
            slow_len = len(self._slow_ring)
        return {
            "enabled": self.enabled,
            "sample": self.sample_n,
            "ring": ring_len,
            "slow_ring": slow_len,
            "slowlog_threshold_ms": self.slow_ms,
            "started": self.traces_started,
            "committed": self.traces_committed,
            "sampled_out": self.traces_sampled_out,
            "slow": self.slow_traces,
            "spans_dropped": self.spans_dropped,
            "shape_log": self.shape_path,
            "shape_lines": self.shape_lines,
        }
