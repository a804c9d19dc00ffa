"""Observability (ref: ``src/stats/``).

- :class:`StatsCollector` — push-style visitor every component implements
  ``collect_stats(collector)`` against (ref: StatsCollector.java:35).
- :class:`Histogram` — fixed-bucket latency histogram with percentile
  extraction (ref: src/stats/Histogram.java:38).
- :class:`QueryStats` — per-query trace threaded through the read path,
  with a registry of running/completed queries for ``/api/stats/query``
  (ref: src/stats/QueryStats.java:58).
"""

from __future__ import annotations

import bisect
import threading
import time
from collections import deque
from enum import Enum
from typing import Any


class DuplicateQueryError(ValueError):
    """An identical query is already in flight from the same endpoint
    and ``tsd.query.allow_simultaneous_duplicates`` is off (ref:
    QueryException from QueryStats.java:263)."""


class StatsCollector:
    """(ref: StatsCollector.java:35) Collects ``name value tags`` records."""

    def __init__(self, prefix: str = "tsd"):
        self.prefix = prefix
        # tsdlint: allow[unbounded-growth] one collector per stats
        # snapshot — it lives for a single collect() pass
        self.records: list[tuple[str, float, dict[str, str]]] = []
        self._extra_tags: dict[str, str] = {}

    def add_extra_tag(self, key: str, value: str) -> None:
        self._extra_tags[key] = value

    def clear_extra_tag(self, key: str) -> None:
        self._extra_tags.pop(key, None)

    def record(self, name: str, value: float, **tags: str) -> None:
        all_tags = dict(self._extra_tags)
        all_tags.update({k: str(v) for k, v in tags.items()})
        self.records.append((f"{self.prefix}.{name}", float(value), all_tags))

    def lines(self) -> list[str]:
        """Telnet ``stats`` output format: ``name timestamp value k=v ...``"""
        now = int(time.time())
        out = []
        for name, value, tags in self.records:
            tag_str = " ".join(f"{k}={v}" for k, v in sorted(tags.items()))
            val = int(value) if float(value).is_integer() else value
            out.append(f"{name} {now} {val}"
                       + (f" {tag_str}" if tag_str else ""))
        return out

    def as_json(self) -> list[dict[str, Any]]:
        now = int(time.time())
        return [{"metric": name, "timestamp": now, "value": value,
                 "tags": tags} for name, value, tags in self.records]


#: percentile points exported for every latency histogram
LATENCY_PCTS = (("p50", 50.0), ("p95", 95.0), ("p99", 99.0),
                ("p999", 99.9))


class StatsCollectorRegistry:
    """Aggregates collect_stats providers; owned by the TSDB.

    Also owns the latency histograms: the request-level
    ``latency_put``/``latency_query`` pair (fed by the server per
    request) and the per-STAGE map fed by the tracer for every traced
    request (``wal.commit_wait``, ``query.execute``,
    ``cluster.merge``, ``query.serialize``, ... — one histogram per
    registered span name that actually fires). All export
    p50/p95/p99/p999 at ``/api/stats`` (``tsd.latency.*``) and
    ``/api/health``."""

    def __init__(self) -> None:
        # tsdlint: allow[unbounded-growth] one registration per
        # component at construction — bounded by the component count
        self._providers: list[Any] = []
        # 1ms linear buckets (not the reference's 100ms): these now
        # EXPORT percentiles, and a bucket-upper-bound percentile
        # over 100ms buckets would report p50=100 for every
        # single-digit-ms workload — a 30x misreading
        self.latency_put = Histogram(16000, 2, 1)
        self.latency_query = Histogram(16000, 2, 1)
        self._stage_lock = threading.Lock()
        # tsdlint: allow[unbounded-growth] keyed by span stage name —
        # the CLOSED obs.trace.KNOWN_SPANS registry (runtime-raised
        # and tsdlint-gated), so the keyspace cannot grow unchecked
        self.stage_latency: dict[str, Histogram] = {}
        # tsdlint: allow[unbounded-growth] the same closed keyspace:
        # SELF time (duration minus children) of stages with children
        self.stage_self: dict[str, Histogram] = {}

    def register(self, provider: Any) -> None:
        self._providers.append(provider)

    def observe_stage(self, stage: str, ms: float) -> None:
        """Record one stage latency (ms). Histograms are created on
        first observation; the population is bounded by the closed
        span-name registry (obs/trace.py KNOWN_SPANS)."""
        h = self.stage_latency.get(stage)
        if h is None:
            with self._stage_lock:
                h = self.stage_latency.setdefault(
                    stage, Histogram(16000, 2, 1))
        h.add(ms)

    def observe_stage_self(self, stage: str, ms: float) -> None:
        """Record the self time (ms) of one span that had children:
        what none of them names (``tsd_stage_self_ms``)."""
        h = self.stage_self.get(stage)
        if h is None:
            with self._stage_lock:
                h = self.stage_self.setdefault(
                    stage, Histogram(16000, 2, 1))
        h.add(ms)

    def _stage_snapshot(self) -> dict[str, Histogram]:
        """Iteration-safe copy: observe_stage inserts first-seen
        stages concurrently, and iterating the live dict would raise
        'dictionary changed size during iteration' mid-/api/stats."""
        with self._stage_lock:
            return dict(self.stage_latency)

    def latency_summary(self) -> dict[str, Any]:
        """Percentile summaries for /api/health."""
        out: dict[str, Any] = {
            "put": self.latency_put.percentiles(),
            "query": self.latency_query.percentiles(),
        }
        stages = {}
        for name, h in sorted(self._stage_snapshot().items()):
            if h.count:
                stages[name] = h.percentiles()
        out["stages"] = stages
        return out

    def histograms(self) -> "list[tuple[str, dict[str, str], Histogram]]":
        """Every histogram this registry owns, with its exposition
        identity ``(family name, labels, histogram)`` — the ONE
        enumeration the ``/metrics`` renderer and the per-node
        ``/api/stats/raw`` fleet-merge source both walk (tsdlint's
        ``histogram-export`` pass checks that every ``Histogram``
        constructed in the package is reachable from here or from the
        renderer directly)."""
        out: list[tuple[str, dict[str, str], Histogram]] = [
            ("tsd_request_latency_ms", {"op": "put"},
             self.latency_put),
            ("tsd_request_latency_ms", {"op": "query"},
             self.latency_query),
        ]
        # direct load (not via _stage_snapshot): the histogram-export
        # pass proves reachability lexically, and this method IS the
        # reachability evidence for the stage registry
        with self._stage_lock:
            stages = dict(self.stage_latency)
            selfs = dict(self.stage_self)
        for stage, h in sorted(stages.items()):
            out.append(("tsd_stage_latency_ms", {"stage": stage}, h))
        for stage, h in sorted(selfs.items()):
            out.append(("tsd_stage_self_ms", {"stage": stage}, h))
        return out

    def collect(self, prefix: str = "tsd",
                latency_percentiles: bool = True) -> StatsCollector:
        collector = StatsCollector(prefix)
        for p in self._providers:
            p.collect_stats(collector)
        if not latency_percentiles:
            # the /metrics renderer serves the SAME histograms in
            # native cumulative-bucket form — percentile records
            # would double-export them under a second name
            return collector
        # latency percentiles ride the same record stream so
        # /api/stats, telnet `stats` and the self-telemetry pump all
        # see them without extra plumbing
        named = [("latency.put", self.latency_put),
                 ("latency.query", self.latency_query)]
        named += [(f"latency.{name}", h)
                  for name, h in sorted(
                      self._stage_snapshot().items())]
        for name, hist in named:
            if not hist.count:
                continue
            vals = hist.percentile_many(
                [q for _l, q in LATENCY_PCTS])
            for (label, _q), v in zip(LATENCY_PCTS, vals):
                collector.record(name, v, pct=label)
            collector.record(f"{name}.count", hist.count)
        return collector


#: observations a Histogram holds back before it folds them into its
#: quantile sketch in one pass
_SKETCH_FOLD_AT = 256


class Histogram:
    """Exponentially-bucketed histogram (ref: src/stats/Histogram.java:38).

    Buckets are linear (width ``interval``) up to ``cutoff``, then double
    per bucket — same shape as the reference's constructor
    ``Histogram(max, num_linear? , interval)`` usage for latencies.
    """

    def __init__(self, max_value: int = 16000, num_bands: int = 2,
                 interval: int = 100):
        self.interval = interval
        self.max_value = max_value
        n_linear = max(1, (max_value // (2 ** (num_bands - 1))) // interval)
        self.bounds: list[int] = [interval * (i + 1) for i in range(n_linear)]
        while self.bounds[-1] < max_value:
            self.bounds.append(min(self.bounds[-1] * 2, max_value))
        self.buckets = [0] * (len(self.bounds) + 1)
        self.count = 0
        # running sum of observed values: the OpenMetrics ``_sum``
        # series — fleet merges add sums like they add bucket counts
        self.sum = 0.0
        self._lock = threading.Lock()
        # companion quantile sketch: relative-error percentiles that
        # merge across nodes even when bucket tables differ — the
        # fleet merge's escape hatch for mixed-build fleets (see
        # cluster/fleet.py). Rides the snapshot as a base64 field.
        from opentsdb_tpu.sketch.ddsketch import DDSketch
        self._sketch = DDSketch()
        # values observed and not yet folded into the sketch: one
        # value costs the sketch what 256 cost it (a mask, a unique,
        # a merge of arrays), and an observation sits on a request's
        # path, so add() appends here and whoever reads the sketch
        # folds first. A DDSketch's state is canonical, so values
        # folded at once leave what one by one would (a test holds
        # every exported number and the base64 sketch to that).
        self._pending: list[float] = []

    def add(self, value: float) -> None:
        # bisect_left: first bound >= value, i.e. the first bucket
        # whose `value <= bound` test passes — identical placement to
        # a linear scan at O(log n) per observation
        idx = bisect.bisect_left(self.bounds, value)
        with self._lock:
            self.buckets[min(idx, len(self.buckets) - 1)] += 1
            self.count += 1
            self.sum += value
            self._pending.append(value)
            if len(self._pending) >= _SKETCH_FOLD_AT:
                self._fold_pending()

    def _fold_pending(self) -> None:
        """Under the lock: the pending values into the sketch."""
        if self._pending:
            self._sketch.add_values(self._pending)
            self._pending.clear()

    def snapshot(self) -> dict[str, Any]:
        """Consistent copy of the raw state — the wire form the
        ``/metrics`` renderer and the fleet bucket-merge consume
        (bounds are construction-time constants; counts/sum are read
        under the lock so a snapshot is never torn mid-``add``)."""
        with self._lock:
            self._fold_pending()
            return {"bounds": list(self.bounds),
                    "buckets": list(self.buckets),
                    "count": self.count, "sum": self.sum,
                    "sketch": self._sketch.to_b64()}

    def percentile(self, pct: float) -> float:
        """(ref: Histogram.percentile)"""
        if not 0 < pct <= 100:
            raise ValueError(f"invalid percentile {pct}")
        return self.percentile_many([pct])[0]

    def percentile_many(self, pcts: "list[float]") -> "list[float]":
        """All requested percentiles from ONE cumulative pass over a
        snapshot of the buckets — the scan runs OUTSIDE the lock (a
        stats/health collection walking thousands of 1ms buckets
        per-percentile under the lock would repeatedly block
        hot-path ``add()`` calls)."""
        with self._lock:
            count = self.count
            buckets = list(self.buckets)  # C-level copy
        return percentiles_from_buckets(self.bounds, buckets, count,
                                        pcts)

    def percentiles(self) -> dict[str, float]:
        """The standard export points + the sample count."""
        vals = self.percentile_many([q for _l, q in LATENCY_PCTS])
        out = {label: v for (label, _q), v in zip(LATENCY_PCTS, vals)}
        out["count"] = self.count
        return out

    def print_ascii(self) -> str:
        lines = []
        lo = 0
        for i, c in enumerate(self.buckets[:-1]):
            lines.append(f"[{lo}-{self.bounds[i]}): {c}")
            lo = self.bounds[i]
        lines.append(f"[{lo}-inf): {self.buckets[-1]}")
        return "\n".join(lines)


def percentiles_from_buckets(bounds: "list[int]", buckets: "list[int]",
                             count: int,
                             pcts: "list[float]") -> "list[float]":
    """Bucket-upper-bound percentiles in one cumulative pass — shared
    by :meth:`Histogram.percentile_many` and the fleet bucket-merge,
    so a fleet percentile over summed buckets is BIT-identical to the
    same observations landing in one histogram (both read the same
    bound for the same cumulative rank)."""
    if count == 0:
        return [0.0] * len(pcts)
    targets = sorted((count * p / 100.0, j) for j, p in enumerate(pcts))
    out = [0.0] * len(pcts)
    acc = 0
    t = 0
    last_bound = len(bounds) - 1
    for i, c in enumerate(buckets):
        acc += c
        while t < len(targets) and acc >= targets[t][0]:
            out[targets[t][1]] = float(bounds[min(i, last_bound)])
            t += 1
        if t >= len(targets):
            break
    for k in range(t, len(targets)):
        out[targets[k][1]] = float(bounds[-1])
    return out


def merge_histogram_snapshots(snaps: "list[dict]") -> "dict | None":
    """Element-wise bucket/count/sum merge of :meth:`Histogram.
    snapshot` documents sharing one bound table (every histogram in
    the package uses the same 1ms construction, so per-shard
    snapshots of the same stage always merge). Returns None on an
    empty list or mismatched bounds — the caller reports the node
    instead of producing a silently wrong distribution."""
    merged: dict | None = None
    for s in snaps:
        bounds = s.get("bounds")
        buckets = s.get("buckets")
        if not isinstance(bounds, list) or not isinstance(
                buckets, list) or len(buckets) != len(bounds) + 1:
            return None
        if merged is None:
            merged = {"bounds": list(bounds),
                      "buckets": list(buckets),
                      "count": int(s.get("count", 0)),
                      "sum": float(s.get("sum", 0.0))}
            continue
        if bounds != merged["bounds"]:
            return None
        mb = merged["buckets"]
        for i, c in enumerate(buckets):
            mb[i] += int(c)
        merged["count"] += int(s.get("count", 0))
        merged["sum"] += float(s.get("sum", 0.0))
    return merged


# ---------------------------------------------------------------------------
# counter-vs-gauge classification (exposition + fleet merge)
# ---------------------------------------------------------------------------
# The push-style record stream carries no type information, so the
# OpenMetrics renderer and the fleet aggregator share one advisory
# classification: a GAUGE is a point-in-time level (summing it across
# nodes or scrapes is meaningless); everything else is a monotonic
# counter. Exact names first, then substring markers for the families
# (`*_bytes`, `*pending*`, ...) the codebase consistently uses for
# levels. Misclassification is cosmetic for Prometheus (TYPE line);
# for fleet merges it decides sum-vs-min/max presentation only.

_GAUGE_NAMES: frozenset[str] = frozenset({
    "admission.inflight",
    "cluster.epoch",
    "cluster.rf",
    "datapoints.memory",
    "runtime.gc_max_pause_ms",  # the longest pause so far
    "startup.phase_s",       # how long a start-up phase took
    "uptime.seconds",
    "wal.sync_lag",          # records not yet fsynced: a level
    "wal.records_per_sync",  # a ratio, not a count
    "wal.degraded",          # 0/1 flag
})

_GAUGE_MARKERS: tuple[str, ...] = (
    "_bytes", ".bytes", "pending", "backlog", "depth",
    "inflight", "entries", "resident", "uptime",
    ".lag", "_size", ".size", "open_", ".open", "_open", "queue",
    "interval", "cache-size", "burn_rate",
)


def is_gauge(name: str) -> bool:
    """Advisory: True when the record named ``name`` (without the
    collector prefix) reads as a level rather than a monotonic
    count. A ``*_total``/``*.total`` name is a counter no matter
    what substring it also contains (``query.payload.bytes_total``
    is a monotonic byte count, not a level)."""
    if name.endswith("_total") or name.endswith(".total"):
        return False
    if name in _GAUGE_NAMES:
        return True
    return any(m in name for m in _GAUGE_MARKERS)


class QueryStat(Enum):
    """Stat points recorded along the read path
    (ref: QueryStats.java QueryStat enum :132)."""
    COMPILATION_TIME = "compilationTime"
    UID_TO_STRING_TIME = "uidToStringTime"
    STRING_TO_UID_TIME = "stringToUidTime"
    SCANNER_TIME = "scannerTime"
    SCANNER_UID_TO_STRING_TIME = "scannerUidToStringTime"
    MATERIALIZE_TIME = "materializeTime"
    DEVICE_TRANSFER_TIME = "deviceTransferTime"
    COMPUTE_TIME = "computeTime"
    AGGREGATION_TIME = "aggregationTime"
    GROUP_BY_TIME = "groupByTime"
    SERIALIZATION_TIME = "serializationTime"
    TOTAL_TIME = "totalTime"
    ROWS_SCANNED = "rowsScanned"
    DPS_PRE_FILTER = "dpsPreFilter"
    DPS_POST_FILTER = "dpsPostFilter"
    EMITTED_DPS = "emittedDPs"
    MAX_HBM_BYTES = "maxHbmBytes"
    # storage stats — TPU mapping: "storage" is the host column store,
    # a column ≙ a stored point, a row ≙ a series
    COLUMNS_FROM_STORAGE = "columnsFromStorage"
    ROWS_FROM_STORAGE = "rowsFromStorage"
    BYTES_FROM_STORAGE = "bytesFromStorage"
    SUCCESSFUL_SCAN = "successfulScan"
    ROWS_PRE_FILTER = "rowsPreFilter"
    ROWS_POST_FILTER = "rowsPostFilter"
    COMPACTION_TIME = "compactionTime"      # lazy sort/dedupe (N/A: 0)
    HBASE_TIME = "hbaseTime"                # storage engine wait
    UID_PAIRS_RESOLVED = "uidPairsResolved"
    SCANNER_MERGE_TIME = "saltScannerMergeTime"
    QUERY_SCAN_TIME = "queryScanTime"
    NAN_DPS = "nanDPs"
    PROCESSING_PRE_WRITE_TIME = "processingPreWriteTime"
    # serve-path result cache outcomes (no reference equivalent: the
    # reference's graph cache lives outside QueryStats entirely)
    RESULT_CACHE_HIT = "resultCacheHit"
    RESULT_CACHE_COALESCED = "resultCacheCoalesced"
    # served from a continuous query's maintained live windows
    # (opentsdb_tpu/streaming/) — no store scan, tail-only compute
    STREAMING_HIT = "streamingHit"
    # serve-path payload observability: response body bytes actually
    # written for this query (materialized or streamed), and the
    # pixel budget its output was reduced under (0 = full resolution)
    PAYLOAD_BYTES = "payloadBytes"
    DOWNSAMPLE_PIXELS = "downsamplePixels"


# time-based stats that get the reference's derived max*/avg* twins in
# /api/stats/query output (one logical scanner here, so max == avg ==
# the base value; consumers of the reference's schema still find them)
_DERIVED_TIMES = {
    "hbaseTime": ("maxHBaseTime", "avgHBaseTime"),
    "scannerTime": ("maxScannerTime", "avgScannerTime"),
    "uidToStringTime": ("maxUidToStringTime", "avgUidToStringTime"),
    "compactionTime": ("maxCompactionTime", "avgCompactionTime"),
    "scannerUidToStringTime": ("maxScannerUidtoStringTime",
                               "avgScannerUidToStringTime"),
    "saltScannerMergeTime": ("maxSaltScannerMergeTime",
                             "avgSaltScannerMergeTime"),
    "queryScanTime": ("maxQueryScanTime", "avgQueryScanTime"),
    "aggregationTime": ("maxAggregationTime", "avgAggregationTime"),
    "serializationTime": ("maxSerializationTime",
                          "avgSerializationTime"),
}


class ServePayloadStats:
    """Aggregate serve-path payload counters: total response bytes,
    serialization milliseconds and response count across every
    /api/query answered by this process, so the wire-size effect of
    pixel-aware downsampling is measurable in production (not just in
    bench) — exported at ``/api/stats`` and ``/api/health``."""

    __slots__ = ("_lock", "payload_bytes", "serialization_ms",
                 "responses", "pixel_responses")

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self.payload_bytes = 0
        self.serialization_ms = 0.0
        self.responses = 0
        self.pixel_responses = 0

    def record(self, nbytes: int, ser_ms: float,
               pixels: int = 0) -> None:
        with self._lock:
            self.payload_bytes += int(nbytes)
            self.serialization_ms += float(ser_ms)
            self.responses += 1
            if pixels:
                self.pixel_responses += 1

    def collect_stats(self, collector) -> None:
        collector.record("query.payload.bytes_total",
                         self.payload_bytes)
        collector.record("query.payload.serialization_ms_total",
                         self.serialization_ms)
        collector.record("query.payload.responses", self.responses)
        collector.record("query.payload.pixel_responses",
                         self.pixel_responses)

    def health_info(self) -> dict[str, Any]:
        n = max(self.responses, 1)
        return {
            "responses": self.responses,
            "pixel_responses": self.pixel_responses,
            "payload_bytes_total": self.payload_bytes,
            "payload_bytes_avg": round(self.payload_bytes / n, 1),
            "serialization_ms_total": round(self.serialization_ms, 1),
            "serialization_ms_avg": round(
                self.serialization_ms / n, 3),
        }


class QueryStats:
    """Per-query trace (ref: QueryStats.java:58). Register on start,
    mark complete on finish; recent queries are browsable at
    ``/api/stats/query``."""

    _running: "dict[int, QueryStats]" = {}
    _completed: "deque[QueryStats]" = deque(maxlen=50)
    _registry_lock = threading.Lock()
    _next_id = 0

    def __init__(self, remote: str = "", query: Any = None,
                 allow_duplicates: bool = True):
        self.remote = remote
        self.query = query
        self.start_ns = time.monotonic_ns()
        self.start_time = time.time()
        # tsdlint: allow[unbounded-growth] per-query stats object,
        # garbage with its response; keys are the QueryStat enum
        self.stats: dict[str, float] = {}
        # sub-queries of one TSQuery may record concurrently (the
        # engine's parallel fan-out); the dict read-modify-write in
        # add_stat must not lose updates
        self._stats_lock = threading.Lock()
        self.executed = False
        # identity for the duplicate check: endpoint + query content
        # (ref: QueryStats.java:70-73 — "hash is the remote + query").
        # Computed only when duplicates are restricted — serializing
        # the whole TSQuery per request would tax the default hot path
        # for a comparison nothing performs.
        self.dup_key = None
        if not allow_duplicates:
            try:
                qjson = query.to_json() if query is not None else None
            except Exception:  # noqa: BLE001
                qjson = repr(query)
            self.dup_key = (remote, repr(qjson))
        with QueryStats._registry_lock:
            if not allow_duplicates and any(
                    r.dup_key == self.dup_key
                    for r in QueryStats._running.values()):
                # (ref: QueryStats ctor :263 throws QueryException when
                # ENABLE_DUPLICATES is off — surfaced as a 400)
                raise DuplicateQueryError(
                    "Query is already executing for endpoint: "
                    f"{remote}")
            QueryStats._next_id += 1
            self.query_id = QueryStats._next_id
            QueryStats._running[self.query_id] = self

    def add_stat(self, stat: QueryStat, value: float) -> None:
        with self._stats_lock:
            self.stats[stat.value] = \
                self.stats.get(stat.value, 0.0) + value

    def mark_serialization_successful(self) -> None:
        """The query produced a response (ref: the reference flips
        ``executed`` only on serialization success)."""
        self.executed = True
        self._complete()

    def mark_complete(self) -> None:
        """Move to the completed registry WITHOUT claiming success —
        the finally-path for failed queries (``executed`` stays
        False so /api/stats/query shows the failure)."""
        self._complete()

    def _complete(self) -> None:
        with QueryStats._registry_lock:
            if QueryStats._running.pop(self.query_id, None) is None:
                return  # already completed
            self.stats[QueryStat.TOTAL_TIME.value] = (
                (time.monotonic_ns() - self.start_ns) / 1e6)
            QueryStats._completed.append(self)

    def to_json(self) -> dict[str, Any]:
        stats = dict(self.stats)
        for base, (mx, avg) in _DERIVED_TIMES.items():
            if base in stats:
                stats.setdefault(mx, stats[base])
                stats.setdefault(avg, stats[base])
        return {
            "queryId": self.query_id,
            "remote": self.remote,
            "queryStartTimestamp": int(self.start_time * 1000),
            "executed": self.executed,
            "stats": stats,
            "query": (self.query.to_json()
                      if hasattr(self.query, "to_json") else None),
        }

    @classmethod
    def running_and_completed(cls) -> dict[str, list[dict[str, Any]]]:
        with cls._registry_lock:
            return {
                "running": [q.to_json() for q in cls._running.values()],
                "completed": [q.to_json() for q in cls._completed],
            }
