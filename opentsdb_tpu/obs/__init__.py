"""Observability subsystem: end-to-end request tracing and
self-telemetry.

- :mod:`opentsdb_tpu.obs.trace` — low-overhead ring-buffered, sampled
  span records wrapping every stage of the three hot paths (ingest,
  query, background maintenance), with cluster trace-id propagation
  (router scatter/forward headers stitch one trace across shards), a
  slow-request log, and a persisted query-shape log for offline
  workload mining.
- :mod:`opentsdb_tpu.obs.telemetry` — the ``tsd.stats.self_interval``
  loop that ingests the TSD's own counters, gauges and stage-latency
  percentiles into its *own* store as ``tsd.*`` series, so dashboards,
  continuous queries, lifecycle policies and the cluster tier all
  apply to the TSD monitoring itself.
- :mod:`opentsdb_tpu.obs.openmetrics` — the ``GET /metrics``
  exposition renderer: the full stats registry in OpenMetrics text,
  histograms in native cumulative ``_bucket``/``_sum``/``_count``
  form, for the Prometheus ecosystem.
- :mod:`opentsdb_tpu.obs.profiler` — the continuous sampling
  profiler: per-thread-role folded stacks over a bounded ring,
  served flamegraph-ready at ``GET /api/profile``.
- :mod:`opentsdb_tpu.obs.slo` — per-endpoint SLO objectives and
  multi-window burn-rate gauges (``tsd.slo.*``).

Surfaces: ``GET /api/trace`` (recent roots), ``GET /api/trace/<id>``
(full span tree, cluster-stitched on a router), per-stage latency
percentiles at ``/api/stats`` + ``/api/health``, ``GET /metrics``,
``GET /api/profile``.

A served query is four intervals that touch, from its first byte
read to its last byte written: ``query.receive`` and
``query.admission`` (from the socket server's stamps), the root
``query.http`` (the worker, up to the handler's return) and
``query.respond`` (from the tracer's finish to the response's last
drain: the loop's wake-up, the latency feeds, gzip, the write; a
retained tree gains that span after the response has left). At
``/api/stats``: ``tsd.runtime.thread_cpu_ms{thread}`` (CPU time the
kernel has charged to the live Python threads, by pool:
``tsd-query``, ``tsd-subq``, ``asyncio`` for the puts, ``MainThread``
for the loop; read from ``/proc/self/task`` when stats are collected,
nothing on a request's path: beside a pool's stage sums, how much of
its requests its threads ran), ``tsd.runtime.minor_faults`` /
``major_faults`` (the process's page faults),
``tsd.trace.finish_ms`` / ``tsd.trace.observations`` (what
the tracer's own bookkeeping cost).
"""

from opentsdb_tpu.obs.trace import (KNOWN_SPANS, Tracer, current,
                                    record_span, trace_begin,
                                    trace_end, trace_span, use)

__all__ = ["KNOWN_SPANS", "Tracer", "current", "record_span",
           "trace_begin", "trace_end", "trace_span", "use"]
