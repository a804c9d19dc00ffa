"""Flat ``tsd.*`` configuration (ref: ``src/utils/Config.java``).

Same shape as the reference: a flat string->string property map with typed
getters, defaults, auto-discovered config file paths, and runtime
overrides. Keys keep the reference's ``tsd.`` namespace so existing
opentsdb.conf files parse unchanged; TPU-specific keys live under
``tsd.tpu.*``.
"""

from __future__ import annotations

import logging
import os
from typing import Any, Iterator

log = logging.getLogger("config")

_DEFAULTS: dict[str, str] = {
    # network (ref: Config.java defaults + src/opentsdb.conf)
    "tsd.network.port": "4242",
    "tsd.network.bind": "0.0.0.0",
    "tsd.network.backlog": "3072",
    "tsd.network.tcp_no_delay": "true",
    "tsd.network.keep_alive": "true",
    "tsd.network.reuse_address": "true",
    # http
    # chunked Transfer-Encoding request bodies, the reference's
    # documented spelling (default off -> 400); the underscore
    # variant below is read as a legacy alias
    "tsd.http.request.enable_chunked": "false",
    "tsd.http.request.max_chunk": "1048576",
    "tsd.http.request.cors_domains": "",
    "tsd.http.request.cors_headers": (
        "Authorization, Content-Type, Accept, Origin, User-Agent, "
        "DNT, Cache-Control, X-Mx-ReqToken, Keep-Alive, X-Requested-With, "
        "If-Modified-Since"),
    "tsd.http.cachedir": "/tmp/opentsdb_tpu",
    "tsd.http.staticroot": "",
    "tsd.http.show_stack_trace": "false",
    # /q PNG renders auto-apply an M4 pixel budget equal to the chart
    # width (visually lossless; opt out per-request with downsample=0px)
    "tsd.http.graph.auto_pixels": "true",
    # core
    "tsd.core.auto_create_metrics": "false",
    "tsd.core.auto_create_tagks": "true",
    "tsd.core.auto_create_tagvs": "true",
    "tsd.core.meta.enable_realtime_ts": "false",
    "tsd.core.meta.enable_realtime_uid": "false",
    "tsd.core.meta.enable_tsuid_incrementing": "false",
    "tsd.core.meta.enable_tsuid_tracking": "false",
    "tsd.core.tree.enable_processing": "false",
    "tsd.core.preload_uid_cache": "false",
    "tsd.core.timezone": "",
    "tsd.mode": "rw",  # rw | ro | wo (ref: TSDB.java:103)
    # uid
    "tsd.core.uid.random_metrics": "false",
    "tsd.storage.uid.width.metric": "3",
    "tsd.storage.uid.width.tagk": "3",
    "tsd.storage.uid.width.tagv": "3",
    # storage
    "tsd.storage.enable_compaction": "true",
    "tsd.storage.enable_appends": "false",
    "tsd.storage.fix_duplicates": "false",
    "tsd.storage.salt.width": "0",
    "tsd.storage.salt.buckets": "20",
    "tsd.storage.flush_interval": "1000",
    "tsd.storage.backend": "native",  # native (C++ arena store) | memory
    "tsd.storage.data_dir": "",       # non-empty => durable snapshots
    # query
    # persistent XLA compilation cache dir: "" = the fixed .jax_cache
    # directory of the checkout, "off" = disabled; the environment's
    # JAX_COMPILATION_CACHE_DIR overrides any directory named here
    # (utils/compile_cache.py). Makes compiles once-per-code-version
    # instead of once-per-process.
    "tsd.query.compile_cache_dir": "",
    # host-tail placement budgets (engine.host_tail_device): 0 =
    # built-in default, -1 = never host. The _linear key covers
    # segment-reducible aggregators (sum/min/max/...); cells/cellgroups
    # cover the rank class (median/percentiles).
    "tsd.query.host_tail_max_cells": "0",
    "tsd.query.host_tail_max_cellgroups": "0",
    "tsd.query.host_tail_max_cells_linear": "0",
    # host-RAM prepared-batch cache for host-tail queries (separate
    # pool from device_cache_mb so host entries never evict HBM grids)
    "tsd.query.host_cache_mb": "512",
    # legacy alias of tsd.http.request.enable_chunked (kept: existing
    # conf files and tests set it; either spelling enables)
    "tsd.http.request_enable_chunked": "false",
    "tsd.query.timeout": "0",
    "tsd.query.allow_simultaneous_duplicates": "true",
    # serve-path query RESULT cache (query/result_cache.py): sharded
    # LRU of engine result groups keyed on the normalized query +
    # the mutation epoch of every store read, so writes invalidate
    # implicitly; concurrent identical queries single-flight onto one
    # execution. enable is consulted per query (runtime-togglable);
    # mb = 0 disables permanently.
    "tsd.query.cache.enable": "true",
    "tsd.query.cache.mb": "256",
    "tsd.query.cache.shards": "8",
    #   relative-time (end=now) queries may be served up to one
    #   downsample interval stale, clamped to ttl_max_s (the
    #   reference's GraphHandler staleness rule); relative queries
    #   WITHOUT a downsample are cached for ttl_relative_s (0 = not
    #   cached at all, the conservative default)
    "tsd.query.cache.ttl_max_s": "300",
    "tsd.query.cache.ttl_relative_s": "0",
    # parallel sub-query fan-out: independent sub-queries of one
    # TSQuery dispatch onto a dedicated worker pool and join (0 =
    # serial). Deliberately NOT the server's query pool — parents run
    # there and would deadlock waiting on unschedulable children.
    "tsd.query.fanout.workers": "4",
    "tsd.query.limits.bytes.default": "0",
    "tsd.query.limits.data_points.default": "0",
    "tsd.query.skip_unresolved_tagvs": "false",
    # rollups (ref: TSDB.java:170-185)
    "tsd.rollups.enable": "false",
    "tsd.rollups.config": "",
    "tsd.rollups.tag_raw": "false",
    "tsd.rollups.agg_tag_key": "_aggregate",
    "tsd.rollups.raw_agg_tag_value": "RAW",
    "tsd.rollups.block_derived": "true",
    # robustness / graceful degradation. NOTE: tsd.faults.* injection
    # keys (tsd.faults.<site>_<error_rate|error_count|error_once|
    # latency_ms>) deliberately have NO defaults here — any present
    # key arms its fault point (utils/faults.py).
    #   WAL fsync/append retry ladder; exhaustion degrades durability
    #   (loudly: /api/health wal.degraded) instead of failing writes
    "tsd.storage.wal.retry.attempts": "4",
    "tsd.storage.wal.retry.base_ms": "5",
    "tsd.storage.wal.retry.deadline_ms": "2000",
    "tsd.storage.wal.resync_interval_ms": "1000",
    #   group commit v2: bounded commit window the fsync leader holds
    #   to absorb concurrent writers' buffered bytes (0 = commit
    #   immediately; the window never delays a lone writer — it ends
    #   at the first quiet poll slice), cut short by the caps below.
    #   "" = auto: 0 standalone, 2 ms when tsd.cluster.role=shard —
    #   a routed shard sees genuinely concurrent writers (one router
    #   connection per client), so the window amortizes fsyncs while
    #   the quiet-log early exit keeps a lone writer at ~one poll
    #   slice of added latency
    "tsd.storage.wal.group_window_ms": "",
    "tsd.storage.wal.group_max_records": "4096",
    "tsd.storage.wal.group_max_bytes": "4194304",
    #   snapshot flush retry (tsd.storage.data_dir writes)
    "tsd.storage.flush.retry.attempts": "3",
    "tsd.storage.flush.retry.base_ms": "20",
    "tsd.storage.flush.retry.deadline_ms": "10000",
    #   device-pipeline circuit breaker: consecutive failures before
    #   tripping to the host CPU fallback (0 disables the breaker)
    "tsd.query.breaker.failure_threshold": "5",
    "tsd.query.breaker.reset_timeout_ms": "30000",
    #   re-answer failed device tails on the host CPU backend; off =
    #   surface the failure (breaker-open queries then shed with 503)
    "tsd.query.degraded.host_fallback": "true",
    #   query admission control (0 = unlimited): shed with 503 +
    #   Retry-After past these in-flight / queue-depth thresholds
    "tsd.query.admission.max_inflight": "0",
    "tsd.query.admission.max_queue": "0",
    "tsd.query.admission.retry_after_s": "1",
    # data lifecycle (opentsdb_tpu/lifecycle/): retention, age-based
    # rollup demotion, store compaction. Per-metric overrides:
    # tsd.lifecycle.policy.<metric>.<retention|demote_after|
    # demote_tiers>. Durations are reference duration strings (30d,
    # 6h, ...); "" disables the mechanism.
    "tsd.lifecycle.enable": "false",
    "tsd.lifecycle.interval_s": "0",     # 0 = manual sweeps only
    "tsd.lifecycle.retention": "",       # default policy: keep forever
    "tsd.lifecycle.demote_after": "",    # default policy: never demote
    "tsd.lifecycle.demote_tiers": "",    # "" = every configured tier
    "tsd.lifecycle.compact": "true",
    "tsd.lifecycle.pack_timestamps": "true",
    #   snapshot + WAL-truncate after a sweep that purged/demoted:
    #   the WAL has no delete records, so without this a restart's
    #   replay would resurrect expired points
    "tsd.lifecycle.flush_after_sweep": "true",
    "tsd.lifecycle.breaker.failure_threshold": "3",
    "tsd.lifecycle.breaker.reset_timeout_ms": "60000",
    # SSE resume replay depth (Last-Event-ID; 0 disables resume)
    "tsd.streaming.resume_events": "64",
    # shared fold-worker pool (streaming/workers.py): folds run off
    # the ingest path on this many threads; 0 = inline drains (v1)
    "tsd.streaming.workers.count": "2",
    #   backlog cap per shared partial: past it the lagging partial
    #   is DEGRADED to rebuild-on-serve (backlog dropped, counted)
    #   instead of buffering unboundedly or blocking the write path
    "tsd.streaming.workers.max_pending_points": "262144",
    # sharded cluster tier (opentsdb_tpu/cluster/): role "" =
    # standalone, "router" = stateless consistent-hash scatter-gather
    # tier over tsd.cluster.peers ("[name=]host:port,..."), "shard" =
    # a peer TSD behind a router (flips the WAL group-commit window
    # default; see tsd.storage.wal.group_window_ms)
    "tsd.cluster.role": "",
    "tsd.cluster.peers": "",
    "tsd.cluster.vnodes": "64",
    #   replication factor: each series lives on the next rf distinct
    #   ring shards (Monarch replicates each target on 2-3 leaves).
    #   Writes fan out to every replica; reads go to ONE replica per
    #   set and fall back to the next on failure, so a single shard
    #   death yields a COMPLETE marker-less 200. Clamped to the shard
    #   count.
    "tsd.cluster.rf": "1",
    #   anti-entropy: when a replica returns, re-copy its dirty
    #   (peer, metric) windows from a surviving replica — covers the
    #   divergence the spool cannot (lost/refused spool records)
    "tsd.cluster.replica.repair": "true",
    #   online resharding: backfill pacing + per-forward batch size
    #   (POST /api/cluster/reshard installs the new ring; the window
    #   dual-writes old+new owners while moved history streams over)
    "tsd.cluster.reshard.interval_ms": "250",
    "tsd.cluster.reshard.backfill_batch": "4000",
    #   stale-copy retire pass: after a finalized reshard, delete the
    #   moved series backfill left on former owners (reads already
    #   hide them via replicaSel — this reclaims the bytes); one
    #   (shard, metric) delete unit per interval wake
    "tsd.cluster.retire.enable": "true",
    "tsd.cluster.retire.interval_ms": "1000",
    #   per-peer connect+read deadline; a hung shard becomes a
    #   degraded partial after this, never a stuck request
    "tsd.cluster.timeout_ms": "5000",
    #   tail-latency hedging: duplicate a peer request that hasn't
    #   answered after this many ms, first completion wins (0 = off)
    "tsd.cluster.hedge_after_ms": "0",
    #   binary columnar cluster wire (cluster/wire.py): persistent
    #   framed router↔shard links with pipelined columnar writes and
    #   streamed partial-grid reads; false = JSON HTTP only (also
    #   honored shard-side: a disabled shard refuses the handshake
    #   and the router falls back transparently)
    "tsd.cluster.wire.enable": "true",
    #   write pipelining bound per peer: past this many unacked
    #   deliveries in flight the router sheds into the durable spool
    #   (backpressure, not failure — the breaker is untouched)
    "tsd.cluster.wire.max_inflight": "32",
    #   how long a failed negotiation pins a peer to JSON HTTP before
    #   the wire is re-tried (version-skew fallback window)
    "tsd.cluster.wire.fallback_ttl_ms": "30000",
    #   wire connect + handshake deadline; past it the peer is
    #   treated as not speaking wire (HTTP fallback), while a refused
    #   TCP connect stays a normal peer failure (breaker/spool)
    "tsd.cluster.wire.connect_timeout_ms": "1000",
    #   cap on concurrent single-sub re-asks against ONE peer when a
    #   multi-sub 400 cannot be attributed to a metric (the per-sub
    #   sweep); bounds scatter amplification on partially-known shards
    "tsd.cluster.sub_retry.max_concurrent": "4",
    #   per-(peer, metric) known/unknown memo for the scatter path:
    #   a shard that 400'd "no such name" for a metric is not re-asked
    #   about it until a write for that metric is forwarded/replayed
    #   to it (0 = cache forever until invalidated; >0 adds a TTL for
    #   deployments where writes can bypass this router)
    "tsd.cluster.sub_memo.ttl_ms": "0",
    #   hard cap on memoized unknown (peer, metric) entries — the
    #   replay loop sweeps expired/over-cap entries (oldest first) so
    #   a probing workload of ever-new metric names stays bounded
    "tsd.cluster.sub_memo.max_entries": "4096",
    #   per-metric result-cache version map cap: past it the map
    #   folds into one global invalidation and restarts empty
    "tsd.cluster.metric_versions.max_entries": "100000",
    #   write-forward retry ladder (reads never retry — they degrade)
    "tsd.cluster.retry.attempts": "2",
    "tsd.cluster.retry.base_ms": "25",
    "tsd.cluster.retry.deadline_ms": "2000",
    #   per-peer circuit breaker (utils/faults.py CircuitBreaker)
    "tsd.cluster.breaker.failure_threshold": "3",
    "tsd.cluster.breaker.reset_timeout_ms": "5000",
    #   durable per-peer write spool: dir "" = <data_dir>/cluster_spool
    #   (in-memory fallback without a data_dir); a FULL spool refuses
    #   writes loudly instead of dropping acknowledged points
    "tsd.cluster.spool.dir": "",
    "tsd.cluster.spool.max_mb": "256",
    # replayed-prefix bytes beyond which a partially drained spool
    # file is compacted (the drained-at-zero truncate alone would let
    # an oscillating spool grow without bound)
    "tsd.cluster.spool.compact_mb": "4",
    "tsd.cluster.spool.replay_interval_ms": "500",
    "tsd.cluster.spool.replay_batch": "64",
    #   scatter/forward worker pool (0 = 2x peer count)
    "tsd.cluster.fanout_workers": "0",
    #   TTL on the router /api/health `fleet` section (a per-shard
    #   health scatter): health is a probe surface polled every
    #   second or two — the cache keeps it O(local) between
    #   refreshes (0 = scatter every call)
    "tsd.cluster.fleet_health_ttl_ms": "5000",
    #   multi-router front door: sibling routers ("[name=]host:port,
    #   ..." — the OTHER routers behind the LB, not this one) exchange
    #   write-version + reshard-epoch deltas so every router's
    #   epoch-qualified result cache invalidates on writes any
    #   sibling forwarded. "" = single-router deployment, no bus.
    "tsd.cluster.routers": "",
    #   gossip push cadence; heartbeats flow every interval even with
    #   no writes, so an idle fleet never looks partitioned
    "tsd.cluster.gossip.interval_ms": "250",
    #   a sibling that hasn't acked a push within this window is
    #   PARTITIONED: this router serves cache-bypassed (exact, never
    #   stale, never a 5xx) until a push lands again
    "tsd.cluster.gossip.stale_ms": "5000",
    #   bounded delta log: a sibling lagging past the trim re-syncs
    #   via one conservative global bump (anti-entropy full-sync)
    "tsd.cluster.gossip.log_max": "4096",
    #   per-sibling push deadline (gossip bodies are tiny; a hung
    #   sibling must age toward stale_ms, not wedge the push loop)
    "tsd.cluster.gossip.timeout_ms": "2000",
    #   query-path read-repair: a read that observes replica
    #   divergence (failed reader covered by a fallback round;
    #   replicas disagreeing whether a metric exists) stages the
    #   window into a bounded queue the replay loop drains into the
    #   DirtyTracker — past max_pending, hints shed-and-count (a shed
    #   hint re-stages on the next read that observes the divergence)
    "tsd.cluster.read_repair.enable": "true",
    "tsd.cluster.read_repair.max_pending": "1024",
    # auth
    "tsd.core.authentication.enable": "false",
    # stats
    "tsd.stats.canonical": "false",
    # self-telemetry (obs/telemetry.py): every interval the TSD
    # ingests its own counters/gauges/stage-latency percentiles as
    # tsd.* series through the normal write path (0 = off)
    "tsd.stats.self_interval": "0",
    #   node identity tag on every self-telemetry record (host=...);
    #   "" = auto: hostname-port, so a fleet's per-shard tsd.* series
    #   stay distinguishable through a router-side merge
    "tsd.stats.self_tag": "",
    # request tracing (obs/trace.py): ring-buffered sampled span
    # records over ingest/query/background hot paths. sample = keep
    # 1 in N request roots (slow/error traces are always kept); ring/
    # slow_ring bound retained roots; max_spans bounds one trace.
    "tsd.trace.enable": "true",
    "tsd.trace.sample": "64",
    "tsd.trace.ring": "256",
    "tsd.trace.slow_ring": "64",
    "tsd.trace.max_spans": "512",
    #   query-shape log: one JSONL line per retained query trace
    #   (metric/filters/downsample/pixels/cache outcome/stage
    #   breakdown) in <data_dir>/query_shapes.jsonl, rotated past
    #   max_kb — the offline mining input for workload-adaptive
    #   summaries (ROADMAP item 5)
    "tsd.trace.shapes.enable": "true",
    "tsd.trace.shapes.max_kb": "1024",
    # slow-request log: a query root slower than this is retained at
    # full fidelity regardless of sampling + WARNed into /logs with
    # its trace id (0 = off)
    "tsd.query.slowlog.threshold_ms": "0",
    # continuous sampling profiler (obs/profiler.py): a bounded
    # background thread folds sys._current_frames() into per-role
    # stack counts at `hz`, keeping the last `ring_s` seconds —
    # GET /api/profile serves the window flamegraph-ready. The
    # default rate is deliberately low enough to leave on (the obs2
    # bench holds it to <= 5% overhead).
    "tsd.profile.enable": "true",
    "tsd.profile.hz": "4",
    "tsd.profile.ring_s": "60",
    "tsd.profile.max_depth": "48",
    # SLO burn-rate gauges (obs/slo.py): per-endpoint latency +
    # availability objectives; burn = bad-fraction / error budget,
    # derived over each window and exported at /metrics +
    # /api/health. 1.0 = consuming the budget exactly.
    "tsd.slo.enable": "true",
    "tsd.slo.windows": "300,3600",
    "tsd.slo.query.latency_ms": "1000",
    "tsd.slo.query.latency_objective": "0.99",
    "tsd.slo.query.availability_objective": "0.999",
    "tsd.slo.put.latency_ms": "500",
    "tsd.slo.put.latency_objective": "0.99",
    "tsd.slo.put.availability_objective": "0.999",
    # TPU-native keys (no reference equivalent)
    "tsd.tpu.platform": "",  # force jax platform (cpu|tpu); "" = auto
}

_SEARCH_PATHS = (
    "./opentsdb.conf",
    "/etc/opentsdb.conf",
    "/etc/opentsdb/opentsdb.conf",
    "/opt/opentsdb/opentsdb.conf",
)

# ---------------------------------------------------------------------------
# declared-key registry
# ---------------------------------------------------------------------------
# Every ``tsd.*`` key the codebase reads must be DECLARED: either in
# ``_DEFAULTS`` above, or here (keys whose default lives at the call
# site), or under a dynamic prefix. The registry is machine-checked
# two ways: tsdlint's ``config-keys`` pass verifies every
# ``config.get_*("tsd...")`` literal in the tree resolves here, and
# ``Config.warn_unknown_keys`` (called at TSDB startup) warns about
# configured keys nothing will ever read — a typo'd knob used to be
# silently ignored.

# keys read with a call-site default only (no entry in _DEFAULTS)
_DECLARED_EXTRA: frozenset[str] = frozenset({
    # cold tier (opentsdb_tpu/coldstore/)
    "tsd.coldstore.breaker.failure_threshold",
    "tsd.coldstore.breaker.reset_timeout_ms",
    "tsd.coldstore.compact_segments",
    "tsd.coldstore.dir",
    "tsd.coldstore.enable",
    # control plane (opentsdb_tpu/control/)
    "tsd.control.enable",
    "tsd.control.interval_s",
    "tsd.control.breaker.failure_threshold",
    "tsd.control.breaker.reset_timeout_ms",
    "tsd.control.materialize.enable",
    "tsd.control.materialize.max",
    "tsd.control.materialize.min_score",
    "tsd.control.materialize.hysteresis",
    "tsd.control.materialize.mem_penalty_mb",
    "tsd.control.tenant.tag",
    "tsd.control.tenant.header",
    "tsd.control.qos.enable",
    "tsd.control.qos.weights",
    "tsd.control.qos.max_tenants",
    "tsd.control.qos.burn_penalty",
    "tsd.control.qos.tenant_cache_mb",
    "tsd.control.qos.tenant_fold_mb",
    "tsd.control.placement.enable",
    "tsd.control.placement.auto",
    "tsd.control.placement.hot_ratio",
    # auth / plugins / server
    "tsd.core.authentication.roles",
    "tsd.core.authentication.users",
    "tsd.core.histograms.config",
    "tsd.core.plugins.enable",
    "tsd.core.connections.limit",
    "tsd.core.socket.timeout",
    "tsd.http.query.allow_delete",
    "tsd.http.query.stream_threshold_dps",
    "tsd.http.serializer.plugin",
    # lifecycle spill knob (read alongside the tsd.lifecycle.* defaults)
    "tsd.lifecycle.spill_after",
    # multi-host mesh rendezvous
    "tsd.mesh.coordinator",
    "tsd.mesh.init_timeout",
    "tsd.mesh.num_processes",
    "tsd.mesh.process_id",
    # query engine placement / budgets
    "tsd.query.device_cache_mb",
    "tsd.query.grid_reduce",
    "tsd.query.limits.overrides.config",
    "tsd.query.limits.overrides.interval",
    "tsd.query.max_device_cells",
    "tsd.query.mesh",
    "tsd.query.workers",
    "tsd.rollups.job.device",
    # quantile-sketch subsystem (opentsdb_tpu/sketch/)
    "tsd.sketch.enable",
    "tsd.sketch.alpha",
    "tsd.sketch.max_buckets",
    # WAL enable/tuning (mode default lives in core/persist.py)
    "tsd.storage.wal.enable",
    "tsd.storage.wal.fsync",
    "tsd.storage.wal.fsync_interval_ms",
    "tsd.storage.wal.segment_mb",
    # streaming / continuous queries
    "tsd.streaming.breaker.failure_threshold",
    "tsd.streaming.breaker.reset_timeout_ms",
    "tsd.streaming.buffer_points",
    "tsd.streaming.enable",
    "tsd.streaming.heartbeat_s",
    "tsd.streaming.max_queries",
    "tsd.streaming.max_windows",
    "tsd.streaming.publish_min_interval_ms",
    "tsd.streaming.queue_events",
    "tsd.streaming.serve",
    "tsd.streaming.sse.max_lifetime_s",
    # warmup
    "tsd.tpu.warmup",
    "tsd.tpu.warmup.buckets",
    "tsd.tpu.warmup.budget_s",
    "tsd.tpu.warmup.percentiles",
    # plugin slots (read as f"{prefix}.enable"/f"{prefix}.plugin" by
    # utils/plugin.py for the prefixes TSDB.initialize_plugins and
    # the HTTP router pass in)
    "tsd.rtpublisher.enable", "tsd.rtpublisher.plugin",
    "tsd.search.enable", "tsd.search.plugin",
    "tsd.core.storage_exception_handler.enable",
    "tsd.core.storage_exception_handler.plugin",
    "tsd.core.write_filter.enable", "tsd.core.write_filter.plugin",
    "tsd.uid.filter.enable", "tsd.uid.filter.plugin",
    "tsd.core.meta.cache.enable", "tsd.core.meta.cache.plugin",
    "tsd.http.rpc.enable", "tsd.http.rpc.plugin",
    # UID auto-assignment allow-patterns (plugins.py DefaultUidFilter)
    "tsd.uidfilter.metric_patterns",
    "tsd.uidfilter.tagk_patterns",
    "tsd.uidfilter.tagv_patterns",
})

# key families with config-driven tails: any key under these prefixes
# is declared by construction
DYNAMIC_KEY_PREFIXES: tuple[str, ...] = (
    # fault arming: tsd.faults.<site>_<knob> (utils/faults.py — the
    # SITE half is validated against faults.KNOWN_SITES separately)
    "tsd.faults.",
    # per-metric lifecycle overrides:
    # tsd.lifecycle.policy.<metric>.<retention|demote_after|...>
    "tsd.lifecycle.policy.",
)


# runtime-registered families: dynamically loaded plugins own their
# config namespaces (tsd.search.es.host, ...) which no static scan
# can enumerate — the loader registers each enabled slot's prefix
# tsdlint: allow[unbounded-growth] one prefix per ENABLED plugin
# slot, registered at load time — bounded by the plugin config
_RUNTIME_KEY_PREFIXES: set[str] = set()


def register_dynamic_key_prefix(prefix: str) -> None:
    """Declare a runtime key family (e.g. a plugin's own knobs under
    its slot prefix) so startup hygiene doesn't flag keys the plugin
    reads at runtime."""
    _RUNTIME_KEY_PREFIXES.add(prefix)


def declared_keys() -> frozenset[str]:
    """Every statically-declared ``tsd.*`` key (defaults + call-site
    defaulted keys). Dynamic families are in
    :data:`DYNAMIC_KEY_PREFIXES` and the runtime-registered set."""
    return frozenset(_DEFAULTS) | _DECLARED_EXTRA


def is_declared_key(key: str) -> bool:
    if key in _DEFAULTS or key in _DECLARED_EXTRA:
        return True
    return any(key.startswith(p) for p in DYNAMIC_KEY_PREFIXES) or \
        any(key.startswith(p) for p in _RUNTIME_KEY_PREFIXES)


class Config:
    """(ref: src/utils/Config.java:52)"""

    def __init__(self, config_file: str | None = None,
                 auto_load: bool = False, **overrides: Any):
        self._props: dict[str, str] = dict(_DEFAULTS)
        self.config_location: str | None = None
        if config_file:
            self.load_file(config_file)
        elif auto_load:
            for path in _SEARCH_PATHS:
                if os.path.isfile(path):
                    self.load_file(path)
                    break
        for key, val in overrides.items():
            self._props[key.replace("__", ".")] = str(val)

    def load_file(self, path: str) -> None:
        """Parse a java-properties-style file (``key = value`` lines)."""
        with open(path, "r", encoding="utf-8") as fh:
            for line in fh:
                line = line.strip()
                if not line or line.startswith(("#", "!")):
                    continue
                for sep in ("=", ":"):
                    idx = line.find(sep)
                    if idx > 0:
                        self._props[line[:idx].strip()] = line[idx + 1:].strip()
                        break
        self.config_location = path

    # typed getters (ref: Config.java:328-429)

    def get_string(self, key: str, default: str | None = None) -> str:
        if key in self._props:
            return self._props[key]
        if default is not None:
            return default
        raise KeyError(key)

    def get_int(self, key: str, default: int | None = None) -> int:
        try:
            return int(self._props[key])
        except KeyError:
            if default is not None:
                return default
            raise

    def get_float(self, key: str, default: float | None = None) -> float:
        try:
            return float(self._props[key])
        except KeyError:
            if default is not None:
                return default
            raise

    def get_bool(self, key: str, default: bool = False) -> bool:
        val = self._props.get(key)
        if val is None:
            return default
        return val.strip().lower() in ("true", "1", "yes")

    def has_property(self, key: str) -> bool:
        return key in self._props

    def _enabled_plugin_prefixes(self) -> list[str]:
        """Key families owned by plugins THIS config enables: a
        loaded plugin reads its own knobs at runtime (no static scan
        can enumerate them), so ``tsd.search.*`` is fair game once
        ``tsd.search.enable`` is on."""
        out = []
        for key in declared_keys():
            if key.endswith(".plugin"):
                slot = key[: -len(".plugin")]
                if self.get_bool(f"{slot}.enable", False):
                    out.append(slot + ".")
        return out

    def unknown_keys(self) -> list[str]:
        """Configured ``tsd.*`` keys nothing in the codebase reads —
        almost always a typo'd knob (the declared-key registry above
        is enforced by tsdlint, so an undeclared key really is
        unread). Keys under an ENABLED plugin slot's prefix are
        exempt — the plugin owns that namespace."""
        plugin_prefixes = self._enabled_plugin_prefixes()
        return sorted(
            k for k in self._props
            if k.startswith("tsd.") and not is_declared_key(k)
            and not any(k.startswith(p) for p in plugin_prefixes))

    def warn_unknown_keys(self, logger: logging.Logger | None = None
                          ) -> list[str]:
        """Startup hygiene: log one warning per unknown/misspelled
        ``tsd.*`` key instead of silently ignoring it. Returns the
        offending keys (tests assert on it)."""
        logger = logger or log
        unknown = self.unknown_keys()
        for key in unknown:
            logger.warning(
                "unknown config key %r is not read by anything and "
                "will be IGNORED — check for a typo (see "
                "utils/config.py declared-key registry)", key)
        return unknown

    def override_config(self, key: str, value: Any) -> None:
        """(ref: Config.java:317)"""
        self._props[key] = str(value)

    def dump_configuration(self) -> dict[str, str]:
        """All properties for ``/api/config`` (secrets redacted like the
        reference redacts passwords)."""
        out = {}
        for k, v in sorted(self._props.items()):
            out[k] = "********" if "pass" in k.lower() else v
        return out

    def __iter__(self) -> Iterator[tuple[str, str]]:
        return iter(self._props.items())
