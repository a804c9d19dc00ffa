"""The TSDB facade (ref: ``src/core/TSDB.java:87``).

Central object owning the UID registry, the storage backend, plugin
slots, and rollup configuration. Mirrors the reference surface:
``add_point`` (TSDB.java:1012-1097), ``add_aggregate_point`` (:1320),
``new_query`` (:963), ``suggest_*`` (:1762-1816), ``assign_uid``
(:1838), ``flush`` (:1603), ``shutdown`` (:1632), plus operating modes
rw/ro/wo (:103).
"""

from __future__ import annotations

import contextlib
import logging
import os
import threading
import time
from typing import Any, Callable, Sequence

import numpy as np

from opentsdb_tpu.core import codec, const, tags as tags_mod
from opentsdb_tpu.core.store import PointBatch, TimeSeriesStore
from opentsdb_tpu.core.uid import UidRegistry
from opentsdb_tpu.utils.config import Config


class PartialWriteError(Exception):
    """A bulk write landed ``written`` points before one failed.

    Raised by the per-point hook fallback in :meth:`TSDB.add_points` so
    batch callers replay only the remainder — re-running already-landed
    points would double realtime-publisher events and meta counters
    (the store itself dedupes the cells, but the hooks are not
    idempotent)."""

    def __init__(self, written: int, cause: Exception):
        super().__init__(str(cause))
        self.written = written
        self.cause = cause


class TSDB:
    """(ref: src/core/TSDB.java:87)"""

    def __init__(self, config: Config | None = None):
        self.config = config or Config()
        # startup hygiene: a typo'd tsd.* knob used to be silently
        # ignored — warn about every configured key nothing reads
        # (the declared-key registry in utils/config.py is enforced
        # by tsdlint, so "undeclared" really means "unread")
        self.config.warn_unknown_keys()
        # Force the JAX platform when configured (tsd.tpu.platform =
        # cpu|tpu|""): the in-process twin of the JAX_PLATFORMS
        # environment variable, for callers that build a TSDB after
        # jax was imported.
        platform = self.config.get_string("tsd.tpu.platform", "")
        if platform:
            import jax
            import jax.extend.backend
            jax.config.update("jax_platforms", platform)
            # config.update alone is ignored once backends are
            # initialized — drop them so the override actually takes
            # (clear_backends initializes nothing itself; asking
            # backends() whether any exist would)
            jax.extend.backend.clear_backends()
        self._check_host_backend()
        # multi-host (DCN) rendezvous must precede any backend touch
        # (ref-analogue: multi-TSD scale-out, RpcManager.java:274-327)
        if self.config.get_string("tsd.mesh.coordinator", ""):
            from opentsdb_tpu.parallel.distributed import \
                initialize_from_config
            initialize_from_config(self.config)
        const.set_salt_width(self.config.get_int("tsd.storage.salt.width", 0))
        const.set_salt_buckets(
            self.config.get_int("tsd.storage.salt.buckets", 20))
        self.uids = UidRegistry(
            metric_width=self.config.get_int("tsd.storage.uid.width.metric", 3),
            tagk_width=self.config.get_int("tsd.storage.uid.width.tagk", 3),
            tagv_width=self.config.get_int("tsd.storage.uid.width.tagv", 3),
            random_metrics=self.config.get_bool(
                "tsd.core.uid.random_metrics"))
        # deterministic fault-injection layer (armed via tsd.faults.*
        # keys; a no-op dict miss per injection point when disarmed)
        from opentsdb_tpu.utils.faults import (CircuitBreaker,
                                               FaultInjector)
        self.faults = FaultInjector(self.config)
        from opentsdb_tpu.native.store_backend import make_store
        self.store = make_store(self.config,
                                num_shards=const.salt_buckets())
        self.store.fault_injector = self.faults
        self.mode = self.config.get_string("tsd.mode", "rw")
        self.auto_metric = self.config.get_bool("tsd.core.auto_create_metrics")
        self.auto_tagk = self.config.get_bool("tsd.core.auto_create_tagks",
                                              True)
        self.auto_tagv = self.config.get_bool("tsd.core.auto_create_tagvs",
                                              True)
        # plugin slots (ref: TSDB.java:146-167); populated by
        # initialize_plugins()
        self.rt_publisher = None
        self.search_plugin = None
        self.storage_exception_handler = None
        self.write_filters: list[Callable[..., bool]] = []
        self.uid_filter = None
        self.meta_cache = None
        self.authentication = None
        # rollups (ref: TSDB.java:170-185)
        self.rollup_config = None
        self.agg_tag_key = self.config.get_string("tsd.rollups.agg_tag_key",
                                                  "_aggregate")
        if self.config.get_bool("tsd.rollups.enable"):
            from opentsdb_tpu.rollup.config import RollupConfig
            path = self.config.get_string("tsd.rollups.config", "")
            self.rollup_config = (RollupConfig.from_file(path) if path
                                  else RollupConfig.default())
            from opentsdb_tpu.rollup.store import RollupStore
            self.rollup_store = RollupStore(
                self.rollup_config,
                store_factory=lambda: make_store(self.config),
                fault_injector=self.faults)
        else:
            self.rollup_store = None
        from opentsdb_tpu.core.histogram import HistogramCodecManager
        self.histogram_manager = HistogramCodecManager(self.config)
        self.histogram_store = TimeSeriesStore(num_shards=const.salt_buckets())
        # columnar per-metric histogram arenas (HistogramArena): flat
        # (ts, sid, counts-row) arrays grouped by bounds — queries
        # slice with vectorized masks instead of walking objects
        self._histogram_arenas: dict[int, Any] = {}
        # guards _histogram_arenas shape for snapshot-vs-write races
        self._histogram_lock = threading.Lock()
        # write version for read-side caches of histogram batches
        self._histogram_version = 0
        from opentsdb_tpu.core.histogram import HistogramStats
        self.histogram_stats = HistogramStats()
        from opentsdb_tpu.meta.annotation import AnnotationStore
        self.annotations = AnnotationStore()
        from opentsdb_tpu.meta.meta_store import MetaStore
        self.meta = MetaStore(self)
        from opentsdb_tpu.query.limits import QueryLimitOverride
        self.query_limits = QueryLimitOverride(self.config)
        # multi-chip query execution (SURVEY §2.11: the reference's
        # 20-way salt-bucket scan fan-out, SaltScanner.java:70, mapped
        # onto a ('series','time') device mesh). Lazy: building the
        # mesh touches jax.devices().
        self._query_mesh_spec = self.config.get_string(
            "tsd.query.mesh", "")
        from opentsdb_tpu.parallel.mesh import parse_mesh_spec
        parse_mesh_spec(self._query_mesh_spec)  # fail fast on typos
        self._query_mesh = None
        self._query_mesh_error = ""
        # device-resident grid cache (HBM ≙ HBase block cache); lazy
        self._device_grid_cache = None
        self._device_cache_lock = threading.Lock()
        self._device_cache_mb = self.config.get_int(
            "tsd.query.device_cache_mb", 1024)
        # host-RAM twin for host-tail prepared batches: deliberately a
        # SEPARATE pool so host entries can never evict HBM-resident
        # grids (whose re-upload is the cost the device cache avoids)
        self._host_prep_cache = None
        self._host_cache_mb = self.config.get_int(
            "tsd.query.host_cache_mb", 512)
        # serve-path query RESULT cache (epoch-invalidated, single-
        # flight coalescing; opentsdb_tpu/query/result_cache.py); lazy
        self._result_cache = None
        self._result_cache_mb = self.config.get_int(
            "tsd.query.cache.mb", 256)
        # parallel sub-query fan-out pool: a DEDICATED executor, not
        # the server's _query_pool — parent queries RUN on that pool,
        # so fanning sub-queries back onto it deadlocks the moment
        # every worker holds a parent waiting on children that can
        # never be scheduled. Admission control still counts the whole
        # TSQuery once (per HTTP request, at the server); lazy.
        self._fanout_pool = None
        self._fanout_workers = self.config.get_int(
            "tsd.query.fanout.workers", 4)
        # continuous-query subsystem (opentsdb_tpu/streaming/): lazy —
        # created on first registration; the write path checks the raw
        # attribute so an idle TSD pays nothing
        self._streaming = None
        # data-lifecycle subsystem (opentsdb_tpu/lifecycle/): lazy —
        # the serve path reads the raw attribute, the `lifecycle`
        # property instantiates only when tsd.lifecycle.enable is set
        self._lifecycle = None
        # sharded cluster tier (opentsdb_tpu/cluster/): lazy — the
        # HTTP layer reads the `cluster` property per request; only a
        # tsd.cluster.role=router TSD instantiates the router
        self._cluster = None
        # self-driving control plane (opentsdb_tpu/control/): lazy —
        # the server's admission seam reads the raw attribute per
        # request; only tsd.control.enable instantiates the loop
        self._control = None
        # per-hook swallowed-error counters: post-write hooks (meta,
        # realtime publisher, external meta cache, stream tap) can
        # never fail an ACKNOWLEDGED write — see _run_hook
        # tsdlint: allow[unbounded-growth] keyed by hook name — a
        # closed, code-defined registry of ~6 hooks
        self.hook_errors: dict[str, int] = {}
        # host-side per-(store, metric) query.plan.PlanIndex cache,
        # invalidated by series count (the metric index is append-only)
        self._tagmat_cache: dict = {}
        from opentsdb_tpu.stats.stats import (ServePayloadStats,
                                              StatsCollectorRegistry)
        self.stats = StatsCollectorRegistry()
        self.stats.register(self.faults)
        # serve-path payload aggregates (response bytes +
        # serialization time), fed by the /api/query handler
        self.payload_stats = ServePayloadStats()
        self.stats.register(self.payload_stats)
        # device-pipeline circuit breaker: repeated accelerator
        # failures (compile errors, OOM) trip it and queries route to
        # the host CPU fallback instead of 500ing per request;
        # tsd.query.breaker.failure_threshold = 0 disables it
        breaker_threshold = self.config.get_int(
            "tsd.query.breaker.failure_threshold")
        if breaker_threshold > 0:
            self.device_breaker = CircuitBreaker(
                "device.pipeline",
                failure_threshold=breaker_threshold,
                reset_timeout_ms=self.config.get_int(
                    "tsd.query.breaker.reset_timeout_ms"))
            self.stats.register(self.device_breaker)
        else:
            self.device_breaker = None
        self.datapoints_added = 0
        self.start_time = time.time()
        # durable snapshots (ref-analogue of HBase-backed persistence;
        # SURVEY.md §5.4): load on start, save on flush/shutdown.
        # The WAL on top makes every ACKNOWLEDGED write crash-durable,
        # like HBase's WAL does for the reference (IncomingDataPoints
        # .java:355-360); snapshot + replay-since-snapshot on startup.
        self.data_dir = self.config.get_string("tsd.storage.data_dir", "")
        # request tracing (opentsdb_tpu/obs/): ring-buffered sampled
        # span records over the ingest/query/background hot paths +
        # the query-shape log; feeds the per-stage latency histograms
        # in the stats registry. tsd.trace.enable=false makes every
        # instrumentation site a thread-local read returning None.
        from opentsdb_tpu.obs.trace import Tracer
        self.tracer = Tracer(self.config, data_dir=self.data_dir,
                             stats=self.stats)
        self.stats.register(self.tracer)
        # self-telemetry (obs/telemetry.py): the tsd.stats.self_interval
        # loop ingesting this TSD's own counters/gauges/percentiles as
        # tsd.* series through the normal write path (started by
        # TSDServer; pump() is directly callable for tests/operators)
        from opentsdb_tpu.obs.telemetry import SelfTelemetry
        self.telemetry = SelfTelemetry(self)
        self.stats.register(self.telemetry)
        # continuous sampling profiler (obs/profiler.py): a bounded
        # background thread folding sys._current_frames() into
        # per-role stack counts over the last tsd.profile.ring_s
        # seconds — GET /api/profile serves it flamegraph-ready.
        # Started by TSDServer; stopped (joined) by shutdown().
        from opentsdb_tpu.obs.profiler import SamplingProfiler
        self.profiler = SamplingProfiler(self)
        self.stats.register(self.profiler)
        # SLO burn-rate tracker (obs/slo.py): per-endpoint
        # latency/availability objectives from tsd.slo.*, fed by the
        # HTTP router per served request, exported at /metrics and
        # /api/health
        from opentsdb_tpu.obs.slo import SloTracker
        self.slo = SloTracker(self.config)
        self.stats.register(self.slo)
        # persistent XLA compilation cache: every jitted query program
        # survives restarts (the reference's warm JVM never pays a
        # compile — ref QueryRpc.java:128 cold path is ms)
        from opentsdb_tpu.utils.compile_cache import enable_from_config
        enable_from_config(self.config)
        self.wal = None
        self._wal_applied_seq = 0
        if self.data_dir:
            from opentsdb_tpu.core import persist
            from opentsdb_tpu.obs.trace import RUNTIME
            with RUNTIME.phase("snapshot_load"):
                persist.load_store(self, self.data_dir)
            if self.config.get_bool("tsd.storage.wal.enable", True):
                from opentsdb_tpu.core.wal import WriteAheadLog
                from opentsdb_tpu.utils.faults import RetryPolicy
                wal = WriteAheadLog(
                    os.path.join(self.data_dir, "wal"),
                    fsync_mode=self.config.get_string(
                        "tsd.storage.wal.fsync", "always"),
                    segment_bytes=self.config.get_int(
                        "tsd.storage.wal.segment_mb", 64) << 20,
                    interval_ms=self.config.get_int(
                        "tsd.storage.wal.fsync_interval_ms", 200),
                    faults=self.faults,
                    retry=RetryPolicy.from_config(
                        self.config, "tsd.storage.wal.retry"),
                    resync_ms=self.config.get_int(
                        "tsd.storage.wal.resync_interval_ms"),
                    group_window_ms=self._wal_group_window_ms(),
                    group_max_records=self.config.get_int(
                        "tsd.storage.wal.group_max_records", 4096),
                    group_max_bytes=self.config.get_int(
                        "tsd.storage.wal.group_max_bytes", 4 << 20))
                self.stats.register(wal)
                # snapshot-covered sids keep their numbering on load
                # (histograms WAL by name, not sid — nothing to seed)
                wal.seed_known("data", self.store.num_series())
                if self.rollup_store is not None:
                    wal.seed_known(
                        "preagg",
                        self.rollup_store.preagg_store().num_series())
                    for (iv, agg), st in \
                            self.rollup_store._tiers.items():
                        wal.seed_known(f"tier:{iv}:{agg}",
                                       st.num_series())
                with RUNTIME.phase("wal_replay"):
                    recovered = wal.replay(self, self._wal_applied_seq)
                if recovered:
                    logging.getLogger("tsdb").info(
                        "WAL replay recovered %d points", recovered)
                self.wal = wal
                self.annotations.wal = wal

    def _check_host_backend(self) -> None:
        """Fail at boot, not on the first dashboard query: host-placed
        query tails, the degraded fallback and continuous-query pulls
        run on JAX's CPU backend BESIDE the accelerator
        (``ops.pipeline.host_cpu_device``), and an explicit platform
        list without ``cpu`` takes it away. Reads the config only; no
        backend is initialised. A router runs no device program."""
        if self.config.get_string("tsd.cluster.role", "") == "router":
            return
        import jax
        platforms = jax.config.jax_platforms or ""
        if platforms and "cpu" not in \
                [p.strip() for p in platforms.split(",")]:
            source = "tsd.tpu.platform" if self.config.get_string(
                "tsd.tpu.platform", "") else "JAX_PLATFORMS"
            raise ValueError(
                f"{source}={platforms!r} leaves JAX no CPU backend "
                "beside the accelerator, and host-placed query tails "
                f"need one: set {source}={platforms},cpu (the first "
                "platform stays the default), or leave it unset")

    def _wal_group_window_ms(self) -> int:
        """``tsd.storage.wal.group_window_ms`` with the role-aware
        auto default: "" (unset) means 0 standalone but 2 ms when
        running as a cluster SHARD — behind a router every shard sees
        genuinely concurrent writers (one connection per client), so
        an opportunistic commit window amortizes fsyncs, while the
        window's quiet-log early exit (``idle_breaks``) keeps a lone
        writer's added latency at ~one poll slice. An explicit value
        (including 0) always wins."""
        raw = self.config.get_string("tsd.storage.wal.group_window_ms",
                                     "").strip()
        if raw:
            return int(raw)
        role = self.config.get_string("tsd.cluster.role", "").strip()
        return 2 if role == "shard" else 0

    # ------------------------------------------------------------------
    # plugins (ref: TSDB.java initializePlugins :390)
    # ------------------------------------------------------------------

    def initialize_plugins(self) -> None:
        from opentsdb_tpu.utils.plugin import load_plugin_instances
        cfg = self.config
        if cfg.get_bool("tsd.core.plugins.enable", False) or True:
            self.rt_publisher = load_plugin_instances(
                cfg, "tsd.rtpublisher", single=True, init_arg=self)
            self.search_plugin = load_plugin_instances(
                cfg, "tsd.search", single=True, init_arg=self)
            self.storage_exception_handler = load_plugin_instances(
                cfg, "tsd.core.storage_exception_handler", single=True,
                init_arg=self)
            raw_filters = load_plugin_instances(
                cfg, "tsd.core.write_filter", init_arg=self) or []
            # honor the filter's opt-out gate
            # (ref: WriteableDataPointFilterPlugin.filterDataPoints)
            self.write_filters = [
                f for f in raw_filters
                if not hasattr(f, "filter_data_points")
                or f.filter_data_points()]
            # UID auto-assignment gate (ref: UniqueIdFilterPlugin,
            # TSDB.java uid_filter slot)
            self.uid_filter = load_plugin_instances(
                cfg, "tsd.uid.filter", single=True, init_arg=self)
            # external TSMeta counter cache (ref: MetaDataCache,
            # TSDB.java:158)
            self.meta_cache = load_plugin_instances(
                cfg, "tsd.core.meta.cache", single=True, init_arg=self)
        if cfg.get_bool("tsd.core.authentication.enable"):
            from opentsdb_tpu.auth.simple import SimpleAuthentication
            self.authentication = SimpleAuthentication(cfg)

    # ------------------------------------------------------------------
    # write path (ref: TSDB.java:1012-1291)
    # ------------------------------------------------------------------

    def _wal_scope(self):
        """One ingest request's WAL batch scope: every record appended
        inside lands as a single framed write, and all the deferred
        ``sync()`` calls collapse into at most one group-committed
        fsync at scope exit (see :meth:`WriteAheadLog.batch`). No-op
        when the WAL is off. Callers must not acknowledge
        durability-requiring writes until the scope exits."""
        if self.wal is None:
            return contextlib.nullcontext()
        return self.wal.batch()

    def _run_hook(self, name: str, fn, *args) -> None:
        """Run one post-write hook (realtime publisher, meta tracking,
        external meta cache, streaming ingest tap) so that a
        misbehaving plugin can NEVER fail an acknowledged write: the
        point is already durable in the store (and WAL) when hooks
        run, so propagating a hook error would report a failure for a
        write that actually happened — clients would retry and
        double-write. Errors are swallowed with a per-hook counter
        (``hooks.errors`` in /api/stats) and a logring entry."""
        try:
            fn(*args)
        except Exception:  # noqa: BLE001 - deliberate firewall
            n = self.hook_errors.get(name, 0) + 1
            self.hook_errors[name] = n
            # first few at full traceback, then sampled — a hook
            # failing on every point must not flood the log ring
            if n <= 5 or n % 1000 == 0:
                logging.getLogger("tsdb").exception(
                    "%s hook failed (swallowed; %d total) — the "
                    "write itself succeeded", name, n)

    def add_point(self, metric: str, timestamp: int, value: int | float,
                  tags: dict[str, str], durable: bool = True) -> int:
        """Write one datapoint; returns the series id. ``durable=False``
        skips write-ahead logging (setDurable(false) parity).

        (ref: TSDB.addPoint :1012/:1057/:1097 -> addPointInternal :1150)
        """
        if self.mode == "ro":
            raise PermissionError("TSD is in read-only mode")
        self._check_timestamp(timestamp)
        tags_mod.check_metric_and_tags(metric, tags)
        is_int = isinstance(value, int) and not isinstance(value, bool)
        fval = float(value)
        for filt in self.write_filters:
            allow = getattr(filt, "allow_data_point", filt)
            if not allow(metric, timestamp, value, tags):
                return -1
        metric_id, tag_ids = self._resolve_write_uids(metric, tags)
        sid = self.store.get_or_create_series(metric_id, tag_ids)
        ts_ms = codec.to_ms(timestamp)
        self.store.append(sid, ts_ms, fval, is_int)
        if self.wal is not None and durable:
            self.wal.ensure_series("data", sid, metric, tags)
            self.wal.log_point("data", sid, ts_ms, fval, is_int)
            self.wal.sync()
        self.datapoints_added += 1
        if self._streaming is not None:
            # streaming v2 tap: an O(1) columnar enqueue into the
            # metric's shared partial buffers — folds run on the
            # shared worker pool, never here (a lagging plan degrades
            # to rebuild-on-serve instead of slowing this path)
            self._run_hook("stream.tap", self._streaming.offer,
                           metric_id, sid, ts_ms, fval)
        tsuid = (self.uids.tsuid(metric_id, tag_ids)
                 if self.meta_cache is not None
                 or self.rt_publisher is not None else None)
        if self.meta_cache is not None:
            # external counter service replaces built-in tracking
            # (ref: TSDB.java:1225-1245 meta_cache branch)
            self._run_hook("meta_cache",
                           self.meta_cache.increment_and_get_counter,
                           tsuid)
        elif self.meta is not None:
            self._run_hook("meta", self.meta.on_datapoint, metric_id,
                           tag_ids, sid)
        if self.rt_publisher is not None:
            self._run_hook("rt_publisher",
                           self.rt_publisher.publish_data_point,
                           metric, timestamp, value, tags, tsuid)
        return sid

    def _check_timestamp(self, timestamp: int) -> None:
        # ref: TSDB.java:1274 checkTimestampAndTags
        if timestamp <= 0:
            raise ValueError(f"invalid timestamp {timestamp}")
        if codec.is_ms_timestamp(timestamp) and timestamp > (1 << 47):
            raise ValueError(f"timestamp out of range: {timestamp}")

    def _resolve_write_uids(self, metric: str, tags: dict[str, str]
                            ) -> tuple[int, list[tuple[int, int]]]:
        from opentsdb_tpu.core.uid import (FailedToAssignUniqueIdError,
                                           NoSuchUniqueName)

        def create_allowed(kind: str, name: str) -> bool:
            # ref: UniqueIdFilterPlugin.allowUIDAssignment consulted
            # before any new UID is minted (UniqueId.getOrCreateIdAsync)
            if self.uid_filter is None:
                return True
            return self.uid_filter.allow_uid_assignment(
                kind, name, metric, tags)

        def resolve(registry, kind: str, name: str, auto: bool) -> int:
            if not auto:
                return registry.get_id(name)  # may raise
            try:
                return registry.get_id(name)
            except NoSuchUniqueName:
                if not create_allowed(kind, name):
                    raise FailedToAssignUniqueIdError(
                        f"UID filter rejected assignment of {kind} "
                        f"{name!r}") from None
                return registry.get_or_create_id(name)

        metric_id = resolve(self.uids.metrics, "metric", metric,
                            self.auto_metric)
        tag_ids = []
        for k, v in tags.items():
            kid = resolve(self.uids.tag_names, "tagk", k, self.auto_tagk)
            vid = resolve(self.uids.tag_values, "tagv", v, self.auto_tagv)
            tag_ids.append((kid, vid))
        return metric_id, tag_ids

    def add_points(self, metric: str, timestamps, values,
                   tags: dict[str, str], is_int=None) -> int:
        """Bulk write many points of ONE series; returns the series id.

        Vectorized twin of :meth:`add_point` — validation and UID
        resolution happen once, timestamps normalize in numpy, and the
        store takes one ``append_many``. The WHOLE batch is validated
        before anything is written, so a raise never leaves a partial
        batch behind. Per-point plugin hooks (write filters, realtime
        publisher, external meta counter) fall back to the per-point
        path after validation, matching the reference where those
        hooks are inherently per-datapoint (TSDB.java:1225-1253).

        ``is_int`` optionally carries per-point integer flags (bool
        [N]); by default the flag derives from the values' dtype.
        (ref: WritableDataPoints batching, IncomingDataPoints.java:36)
        """
        ts = np.asarray(timestamps, dtype=np.int64)
        vals = np.asarray(values)
        if ts.shape != vals.shape or ts.ndim != 1:
            raise ValueError("timestamps/values must be equal-length 1-D")
        if self.mode == "ro":
            raise PermissionError("TSD is in read-only mode")
        if len(ts) == 0:
            raise ValueError("empty point batch")
        if int(ts.min()) <= 0:
            raise ValueError(f"invalid timestamp {int(ts.min())}")
        # positive ts & SECOND_MASK != 0 <=> ts >= 2^32 (the mask
        # itself overflows signed int64 in numpy)
        is_ms = ts >= (1 << 32)
        if int(ts[is_ms].max(initial=0)) > (1 << 47):
            raise ValueError("timestamp out of range")
        tags_mod.check_metric_and_tags(metric, tags)
        if is_int is None:
            flags = np.full(len(ts),
                            np.issubdtype(vals.dtype, np.integer))
        else:
            flags = np.asarray(is_int, dtype=bool)
        if (self.write_filters or self.rt_publisher is not None
                or self.meta_cache is not None):
            # inherently per-point hooks; batch already validated.
            # The WAL scope commits durability ONCE at batch end
            # instead of one fsync per fallback point — and still
            # commits on a raise (PartialWriteError reports already-
            # landed points, so they must be on the durability path)
            sid = -1
            done = 0
            with self._wal_scope():
                for t, v, f in zip(ts.tolist(), vals.tolist(),
                                   flags.tolist()):
                    try:
                        sid = self.add_point(metric, t,
                                             int(v) if f else float(v),
                                             tags)
                    except Exception as e:  # noqa: BLE001
                        raise PartialWriteError(done, e) from e
                    done += 1
            return sid
        metric_id, tag_ids = self._resolve_write_uids(metric, tags)
        sid = self.store.get_or_create_series(metric_id, tag_ids)
        ts_ms = np.where(is_ms, ts, ts * 1000)
        fvals = vals.astype(np.float64)
        self.store.append_many(sid, ts_ms, fvals, flags)
        if self.wal is not None:
            # batch scope: identity + points + sync land as one framed
            # write under one lock take (joins any enclosing request
            # scope, e.g. add_point_groups')
            with self.wal.batch():
                self.wal.ensure_series("data", sid, metric, tags)
                self.wal.log_points("data", sid, ts_ms, fvals, flags)
                self.wal.sync()
        self.datapoints_added += len(ts)
        if self._streaming is not None:
            self._run_hook("stream.tap", self._streaming.offer_many,
                           metric_id, sid, ts_ms, fvals)
        if self.meta is not None:
            self._run_hook("meta", self.meta.on_datapoint, metric_id,
                           tag_ids, sid, len(ts))
        return sid

    def add_point_batch(self, points, on_error=None
                        ) -> tuple[int, list[str]]:
        """Bulk write a mixed batch of ``(metric, ts, value, tags)``
        tuples, grouping by series so UID resolution and store locking
        amortize. A group whose bulk write fails is replayed per point
        so every valid point still lands and errors stay per-point.
        Returns (points_written, error strings); ``on_error(i, exc)``
        additionally receives the input index of each failing point.
        """
        groups: dict[tuple, tuple] = {}
        for i, (metric, ts, value, tags) in enumerate(points):
            key = (metric, tuple(sorted(tags.items())))
            g = groups.get(key)
            if g is None:
                g = groups[key] = (metric, tags, [], [], [])
            g[2].append(i)
            g[3].append(ts)
            g[4].append(value)
        return self.add_point_groups(groups.values(),
                                     on_error=on_error)

    def add_point_groups(self, groups, on_error=None
                         ) -> tuple[int, list[str]]:
        """Columnar bulk write of points already grouped by series:
        ``groups`` yields ``(metric, tags, refs, timestamps, values)``
        where ``refs[i]`` is an opaque per-point handle handed back to
        ``on_error(ref, exc)`` for failing points. The whole request
        runs under ONE WAL batch scope — an N-group put body commits
        as a single framed WAL write and a single group-committed
        fsync instead of one sync per series-group. A group whose
        bulk write fails replays per point so every valid point still
        lands and errors stay per-point."""
        errors: list[str] = []
        written = 0

        def fail(ref, metric: str, ts, e: Exception) -> None:
            errors.append(f"{metric} @{ts}: {e}")
            if on_error is not None:
                on_error(ref, e)

        with self._wal_scope():
            for metric, tags, refs, ts_list, raw in groups:
                try:
                    n = len(ts_list)
                    ts_arr = np.asarray(ts_list, dtype=np.int64)
                    vals = np.asarray(raw, dtype=np.float64)
                    # type(v) is int: excludes bool, one pass
                    flags = np.fromiter((type(v) is int for v in raw),
                                        dtype=bool, count=n)
                    self.add_points(metric, ts_arr, vals, tags,
                                    is_int=flags)
                    written += n
                except PartialWriteError as pe:
                    # the hook-fallback loop landed pe.written points;
                    # the next one failed mid-hooks (don't retry it —
                    # hooks are not idempotent); the rest replay per
                    # point
                    written += pe.written
                    k = pe.written
                    fail(refs[k], metric, ts_list[k], pe.cause)
                    for j in range(k + 1, len(ts_list)):
                        try:
                            self.add_point(metric, ts_list[j], raw[j],
                                           tags)
                            written += 1
                        except Exception as e:  # noqa: BLE001
                            fail(refs[j], metric, ts_list[j], e)
                except Exception:  # noqa: BLE001
                    # bulk path failed before anything landed: per-
                    # point replay so valid points land and errors map
                    # back
                    for j in range(len(ts_list)):
                        try:
                            self.add_point(metric, ts_list[j], raw[j],
                                           tags)
                            written += 1
                        except Exception as e:  # noqa: BLE001
                            fail(refs[j], metric, ts_list[j], e)
        return written, errors

    def import_buffer(self, buf: bytes, on_error=None,
                      durable: bool = True) -> tuple[int, list[str]]:
        """Columnar bulk import of the reference's text line format
        (``metric ts value tagk=tagv ...``; ref: TextImporter.java:40).

        One native pass parses the whole buffer and labels every line
        with its distinct (metric, sorted tags) key, so UID resolution
        and series lookup run once per distinct SERIES and the points
        land via per-group ``append_many`` — the per-point Python loop
        only runs when per-point plugin hooks (write filters, realtime
        publisher, external meta counters) are active.

        Returns (points_written, error strings); ``on_error(lineno,
        exc)`` gets each failing 1-based line number.
        """
        if self.mode == "ro":
            raise PermissionError("TSD is in read-only mode")
        from opentsdb_tpu.obs import trace as trace_mod
        if trace_mod.current() is not None:
            return self._import_buffer(buf, on_error, durable)
        # a loader outside any request (``tsdb import``, a plugin at
        # start-up): root a sampled background trace, so that the
        # stages below record and feed their histograms
        ctx = self.tracer.start_background("ingest.import", sample=True,
                                           bytes=len(buf))
        try:
            with trace_mod.use(ctx):
                return self._import_buffer(buf, on_error, durable)
        finally:
            self.tracer.finish(ctx)

    def _import_buffer(self, buf: bytes, on_error,
                       durable: bool) -> tuple[int, list[str]]:
        from opentsdb_tpu.native.store_backend import (IMPORT_ERRORS,
                                                       parse_import_buffer)
        from opentsdb_tpu.obs.trace import trace_begin, trace_end
        _h_dec = trace_begin("ingest.decode")
        parsed = parse_import_buffer(buf)
        errors: list[str] = []

        def fail(lineno: int, msg: str) -> None:
            errors.append(f"line {lineno}: {msg}")
            if on_error is not None:
                on_error(lineno, ValueError(msg))

        for i in np.nonzero(parsed.errors > 0)[0].tolist():
            fail(i + 1, IMPORT_ERRORS.get(int(parsed.errors[i]),
                                          "parse error"))
        if _h_dec is not None:
            _h_dec.tag(lines=int(parsed.num_lines)
                       if hasattr(parsed, "num_lines")
                       else len(parsed.ts))
        trace_end(_h_dec)
        _h_res = trace_begin("ingest.resolve",
                             groups=int(parsed.num_groups))
        # resolve each distinct series once. The parser already
        # enforced the reference's charset/shape rules (code 5), so no
        # per-name re-validation here.
        use_hooks = (bool(self.write_filters)
                     or self.rt_publisher is not None
                     or self.meta_cache is not None)
        gsid = np.full(parsed.num_groups, -1, dtype=np.int64)
        ginfo: list = [None] * parsed.num_groups
        for g, line in enumerate(parsed.rep_lines):
            try:
                text = line.decode("utf-8")
                words = text.split()
                metric = words[0]
                tags = {}
                for w in words[3:]:
                    k, _, v = w.partition("=")
                    tags[k] = v
                if not text.isascii():
                    # the native parser passes UTF-8 bytes through;
                    # precise unicode-letter validation happens here
                    # (rare path — once per distinct non-ASCII series)
                    tags_mod.check_metric_and_tags(metric, tags)
                if use_hooks:
                    ginfo[g] = (metric, tags, None, None)
                else:
                    metric_id, tag_ids = self._resolve_write_uids(
                        metric, tags)
                    gsid[g] = self.store.get_or_create_series(
                        metric_id, tag_ids)
                    ginfo[g] = (metric, tags, metric_id, tag_ids)
            except Exception as e:  # noqa: BLE001
                ginfo[g] = e

        failed = [g for g in range(parsed.num_groups)
                  if isinstance(ginfo[g], Exception)]
        for g in failed:
            for i in np.nonzero(parsed.group_ids == g)[0].tolist():
                fail(i + 1, str(ginfo[g]))
        trace_end(_h_res)
        written = 0
        if use_hooks:
            # per-point hooks are inherently per-datapoint: group runs
            # still amortize the metric/tag resolution, and the WAL
            # scope commits ONE fsync for the whole buffer instead of
            # one per point
            with self._wal_scope():
                for g in range(parsed.num_groups):
                    if isinstance(ginfo[g], Exception):
                        continue
                    metric, tags, _, _ = ginfo[g]
                    members = np.nonzero(parsed.group_ids == g)[0]
                    for i, t, v, f in zip(
                            members.tolist(),
                            parsed.ts[members].tolist(),
                            parsed.values[members].tolist(),
                            parsed.is_int[members].tolist()):
                        try:
                            self.add_point(metric, t,
                                           int(v) if f else v, tags,
                                           durable=durable)
                            written += 1
                        except Exception as e:  # noqa: BLE001
                            fail(i + 1, str(e))
            return written, errors
        if parsed.num_groups == 0:
            return 0, errors
        # one scatter-append call lands every line on its series
        gids = parsed.group_ids
        line_sids = np.where(gids >= 0,
                             gsid[np.maximum(gids, 0)], -1)
        ts_ms = np.where(parsed.ts >= (1 << 32), parsed.ts,
                         parsed.ts * 1000)
        _h_sc = trace_begin("store.scatter")
        written = self.store.append_lines(line_sids, ts_ms,
                                          parsed.values, parsed.is_int)
        trace_end(_h_sc)
        if self.wal is not None and durable:
            # durable=False ≙ the reference's batch-import WAL opt-out
            # (PutRequest.setDurable(false), IncomingDataPoints:355-360)
            # batch scope: N ensure_series + the lines record land as
            # one framed write under one lock take, one fsync
            with self.wal.batch():
                for g in range(parsed.num_groups):
                    info = ginfo[g]
                    if isinstance(info, Exception):
                        continue
                    self.wal.ensure_series("data", int(gsid[g]),
                                           info[0], info[1])
                self.wal.log_lines("data", line_sids, ts_ms,
                                   parsed.values, parsed.is_int)
                self.wal.sync()
        self.datapoints_added += written
        if self._streaming is not None and written:
            _h_tap = trace_begin("stream.tap")
            for g in range(parsed.num_groups):
                info = ginfo[g]
                if isinstance(info, Exception):
                    continue
                m = parsed.group_ids == g
                if m.any():
                    self._run_hook("stream.tap",
                                   self._streaming.offer_many,
                                   info[2], int(gsid[g]), ts_ms[m],
                                   parsed.values[m])
            trace_end(_h_tap)
        if self.meta is not None and written:
            counts = np.bincount(gids[gids >= 0],
                                 minlength=parsed.num_groups)
            for g in range(parsed.num_groups):
                info = ginfo[g]
                if isinstance(info, Exception) or not counts[g]:
                    continue
                self._run_hook("meta", self.meta.on_datapoint,
                               info[2], info[3], int(gsid[g]),
                               int(counts[g]))
        return written, errors

    def add_aggregate_point(self, metric: str, timestamp: int,
                            value: int | float, tags: dict[str, str],
                            is_groupby: bool, interval: str | None,
                            rollup_agg: str | None,
                            groupby_agg: str | None = None) -> None:
        """Write a rollup / pre-aggregated point (ref: TSDB.java:1320-1418):
        :meth:`add_aggregate_batch` of a run of one, its error raised.
        """
        def raise_it(_ref, e: Exception) -> None:
            raise e

        self.add_aggregate_batch(
            [(interval, rollup_agg, metric, tags, (timestamp,), (value,),
              groupby_agg, is_groupby)], on_error=raise_it)

    def add_aggregate_batch(self, runs, on_error=None
                            ) -> tuple[int, list[str]]:
        """Columnar write of rollup cells: the one entry of
        ``/api/rollup``, telnet ``rollup``, :meth:`add_aggregate_point`
        and a bulk loader. ``runs`` yields
        :class:`~opentsdb_tpu.rollup.store.AggregateRun` (or its first
        six fields as a tuple): one series' cells of one (tier,
        aggregator). A run is validated and resolved once (the
        agg-tag of a pre-aggregate, ``tsd.rollups.agg_tag_key``, as
        the reference's), lands by one ``append_many`` and one framed
        WAL record, and the whole call syncs the WAL once: an answer
        given after it returns is given after the fsync of every cell
        it landed. A run that fails fails each of its cells with the
        same error (``on_error(ref, exc)``); a run whose values are no
        numbers is landed a cell at a time so that the others land.
        Returns (cells written, error strings)."""
        from opentsdb_tpu.obs import trace as trace_mod
        if trace_mod.current() is not None:
            return self._add_aggregate_batch(runs, on_error)
        ctx = self.tracer.start_background("ingest.rollup", sample=True)
        try:
            with trace_mod.use(ctx):
                written, errors = self._add_aggregate_batch(runs,
                                                            on_error)
                if ctx is not None:
                    ctx.tag(points=written)
                return written, errors
        finally:
            self.tracer.finish(ctx)

    def _add_aggregate_batch(self, runs, on_error
                             ) -> tuple[int, list[str]]:
        from opentsdb_tpu.rollup.store import AggregateRun
        errors: list[str] = []
        written = 0

        def land(run: AggregateRun, ts, values) -> int:
            if self.rollup_store is None:
                raise RuntimeError("rollups are not enabled "
                                   "(tsd.rollups.enable=false)")
            tags = dict(run.tags)
            if run.is_groupby:
                agg = (run.groupby_agg or run.aggregator or "").upper()
                if not agg:
                    raise ValueError("missing group-by aggregator")
                tags[self.agg_tag_key] = agg
            tags_mod.check_metric_and_tags(run.metric, tags)
            ts = np.asarray(ts, dtype=np.int64)
            vals = np.asarray(values, dtype=np.float64)
            if ts.shape != vals.shape or ts.ndim != 1:
                raise ValueError(
                    "timestamps/values must be equal-length 1-D")
            metric_id, tag_ids = self._resolve_write_uids(run.metric,
                                                          tags)
            # codec.to_ms, a column at a time
            ts_ms = np.where(ts >= (1 << 32), ts, ts * 1000)
            kind, sid = self.rollup_store.append_run(
                run.interval, run.aggregator, metric_id, tag_ids,
                ts_ms, vals)
            if self.wal is not None:
                self.wal.ensure_series(kind, sid, run.metric, tags)
                self.wal.log_points(kind, sid, ts_ms, vals,
                                    np.zeros(len(ts_ms), dtype=bool))
            self.datapoints_added += len(ts_ms)
            return len(ts_ms)

        with self._wal_scope():
            for run in runs:
                run = AggregateRun(*run)
                n = len(run.timestamps)
                refs = run.refs if run.refs is not None else range(n)
                try:
                    written += land(run, run.timestamps, run.values)
                    continue
                except Exception as e:  # noqa: BLE001
                    whole = e
                for j in range(n):
                    # a cell at a time, so that what can land does and
                    # each error is its own cell's (a cell appended
                    # twice is one cell: the store keeps the last); a
                    # run of one has its error already
                    try:
                        if n == 1:
                            raise whole
                        written += land(run, run.timestamps[j:j + 1],
                                        run.values[j:j + 1])
                    except Exception as e:  # noqa: BLE001
                        errors.append(
                            f"{run.metric} @{run.timestamps[j]}: {e}")
                        if on_error is not None:
                            on_error(refs[j], e)
            if written and self.wal is not None:
                self.wal.sync()
        return written, errors

    def add_histogram_batch(self, points, on_error=None
                            ) -> tuple[int, list[str]]:
        """Bulk write ``(metric, timestamp, raw_blob, tags)`` histogram
        tuples: the one entry of ``/api/histogram``, telnet
        ``histogram`` and a bulk loader. Points are grouped by series,
        so validation and UID resolution run once a series (the
        histogram twin of :meth:`add_point_batch`), and a series'
        blobs of the built-in codec that share one bounds header are
        decoded by one ``np.frombuffer`` and landed by one
        ``append_many`` (:func:`~opentsdb_tpu.core.histogram.
        decode_simple_run`; ``tsd.histogram.bulk_points``). Blobs that
        are no such run (another codec, differing bounds, a malformed
        one among them) are decoded and appended a point at a time,
        with that path's errors (``tsd.histogram.slow_points``).
        WAL-synced once per batch. Returns (written, error strings)."""
        from opentsdb_tpu.core.histogram import (HistogramArena,
                                                 SimpleHistogramCodec,
                                                 decode_simple_run)
        groups: dict[tuple, list] = {}
        errors: list[str] = []
        written = 0

        def fail(idx: int, metric: str, ts, e: Exception) -> None:
            errors.append(f"{metric} @{ts}: {e}")
            if on_error is not None:
                on_error(idx, e)

        # consecutive points of one series (a loader's order) find
        # their group by comparing tags with a copy of the last ones:
        # a sort and a tuple a series, not a point
        last_metric = last_tags = items = None
        for i, (metric, ts, blob, tags) in enumerate(points):
            if metric != last_metric or tags != last_tags:
                last_metric, last_tags = metric, dict(tags)
                items = groups.setdefault(
                    (metric, tuple(sorted(tags.items()))), [])
            items.append((i, ts, blob, tags))
        bulk = type(self.histogram_manager.codec(
            SimpleHistogramCodec.id)) is SimpleHistogramCodec
        stats = self.histogram_stats
        with self._wal_scope():
            for (metric, _), items in groups.items():
                tags = items[0][3]
                try:
                    tags_mod.check_metric_and_tags(metric, tags)
                except Exception as e:  # noqa: BLE001
                    for idx, ts, _b, _t in items:
                        fail(idx, metric, ts, e)
                    continue
                # validate + decode every point BEFORE touching the
                # UID tables: a fully-invalid group must not pollute
                # UID space or create an empty series (matches
                # add_histogram_point, which validates first and
                # creates nothing on failure)
                timed: list[tuple] = []
                for idx, ts, blob, _t in items:
                    try:
                        self._check_timestamp(ts)
                        timed.append((idx, ts, blob, codec.to_ms(ts)))
                    except Exception as e:  # noqa: BLE001
                        fail(idx, metric, ts, e)
                if not timed:
                    continue
                run = decode_simple_run([t[2] for t in timed]) \
                    if bulk else None
                valid: list[tuple] = []
                if run is None:
                    for idx, ts, blob, ts_ms in timed:
                        try:
                            valid.append(
                                (idx, ts, blob, ts_ms,
                                 self.histogram_manager.decode(blob)))
                        except Exception as e:  # noqa: BLE001
                            fail(idx, metric, ts, e)
                    if not valid:
                        continue
                try:
                    metric_id, tag_ids = self._resolve_write_uids(
                        metric, tags)
                    sid = self.histogram_store.get_or_create_series(
                        metric_id, tag_ids)
                except Exception as e:  # noqa: BLE001
                    for idx, ts, *_ in (timed if run else valid):
                        fail(idx, metric, ts, e)
                    continue
                landed = timed
                # one lock take for the whole group's appends
                with self._histogram_lock:
                    arena = self._histogram_arenas.get(metric_id)
                    if arena is None:
                        arena = self._histogram_arenas[metric_id] = \
                            HistogramArena()
                    if run:
                        arena.append_run(
                            np.fromiter((t[3] for t in timed),
                                        dtype=np.int64,
                                        count=len(timed)), sid, *run)
                    else:
                        landed = []
                        for point in valid:
                            idx, ts, _b, ts_ms, hist = point
                            try:
                                # what decodes need not fit the arena
                                # (a counter past int64, no bucket)
                                arena.append(ts_ms, sid, hist)
                                landed.append(point)
                            except Exception as e:  # noqa: BLE001
                                fail(idx, metric, ts, e)
                    self._histogram_version += 1
                stats.add(**{"bulk_points" if run else "slow_points":
                             len(landed)})
                if self.wal is not None:
                    for _idx, ts, blob, *_ in landed:
                        self.wal.log_histogram(metric, tags, ts, blob)
                self.datapoints_added += len(landed)
                written += len(landed)
            if written and self.wal is not None:
                self.wal.sync()
        return written, errors

    def add_histogram_point(self, metric: str, timestamp: int,
                            raw_blob: bytes, tags: dict[str, str],
                            _wal: bool = True) -> int:
        """Write an encoded histogram datapoint (ref: TSDB.java:1132)."""
        tags_mod.check_metric_and_tags(metric, tags)
        self._check_timestamp(timestamp)
        hist = self.histogram_manager.decode(raw_blob)
        metric_id, tag_ids = self._resolve_write_uids(metric, tags)
        sid = self.histogram_store.get_or_create_series(metric_id, tag_ids)
        ts_ms = codec.to_ms(timestamp)
        with self._histogram_lock:
            from opentsdb_tpu.core.histogram import HistogramArena
            arena = self._histogram_arenas.get(metric_id)
            if arena is None:
                arena = self._histogram_arenas[metric_id] = \
                    HistogramArena()
            arena.append(ts_ms, sid, hist)
            self._histogram_version += 1
        self.histogram_stats.add(slow_points=1)
        if _wal and self.wal is not None:
            self.wal.log_histogram(metric, tags, timestamp, raw_blob)
            self.wal.sync()
        self.datapoints_added += 1
        return sid

    def purge_histograms_before(self, metric_id: int,
                                cutoff_ms: int) -> int:
        """Lifecycle retention for histogram arenas: drop one metric's
        histogram points older than the cutoff and bump the histogram
        version + store epoch so every read-side cache (result cache,
        streaming plans) invalidates. Returns points removed."""
        with self._histogram_lock:
            arena = self._histogram_arenas.get(metric_id)
            if arena is None:
                return 0
            removed = arena.purge_before(cutoff_ms)
            if removed:
                if not arena.groups:
                    del self._histogram_arenas[metric_id]
                self._histogram_version += 1
                self.histogram_store.mutation_epoch += 1
        return removed

    # ------------------------------------------------------------------
    # read path entry (ref: TSDB.java newQuery :963)
    # ------------------------------------------------------------------

    @property
    def query_mesh(self):
        """The ('series','time') device mesh ``/api/query`` executes
        over, or None for single-device execution. Configured with
        ``tsd.query.mesh`` (ref: SaltScanner.java:70 — the fixed 20-way
        scan fan-out this replaces with a device-mesh shard_map)."""
        if self._query_mesh is None and self._query_mesh_spec:
            from opentsdb_tpu.parallel.mesh import mesh_from_spec
            try:
                self._query_mesh = mesh_from_spec(self._query_mesh_spec)
            except ValueError as exc:
                # e.g. spec wants more devices than exist: degrade to
                # single-device once, loudly — NOT a 500 on every
                # query; device_info() reports the mesh really in use
                self._query_mesh_error = str(exc)
                logging.getLogger("tsdb").exception(
                    "tsd.query.mesh=%r unusable; queries run "
                    "single-device", self._query_mesh_spec)
            if self._query_mesh is None:  # single device: stop retrying
                self._query_mesh_spec = ""
        return self._query_mesh

    def device_info(self) -> dict[str, Any]:
        """What this process runs on (``/api/health`` ``device``, and
        the ``tsd.device.*`` stats): the JAX platform, device kind and
        count, x64, the compile-cache directory, the storage backend
        actually loaded, the mesh actually built and what warm-up
        did. Read-only; lets a client tell a TPU server from a CPU
        one."""
        import jax

        from opentsdb_tpu.native.store_backend import \
            NativeTimeSeriesStore
        from opentsdb_tpu.utils.compile_cache import active_cache_dir
        info: dict[str, Any] = {
            "platform": None, "device_kind": None, "count": 0,
            "x64": bool(jax.config.jax_enable_x64),
            "compile_cache_dir": active_cache_dir(),
            "storage_backend": "native" if isinstance(
                self.store, NativeTimeSeriesStore) else "memory",
        }
        if self.config.get_string("tsd.cluster.role", "") != "router":
            # a router runs no device program and must never
            # initialise a backend: the chip belongs to the shard
            # process beside it
            devices = jax.devices()
            info.update(platform=devices[0].platform,
                        device_kind=devices[0].device_kind,
                        count=len(devices))
        mesh = self._query_mesh
        info["mesh"] = {
            "requested": self.config.get_string("tsd.query.mesh", ""),
            "shape": dict(mesh.shape) if mesh is not None else None,
            "devices": int(mesh.devices.size) if mesh is not None
            else min(info["count"], 1),
            "error": self._query_mesh_error,
        }
        cache = self._device_grid_cache
        info["resident"] = cache.placement() if cache is not None \
            else {"entries": 0, "bytes_by_device": {}}
        from opentsdb_tpu.tsd.warmup import WarmupReport
        info["warmup"] = (getattr(self, "warmup_report", None)
                          or WarmupReport("off")).as_dict()
        return info

    @property
    def device_grid_cache(self):
        """Device-resident [S, B] grid cache (see
        :mod:`opentsdb_tpu.query.device_cache`), or None when disabled
        (``tsd.query.device_cache_mb = 0``)."""
        if self._device_grid_cache is None and self._device_cache_mb:
            with self._device_cache_lock:
                if self._device_grid_cache is None:
                    from opentsdb_tpu.query.device_cache import \
                        DeviceGridCache
                    cache = DeviceGridCache(
                        self._device_cache_mb * (1 << 20))
                    self.stats.register(cache)
                    self._device_grid_cache = cache
        return self._device_grid_cache

    @property
    def host_prep_cache(self):
        """Host-RAM prepared-batch cache for host-tail queries (warm
        repeats skip materialize + union-grid construction), or None
        when disabled (``tsd.query.host_cache_mb = 0``)."""
        if self._host_prep_cache is None and self._host_cache_mb:
            with self._device_cache_lock:
                if self._host_prep_cache is None:
                    from opentsdb_tpu.query.device_cache import \
                        DeviceGridCache
                    cache = DeviceGridCache(
                        self._host_cache_mb * (1 << 20),
                        stat_prefix="query.hostcache")
                    self.stats.register(cache)
                    self._host_prep_cache = cache
        return self._host_prep_cache

    @property
    def result_cache(self):
        """Serve-path query result cache
        (:mod:`opentsdb_tpu.query.result_cache`), or None when
        disabled. ``tsd.query.cache.enable`` is consulted per call so
        operators (and the bench) can toggle it at runtime without
        losing the populated cache."""
        if self._result_cache_mb <= 0 or not self.config.get_bool(
                "tsd.query.cache.enable", True):
            return None
        if self._result_cache is None:
            with self._device_cache_lock:
                if self._result_cache is None:
                    from opentsdb_tpu.query.result_cache import \
                        QueryResultCache
                    cache = QueryResultCache(
                        self._result_cache_mb * (1 << 20),
                        shards=self.config.get_int(
                            "tsd.query.cache.shards", 8))
                    self.stats.register(cache)
                    self._result_cache = cache
        return self._result_cache

    @property
    def streaming(self):
        """Continuous-query registry
        (:mod:`opentsdb_tpu.streaming.registry`), or None when
        disabled (``tsd.streaming.enable = false``). Lazy: the write
        path's tap check reads the raw ``_streaming`` attribute, so a
        TSD with no registered continuous queries pays one attribute
        read per write."""
        if not self.config.get_bool("tsd.streaming.enable", True):
            return None
        if self._streaming is None:
            with self._device_cache_lock:
                if self._streaming is None:
                    from opentsdb_tpu.streaming.registry import \
                        ContinuousQueryRegistry
                    reg = ContinuousQueryRegistry(self)
                    self.stats.register(reg)
                    self._streaming = reg
        return self._streaming

    @property
    def lifecycle(self):
        """Data-lifecycle manager
        (:mod:`opentsdb_tpu.lifecycle.manager`), or None when disabled
        (``tsd.lifecycle.enable = false``, the default). The query
        engine consults it per sub-query for demotion-boundary
        stitching; the server starts its sweeper thread."""
        if not self.config.get_bool("tsd.lifecycle.enable", False):
            return None
        if self._lifecycle is None:
            with self._device_cache_lock:
                if self._lifecycle is None:
                    from opentsdb_tpu.lifecycle.manager import \
                        LifecycleManager
                    lc = LifecycleManager(self)
                    self.stats.register(lc)
                    self._lifecycle = lc
        return self._lifecycle

    @property
    def cluster(self):
        """Cluster router (:mod:`opentsdb_tpu.cluster.router`), or
        None unless this TSD runs as ``tsd.cluster.role = router``.
        The HTTP layer branches ``/api/put`` and ``/api/query``
        through it; shards and standalone TSDs serve locally."""
        if self.config.get_string("tsd.cluster.role", "") != "router":
            return None
        if self._cluster is None:
            with self._device_cache_lock:
                if self._cluster is None:
                    from opentsdb_tpu.cluster.router import \
                        ClusterRouter
                    router = ClusterRouter(self)
                    self.stats.register(router)
                    self._cluster = router
        return self._cluster

    @property
    def control(self):
        """Self-driving control plane
        (:mod:`opentsdb_tpu.control.plane`), or None when disabled
        (``tsd.control.enable = false``, the default). The server's
        admission seam reads the raw ``_control`` attribute so an
        uncontrolled TSD pays one attribute read per request."""
        if not self.config.get_bool("tsd.control.enable", False):
            return None
        if self._control is None:
            with self._device_cache_lock:
                if self._control is None:
                    from opentsdb_tpu.control.plane import \
                        ControlPlane
                    ctl = ControlPlane(self)
                    self.stats.register(ctl)
                    self._control = ctl
        # outside the lock: wire() builds the lazy result_cache, which
        # takes the same lock
        self._control.wire()
        return self._control

    @property
    def query_fanout_pool(self):
        """Executor independent sub-queries of one TSQuery fan out
        onto (None = serial; ``tsd.query.fanout.workers``). See the
        constructor comment for why this is NOT the server's
        _query_pool."""
        if self._fanout_pool is None and self._fanout_workers > 0:
            with self._device_cache_lock:
                if self._fanout_pool is None:
                    import concurrent.futures
                    self._fanout_pool = \
                        concurrent.futures.ThreadPoolExecutor(
                            max_workers=self._fanout_workers,
                            thread_name_prefix="tsd-subq")
        return self._fanout_pool

    def storage_memory_info(self) -> dict:
        """Per-store memory footprint (resident/live/dead bytes,
        series and point counts) for /api/health and /api/stats —
        makes lifecycle reclamation observable before/after sweeps.
        Per-store entries are cached inside each store; totals sum
        whatever stores exist."""
        out: dict = {}
        if hasattr(self.store, "memory_info"):
            out["raw"] = self.store.memory_info()
        if hasattr(self.histogram_store, "memory_info"):
            out["histogram"] = self.histogram_store.memory_info()
        if self.rollup_store is not None:
            rs = self.rollup_store
            preagg = rs.preagg_store()
            if hasattr(preagg, "memory_info"):
                out["rollup:preagg"] = preagg.memory_info()
            with rs._tiers_lock:
                tiers = list(rs._tiers.items())
            for (interval, agg), store in sorted(tiers):
                if hasattr(store, "memory_info"):
                    out[f"rollup:{interval}:{agg}"] = \
                        store.memory_info()
        # cold tier: disk-resident mmap segments, reported separately
        # from RAM (the whole point is that they are NOT resident)
        lc = self._lifecycle
        cold = getattr(lc, "coldstore", None) if lc is not None \
            else None
        if cold is not None:
            out["cold"] = cold.memory_info()
        totals = {"resident_bytes": 0, "live_bytes": 0,
                  "dead_bytes": 0, "series": 0, "points": 0}
        for info in out.values():
            for k in totals:
                totals[k] += info.get(k, 0)
        totals["cold_bytes"] = (out["cold"]["disk_bytes"]
                                if cold is not None else 0)
        out["total"] = totals
        return out

    def serve_version(self) -> tuple:
        """Version tuple over every store the query surface can read
        (raw + rollup tiers + preagg + histograms + annotations):
        cheap counter reads, bumped by every write and every
        destructive op. Read-side caches key their entries on it, so
        a version mismatch <=> the data MAY have changed — no cached
        result can ever outlive a write it should reflect."""
        s = self.store
        parts: list = [
            s.points_written, getattr(s, "mutation_epoch", 0),
            self._histogram_version,
            self.histogram_store.points_written,
            self.histogram_store.mutation_epoch,
            getattr(self.annotations, "version", 0),
        ]
        if self.rollup_store is not None:
            parts.append(self.rollup_store.version())
        return tuple(parts)

    def new_query(self):
        from opentsdb_tpu.query.engine import QueryEngine
        return QueryEngine(self)

    def execute_query(self, ts_query) -> list:
        """Run a validated TSQuery end-to-end, returning result groups."""
        return self.new_query().run(ts_query)

    # ------------------------------------------------------------------
    # suggest / uid surface (ref: TSDB.java:1762-1846)
    # ------------------------------------------------------------------

    def suggest_metrics(self, search: str = "", max_results: int = 25):
        return self.uids.metrics.suggest(search, max_results)

    def suggest_tag_names(self, search: str = "", max_results: int = 25):
        return self.uids.tag_names.suggest(search, max_results)

    def suggest_tag_values(self, search: str = "", max_results: int = 25):
        return self.uids.tag_values.suggest(search, max_results)

    def assign_uid(self, kind: str, name: str) -> int:
        tags_mod.validate_string(f"{kind} name", name)
        uid = self.uids.by_kind(kind).assign_id(name)
        if self.wal is not None:
            self.wal.log_uid(kind, name)
            self.wal.sync()
        return uid

    # ------------------------------------------------------------------
    # lifecycle (ref: TSDB.java flush :1603, shutdown :1632)
    # ------------------------------------------------------------------

    def flush(self) -> None:
        if self.data_dir:
            from opentsdb_tpu.core import persist
            from opentsdb_tpu.utils.faults import (RetryPolicy,
                                                   call_with_retries)
            # a slow/flaky disk under the snapshot directory gets the
            # same retry-with-backoff discipline as the WAL fsync path
            wal_seq = call_with_retries(
                lambda: persist.save_store(self, self.data_dir),
                RetryPolicy.from_config(self.config,
                                        "tsd.storage.flush.retry"),
                retryable=(OSError,),
                on_retry=lambda attempt, exc: logging.getLogger(
                    "tsdb").warning(
                        "snapshot flush failed (attempt %d: %s); "
                        "retrying", attempt, exc))
            if self.wal is not None:
                # snapshot covers seq <= wal_seq: those segments are done
                self.wal.truncate(wal_seq)

    def shutdown(self) -> None:
        # the control plane steers every other subsystem, so it stops
        # FIRST — a tick must not race a registry/router teardown
        if self._control is not None:
            self._control.stop()
        self.telemetry.stop()
        self.profiler.stop()
        if self._cluster is not None:
            self._cluster.stop()
        if self._lifecycle is not None:
            self._lifecycle.stop()
        self.tracer.close()
        self.flush()
        if self._streaming is not None:
            self._streaming.shutdown()
        if self._fanout_pool is not None:
            self._fanout_pool.shutdown(wait=False)
        if self.wal is not None:
            self.wal.close()
        if self.rt_publisher is not None:
            self.rt_publisher.shutdown()
        if self.search_plugin is not None:
            self.search_plugin.shutdown()

    def drop_caches(self) -> None:
        """(ref: TSDB.dropCaches) UID caches are authoritative here;
        the device-resident grid cache and its host-RAM prepared-batch
        twin are droppable."""
        if self._device_grid_cache is not None:
            self._device_grid_cache.clear()
        if self._host_prep_cache is not None:
            self._host_prep_cache.clear()
        if self._result_cache is not None:
            self._result_cache.clear()
        if self._streaming is not None:
            # continuous-query plans re-seed from the store on their
            # next serve/pump (operator escape hatch)
            self._streaming.invalidate()

    # ------------------------------------------------------------------
    # stats (ref: TSDB.collectStats :753)
    # ------------------------------------------------------------------

    def collect_stats(self, collector) -> None:
        self.uids.metrics.collect_stats(collector)
        self.uids.tag_names.collect_stats(collector)
        self.uids.tag_values.collect_stats(collector)
        self.store.collect_stats(collector)
        lc = self._lifecycle
        cold = getattr(lc, "coldstore", None) if lc is not None \
            else None
        collector.record("storage.cold_bytes",
                         cold.cold_bytes() if cold is not None else 0)
        collector.record("datapoints.added", self.datapoints_added)
        self.histogram_stats.collect_stats(collector,
                                           self._device_grid_cache)
        if self.rollup_store is not None:
            self.rollup_store.stats.collect_stats(
                collector, self._device_grid_cache)
        dev = self.device_info()
        dev_tags = {
            "platform": dev["platform"] or "none",
            "kind": "".join(c if c.isalnum() else "_"
                            for c in dev["device_kind"] or "none"),
            "backend": dev["storage_backend"]}
        collector.record("device.count", dev["count"], **dev_tags)
        collector.record("device.mesh.devices", dev["mesh"]["devices"])
        collector.record("device.warmup.compiled",
                         dev["warmup"]["compiled"])
        collector.record("device.warmup.failed",
                         dev["warmup"]["failed"])
        for hook, n in sorted(self.hook_errors.items()):
            collector.record("hooks.errors", n, hook=hook)
        collector.record("uptime.seconds",
                         int(time.time() - self.start_time))
