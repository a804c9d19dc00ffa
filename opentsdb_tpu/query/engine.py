"""The query engine (ref: ``src/core/TsdbQuery.java:64``).

Compiles one validated :class:`TSQuery` into the array pipeline:

1. resolve metric + filters against the UID tables
   (``configureFromQuery`` :434)
2. vectorized series selection over the metric's tag index
   (replaces scanner row-regex + post-scan filters, ``findSpans`` :795)
3. group-key construction from group-by tagv ids
   (``GroupByAndAggregateCB`` :916-1045)
4. time-grid construction: downsample buckets, or the union of distinct
   timestamps when no downsample is given (the reference's
   AggregationIterator emits at the union of span timestamps)
5. one fused device pipeline per sub-query
   (:mod:`opentsdb_tpu.ops.pipeline`)
6. result assembly with the reference's tags/aggregateTags semantics
   (SpanGroup: tags = identical k=v across all series; aggregateTags =
   keys present everywhere with differing values)
"""

from __future__ import annotations

import logging
import time
from collections import Counter
from dataclasses import replace
from typing import Any

import numpy as np

from opentsdb_tpu.core import store as store_mod
from opentsdb_tpu.core.store import TimeSeriesStore
from opentsdb_tpu.obs import trace as trace_mod
from opentsdb_tpu.obs.trace import trace_begin, trace_end, trace_span
from opentsdb_tpu.ops import downsample as ds_mod
from opentsdb_tpu.ops.blocked import (DEFAULT_CELL_BUDGET,
                                      execute_blocked,
                                      pick_block_buckets)
from opentsdb_tpu.ops.pipeline import (PipelineSpec, execute_avg_divide,
                                       flatten_padded, prepare_auto,
                                       prepare_flat, run_prepared)
from opentsdb_tpu.query import device_cache
from opentsdb_tpu.query import filters as filters_mod
from opentsdb_tpu.query.limits import QueryLimitExceeded
from opentsdb_tpu.query.model import (BadRequestError, TSQuery,
                                      TSSubQuery,
                                      effective_pixels as
                                      model_effective_pixels)
from opentsdb_tpu.query.plan import (PlanIndex, TagMatrix,
                                     _match_series_by_tags,
                                     _UidNameCache, group_labels,
                                     group_tag_summary)
from opentsdb_tpu.stats.stats import QueryStat, QueryStats
from opentsdb_tpu.utils.faults import DegradedError

LOG = logging.getLogger("query.engine")


class QueryResult:
    """One output group — the analogue of one ``DataPoints`` object.

    ``dps`` (the (ts_ms, value) tuple list) is LAZY when the engine
    produced the columnar ``dps_arrays`` twin: a wildcard group-by
    response has thousands of groups and the serializer formats
    straight from the arrays, so eagerly zipping per-group Python
    tuple lists taxed every large query for a list most consumers
    never read. Reading ``.dps`` materializes on first touch;
    size checks should use :attr:`num_dps` (doesn't materialize)."""

    __slots__ = ("metric", "tags", "aggregated_tags", "tsuids",
                 "annotations", "global_annotations",
                 "sub_query_index", "dps_arrays", "_dps", "sketches")

    def __init__(self, metric: str, tags: dict, aggregated_tags: list,
                 dps: list | None = None, tsuids: list | None = None,
                 annotations: list | None = None,
                 global_annotations: list | None = None,
                 sub_query_index: int = 0, dps_arrays: Any = None):
        self.metric = metric
        self.tags = tags
        self.aggregated_tags = aggregated_tags
        self._dps = dps
        self.tsuids = tsuids if tsuids is not None else []
        self.annotations = annotations if annotations is not None \
            else []
        self.global_annotations = global_annotations \
            if global_annotations is not None else []
        self.sub_query_index = sub_query_index
        self.dps_arrays = dps_arrays
        # percentile partials (cluster scatter): [(ts_ms, sketch
        # bytes)] per output bucket, merged exactly by the router
        self.sketches = None

    @property
    def dps(self) -> list:
        if self._dps is None:
            if self.dps_arrays is None:
                self._dps = []
            else:
                ts_arr, vals = self.dps_arrays
                self._dps = list(zip(ts_arr.tolist(), vals.tolist()))
        return self._dps

    @dps.setter
    def dps(self, value: list) -> None:
        self._dps = value

    @property
    def num_dps(self) -> int:
        if self._dps is not None:
            return len(self._dps)
        if self.dps_arrays is None:
            return 0
        return len(self.dps_arrays[0])

    def with_sub_index(self, index: int) -> "QueryResult":
        """A shallow twin carrying a different ``sub_query_index`` —
        the result-cache hit path re-labels shared results when the
        same sub-query content sits at a different position in the
        requesting TSQuery (the cache key excludes the index)."""
        if self.sub_query_index == index:
            return self
        twin = QueryResult(
            self.metric, self.tags, self.aggregated_tags,
            dps=self._dps, tsuids=self.tsuids,
            annotations=self.annotations,
            global_annotations=self.global_annotations,
            sub_query_index=index, dps_arrays=self.dps_arrays)
        twin.sketches = self.sketches
        return twin

    def cache_copy(self) -> "QueryResult":
        """Detached twin for the result cache: shares the immutable
        columnar payload but NOT the lazily-materialized ``_dps``
        tuple list — a consumer touching ``.dps`` (~100 bytes/point)
        fattens only its own request-scoped copy, so a cached entry's
        real footprint stays what ``results_nbytes`` charged against
        the byte budget. ``_dps`` is kept only when it IS the payload
        (no columnar twin)."""
        twin = QueryResult(
            self.metric, self.tags, self.aggregated_tags,
            dps=self._dps if self.dps_arrays is None else None,
            tsuids=self.tsuids, annotations=self.annotations,
            global_annotations=self.global_annotations,
            sub_query_index=self.sub_query_index,
            dps_arrays=self.dps_arrays)
        twin.sketches = self.sketches
        return twin

    def __repr__(self) -> str:  # debugging/test output only
        return (f"QueryResult(metric={self.metric!r}, "
                f"tags={self.tags!r}, "
                f"aggregated_tags={self.aggregated_tags!r}, "
                f"num_dps={self.num_dps})")


class NoSuchMetricError(BadRequestError):
    pass


#: first item of the HBM cache's key for a metric's resident grid
RESIDENT_GRID_KEY = "metricgrid"
#: and for ONE whole bucket of a metric, the column such a grid is
#: assembled from when its window is not resident
RESIDENT_COLUMN_KEY = "metriccol"
#: first item of the key of a rollup average's tier pair
TIER_PAIR_KEY = "avgdiv"
#: the ``grid`` tag of the ``query.grid_build stage=cache_lookup`` span,
#: (a hit's, a build's): of a metric's resident entry built whole, of
#: one assembled from the metric's columns, of a request's own rows
_LOOKUP_RESIDENT = ("resident_hit", "resident_built")
_LOOKUP_COLUMNS = ("resident_hit", "resident_columns")
_LOOKUP_SELECTION = ("selection", "selection")


def _store_id(store) -> int:
    """Monotonic per-process store identity for cache keys. id(store)
    could alias a freed store whose address was reused with a
    coincidentally equal ``device_cache.store_version``."""
    return getattr(store, "instance_id", id(store))


# default padded [S, B] cell count below which the pipeline tail runs
# on the host CPU backend instead of the accelerator
HOST_TAIL_DEFAULT_CELLS = 1 << 20
# and the [S, B] x G work-product cap for RANK-class aggregators
# (median/percentiles): their group stage sorts/broadcasts with a
# G-factor that a single-core host grinds through slowly.
HOST_TAIL_DEFAULT_CELLGROUPS = 1 << 25
# LINEAR aggregators (sum/min/max/avg/dev/count/... — everything the
# pipeline reduces with segment ops) have a cells-only budget: with
# PipelineSpec.host=True the group stage lowers to segment scatter, an
# O(cells) pass with no group factor. The budget is the crossover of
# the placement sweep on a TPU v5e and its host, rounded down to a
# power of two (PERF.md section 6, PR 32: upload + program + download
# of execute_grid, sum and max, 64 x 112 padded): at 1,024 x 64 =
# 65,536 cells the host takes 2.3-2.5 ms against the chip's 2.6-2.7
# (a dispatch, a transfer and a download cost that at any size), at
# 2,048 x 64 2.8-2.9 against 2.6-2.7, at 114,688 x 64 (BASELINE
# config 2's dashboard) 112-113 against 13-15, and 160.5 a sub-query
# inside a server that runs two such tails side by side (ledger PR
# 31). The panels' class (8 series x 60 buckets) takes 0.7 ms on the
# host and 2.7 on the chip.
HOST_TAIL_DEFAULT_CELLS_LINEAR = 1 << 16
# the least share of its metric's series a selection must hold to run
# over the metric's RESIDENT grid (QueryEngine._resident_grid) and not
# over a grid of its own rows: the measured costs behind the half are
# with the ``metricgrid`` kind in query/device_cache.py
RESIDENT_GRID_MIN_SHARE = 0.5
# the most buckets of a window whose resident grid is assembled from
# per-bucket columns: a column is an operand of the program and an
# entry of the cache, and past this a window is built whole
RESIDENT_COLUMNS_MAX_BUCKETS = 128


def _rank_class_agg(agg_name: str) -> bool:
    """:attr:`Aggregator.rank_class` by name, for callers that hold no
    sub-query (the warm-up); an unknown name gets the conservative
    rank budgets."""
    from opentsdb_tpu.ops import aggregators as _aggs
    try:
        return _aggs.get(agg_name).rank_class
    except KeyError:  # unknown agg: be conservative
        return True


def host_tail_device(config, padded_cells: int,
                     padded_groups: int = 1,
                     linear_agg: bool = False):
    """Device override for small-query tails.

    For rank-class aggregators: below ``tsd.query.host_tail_max_cells``
    AND ``cells * groups`` below ``tsd.query.host_tail_max_cellgroups``.
    For linear (segment-reducible) aggregators: at or below
    ``tsd.query.host_tail_max_cells_linear`` — no group factor, the
    host group stage is O(cells) segment scatter (see
    HOST_TAIL_DEFAULT_CELLS_LINEAR, the measured crossover). All dims
    are shape-bucket-PADDED, so the decision is deterministic per
    compiled-shape class and warmup can pre-compile the same programs.

    The reference serves this class straight from the local JVM heap
    (ref: QueryRpc.java:128 -> TsdbQuery compute in-process). Set a key
    to -1 to disable; 0 means the default. Mesh queries never take
    this path (sharded data is already device-resident). Returns a
    committed CPU ``jax.Device`` or None (= use the default device)."""
    if linear_agg:
        limit = config.get_int(
            "tsd.query.host_tail_max_cells_linear", 0) \
            or HOST_TAIL_DEFAULT_CELLS_LINEAR
        if limit < 0 or padded_cells > limit:
            return None
    else:
        limit = config.get_int("tsd.query.host_tail_max_cells", 0) \
            or HOST_TAIL_DEFAULT_CELLS
        glimit = config.get_int(
            "tsd.query.host_tail_max_cellgroups", 0) \
            or HOST_TAIL_DEFAULT_CELLGROUPS
        if limit < 0 or glimit < 0 or padded_cells > limit \
                or padded_cells * max(padded_groups, 1) > glimit:
            return None
    from opentsdb_tpu.ops.pipeline import host_cpu_device
    return host_cpu_device()


def host_tail_for_dims(config, s: int, b: int, num_groups: int,
                       emit_raw: bool = False,
                       agg_name: str = "p99",
                       rank_class: bool | None = None):
    """:func:`host_tail_device` from RAW query dims — the ONE place the
    decision inputs are shape-bucketed, shared by the engine paths and
    tsd.warmup so a warmed placement cannot drift from the engine's.
    emit_raw has no group contraction: group factor 1.
    ``rank_class`` (the sub-query's ``agg.rank_class``) picks the
    linear vs rank-class budget; without it ``agg_name`` does, and the
    default is a rank-class name so legacy callers keep the
    conservative rule."""
    from opentsdb_tpu.ops import shapes as _shapes
    if rank_class is None:
        rank_class = _rank_class_agg(agg_name)
    return host_tail_device(
        config,
        _shapes.shape_bucket(s) * _shapes.shape_bucket(b),
        1 if emit_raw else _shapes.shape_bucket(num_groups + 1),
        linear_agg=not rank_class)


#: downsample functions the storage-side pre-reduction can serve, by
#: the bucket statistic that answers each (avg is sum over count)
GRID_STATS = {"sum": "sum", "zimsum": "sum", "pfsum": "sum",
              "count": "count", "avg": "avg", "min": "min",
              "mimmin": "min", "max": "max", "mimmax": "max"}


def fill_padded_grid(stat: str, sums: np.ndarray, cnts: np.ndarray,
                     mins: np.ndarray | None, maxs: np.ndarray | None,
                     grid: np.ndarray, has_data: np.ndarray) -> None:
    """What the tail program reads, from ``bucket_reduce``'s [S, B]
    f64 grids: statistic ``stat`` (a value of :data:`GRID_STATS`) of
    every bucket written in place into the padded ``grid`` ([s_pad,
    b_pad], the compute dtype) with the presence mask ``has_data``
    beside it; a bucket without a point and both pads hold NaN /
    False. avg divides in f64 and rounds to the grid's dtype once.

    This is the contract in plain NumPy: a store with a fused
    ``bucket_grid`` (the native one) writes the same bits in its
    storage pass, and every other store comes through here."""
    s, b = cnts.shape
    cells, mask = grid[:s, :b], has_data[:s, :b]
    np.greater(cnts, 0.0, out=mask)
    if stat == "avg":
        np.divide(sums, cnts, out=cells, where=mask)
    else:
        src = {"sum": sums, "count": cnts, "min": mins,
               "max": maxs}[stat]
        np.copyto(cells, src, casting="same_kind")
    np.copyto(cells, np.nan, where=~mask)
    grid[s:] = np.nan
    grid[:s, b:] = np.nan
    has_data[s:] = False
    has_data[:s, b:] = False


# Padded-layout guards: padding inflation is bounded by the skew factor
# (pad cells per real point) once batches are big enough to matter, and
# by an absolute S*Pmax cell ceiling (host RAM).
_PADDED_SKEW_FACTOR = 4
_PADDED_MIN_CELLS = 10_000_000
_PADDED_ABS_MAX_CELLS = 500_000_000


class QueryEngine:
    """(ref: TsdbQuery; one instance per TSQuery execution)"""

    def __init__(self, tsdb):
        self.tsdb = tsdb
        self._filter_eval = filters_mod.FilterEvaluator(tsdb.uids)

    # ------------------------------------------------------------------
    # graceful degradation: the device circuit breaker
    # ------------------------------------------------------------------

    def _device_degraded(self) -> bool:
        """True while the device-pipeline breaker is OPEN (inside its
        reset window): tails must not dispatch to the accelerator.
        Read-only (``blocking``) — the half-open probe transition
        belongs to :meth:`_run_device`'s dispatch gate alone."""
        breaker = self.tsdb.device_breaker
        return breaker is not None and breaker.blocking()

    @staticmethod
    def _host_cpu():
        """The committed host CPU device every degraded fallback pins
        to (one definition so the fallback discipline cannot drift
        between the point/grid/avg paths)."""
        from opentsdb_tpu.ops.pipeline import host_cpu_device
        return host_cpu_device()

    def _tail_device(self, s: int, b: int, num_groups: int,
                     emit_raw: bool, rank_class: bool):
        """:func:`host_tail_for_dims` + the degraded override: an OPEN
        breaker pins the tail to the host CPU backend (the
        always-available in-process compute path — the analogue of the
        reference answering straight from the JVM heap)."""
        if self._device_degraded():
            if not self.tsdb.config.get_bool(
                    "tsd.query.degraded.host_fallback", True):
                raise DegradedError(
                    "device pipeline circuit breaker is open and "
                    "host fallback is disabled "
                    "(tsd.query.degraded.host_fallback)")
            return self._host_cpu()
        return host_tail_for_dims(self.tsdb.config, s, b, num_groups,
                                  emit_raw, rank_class=rank_class)

    def _run_device(self, compute, host_retry=None,
                    on_device: bool = True):
        """Run a pipeline tail under the device circuit breaker.

        ``compute`` is the already-placed dispatch; accelerator
        failures count toward ``tsd.query.breaker.*`` and — when a
        ``host_retry`` twin exists — the query is re-answered on the
        host CPU backend instead of surfacing a 500. ``on_device=False``
        (tail already pinned to the host) bypasses the breaker
        entirely: a host success says nothing about accelerator
        health, so it must not close an open breaker.

        Failure classification is deliberately coarse: any exception
        from the dispatch (including prepare/cache code, which can
        fail for data-shaped reasons) counts toward the breaker. A
        repeatable non-device error can therefore trip it spuriously —
        the half-open probe bounds that cost to one reset window, and
        the fallback answer is still correct (same kernels, host
        placement)."""
        if not on_device:
            return compute()
        faults = getattr(self.tsdb, "faults", None)
        breaker = self.tsdb.device_breaker
        if breaker is not None and not breaker.allow():
            # OPEN breaker: never touch the failing device. Paths
            # whose placement happens up front (_tail_device) don't
            # reach here; this guards the mesh/blocked/cache-hit
            # dispatches, which otherwise would hammer the device for
            # the whole reset window.
            if host_retry is not None and self.tsdb.config.get_bool(
                    "tsd.query.degraded.host_fallback", True):
                breaker.fallbacks += 1
                return host_retry()
            raise DegradedError(
                "device pipeline circuit breaker is open and this "
                "query has no host fallback")
        try:
            if faults is not None:
                faults.check("device.compile")
            out = compute()
        except Exception as exc:  # noqa: BLE001
            if breaker is not None:
                breaker.record_failure()
            if host_retry is None or not self.tsdb.config.get_bool(
                    "tsd.query.degraded.host_fallback", True):
                raise
            LOG.warning("device pipeline failed (%s: %s); answering "
                        "on the host CPU backend",
                        type(exc).__name__, exc)
            if breaker is not None:
                breaker.fallbacks += 1
            return host_retry()
        if breaker is not None:
            breaker.record_success()
        return out

    # ------------------------------------------------------------------

    def run(self, ts_query: TSQuery,
            stats: QueryStats | None = None) -> list[QueryResult]:
        subs = ts_query.queries
        if len(subs) > 1 and not ts_query.delete:
            # delete=true stays serial: a sub's delete_range shifts
            # the per-series buffers IN PLACE while a parallel sibling
            # may still hold live views into them (scanned-and-deleted
            # semantics make the order matter too)
            pool = self.tsdb.query_fanout_pool
            if pool is not None:
                return self._run_fanout(ts_query, subs, stats, pool)
        results: list[QueryResult] = []
        for sub in subs:
            results.extend(self._run_sub_cached(ts_query, sub, stats))
        return results

    def _run_fanout(self, tsq: TSQuery, subs, stats,
                    pool) -> list[QueryResult]:
        """Dispatch independent sub-queries in parallel and join.

        Per-sub result ordering is preserved (results concatenate in
        sub order regardless of completion order) and per-sub
        QueryStats attribution is intact: every sub records into the
        shared (now lock-guarded) QueryStats. The first sub runs on
        the calling thread — it already holds a worker slot of the
        server's _query_pool, and idling it while children queue
        would waste exactly one unit of the fan-out budget. On error,
        the earliest failing sub (in sub order) wins after every
        in-flight sibling has been joined — a still-running future
        must not outlive its TSQuery."""
        # fan-out workers run on other threads: re-bind the parent
        # request's trace context so sub-query spans land in the trace
        tctx = trace_mod.current()
        futures = [pool.submit(self._run_sub_traced, tctx, tsq, sub,
                               stats)
                   for sub in subs[1:]]
        results: list[QueryResult] = []
        first_err: BaseException | None = None
        try:
            results.extend(self._run_sub_cached(tsq, subs[0], stats))
        except BaseException as exc:  # noqa: BLE001 - joined below
            first_err = exc
        for fut in futures:
            try:
                out = fut.result()
            except BaseException as exc:  # noqa: BLE001
                if first_err is None:
                    first_err = exc
            else:
                if first_err is None:
                    results.extend(out)
        if first_err is not None:
            raise first_err
        return results

    def _run_sub_traced(self, tctx, tsq: TSQuery, sub: TSSubQuery,
                        stats: QueryStats | None) -> list[QueryResult]:
        """Fan-out entry: bind the parent request's trace context on
        this worker thread, then run the sub normally."""
        with trace_mod.use(tctx):
            return self._run_sub_cached(tsq, sub, stats)

    def _run_sub_timed(self, tsq: TSQuery, sub: TSSubQuery,
                       stats: QueryStats | None) -> list[QueryResult]:
        """One real engine execution under the ``query.execute`` span
        (scan + device pipeline + assembly; cache hits never get
        here) — the span feeds the ``query.execute`` stage histogram
        exported with percentiles at /api/stats."""
        with trace_span("query.execute", sub=sub.index,
                        metric=sub.metric or "<tsuid>"):
            return self._run_sub(tsq, sub, stats)

    def _run_sub_cached(self, tsq: TSQuery, sub: TSSubQuery,
                        stats: QueryStats | None) -> list[QueryResult]:
        """One sub-query through the serve-path result cache: hits
        skip the engine entirely, misses single-flight (concurrent
        identical queries share ONE execution and a failed leader
        poisons nothing)."""
        from opentsdb_tpu.query import result_cache as rc_mod
        # continuous-query live windows come FIRST: a registered
        # standing query answers its dashboard window from maintained
        # partial aggregates — fresher than any cache entry (it
        # reflects every acknowledged write) and immune to the
        # epoch-invalidation that evicts cached live queries under
        # ingest. Streaming failures always fall through to the
        # batch path — the feeder can shed, never 500.
        streaming = self.tsdb._streaming
        if streaming is not None and not tsq.delete:
            try:
                with trace_span("query.streaming_lookup",
                                sub=sub.index):
                    served = streaming.try_serve(tsq, sub, self)
            except (BadRequestError, QueryLimitExceeded):
                raise  # semantic errors the batch path would raise too
            except Exception as exc:  # noqa: BLE001 - shed to batch
                LOG.warning("streaming serve failed (%s: %s); "
                            "answering from the batch engine",
                            type(exc).__name__, exc)
                served = None
            if served is not None:
                if stats:
                    stats.add_stat(QueryStat.STREAMING_HIT, 1)
                return served
        cache = self.tsdb.result_cache
        if cache is None:
            return self._run_sub_timed(tsq, sub, stats)
        plan = rc_mod.cache_plan(tsq, sub, self.tsdb.config)
        if plan is None:
            cache.count_bypass()
            return self._run_sub_timed(tsq, sub, stats)
        key, ttl_ms = plan
        # the version MUST be captured before compute: a write landing
        # mid-execution then leaves the entry already-stale instead of
        # wrongly fresh (see QueryResultCache.get_or_compute)
        version = self._sub_version(sub)
        value, outcome = cache.get_or_compute(
            key, version,
            lambda: self._run_sub_timed(tsq, sub, stats),
            ttl_ms)
        if stats and outcome != rc_mod.MISS:
            stats.add_stat(
                QueryStat.RESULT_CACHE_HIT
                if outcome == rc_mod.HIT
                else QueryStat.RESULT_CACHE_COALESCED, 1)
        if value and value[0].sub_query_index != sub.index:
            value = [r.with_sub_index(sub.index) for r in value]
        return value

    def _sub_version(self, sub: TSSubQuery) -> tuple:
        """Invalidation version over the stores THIS sub-query's plan
        reads — not the whole TSDB — so dashboards answered from a
        rollup tier keep hitting while raw ingest streams in (and vice
        versa). Tier selection is re-derived per lookup, so a write
        that flips the selection (e.g. the first point landing in a
        previously-empty tier) changes the selected store identity and
        misses naturally. Falls back to the conservative whole-TSDB
        :meth:`TSDB.serve_version` when selection itself fails (the
        engine will surface the same error to the caller)."""
        t = self.tsdb
        ann = getattr(t.annotations, "version", 0)
        if sub.percentiles:
            parts = ["hist", t._histogram_version,
                     t.histogram_store.points_written,
                     t.histogram_store.mutation_epoch, ann]
            # the sketch path also reads the raw tail, the sketch
            # tier, and (through it) the cold segments
            lc = t.lifecycle
            if lc is not None and lc.sketches is not None:
                cold = lc.coldstore
                parts += [t.store.points_written,
                          getattr(t.store, "mutation_epoch", 0),
                          lc.sketches.cells_folded,
                          lc.sketches.cells_spilled,
                          cold.mutation_epoch
                          if cold is not None else 0]
            else:
                parts += [t.store.points_written,
                          getattr(t.store, "mutation_epoch", 0)]
            return tuple(parts)
        try:
            (store, _metric, _sids, avg_count_store,
             _ds, _source) = self._select_store(sub)
        except Exception:  # noqa: BLE001 - compute re-raises for real
            return ("all", t.serve_version(), ann)
        parts = ["sel", ann, _store_id(store), store.points_written,
                 getattr(store, "mutation_epoch", 0)]
        if avg_count_store is not None:
            # the avg-over-budget branch in _run_sub may still swap to
            # the RAW store mid-plan; cover both outcomes
            raw = t.store
            parts += [_store_id(avg_count_store),
                      avg_count_store.points_written,
                      getattr(avg_count_store, "mutation_epoch", 0),
                      _store_id(raw), raw.points_written,
                      getattr(raw, "mutation_epoch", 0)]
        return tuple(parts)

    # ------------------------------------------------------------------

    def _run_sub(self, tsq: TSQuery, sub: TSSubQuery,
                 stats: QueryStats | None) -> list[QueryResult]:
        t0 = time.monotonic()
        uids = self.tsdb.uids
        if sub.percentiles:
            from opentsdb_tpu.query.histogram_engine import \
                run_histogram_subquery
            from opentsdb_tpu.sketch.query import (merge_pct_rows,
                                                   run_sketch_percentiles)
            partials = bool(getattr(tsq, "sketch_partials", False))
            sk_rows = run_sketch_percentiles(self.tsdb, tsq, sub,
                                             partials=partials)
            if partials:
                # cluster scatter: the shard hands back mergeable
                # sketch partials, never locally-extracted quantiles.
                # Disabled sketches 400 honestly — an empty partial
                # would make the router's merged answer silently wrong
                if sk_rows is None:
                    raise BadRequestError(
                        "sketch partials requested but the sketch "
                        "subsystem is disabled (tsd.sketch.enable)")
                return sk_rows
            hist_rows = run_histogram_subquery(self, tsq, sub)
            if sk_rows is not None:
                # live arena rows + spilled/demoted sketch history
                # splice by group (disjoint time windows)
                hist_rows = merge_pct_rows(hist_rows, sk_rows)
            # `_pct_<q>` rows are plain emitted rows once assembled, so
            # the pixel budget applies post-assembly like every other
            # producer (the router reduces merged partials itself)
            px, pfn = model_effective_pixels(tsq, sub)
            if px and not tsq.delete:
                from opentsdb_tpu.ops.visual_downsample import reduce_dps
                for row in hist_rows:
                    row.dps = reduce_dps(row.dps, tsq.start_ms,
                                         tsq.end_ms, px, pfn)
            return hist_rows
        # planning stage span: tier selection, filter evaluation,
        # group construction (ended at every exit of the stage — an
        # unfinished handle on an error path simply isn't recorded;
        # the enclosing query.execute span still carries the error)
        _h_plan = trace_begin("query.plan", sub=sub.index)
        (store, metric_name, sids, avg_count_store,
         ds_fn_override, source) = self._select_store(sub)
        if _h_plan is not None:
            _h_plan.tag(**source)
        budget = self.tsdb.config.get_int(
            "tsd.query.max_device_cells", 0) or DEFAULT_CELL_BUDGET
        if avg_count_store is not None:
            # the sum/count grid division materializes [S, B] whole;
            # oversized ranges go to the raw streaming path instead —
            # but only when raw data actually exists (rolled-up data
            # may outlive its raw source), else an expensive exact
            # answer beats a cheap empty one
            b_est = ((tsq.end_ms - tsq.start_ms)
                     // max(sub.ds_spec.interval_ms, 1)) + 2
            if len(sids) * b_est > budget:
                raw_sids = self.tsdb.store.series_ids_for_metric(
                    uids.metrics.get_id(sub.metric))
                if len(raw_sids):
                    avg_count_store = None
                    store = self.tsdb.store
                    sids = raw_sids
        if len(sids) == 0:
            trace_end(_h_plan)
            return []
        if stats:
            stats.add_stat(QueryStat.ROWS_PRE_FILTER, len(sids))

        # --- filters -> series mask (ref: findSpans post-scan filters)
        metric_sids = sids
        sids, tag_mat, plan_tags = self._apply_filters(store, sub, sids)
        if _h_plan is not None:
            _h_plan.tag(**plan_tags)
        if len(sids) == 0:
            trace_end(_h_plan)
            return []
        if tsq.replica_sel is not None and sub.metric:
            # replicated-router scatter: keep only series whose
            # replica set this request was assigned (cluster/replica),
            # so each series is read by exactly one replica
            # cluster-wide and merged partials never double-count
            from opentsdb_tpu.cluster import replica as replica_mod
            keep = np.asarray(replica_mod.series_mask(
                tsq.replica_sel, sub.metric,
                (tag_mat.tags_of(i) for i in range(len(sids))),
                _UidNameCache(uids.tag_names),
                _UidNameCache(uids.tag_values)), dtype=bool)
            if not keep.all():
                sids = sids[keep]
                tag_mat = tag_mat.select(keep)
            if len(sids) == 0:
                trace_end(_h_plan)
                return []
        if stats:
            stats.add_stat(QueryStat.STRING_TO_UID_TIME,
                           (time.monotonic() - t0) * 1e3)
            stats.add_stat(QueryStat.ROWS_POST_FILTER, len(sids))
            stats.add_stat(QueryStat.UID_PAIRS_RESOLVED,
                           tag_mat.num_pairs())

        # --- group construction (ref: GroupByAndAggregateCB :916)
        gb_tagks = sorted({f.tagk for f in sub.filters if f.group_by})
        gb_kids = []
        for k in gb_tagks:
            try:
                gb_kids.append(uids.tag_names.get_id(k))
            except LookupError:
                trace_end(_h_plan)
                return []
        group_ids, num_groups = self._group_ids(tag_mat, gb_kids)
        emit_raw = sub.agg.is_none
        if emit_raw:
            group_ids = np.arange(len(sids), dtype=np.int32)
            num_groups = len(sids)
        if _h_plan is not None:
            _h_plan.tag(series=len(sids), groups=num_groups)
        trace_end(_h_plan)

        # a selection planned from the plan index knows its rows of the
        # metric (filters, explicit_tags and the replica mask
        # composed): what the metric's resident grid, or its resident
        # tier pair, is labelled by
        metric_rows = None
        if tag_mat.origin is not None:
            rows = tag_mat.origin[1]
            metric_rows = (metric_sids,
                           slice(None) if rows is None else rows)

        if avg_count_store is not None:
            out = self._avg_rollup_pipeline(
                store, avg_count_store, sids, tsq, sub, metric_name,
                group_ids, num_groups, emit_raw, stats, metric_rows)
            if out is None:
                return []
            result, emit, bucket_ts = out
            return self._build_results(
                tsq, sub, metric_name, sids, tag_mat, group_ids,
                num_groups, gb_kids, bucket_ts, result, emit)

        # --- pre-bucketized grid fast path: for fixed-interval simple
        # downsample functions the storage engine reduces the window to
        # the [S, B] grid in one native pass, so the device never sees
        # per-point data (SURVEY §7: HBM/transfer bandwidth is the
        # bottleneck; here the "scan" IS the downsample)
        out = self._grid_pipeline(store, sids, tsq, sub, metric_name,
                                  group_ids, num_groups, emit_raw,
                                  budget, stats, ds_fn_override,
                                  metric_rows)
        if out is not None:
            result, emit, bucket_ts = out
            if result is None:
                return []
            return self._build_results(
                tsq, sub, metric_name, sids, tag_mat, group_ids,
                num_groups, gb_kids, bucket_ts, result, emit)

        # --- device-prepared batch cache: a warm repeat of the same
        # (store, series set, window, downsample) skips materialize AND
        # the upload — the data lives in HBM already (the point-path
        # twin of _grid_pipeline's resident grids)
        mesh = self.tsdb.query_mesh
        prep_cache = self.tsdb.device_grid_cache
        pkey = pver = None
        if prep_cache is not None:
            from opentsdb_tpu.parallel.sharded_pipeline import \
                agg_mesh_class
            # the aggregator's memory CLASS is part of the key: the
            # use_blocked verdict depends on it (mesh_scale), and a hit
            # must imply the cold path would have taken the same
            # (non-blocked) branch — an entry cached by a psum-safe
            # aggregator must not serve an all_gather one past its
            # unscaled budget
            acls = agg_mesh_class(sub.agg.name)
            if acls == "pct":
                # histogram eligibility (and so the budget verdict)
                # depends on the group count too
                acls = ("pct", num_groups)
            if mesh is None:
                # single-device: the linear-vs-rank PLACEMENT class is
                # the key dimension — a host-pool entry cached by a
                # linear agg must not serve a rank-class query whose
                # budget would have placed it on the accelerator. The
                # rank-class budget is cells * groups, so the bucketed
                # group count is part of the key: two group-by
                # cardinalities of one series set must not share a
                # placement (as the mesh's ('pct', num_groups) above)
                if not sub.agg.rank_class:
                    acls = "lin"
                else:
                    from opentsdb_tpu.ops import shapes as _shapes
                    acls = ("rank",
                            _shapes.shape_bucket(num_groups + 1))
            pkey = ("prep", _store_id(store), device_cache.array_digest(
                np.ascontiguousarray(sids)), tsq.start_ms, tsq.end_ms,
                sub.downsample or "union",
                getattr(sub.ds_spec, "timezone", None), mesh, acls)
            # read before the store is (the rule resident() enforces
            # for every other kind)
            pver = device_cache.store_version(store)
            # degraded (breaker open): skip the DEVICE pool — a hit
            # would re-dispatch to the failing accelerator; host-pool
            # hits below remain valid
            hit = None if self._device_degraded() \
                else prep_cache.get(pkey, pver)
            if hit is None:
                # host-tail twin: same key space, host-RAM pool
                hcache = self.tsdb.host_prep_cache
                if hcache is not None:
                    hit = hcache.get(pkey, pver)
            if hit is not None:
                try:
                    return self._run_prep_hit(
                        hit, mesh, store, sids, tsq, sub, metric_name,
                        tag_mat, group_ids, num_groups, gb_kids,
                        emit_raw, stats)
                except (BadRequestError, QueryLimitExceeded):
                    raise
                except Exception as exc:  # noqa: BLE001
                    # a warm entry failing on the device must not make
                    # warm queries 500 while cold ones fall back: the
                    # breaker's bookkeeping happened in _run_device;
                    # drop to the cold path and its host fallback
                    LOG.warning("cached device batch failed (%s: %s); "
                                "re-running the query cold",
                                type(exc).__name__, exc)

        # --- materialize + time grid (row-padded layout: the ragged ->
        # dense transposition happens inside materialize, so the device
        # path never needs a scatter; see PaddedBatch). Skewed batches
        # (one dense series among many sparse ones would blow S * Pmax
        # up quadratically) stay on the flat layout.
        scan = self._scan_begin()
        counts = store.count_range(sids, tsq.start_ms, tsq.end_ms)
        total = int(counts.sum())
        pmax = int(counts.max()) if len(counts) else 0
        cells = len(sids) * pmax
        use_padded = total > 0 and \
            cells <= max(_PADDED_SKEW_FACTOR * total,
                         _PADDED_MIN_CELLS) and \
            cells <= _PADDED_ABS_MAX_CELLS
        if use_padded:
            padded = store.materialize_padded(sids, tsq.start_ms,
                                              tsq.end_ms)
            num_points = total
        else:
            padded = None
            batch = store.materialize(sids, tsq.start_ms, tsq.end_ms)
            num_points = batch.num_points
        self._record_scan(stats, scan, num_points, len(sids))
        # byte/dp guardrails (ref: SaltScanner budget enforcement via
        # QueryLimitOverride)
        self.tsdb.query_limits.check(metric_name, num_points)
        if tsq.delete and hasattr(store, "delete_range"):
            # scanned-and-deleted semantics: the response still carries
            # the data just removed (ref: TsdbQuery delete=true turning
            # scans into DeleteRequests after collection)
            store.delete_range(sids, tsq.start_ms, tsq.end_ms)
        if num_points == 0:
            return []
        bucket_idx2d = bucket_idx = None
        grid_complete = False
        # points -> bucket indices on the host (the point path's twin
        # of _grid_pipeline's fill and pad)
        _h_build = trace_begin("query.grid_build", cells=cells,
                               bytes=num_points * 16)
        if sub.ds_spec is not None:
            ds_function = ds_fn_override or sub.ds_spec.function
            fill_policy = sub.ds_spec.fill_policy
            fill_value = sub.ds_spec.fill_value
            if padded is not None:
                bucket_idx2d, bucket_ts = ds_mod.assign_buckets_padded(
                    padded.ts2d, padded.counts, sub.ds_spec,
                    tsq.start_ms, tsq.end_ms)
            else:
                bucket_idx, bucket_ts = ds_mod.assign_buckets(
                    batch.ts_ms, sub.ds_spec, tsq.start_ms, tsq.end_ms)
        else:
            # union-of-timestamps grid: every distinct input timestamp
            # is an output point, like the reference's merge iterator
            ds_function = "sum"  # one point per (series, ts) after dedupe
            fill_policy = ds_mod.FillPolicy.NONE
            fill_value = float("nan")
            if padded is not None:
                pad = store_mod.pad_mask(padded.counts,
                                         padded.ts2d.shape[1])
                # regular-cadence fast path: when every series carries
                # the SAME timestamp row (the monitoring-data common
                # case), the union IS row 0 — one vectorized equality
                # check replaces the 3M-element sort np.unique costs
                # (~160 ms at 100k x 30)
                if not pad.any() and len(padded.ts2d) and \
                        (padded.ts2d == padded.ts2d[0]).all():
                    row0 = padded.ts2d[0]
                    # strictly increasing => no duplicate timestamps,
                    # exactly what np.unique would have produced
                    if (np.diff(row0) > 0).all():
                        bucket_ts = row0.copy()
                        bucket_idx2d = np.broadcast_to(
                            np.arange(len(row0), dtype=np.int32),
                            padded.ts2d.shape).copy()
                        # every cell verified present: the pipeline
                        # may skip interpolation/emission no-ops
                        # (PipelineSpec.complete). Pure DATA property
                        # here; the per-QUERY carve-out (drop_resets
                        # punches per-series holes) applies at spec
                        # build so cached entries stay query-agnostic.
                        grid_complete = not np.isnan(
                            padded.values2d).any()
                    else:
                        bucket_ts = None
                else:
                    bucket_ts = None
                if bucket_ts is None:
                    bucket_ts, inverse = np.unique(
                        padded.ts2d.reshape(-1), return_inverse=True)
                    bucket_idx2d = inverse.reshape(padded.ts2d.shape) \
                        .astype(np.int32)
                    bucket_idx2d[pad] = -1
                if pad.any():
                    # drop union slots only pad sentinels produced
                    used = np.zeros(len(bucket_ts), dtype=bool)
                    used[bucket_idx2d[~pad]] = True
                    remap = np.cumsum(used) - 1
                    bucket_ts = bucket_ts[used]
                    bucket_idx2d = np.where(
                        bucket_idx2d >= 0, remap[bucket_idx2d], -1
                    ).astype(np.int32)
            else:
                bucket_ts, bucket_idx = np.unique(batch.ts_ms,
                                                  return_inverse=True)
                bucket_idx = bucket_idx.astype(np.int32)
        trace_end(_h_build)

        # --- device pipeline
        t2 = time.monotonic()
        # the mesh raises the streaming threshold only when every
        # device truly holds S_loc x B_loc cells (see mesh_scale use
        # below); the blocked verdict must precede the host-tail
        # placement so an over-budget range never lands on the host
        from opentsdb_tpu.parallel.sharded_pipeline import \
            mesh_memory_safe
        n_mesh = int(np.prod(list(mesh.shape.values()))) \
            if mesh is not None else 1
        mesh_scale = n_mesh if mesh_memory_safe(
            sub.agg.name, num_groups, len(bucket_ts)) else 1
        use_blocked = not emit_raw and \
            len(sids) * len(bucket_ts) > budget * mesh_scale
        # host-tail placement for the point/union path, by the same
        # budgets as _grid_pipeline's. B for union queries is the
        # distinct-timestamp count — data-dependent, so unlike the
        # grid path this placement class is not warmup-predictable;
        # the persistent compile cache absorbs the one-off compiles.
        host_dev = None
        if mesh is None and not use_blocked:
            host_dev = self._tail_device(
                len(sids), len(bucket_ts), num_groups, emit_raw,
                sub.agg.rank_class)
        spec = PipelineSpec(
            num_series=len(sids), num_buckets=len(bucket_ts),
            num_groups=num_groups, ds_function=ds_function,
            agg_name=sub.agg.name, fill_policy=fill_policy,
            fill_value=fill_value, rate=sub.rate,
            rate_counter=sub.rate_options.counter,
            rate_drop_resets=sub.rate_options.drop_resets,
            emit_raw=emit_raw, host=host_dev is not None,
            complete=grid_complete
            and not (sub.rate and sub.rate_options.drop_resets))
        if padded is not None and (use_blocked or mesh is not None):
            with trace_span("query.grid_build", cells=cells):
                values, series_idx, bucket_idx = flatten_padded(
                    padded.values2d, bucket_idx2d, padded.counts)
        elif use_blocked or mesh is not None:
            values, series_idx = batch.values, batch.series_idx
        # the one way a point batch reaches a single device: detect
        # the layout (regular -> dense, else the padded einsum, else
        # the flat scatter: prepare_auto -> prepare_flat) and upload
        def prepare(device=None):
            if padded is not None:
                return prepare_auto(padded, bucket_idx2d, spec,
                                    device=device)
            return prepare_flat(batch.values, batch.series_idx,
                                bucket_idx, spec, device=device)

        # the host-retry twin for the single-device path below: on a
        # device-pipeline failure (or an armed device fault) the same
        # tail re-runs pinned to the host CPU backend — a degraded
        # answer instead of a 500. Mesh and blocked executions have no
        # in-process twin; their failures count toward the breaker and
        # propagate.
        def host_retry():
            return run_prepared(prepare(self._host_cpu()), bucket_ts,
                                group_ids, replace(spec, host=True),
                                sub.rate_options)

        # what a kept prepared batch says of itself (_run_prep_hit)
        pmeta = {"num_points": num_points, "bucket_ts": bucket_ts,
                 "ds_function": ds_function, "fill_policy": fill_policy,
                 "fill_value": fill_value}
        if use_blocked:
            # long-range streaming: bound memory at [S x block] cells
            # (SURVEY.md §5.7 time-axis blocking)
            if mesh is not None:
                # the carry-chained block scan runs AS a shard_map
                # program: each block keeps the mesh fan-out and the
                # per-DEVICE budget is O(S_loc x block) — the analogue
                # of the 20 SaltScanners streaming concurrently
                # (SaltScanner.java:463-536)
                from opentsdb_tpu.parallel.sharded_pipeline import \
                    execute_blocked_sharded
                result, emit = self._run_device(
                    lambda: execute_blocked_sharded(
                        mesh, values, series_idx, bucket_idx,
                        bucket_ts, group_ids, spec, sub.rate_options,
                        block_buckets=pick_block_buckets(
                            len(sids), len(bucket_ts),
                            budget * mesh_scale)))
            else:
                result, emit = self._run_device(
                    lambda: execute_blocked(
                        values, series_idx, bucket_idx, bucket_ts,
                        group_ids, spec, sub.rate_options,
                        block_buckets=pick_block_buckets(
                            len(sids), len(bucket_ts), budget)))
        elif mesh is not None:
            # multi-chip: shard the point batch over the
            # ('series','time') mesh — the salt-scanner fan-out/merge
            # as XLA collectives (SaltScanner.java:70, SURVEY §2.11).
            # The sharded device arrays are cached (minus the per-query
            # group ids) so a warm repeat skips materialize AND upload.
            from opentsdb_tpu.ops.pipeline import pipeline_dtype
            from opentsdb_tpu.parallel.sharded_pipeline import (
                prepare_sharded_batch, run_sharded_device,
                sharded_device_args)
            def mesh_compute():
                sbatch = prepare_sharded_batch(
                    values, series_idx, bucket_idx, bucket_ts,
                    group_ids, spec.num_series, spec.num_groups,
                    mesh.shape["series"], mesh.shape["time"])
                margs = sharded_device_args(mesh, sbatch,
                                            pipeline_dtype())
                if prep_cache is not None and pkey is not None:
                    prep_cache.put(pkey, pver, margs[:4], {
                        **pmeta, "s_loc": sbatch.s_loc,
                        "b_loc": sbatch.b_loc,
                        "s_pad": sbatch.s_loc * mesh.shape["series"]})
                return run_sharded_device(
                    mesh, spec, margs, sbatch.s_loc, sbatch.b_loc,
                    num_groups, sub.rate_options)

            result, emit = self._run_device(mesh_compute)
        else:
            # single device: upload once, keep the batch where a cache
            # can hold it, execute. A host-placed tail goes to the
            # host-RAM pool (NOT the device cache — host entries must
            # never evict HBM-resident grids) so warm repeats skip
            # materialize + union-grid construction; with
            # tsd.query.device_cache_mb=0 nothing stays resident and
            # the same program runs
            on_host = host_dev is not None
            pool = self.tsdb.host_prep_cache if on_host else prep_cache

            def compute():
                prep = prepare(host_dev)
                if pool is not None and pkey is not None:
                    pool.put(pkey, pver, (prep,), {
                        **pmeta, "host": on_host,
                        "complete": grid_complete})
                return run_prepared(prep, bucket_ts, group_ids, spec,
                                    sub.rate_options)

            result, emit = self._run_device(compute, host_retry,
                                            on_device=not on_host)
        if stats:
            stats.add_stat(QueryStat.COMPUTE_TIME,
                           (time.monotonic() - t2) * 1e3)

        # --- assemble output groups
        return self._build_results(
            tsq, sub, metric_name, sids, tag_mat, group_ids,
            num_groups, gb_kids, bucket_ts, result, emit)

    # ------------------------------------------------------------------

    def _run_prep_hit(self, hit, mesh, store, sids, tsq, sub,
                      metric_name, tag_mat, group_ids, num_groups,
                      gb_kids, emit_raw, stats) -> list[QueryResult]:
        """Serve one sub-query from a warm prepared-batch cache entry
        (device pool or its host-RAM twin). Raising is allowed: the
        caller falls back to the cold path on device failure."""
        cached_args, pmeta = hit
        bucket_ts = pmeta["bucket_ts"]
        num_points = pmeta["num_points"]
        self.tsdb.query_limits.check(metric_name, num_points)
        t2 = time.monotonic()
        spec = PipelineSpec(
            num_series=len(sids), num_buckets=len(bucket_ts),
            num_groups=num_groups, ds_function=pmeta["ds_function"],
            agg_name=sub.agg.name, fill_policy=pmeta["fill_policy"],
            fill_value=pmeta["fill_value"], rate=sub.rate,
            rate_counter=sub.rate_options.counter,
            rate_drop_resets=sub.rate_options.drop_resets,
            emit_raw=emit_raw,
            host=pmeta.get("host", False),
            complete=pmeta.get("complete", False)
            and not (sub.rate and sub.rate_options.drop_resets))
        if mesh is not None:
            # HBM-resident pre-sharded batch: only the tiny per-query
            # group-id vector uploads
            from opentsdb_tpu.parallel.sharded_pipeline import (
                run_sharded_device, sharded_grid_gids)
            gids_dev = sharded_grid_gids(
                mesh, group_ids, pmeta["s_pad"], num_groups)
            result, emit = self._run_device(
                lambda: run_sharded_device(
                    mesh, spec, cached_args + (gids_dev,),
                    pmeta["s_loc"], pmeta["b_loc"], num_groups,
                    sub.rate_options))
        else:
            (prep,) = cached_args
            result, emit = self._run_device(
                lambda: run_prepared(prep, bucket_ts, group_ids,
                                     spec, sub.rate_options),
                on_device=not spec.host)
        # stats and delete only after the dispatch succeeded: a device
        # failure falls back to the COLD path, which must still find
        # the data (scanned-and-deleted semantics) and must not see
        # DPS_POST_FILTER double-counted
        if stats:
            stats.add_stat(QueryStat.DPS_POST_FILTER, num_points)
            stats.add_stat(QueryStat.COMPUTE_TIME,
                           (time.monotonic() - t2) * 1e3)
        if tsq.delete and hasattr(store, "delete_range"):
            store.delete_range(sids, tsq.start_ms, tsq.end_ms)
        return self._build_results(
            tsq, sub, metric_name, sids, tag_mat, group_ids,
            num_groups, gb_kids, bucket_ts, result, emit)

    def _select_store(self, sub: TSSubQuery):
        """Pick raw store or a rollup tier (ref: TsdbQuery rollup
        best-match :143-150 with ROLLUP_USAGE fallback :750).
        Returns (store, metric_name, sids, avg_count_store,
        ds_fn_override, source); ``source`` is the ``query.plan``
        span's tags for the choice: ``source`` = ``raw`` | ``tier`` |
        ``fallback`` (a tier was the match, held nothing of the
        metric, and ``rollupUsage`` sent the request on to raw) and,
        beside the last two, ``tier`` = its interval.

        ``avg_count_store`` is the COUNT-tier store when an ``avg``
        downsample is being answered from rollups: the reference
        derives rollup averages as SUM cells / COUNT cells
        (RollupConfig, RollupSpan agg-prefixed qualifiers); here the
        sum tier is the primary store and the count tier rides along
        for the grid division (``_avg_rollup_grid``).

        ``ds_fn_override`` replaces the downsample function when the
        tier's cells already carry the statistic: a ``count``
        downsample over the COUNT tier must SUM the stored counts,
        not count cells (ref: Downsampler.java:213 — the rollup-query
        COUNT branch accumulates nextValueCount()).
        """
        uids = self.tsdb.uids
        if sub.tsuids:
            return (*self._tsuid_store(sub), {"source": "raw"})
        try:
            metric_id = uids.metrics.get_id(sub.metric)
        except LookupError:
            raise NoSuchMetricError(
                f"No such name for 'metrics': '{sub.metric}'") from None
        store = self.tsdb.store
        avg_count_store = None
        ds_fn_override = None
        usage = (sub.rollup_usage or "ROLLUP_NOFALLBACK").upper()
        # a metric whose FIRST lifecycle demotion is in flight has
        # partial tier cells but no boundary yet: raw still holds
        # every point, so it is the only fully-correct source until
        # the boundary publishes and stitching takes over
        lc = self.tsdb.lifecycle
        lc_pin_raw = lc is not None and \
            lc.first_demotion_in_flight(metric_id)
        if (self.tsdb.rollup_store is not None and sub.ds_spec is not None
                and not sub.ds_spec.run_all and usage != "ROLLUP_RAW"
                and not lc_pin_raw):
            tier = self.tsdb.rollup_config.best_match(
                sub.ds_spec.interval_ms)
            agg_fn = sub.ds_spec.function
            rs = self.tsdb.rollup_store
            # cold segments ARE tier data: a tier whose RAM store was
            # fully spilled (and emptied) must still win selection, or
            # the on-disk history becomes unreachable. Lazy — the
            # common has_data()=True case never pays the name resolve
            # + segment-list scan (short-circuiting `or`).
            def has_cold():
                return (tier is not None and lc is not None
                        and lc.has_cold(metric_id, tier.interval))
            if tier is not None and agg_fn in ("sum", "count", "min",
                                               "max"):
                if rs.has_data(tier.interval, agg_fn) or has_cold():
                    store = self._maybe_stitch(
                        rs.tier(tier.interval, agg_fn), metric_id,
                        tier.interval, agg_fn)
                    if agg_fn == "count":
                        ds_fn_override = "sum"
            elif tier is not None and agg_fn == "avg" \
                    and (rs.has_data(tier.interval, "sum")
                         or has_cold()) \
                    and (rs.has_data(tier.interval, "count")
                         or has_cold()):
                store = self._maybe_stitch(
                    rs.tier(tier.interval, "sum"), metric_id,
                    tier.interval, "sum")
                avg_count_store = self._maybe_stitch(
                    rs.tier(tier.interval, "count"), metric_id,
                    tier.interval, "count")
        sids = store.series_ids_for_metric(metric_id)
        source = {"source": "raw"} if store is self.tsdb.store \
            else {"source": "tier", "tier": tier.interval}
        if store is not self.tsdb.store and len(sids) == 0 and \
                usage in ("ROLLUP_FALLBACK", "ROLLUP_FALLBACK_RAW"):
            store = self.tsdb.store
            sids = store.series_ids_for_metric(metric_id)
            avg_count_store = None
            ds_fn_override = None
            source["source"] = "fallback"
        return (store, sub.metric, sids, avg_count_store,
                ds_fn_override, source)

    def _maybe_stitch(self, tier_store, metric_id: int, interval: str,
                      agg: str):
        """Replace a selected tier store with the lifecycle manager's
        stitched view (tier history before the demotion boundary +
        raw tail after it) when the metric has a boundary; a metric
        that was never demoted keeps plain tier serving."""
        lc = self.tsdb.lifecycle
        if lc is None:
            return tier_store
        return lc.stitched(metric_id, interval, agg, tier_store) \
            or tier_store

    @staticmethod
    def _scan_begin():
        """Open one sub-query's storage read: the ``query.scan`` span
        where the request is traced (the span IS the timer), else a
        clock reading. Closed by :meth:`_record_scan`."""
        return trace_begin("query.scan") or time.monotonic()

    @staticmethod
    def _record_scan(stats, scan, num_points: int,
                     n_rows: int) -> None:
        """Close the storage read ``scan`` (:meth:`_scan_begin`) and
        record its stat points (ref: the per-scanner stats block,
        QueryStats.java:137-151 — 'storage' here is the host column
        store, a column ≙ a stored point, a row ≙ a series)."""
        if isinstance(scan, float):
            ms = (time.monotonic() - scan) * 1e3
        else:
            scan.tag(points=num_points, series=n_rows)
            ms = scan.finish()
        if not stats:
            return
        stats.add_stat(QueryStat.MATERIALIZE_TIME, ms)
        stats.add_stat(QueryStat.QUERY_SCAN_TIME, ms)
        stats.add_stat(QueryStat.HBASE_TIME, ms)
        stats.add_stat(QueryStat.DPS_POST_FILTER, num_points)
        stats.add_stat(QueryStat.COLUMNS_FROM_STORAGE, num_points)
        stats.add_stat(QueryStat.ROWS_FROM_STORAGE, n_rows)
        # 17 bytes per stored point: int64 ts + float64 value + flag
        stats.add_stat(QueryStat.BYTES_FROM_STORAGE, num_points * 17)
        stats.add_stat(QueryStat.SUCCESSFUL_SCAN, 1)

    @staticmethod
    def _fixed_interval(spec) -> bool:
        """Buckets of one fixed width: what lays out as a grid."""
        return (not spec.run_all and not spec.use_calendar
                and spec.unit not in ("n", "y") and spec.interval_ms > 0)

    def _grid_eligible(self, sub: TSSubQuery) -> bool:
        spec = sub.ds_spec
        return (spec is not None and self._fixed_interval(spec)
                and spec.function in GRID_STATS
                and self.tsdb.config.get_bool("tsd.query.grid_reduce",
                                              True))

    def _reduce_to_grid(self, store, sids: np.ndarray, tsq: TSQuery,
                        bucket_ts: np.ndarray, interval_ms: int,
                        stat: str, scanned):
        """One sub-query's storage pass -> ``(grid, has_data,
        num_points)``: the tail program's operands made ONCE, padded
        to the geometric shape buckets (cached device grids are
        pre-padded, warm queries never pay a per-query device pad,
        and on BOTH the single-device and mesh paths compiled
        programs are keyed on bucketed shapes, so warmup's
        pre-compiles and repeat queries of a class actually hit) and
        in the compute dtype, so the upload's cast and pads find
        nothing to do.

        A store with a fused ``bucket_grid`` (the native one) writes
        them in its storage pass; any other reduces to f64 grids that
        :func:`fill_padded_grid` finishes: two paths that share the
        contract and no logic. ``scanned(scan, num_points)`` closes
        the storage read (:meth:`_record_scan`, with the rows the
        caller answers for)."""
        from opentsdb_tpu.ops import shapes
        from opentsdb_tpu.ops.pipeline import pipeline_dtype
        b = len(bucket_ts)
        padded = (shapes.shape_bucket(len(sids)), shapes.shape_bucket(b))
        dtype = np.dtype(pipeline_dtype())
        fused = getattr(store, "bucket_grid", None)
        cells = padded[0] * padded[1]
        tags = {"fused": fused is not None, "cells": cells,
                "bytes": cells * (dtype.itemsize + 1)}

        def alloc():
            return np.empty(padded, dtype), np.empty(padded, np.bool_)

        window = (sids, tsq.start_ms, tsq.end_ms, int(bucket_ts[0]),
                  interval_ms, b)
        if fused is not None:
            with trace_span("query.grid_build", stage="alloc", **tags):
                grid, has_data = alloc()
            scan = self._scan_begin()
            num_points = fused(*window, stat, grid, has_data)
            scanned(scan, num_points)
            return grid, has_data, num_points
        scan = self._scan_begin()
        reduced = store.bucket_reduce(
            *window, want_minmax=stat in ("min", "max"))
        num_points = int(reduced[1].sum())
        scanned(scan, num_points)
        with trace_span("query.grid_build", stage="fill_pad", **tags):
            grid, has_data = alloc()
            fill_padded_grid(stat, *reduced, grid, has_data)
        return grid, has_data, num_points

    def _resident_operands(self, cache, kind: str, lookup: tuple,
                           key_of, stores, build, stats,
                           metric_name: str, n_rows: int, delete=None,
                           points_of=None, covers=None):
        """What a grid-shaped kind of the HBM cache does around its
        build, once: the operands through :func:`device_cache.resident`
        under ``(kind, *key_of())``, versioned by ``stores`` (``cache``
        None: built, nothing kept), the scan's stat points, the limits'
        check, ``delete`` (the response still carries what it
        removes), the empty window. ``(arrays, meta)``, or None where
        the ``n_rows`` rows hold no point.

        ``build(checked) -> (arrays | None, meta)`` reads ``stores``
        and closes its own scan (:meth:`_record_scan`); a hit records
        a scan of no length with the same stat points,
        ``meta["num_points"]`` or ``points_of(meta)``. ``checked`` is
        the limits' check and ``delete``: a build of what only this
        request reads calls it between its scan and its upload, so
        that a refused request puts nothing up and keeps nothing;
        else, and on a hit, it follows here. The ``cache_lookup``
        span, tagged ``grid`` = ``lookup[0]`` on a hit and
        ``lookup[1]`` on a build, is the key, the look-up and the wait
        for another's build of it, no more; its tag ``stale`` says
        what became of an entry found under an older version of the
        store (``kept``, ``dropped``; ``none``: no such entry).

        ``covers``: the span of time ``(lo_ms, hi_ms)`` the entry
        covers of ``stores``' ONE store; a write that landed after it
        leaves the entry resident (the version rule of
        :mod:`~opentsdb_tpu.query.device_cache`). Absent: any write
        to ``stores`` drops it."""
        span = trace_begin("query.grid_build", stage="cache_lookup") \
            if cache is not None else None
        pending = True
        found = device_cache.Lookup(
            stores[0] if covers is not None else None)

        def end_lookup(built: bool):
            if span is not None:
                span.tag(grid=lookup[built], stale=found.stale)
                span.finish()

        def checked(num_points: int):
            nonlocal pending
            pending = False
            # byte/dp guardrails (ref: SaltScanner's QueryLimitOverride)
            self.tsdb.query_limits.check(metric_name, num_points)
            if delete is not None:
                delete()

        def build_after_lookup():
            end_lookup(True)
            return build(checked)

        arrays, meta, how = device_cache.resident(
            cache, (kind, *key_of()) if cache is not None else None,
            lambda: device_cache.store_version(*stores),
            build_after_lookup, covers, found)
        if how == device_cache.HIT:
            end_lookup(False)  # before the points are counted
        num_points = points_of(meta) if points_of else meta["num_points"]
        if how == device_cache.HIT:
            self._record_scan(stats, self._scan_begin(), num_points,
                              n_rows)
        if pending:
            checked(num_points)
        return (arrays, meta) if num_points else None

    @staticmethod
    def _resident_grid_fits(cache, n: int, num_selected: int, b: int,
                            budget: int | None, grids: int = 1) -> bool:
        """Whether ``num_selected`` of a metric's ``n`` series over
        ``b`` buckets run over the metric's resident entry: by what
        the request shows, no key (:data:`RESIDENT_GRID_MIN_SHARE`,
        the metric's grid within the cell ``budget`` and the cache).
        The entry is a grid and its mask, or, for a rollup average,
        ``grids`` = 2 grids with NaN holes; ``budget`` None where the
        caller lays its own rows out whole whatever their number."""
        from opentsdb_tpu.ops import shapes
        from opentsdb_tpu.ops.pipeline import pipeline_dtype
        cells = shapes.shape_bucket(n) * shapes.shape_bucket(b)
        cell_bytes = np.dtype(pipeline_dtype()).itemsize
        entry = cells * (cell_bytes + 1 if grids == 1
                         else grids * cell_bytes)
        return num_selected >= RESIDENT_GRID_MIN_SHARE * n \
            and (budget is None or n * b <= budget) \
            and entry <= cache.max_bytes

    def _resident_grid(self, cache, store, metric_sids: np.ndarray,
                       rows, num_selected: int, tsq: TSQuery,
                       bucket_ts: np.ndarray, interval_ms: int,
                       fn: str, metric_name: str, stats):
        """:meth:`_resident_operands` of the whole metric's padded
        ``[series x bucket]`` grid of this (window, downsample)
        (``metricgrid`` in :mod:`~opentsdb_tpu.query.device_cache`)
        for ``rows``, the request's ``num_selected`` rows of
        ``metric_sids``. The entry keeps each row's point count of the
        window, so the limits' check and the scan's stat points are
        the selection's, as on the path it replaces.

        A window that is not resident is built from the metric's
        per-bucket columns (:meth:`_metric_columns`) where the store
        has the pass for them, else whole: two builds that share the
        contract and no logic. The first gives the entry as
        :class:`~opentsdb_tpu.ops.pipeline.GridColumns`, ``meta``
        naming its key under ``columns_of``: the program that runs
        over it assembles the grid, which then takes its place
        (:meth:`_grid_pipeline`)."""
        from opentsdb_tpu.ops.pipeline import GridColumns, put_grid
        metric_id = store.series(int(metric_sids[0])).metric_id

        def key_of():
            return (_store_id(store), metric_id,
                    len(metric_sids), tsq.start_ms, tsq.end_ms,
                    int(bucket_ts[0]), interval_ms, len(bucket_ts), fn)

        def points_of(meta) -> int:
            return int(meta["counts"][rows].sum())

        def scanned(meta):
            # the pass reads the metric; the request answers for its
            # own rows of it, as a hit will
            return lambda scan, _: self._record_scan(
                stats, scan, points_of(meta), num_selected)

        def build_whole(_checked):
            meta = {"counts": store.count_range(
                metric_sids, tsq.start_ms, tsq.end_ms)}
            grid, has_data, _ = self._reduce_to_grid(
                store, metric_sids, tsq, bucket_ts, interval_ms,
                GRID_STATS[fn], scanned(meta))
            # a window without a point: nothing to keep. Else kept
            # before THIS selection is checked: others read the entry
            return (put_grid(grid, has_data)
                    if meta["counts"].any() else None), meta

        def build_columns(_checked):
            meta = {"columns_of": (RESIDENT_GRID_KEY, *key_of())}
            columns = self._metric_columns(
                cache, store, metric_sids, metric_id, tsq, bucket_ts,
                interval_ms, fn, meta, scanned(meta))
            return ((GridColumns(columns),)
                    if meta["counts"].any() else None), meta

        by_column = hasattr(store, "bucket_columns") \
            and len(bucket_ts) <= RESIDENT_COLUMNS_MAX_BUCKETS
        return self._resident_operands(
            cache, RESIDENT_GRID_KEY,
            _LOOKUP_COLUMNS if by_column else _LOOKUP_RESIDENT, key_of,
            (store,), build_columns if by_column else build_whole,
            stats, metric_name, num_selected, points_of=points_of,
            covers=(tsq.start_ms, tsq.end_ms))

    def _metric_columns(self, cache, store, metric_sids: np.ndarray,
                        metric_id: int, tsq: TSQuery,
                        bucket_ts: np.ndarray, interval_ms: int,
                        fn: str, meta: dict, scanned) -> tuple:
        """The window's buckets of the whole metric, a ``(values,
        mask)`` pair of padded ``[series]`` device vectors each, and
        ``meta["counts"]``, each row's point count of the window.

        A bucket that lies whole inside the window does not depend on
        the window: its column stays in HBM (``metriccol`` in
        :mod:`~opentsdb_tpu.query.device_cache`; buckets are aligned
        to the epoch, so the next window's are the same cells), under
        the version read here, before anything is looked up or
        scanned. The buckets the window cuts (its first, its last)
        are this request's own and are not kept. ONE storage pass
        (``store.bucket_columns``) writes what is wanted, the cut
        buckets and the whole ones that were not there, and counts
        every row's points of the window on its way;
        ``scanned(scan, num_points)`` closes it. The
        ``query.grid_build stage=columns`` span is the look-ups and
        the wait for another request's build, tagged with the columns
        that ``hit``, were ``built`` and were ``cut``, and ``stale``:
        whether a column found under an older version was ``kept``
        (nothing written since lies in its bucket or before it) or
        ``dropped`` (any was), ``none`` where the store stood still."""
        from opentsdb_tpu.ops import shapes
        from opentsdb_tpu.ops.pipeline import pipeline_dtype, put_columns
        version = device_cache.store_version(store)
        b = len(bucket_ts)
        s_pad = shapes.shape_bucket(len(metric_sids))
        dtype = np.dtype(pipeline_dtype())
        starts = [int(t) for t in bucket_ts]
        whole = [k for k, t in enumerate(starts) if t >= tsq.start_ms
                 and t + interval_ms - 1 <= tsq.end_ms]
        cut = sorted(set(range(b)).difference(whole))
        group = (RESIDENT_COLUMN_KEY, _store_id(store), metric_id,
                 len(metric_sids), interval_ms, fn)
        span = trace_begin("query.grid_build", stage="columns")
        found = device_cache.Lookup(store)

        def build(missing):
            wanted = sorted(cut + [whole[i] for i in missing])
            if span is not None:
                span.tag(hit=len(whole) - len(missing),
                         built=len(missing), cut=len(cut),
                         stale=found.stale)
                span.finish()
            cells = len(wanted) * s_pad
            with trace_span("query.grid_build", stage="alloc",
                            fused=True, cells=cells,
                            bytes=cells * (dtype.itemsize + 1)):
                cols = np.empty((len(wanted), s_pad), dtype)
                masks = np.empty((len(wanted), s_pad), np.bool_)
            scan = self._scan_begin()
            meta["counts"] = store.bucket_columns(
                metric_sids, tsq.start_ms, tsq.end_ms, starts[0],
                interval_ms, b, GRID_STATS[fn], wanted, cols, masks)
            scanned(scan, None)
            up = dict(zip(wanted, put_columns(cols, masks)))
            return [up[whole[i]] for i in missing], \
                {k: up[k] for k in cut}

        kept, own = cache.resident_columns(
            group, [(*group, starts[k]) for k in whole], version, build,
            [(starts[k], starts[k] + interval_ms - 1) for k in whole],
            found)
        columns = {**dict(zip(whole, kept)), **own}
        return tuple(columns[k] for k in range(b))

    def _grid_pipeline(self, store, sids: np.ndarray, tsq: TSQuery,
                       sub: TSSubQuery, metric_name: str,
                       group_ids: np.ndarray, num_groups: int,
                       emit_raw: bool, budget: int, stats,
                       ds_fn_override: str | None = None,
                       metric_rows: tuple | None = None):
        """Storage-side downsample: one fused native pass produces the
        [S, B] grid (ref analogue: the scan + Downsampler stages of
        TsdbQuery.java:795 + Downsampler.java:28 collapsed into the
        storage engine), then the device runs only the
        fill/rate/interpolate/aggregate tail. Returns None when
        ineligible (caller falls through to the point paths), or
        (result, emit, bucket_ts) with result=None for no data.

        ``metric_rows``: ``(the metric's whole sids, the selection's
        rows of them)`` where the plan index planned the selection;
        such a request may run over the metric's resident grid
        (:meth:`_resident_grid`) instead of scanning its own rows."""
        if not self._grid_eligible(sub):
            return None
        from opentsdb_tpu.ops import shapes
        ds_spec = sub.ds_spec
        bucket_ts = ds_mod.fixed_bucket_edges(
            tsq.start_ms, tsq.end_ms, ds_spec.interval_ms)
        b = len(bucket_ts)
        mesh = self.tsdb.query_mesh
        if len(sids) * b > budget:
            return None  # blocked streaming handles the oversized case
        fn = ds_fn_override or ds_spec.function
        # small grids run the tail on the host CPU backend; decision is
        # per padded-shape class, matching warmup's pre-compiles
        host_dev = None
        if mesh is None:
            host_dev = self._tail_device(len(sids), b, num_groups,
                                         emit_raw, sub.agg.rank_class)
        # a warm repeat skips the host scan AND the upload (HBM ≙
        # HBase block cache). Host-tail queries skip the cache: their
        # native re-scan costs milliseconds, and host-RAM entries must
        # not evict HBM-resident grids (nor count as device bytes)
        cache = self.tsdb.device_grid_cache if host_dev is None \
            else None
        # what the tail program runs over: the selection's rows and
        # group ids, or every row of the metric, labelled
        tail_rows, tail_gids = len(sids), group_ids
        # under a mesh, the host grid this request scanned itself (no
        # hit): what the single-device host tail re-answers from
        fresh = None
        if cache is not None and mesh is None and not emit_raw \
                and not tsq.delete and metric_rows is not None \
                and self._resident_grid_fits(
                    cache, len(metric_rows[0]), len(sids), b, budget):
            operands = self._resident_grid(
                cache, store, *metric_rows, len(sids), tsq, bucket_ts,
                ds_spec.interval_ms, fn, metric_name, stats)
            if operands is not None:
                # all that this request puts up: a label a resident
                # row, the rows its filter dropped on the dummy group
                # (the one shapes.pad_group_ids gives padded rows)
                with trace_span("query.upload", stage="labels"):
                    tail_rows = len(metric_rows[0])
                    tail_gids = np.full(tail_rows, num_groups,
                                        np.int32)
                    tail_gids[metric_rows[1]] = group_ids
        else:
            def key_of():
                return (_store_id(store), device_cache.array_digest(
                    np.ascontiguousarray(sids)), tsq.start_ms,
                    tsq.end_ms, int(bucket_ts[0]), ds_spec.interval_ms,
                    b, fn, mesh)

            def build(checked):
                nonlocal fresh
                grid, has_data, num_points = self._reduce_to_grid(
                    store, sids, tsq, bucket_ts, ds_spec.interval_ms,
                    GRID_STATS[fn],
                    lambda scan, points: self._record_scan(
                        stats, scan, points, len(sids)))
                meta = {"num_points": num_points}
                checked(num_points)
                if not num_points:
                    return None, meta
                if mesh is not None:
                    from opentsdb_tpu.parallel.sharded_pipeline import \
                        prepare_sharded_grid
                    fresh = grid, has_data
                    # padded like execute_grid pads (bucket_grid_shapes)
                    data_args, meta["s_loc"], meta["b_loc"], \
                        meta["s_pad"] = prepare_sharded_grid(
                            mesh, grid, has_data, shapes.pad_bucket_ts(
                                np.asarray(bucket_ts),
                                shapes.shape_bucket(b)))
                    return data_args, meta
                if cache is not None:
                    from opentsdb_tpu.ops.pipeline import put_grid
                    return put_grid(grid, has_data), meta
                return (grid, has_data), meta

            operands = self._resident_operands(
                cache, "grid", _LOOKUP_SELECTION, key_of, (store,),
                build, stats, metric_name, len(sids),
                delete=(lambda: store.delete_range(
                    sids, tsq.start_ms, tsq.end_ms))
                if tsq.delete and hasattr(store, "delete_range")
                else None)
        if operands is None:
            return (None, None, bucket_ts)
        arrays, meta = operands
        t2 = time.monotonic()
        spec = PipelineSpec(
            num_series=tail_rows, num_buckets=b, num_groups=num_groups,
            # the grid TAIL never reads ds_function (downsampling
            # already happened storage-side) but it IS part of the jit
            # static key — normalize it so sum/avg/min/... grid queries
            # share one compiled program per shape bucket (and the
            # server warmup covers them all)
            ds_function="avg", agg_name=sub.agg.name,
            fill_policy=ds_spec.fill_policy,
            fill_value=ds_spec.fill_value, rate=sub.rate,
            rate_counter=sub.rate_options.counter,
            rate_drop_resets=sub.rate_options.drop_resets,
            emit_raw=emit_raw, host=host_dev is not None)
        if mesh is not None:
            # the grid-TAIL step runs straight on the mesh (no
            # flatten-to-points re-bucketize), and the pre-sharded
            # device grids are cached — mesh queries get the same
            # warm-repeat behavior as single-device ones. Shapes are
            # geometrically bucketed exactly like execute_grid does
            # (bucket_grid_shapes), so the compiled shard_map program
            # set is bounded and tsd.tpu.warmup's mesh pre-compiles
            # are the programs real queries hit.
            from opentsdb_tpu.ops.pipeline import (_bucket_dims_and_aux,
                                                   execute_grid)
            from opentsdb_tpu.parallel.sharded_pipeline import (
                run_sharded_grid, sharded_grid_gids)
            _, _, _, gids_bk, pspec = _bucket_dims_and_aux(
                bucket_ts, group_ids, spec,
                shapes.shape_bucket(len(sids)), shapes.shape_bucket(b))
            gids_dev = sharded_grid_gids(mesh, gids_bk, meta["s_pad"],
                                         pspec.num_groups)
            host_retry = None
            if fresh is not None:
                def host_retry():
                    return execute_grid(
                        *fresh, bucket_ts, group_ids,
                        replace(spec, host=True), sub.rate_options,
                        device=self._host_cpu())
            result, emit = self._run_device(
                lambda: run_sharded_grid(
                    mesh, pspec, arrays + (gids_dev,), meta["s_loc"],
                    meta["b_loc"], num_groups, sub.rate_options),
                host_retry)
            rows = len(sids) if emit_raw else num_groups
            result = result[:rows, :len(bucket_ts)]
            emit = emit[:rows, :len(bucket_ts)]
        elif "columns_of" in meta:
            # the metric's buckets a column each (_resident_grid): the
            # program puts the grid together, and the grid takes the
            # columns' place under the window's key, where the same
            # window asked again finds it as any resident grid
            from opentsdb_tpu.ops.pipeline import execute_columns
            (operand,) = arrays

            def over_columns(spec, device):
                result, emit, grid, has_data = execute_columns(
                    operand.columns, bucket_ts, tail_gids, spec,
                    sub.rate_options, device=device)
                if device is None:
                    cache.replace(meta["columns_of"], arrays,
                                  (grid, has_data),
                                  {"counts": meta["counts"]})
                return result, emit

            result, emit = self._run_device(
                lambda: over_columns(spec, None),
                lambda: over_columns(replace(spec, host=True),
                                     self._host_cpu()))
        else:
            from opentsdb_tpu.ops.pipeline import execute_grid
            grid, has_data = arrays

            def host_retry():
                return execute_grid(grid, has_data, bucket_ts,
                                    tail_gids,
                                    replace(spec, host=True),
                                    sub.rate_options,
                                    device=self._host_cpu())

            result, emit = self._run_device(
                lambda: execute_grid(grid, has_data, bucket_ts,
                                     tail_gids, spec,
                                     sub.rate_options,
                                     device=host_dev),
                host_retry, on_device=host_dev is None)
        if stats:
            stats.add_stat(QueryStat.COMPUTE_TIME,
                           (time.monotonic() - t2) * 1e3)
        return result, emit, bucket_ts

    def _tier_pair(self, sum_store, cnt_store, sids: np.ndarray,
                   csids: np.ndarray, tsq: TSQuery,
                   bucket_ts: np.ndarray, interval_ms: int, scanned):
        """A rollup average's operands, ``(SUM grid, COUNT grid, cells
        read)``: the cells of ``sids`` in the SUM tier and of their
        COUNT-tier series ``csids`` (-1: the tier has none), each
        tier's bucket sums from :meth:`_reduce_to_grid`'s one pass,
        padded and in the compute dtype, NaN where a bucket holds no
        cell (the presence masks say the same and are dropped).
        ``scanned(scan, cells, of_counts)`` closes each tier's pass."""
        def reduce(store, of):
            grid, _, num_points = self._reduce_to_grid(
                store, of, tsq, bucket_ts, interval_ms, "sum",
                lambda scan, n: scanned(scan, n, store is cnt_store))
            return grid, num_points

        gs, n_s = reduce(sum_store, sids)
        present = np.flatnonzero(csids >= 0)
        if len(present) == len(sids):
            gc, n_c = reduce(cnt_store, csids)
        else:
            # a SUM series the COUNT tier lacks: its row holds nothing
            gc, n_c = np.full_like(gs, np.nan), 0
            if len(present):
                rows, n_c = reduce(cnt_store, csids[present])
                gc[present] = rows[:len(present)]
        return gs, gc, n_s + n_c

    def _avg_rollup_pipeline(self, sum_store, cnt_store,
                             sids: np.ndarray, tsq: TSQuery,
                             sub: TSSubQuery, metric_name: str,
                             group_ids: np.ndarray, num_groups: int,
                             emit_raw: bool, stats,
                             metric_rows: tuple | None = None):
        """Answer an ``avg`` downsample from rollup tiers: bucketized
        SUM cells divided by bucketized COUNT cells — the true weighted
        average, not a mean of per-tier-point averages (ref: RollupSpan
        reading agg-prefixed sum+count qualifiers from one row).
        Returns (result, emit, bucket_ts) or None for no data.

        ``metric_rows`` as :meth:`_grid_pipeline` takes it: a
        device-placed tail over at least half of its metric reads the
        METRIC's tier pair, resident once a window, and sends its
        selection up as labels (the raw path's rule,
        :meth:`_resident_grid_fits`); anything else keeps a pair of
        its own rows."""
        metric_id = sum_store.series(int(sids[0])).metric_id

        def align(of: np.ndarray) -> np.ndarray:
            # count series aligned to sum series by (metric, tags)
            # identity: once a build, never on a hit
            return _match_series_by_tags(sum_store, cnt_store, of,
                                         metric_id)

        def delete():
            csids = align(sids)
            sum_store.delete_range(sids, tsq.start_ms, tsq.end_ms)
            cnt_store.delete_range(csids[csids >= 0], tsq.start_ms,
                                   tsq.end_ms)

        ds_spec = sub.ds_spec
        mesh = self.tsdb.query_mesh
        host_dev = cache = None
        tail_rows, tail_gids = len(sids), group_ids
        rollup_stats = self.tsdb.rollup_store.stats
        if self._fixed_interval(ds_spec):
            # both tiers collapse to padded [S, B] sums in one fused
            # storage pass each — no per-point upload
            from opentsdb_tpu.ops.pipeline import put_pair
            bucket_ts = ds_mod.fixed_bucket_edges(
                tsq.start_ms, tsq.end_ms, ds_spec.interval_ms)
            s, b = len(sids), len(bucket_ts)
            window = (tsq.start_ms, tsq.end_ms, int(bucket_ts[0]),
                      ds_spec.interval_ms, b)
            if mesh is None:
                host_dev = self._tail_device(s, b, num_groups,
                                             emit_raw,
                                             sub.agg.rank_class)
                # host-tail queries skip the device cache (see
                # _grid_pipeline: cheap native re-scan; host RAM must
                # not evict HBM-resident grids)
                if host_dev is None:
                    cache = self.tsdb.device_grid_cache
            whole = cache is not None and not emit_raw \
                and not tsq.delete and metric_rows is not None \
                and self._resident_grid_fits(
                    cache, len(metric_rows[0]), s, b, None, grids=2)
            pair_sids = metric_rows[0] if whole else sids

            def build(checked):
                csids = align(pair_sids)
                meta = {}
                if whole:
                    # each row's cells of the window, a tier: the
                    # limits' check and the stat points are the
                    # selection's, as on a hit
                    cells = np.zeros((2, len(pair_sids)), np.int64)
                    cells[0] = sum_store.count_range(
                        pair_sids, tsq.start_ms, tsq.end_ms)
                    have = csids >= 0
                    cells[1, have] = cnt_store.count_range(
                        csids[have], tsq.start_ms, tsq.end_ms)
                    meta["counts"] = cells.sum(axis=0)
                    mine = cells[:, metric_rows[1]].sum(axis=1)
                gs, gc, num_points = self._tier_pair(
                    sum_store, cnt_store, pair_sids, csids, tsq,
                    bucket_ts, ds_spec.interval_ms,
                    lambda scan, n, of_counts: self._record_scan(
                        stats, scan,
                        int(mine[int(of_counts)]) if whole else n, s))
                if not whole:
                    # a pair of this request's own rows: refused
                    # before it goes up (the metric's is of use to the
                    # selections the limits let through: kept first)
                    meta["num_points"] = num_points
                    checked(num_points)
                if not num_points:
                    return None, meta
                if mesh is None:
                    rollup_stats.add(upload_bytes=gs.nbytes + gc.nbytes)
                    gs, gc = put_pair(gs, gc, device=host_dev)
                return (gs, gc), meta

            if whole:
                rows = metric_rows[1]
                operands = self._resident_operands(
                    cache, TIER_PAIR_KEY, _LOOKUP_RESIDENT,
                    lambda: (_store_id(sum_store), _store_id(cnt_store),
                             metric_id, len(pair_sids), *window),
                    (sum_store, cnt_store), build, stats, metric_name,
                    s, points_of=lambda meta: int(
                        meta["counts"][rows].sum()))
                if operands is not None:
                    # all that this request puts up: a label a
                    # resident row, the rows its filter dropped on the
                    # dummy group (_grid_pipeline's labels)
                    with trace_span("query.upload", stage="labels"):
                        tail_rows = len(pair_sids)
                        tail_gids = np.full(tail_rows, num_groups,
                                            np.int32)
                        tail_gids[rows] = group_ids
            else:
                operands = self._resident_operands(
                    cache, TIER_PAIR_KEY, _LOOKUP_SELECTION,
                    lambda: (_store_id(sum_store), _store_id(cnt_store),
                             device_cache.array_digest(
                                 np.ascontiguousarray(sids)), *window),
                    (sum_store, cnt_store), build, stats, metric_name,
                    s, delete=delete if tsq.delete else None)
            if operands is None:
                return None
            (gs, gc), _ = operands
            rollup_stats.add(upload_bytes=tail_gids.nbytes)
            t2 = time.monotonic()
        else:
            scan = self._scan_begin()
            csids = align(sids)
            present = np.flatnonzero(csids >= 0)
            batch_s = sum_store.materialize(sids, tsq.start_ms,
                                            tsq.end_ms)
            batch_c = cnt_store.materialize(csids[present],
                                            tsq.start_ms, tsq.end_ms)
            num_points = batch_s.num_points + batch_c.num_points
            self._record_scan(stats, scan, num_points, len(sids))
            self.tsdb.query_limits.check(metric_name, num_points)
            if tsq.delete:
                delete()
            if batch_s.num_points == 0:
                return None
            t2 = time.monotonic()
            with trace_span("query.grid_build"):
                bidx_s, bucket_ts = ds_mod.assign_buckets(
                    batch_s.ts_ms, ds_spec, tsq.start_ms, tsq.end_ms)
                bidx_c, _ = ds_mod.assign_buckets(
                    batch_c.ts_ms, ds_spec, tsq.start_ms, tsq.end_ms)
                s, b = len(sids), len(bucket_ts)
                # both grids stay on device: bucketize returns device
                # arrays and the division happens in the same trace
                gs, _ = ds_mod.bucketize(
                    batch_s.values, batch_s.series_idx, bidx_s, s, b,
                    "sum")
                gc, _ = ds_mod.bucketize(
                    batch_c.values,
                    present[batch_c.series_idx].astype(np.int32),
                    bidx_c, s, b, "sum")
        spec = PipelineSpec(
            num_series=tail_rows, num_buckets=b, num_groups=num_groups,
            ds_function="avg", agg_name=sub.agg.name,
            fill_policy=sub.ds_spec.fill_policy,
            fill_value=sub.ds_spec.fill_value, rate=sub.rate,
            rate_counter=sub.rate_options.counter,
            rate_drop_resets=sub.rate_options.drop_resets,
            emit_raw=emit_raw, host=host_dev is not None)
        if mesh is not None:
            # divide host-side, then run the rate/fill/agg tail over
            # the mesh with one point per present grid cell (bucketize
            # of a single-point cell reproduces the cell exactly)
            from opentsdb_tpu.ops.pipeline import avg_divide_grid
            with trace_span("query.grid_build", cells=s * b):
                avg, valid = avg_divide_grid(np.asarray(gs),
                                             np.asarray(gc), xp=np)
                valid = np.asarray(valid)
                sidx2, bidx2 = np.nonzero(valid)
            result, emit = self._run_device(
                lambda: self._mesh_execute(
                    mesh, spec, avg[valid], sidx2.astype(np.int32),
                    bidx2.astype(np.int32), bucket_ts, group_ids,
                    sub.rate_options))
        else:
            def host_retry():
                return execute_avg_divide(
                    gs, gc, bucket_ts, tail_gids,
                    replace(spec, host=True), sub.rate_options,
                    device=self._host_cpu())

            result, emit = self._run_device(
                lambda: execute_avg_divide(
                    gs, gc, bucket_ts, tail_gids, spec,
                    sub.rate_options, device=host_dev),
                host_retry, on_device=host_dev is None)
        if stats:
            stats.add_stat(QueryStat.COMPUTE_TIME,
                           (time.monotonic() - t2) * 1e3)
        return result, emit, bucket_ts

    def _mesh_execute(self, mesh, spec, values, series_idx, bucket_idx,
                      bucket_ts, group_ids, rate_options):
        """Run one sub-query's compute over the configured device mesh
        (series axis ≙ salt buckets, time axis ≙ long-range blocking;
        ref: SaltScanner.java:70, TsdbQuery.java:795)."""
        from opentsdb_tpu.parallel.sharded_pipeline import (
            prepare_sharded_batch, run_sharded)
        batch = prepare_sharded_batch(
            values, series_idx, bucket_idx, bucket_ts, group_ids,
            spec.num_series, spec.num_groups, mesh.shape["series"],
            mesh.shape["time"])
        return run_sharded(mesh, spec, batch, rate_options)

    def _tsuid_store(self, sub: TSSubQuery):
        """Resolve explicit TSUID hex strings to series ids
        (ref: TsdbQuery tsuid query path)."""
        uids = self.tsdb.uids
        store = self.tsdb.store
        mw = uids.metrics.width
        kw = uids.tag_names.width
        vw = uids.tag_values.width
        sids = []
        metric_name = None
        for tsuid in sub.tsuids:
            raw = bytes.fromhex(tsuid)
            metric_id = int.from_bytes(raw[:mw], "big")
            tags = []
            pos = mw
            while pos < len(raw):
                kid = int.from_bytes(raw[pos:pos + kw], "big")
                vid = int.from_bytes(raw[pos + kw:pos + kw + vw], "big")
                tags.append((kid, vid))
                pos += kw + vw
            name = uids.metrics.get_name(metric_id)
            if metric_name is None:
                metric_name = name
            elif name != metric_name:
                raise BadRequestError(
                    "Multiple metrics in the same tsuid query")
            key = (metric_id, tuple(sorted(tags)))
            sid = store._key_to_sid.get(key)
            if sid is not None:
                sids.append(sid)
        return (store, metric_name or "", np.asarray(
            sids, dtype=np.int64), None, None)

    # ------------------------------------------------------------------

    def _apply_filters(self, store: TimeSeriesStore, sub: TSSubQuery,
                       sids: np.ndarray
                       ) -> tuple[np.ndarray, TagMatrix, dict]:
        """The sub-query's series and their tags, and the ``query.plan``
        span's tags for it. ``index``: what the plan index did,
        ``hit`` (planned from the cached :class:`PlanIndex`), ``built``
        (built it first) or ``bypass`` (``sids`` is not the metric's
        whole index: tsuids, a write between the selection and here).
        ``names_read`` and ``resolve_<way>``: the names of stored tag
        values its filters read and how many of them went each way
        (:meth:`FilterEvaluator.apply`'s tally)."""
        metric_id = store.series(int(sids[0])).metric_id
        idx = store.metric_index(metric_id)
        index = None
        state = "bypass"
        if idx is not None and not sub.tsuids:
            idx_sids, triples = idx.arrays()
            if sids is idx_sids:
                # per-(store, metric) plan index: the tag index is
                # append-only, so the series count versions it. Built
                # aside and published by one assignment: two cold
                # sub-queries may both build, either entry is right
                tm_cache = self.tsdb._tagmat_cache
                tm_key = (_store_id(store), metric_id)
                index = tm_cache.get(tm_key)
                state = "hit"
                if index is None or index.version != len(idx_sids):
                    index = tm_cache[tm_key] = PlanIndex(
                        len(idx_sids),
                        TagMatrix.from_triples(sids, triples))
                    state = "built"
                tags = index.select(None)
            else:
                tags = TagMatrix.from_triples(sids, triples)
        else:
            # tsuid queries name few series; a record walk is fine here
            rows = []
            for s in sids:
                rec = store.series(int(s))
                for kid, vid in rec.tags:
                    rows.append((rec.series_id, kid, vid))
            triples = (np.asarray(rows, dtype=np.int64).reshape(-1, 3)
                       if rows else np.empty((0, 3), dtype=np.int64))
            tags = TagMatrix.from_triples(sids, triples)
        plan_tags = Counter(names_read=0)
        if sub.filters:
            source = index if index is not None else tags
            rows = np.flatnonzero(
                self._filter_eval.apply(sub.filters, source, plan_tags))
            sids = sids[rows]
            tags = source.select(rows)
        if sub.explicit_tags and sub.filters:
            # keep series whose tag-KEY set equals the filters' key set
            # (ref: explicit_tags pruning in findSpans)
            filter_keys = set()
            for f in sub.filters:
                try:
                    filter_keys.add(
                        self.tsdb.uids.tag_names.get_id(f.tagk))
                except LookupError:
                    pass
            fk = np.asarray(sorted(filter_keys), dtype=np.int64)
            if len(np.setdiff1d(fk, tags.kids)):
                # a required key no series carries: nothing matches
                keep = np.zeros(len(sids), dtype=bool)
            else:
                in_filter = np.isin(tags.kids, fk)
                keep = ((tags.vids >= 0) == in_filter[None, :]) \
                    .all(axis=1)
            sids = sids[keep]
            tags = tags.select(keep)
        return sids, tags, {"index": state, **plan_tags}

    @staticmethod
    def _group_ids(tags: TagMatrix, gb_kids: list[int]
                   ) -> tuple[np.ndarray, int]:
        """Group id per series + group count. Group key = tuple of
        group-by tagv ids; ids come out ordered by concatenated tagv id,
        matching the reference's ByteMap ordering of group keys
        (ref: GroupByAndAggregateCB, TsdbQuery.java:995-1036).

        Rows selected out of a :class:`PlanIndex` gather the metric's
        cached labels; dropping labels from an ordered labelling keeps
        it ordered, so only the groups the selection emptied are
        closed up. Any other matrix is labelled from its columns."""
        if not gb_kids:
            return np.zeros(tags.num_series, dtype=np.int32), 1
        if tags.origin is None:
            return group_labels(tags, gb_kids)
        index, rows = tags.origin
        labels, count = index.labels(gb_kids)
        if rows is None:
            return labels, count
        labels = labels[rows]
        present = np.bincount(labels, minlength=count) > 0
        if present.all():
            return labels, count
        renumber = np.cumsum(present, dtype=np.int32) - 1
        return renumber[labels], int(renumber[-1]) + 1

    # ------------------------------------------------------------------

    def _build_results(self, tsq, sub, metric_name, sids, tags,
                       group_ids, num_groups, gb_kids, bucket_ts,
                       result, emit) -> list[QueryResult]:
        from opentsdb_tpu.query.model import effective_pixels as _epx
        with trace_span("query.assemble", sub=sub.index,
                        groups=num_groups,
                        pixels=_epx(tsq, sub)[0]) as span:
            return self._build_results_inner(
                tsq, sub, metric_name, sids, tags, group_ids,
                num_groups, gb_kids, bucket_ts, result, emit, span)

    def _build_results_inner(self, tsq, sub, metric_name, sids, tags,
                             group_ids, num_groups, gb_kids,
                             bucket_ts, result, emit, span
                             ) -> list[QueryResult]:
        uids = self.tsdb.uids
        out: list[QueryResult] = []
        # one device->host fetch; per-group row indexing of a device
        # array would round-trip per group
        result = np.asarray(result)
        emit = np.asarray(emit, dtype=bool)
        # pixel-aware output reduction (ops/visual_downsample): the
        # FINAL serve-path stage, after downsample/fill/rate/
        # interpolate/aggregate — a keep-mask intersection, so every
        # emitted point below is a real computed point. Applies to
        # every producer funneling through here (grid / point / avg /
        # prep-hit / streaming plan.serve), keyed off the REQUESTING
        # sub-query, so a pixel-less standing plan still serves a
        # pixel-budgeted pull correctly.
        from opentsdb_tpu.query.model import effective_pixels
        px, px_fn = effective_pixels(tsq, sub)
        if px and not tsq.delete:
            from opentsdb_tpu.ops import visual_downsample as vd
            keep = vd.keep_mask(result, emit, np.asarray(bucket_ts),
                                tsq.start_ms, tsq.end_ms, px, px_fn)
            if keep is not None:
                emit = emit & keep
        fetch_annotations = not tsq.no_annotations and \
            self.tsdb.annotations.has_any()
        # output timestamps precomputed once for every group
        bucket_ts = np.asarray(bucket_ts, dtype=np.int64)
        ts_out = (bucket_ts if tsq.ms_resolution
                  else (bucket_ts // 1000) * 1000)
        # SpanGroup tag semantics for ALL groups from two segment
        # reductions: a key with min vid >= 0 is present on every
        # member; min == max means one distinct value. With agg=none
        # every series is its own group, whatever the index labels
        way, minv, maxv, members_of, source = group_tag_summary(
            tags, group_ids, num_groups,
            None if sub.agg.is_none else gb_kids)
        if span is not None:
            span.tag(tags=way)
        gid_range = np.arange(num_groups, dtype=group_ids.dtype)
        kname = _UidNameCache(uids.tag_names)
        vname = _UidNameCache(uids.tag_values)
        k_cnt = len(tags.kids)
        metric_id = None
        if tsq.show_tsuids or sub.tsuids or fetch_annotations:
            try:
                metric_id = uids.metrics.get_id(metric_name)
            except LookupError:
                metric_id = None
        # emit extraction for ALL groups in one nonzero pass: under
        # wildcard group-by (1000+ groups) the per-group
        # nonzero/slice/asarray loop was the second-largest host cost
        # of the whole query after serialization
        e_gidx, e_bidx = np.nonzero(emit)
        e_starts = np.searchsorted(e_gidx, gid_range, side="left")
        e_ends = np.searchsorted(e_gidx, gid_range, side="right")
        e_ts = ts_out[e_bidx]
        e_vals = np.asarray(result[e_gidx, e_bidx], dtype=np.float64)
        for gid in range(num_groups):
            lo_e, hi_e = e_starts[gid], e_ends[gid]
            if lo_e == hi_e:
                continue
            dps_arrays = (e_ts[lo_e:hi_e], e_vals[lo_e:hi_e])
            g_tags: dict[str, str] = {}
            agg_tags: list[str] = []
            for j in range(k_cnt):
                lo = minv[gid, j]
                if lo < 0:
                    continue  # key absent on some member: vanishes
                if lo == maxv[gid, j]:
                    g_tags[kname(int(tags.kids[j]))] = vname(int(lo))
                else:
                    agg_tags.append(kname(int(tags.kids[j])))
            member_tsuids = [
                uids.tsuid(metric_id, source.tags_of(m)).hex().upper()
                for m in members_of(gid)] if metric_id is not None else []
            tsuids = member_tsuids \
                if tsq.show_tsuids or sub.tsuids else []
            annotations = []
            if fetch_annotations:
                start_s = tsq.start_ms // 1000
                end_s = tsq.end_ms // 1000
                for tsuid_hex in member_tsuids:
                    annotations.extend(
                        self.tsdb.annotations.range(tsuid_hex,
                                                    start_s, end_s))
            global_annotations = []
            if tsq.global_annotations:
                global_annotations = self.tsdb.annotations.global_range(
                    tsq.start_ms // 1000, tsq.end_ms // 1000)
            out.append(QueryResult(
                metric=metric_name, tags=g_tags,
                aggregated_tags=agg_tags,
                tsuids=tsuids, annotations=annotations,
                global_annotations=global_annotations,
                sub_query_index=sub.index, dps_arrays=dps_arrays))
        return out
