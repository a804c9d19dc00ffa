"""Rollup tier storage.

One :class:`TimeSeriesStore` per (tier, aggregator), mirroring the
reference's per-tier HBase tables with agg-prefixed qualifiers
(ref: ``src/rollup/RollupUtils.java:120-178``). Written either by the
external-job API (``TSDB.add_aggregate_point``, ref TSDB.java:1320) or
by the in-framework rollup job (:mod:`opentsdb_tpu.rollup.job`) — which
the reference lacks (SURVEY.md §2.3: "rollups are written by external
jobs"); the TPU build ships one as a jitted segmented reduction.
"""

from __future__ import annotations

import threading
from typing import Any, NamedTuple, Sequence

from opentsdb_tpu.core.store import PointBatch, TimeSeriesStore
from opentsdb_tpu.rollup.config import RollupConfig


class AggregateRun(NamedTuple):
    """One series' run of rollup cells as
    :meth:`TSDB.add_aggregate_batch` takes it (a plain tuple of the
    first six will do). ``interval`` None is a pure pre-aggregate;
    ``refs[i]`` is handed back to ``on_error`` for a failing cell
    (absent: its index in the run)."""
    interval: str | None
    aggregator: str | None
    metric: str
    tags: dict[str, str]
    timestamps: Sequence[int]
    values: Sequence[Any]
    groupby_agg: str | None = None
    is_groupby: bool = False
    refs: Sequence[Any] | None = None


class RollupStats:
    """Counters of the rollup write and query paths, exported at
    ``/api/stats`` by :meth:`TSDB.collect_stats`."""

    def __init__(self):
        self._lock = threading.Lock()
        self.batch_points = 0    # cells landed a run at a time
        self.slow_points = 0     # cells landed a point at a time
        self.upload_bytes = 0    # bytes an avg request put on the device

    def add(self, **grown: int) -> None:
        """Grow counters by name; writers and query workers call side
        by side, and ``+=`` alone would lose an update."""
        with self._lock:
            for name, n in grown.items():
                setattr(self, name, getattr(self, name) + n)

    def collect_stats(self, collector, cache) -> None:
        collector.record("rollup.batch_points", self.batch_points)
        collector.record("rollup.slow_points", self.slow_points)
        collector.record("query.rollup.upload_bytes", self.upload_bytes)
        collector.record(
            "query.rollup.resident_bytes",
            cache.bytes_of(RESIDENT_KEY) if cache is not None else 0)


#: first element of a tier pair's key in the HBM cache
RESIDENT_KEY = "avgdiv"


class RollupStore:
    def __init__(self, config: RollupConfig, store_factory=None,
                 fault_injector=None):
        self.config = config
        self.stats = RollupStats()
        # tier stores come from the same backend factory as the raw
        # store (native C++ by default) — the rollup job's bulk grid
        # writes were 15x slower through the portable Python store
        self._factory = store_factory or TimeSeriesStore
        # scans of tier/preagg stores carry their own fault site
        # ("rollup.store") so a degraded rollup tier is distinguishable
        # from a degraded raw store; lazily-created tiers are wired the
        # moment they exist (ROADMAP open item)
        self.fault_injector = fault_injector
        # guards _tiers shape: writers create tiers lazily while query
        # threads snapshot the dict for the serve version
        self._tiers_lock = threading.Lock()
        # (interval, agg) -> store
        # tsdlint: allow[unbounded-growth] keyed by configured rollup
        # tier (interval, agg) pairs — a handful, fixed by config
        self._tiers: dict[tuple[str, str], TimeSeriesStore] = {}
        self._preagg = self._new_store()
        # (interval, agg) -> (mutation_epoch, points_written, result)
        # tsdlint: allow[unbounded-growth] same (interval, agg)
        # keyspace as _tiers — bounded by configured tiers
        self._has_data_cache: dict[tuple[str, str], tuple] = {}

    def _new_store(self) -> TimeSeriesStore:
        store = self._factory()
        store.fault_injector = self.fault_injector
        store.fault_site = "rollup.store"
        return store

    def tier(self, interval: str, agg: str) -> TimeSeriesStore:
        agg = agg.lower()
        if agg not in self.config.agg_ids:
            raise ValueError(
                f"unsupported rollup aggregator {agg!r} "
                f"(supported: {sorted(self.config.agg_ids)})")
        self.config.get_interval(interval)  # validate tier exists
        key = (interval, agg)
        store = self._tiers.get(key)
        if store is None:
            with self._tiers_lock:
                store = self._tiers.get(key)
                if store is None:
                    store = self._tiers[key] = self._new_store()
        return store

    def version(self) -> tuple:
        """Write/delete version over every tier + the preagg store,
        including the tier COUNT (a tier springing into existence can
        flip tier selection for queries that previously read raw).
        Consumed by the serve-path result cache via
        :meth:`TSDB.serve_version`."""
        with self._tiers_lock:
            tiers = list(self._tiers.items())
        parts: list = [len(tiers), self._preagg.points_written,
                       getattr(self._preagg, "mutation_epoch", 0)]
        for key, store in sorted(tiers):
            parts.append((key, store.points_written,
                          getattr(store, "mutation_epoch", 0)))
        return tuple(parts)

    def append_run(self, interval: str | None, agg: str | None,
                   metric_id: int, tag_ids: Sequence[tuple[int, int]],
                   ts_ms, values) -> tuple[str, int]:
        """The one write entry: one series' run of cells into the
        (interval, agg) tier, or into the pre-aggregate store where
        ``interval`` is None, by one ``append_many``. Returns (the
        WAL's name of the store, the series id)."""
        if interval is None:
            kind, store = "preagg", self._preagg
        else:
            if agg is None:
                raise ValueError("missing rollup aggregator")
            store = self.tier(interval, agg)
            kind = f"tier:{interval}:{agg.lower()}"
        sid = store.get_or_create_series(metric_id, tag_ids)
        store.append_many(sid, ts_ms, values, False)
        self.stats.add(**{"batch_points" if len(ts_ms) > 1
                          else "slow_points": len(ts_ms)})
        return kind, sid

    def preagg_store(self) -> TimeSeriesStore:
        return self._preagg

    def has_data(self, interval: str, agg: str) -> bool:
        """O(1) in steady state: points_written is a cheap counter on
        both backends while total_points() walks every series (seconds
        at 1M series) and this check runs on EVERY query's tier
        selection. Writes only ever add data, so a True verdict stays
        valid until a destructive op bumps mutation_epoch — only then
        does the expensive emptiness walk rerun (a tier fully emptied
        by delete=true must stop winning tier selection)."""
        key = (interval, agg.lower())
        store = self._tiers.get(key)
        if store is None:
            return False
        pw = store.points_written
        if pw == 0:
            return False
        ep = getattr(store, "mutation_epoch", 0)
        cached = self._has_data_cache.get(key)
        if cached is not None and cached[0] == ep:
            if cached[2]:
                return True
            if pw == cached[1]:
                return False
            # writes landed since the False verdict: data exists now
            self._has_data_cache[key] = (ep, pw, True)
            return True
        res = store.total_points() > 0
        self._has_data_cache[key] = (ep, pw, res)
        return res
