"""Host column store: the TPU build's storage engine.

Replaces the reference's HBase tables + asynchbase client
(ref: ``third_party/hbase``, ``src/core/SaltScanner.java``). Instead of
byte-encoded rows scanned over TCP, series live in process memory as
contiguous numpy columns — append is O(1) amortized, and query-time
"scan" is a vectorized gather that materializes a flat point batch
``(series_idx, timestamp, value)`` ready for device upload. The
reference's scan→Span→SpanGroup assembly (Span.java, SpanGroup.java,
SaltScanner.java) collapses into :meth:`TimeSeriesStore.materialize`.

Sharding: each series is assigned ``shard = salt_hash % num_shards``
exactly like the reference salts row keys (RowKey.java:141-165); the
shard index is the device-mesh axis used by :mod:`opentsdb_tpu.parallel`.

The ``StorageBackend`` protocol preserves the reference's swap point
(asynchbase -> asyncbigtable -> asynccassandra, Makefile.am:267-279):
`MemoryBackend` here, a C++ arena store in
:mod:`opentsdb_tpu.native` as the second backend.
"""

from __future__ import annotations

import bisect
import threading
from typing import Iterable, NamedTuple, Protocol, Sequence

import numpy as np

from opentsdb_tpu.core import const

_INITIAL_CAPACITY = 16


class SeriesBuffer:
    """One series' points: growable parallel numpy columns.

    The reference materializes a series as compacted HBase cells parsed
    into ``RowSeq`` objects (RowSeq.java:39); here the canonical form is
    already columnar. Out-of-order and duplicate writes are accepted;
    the buffer is lazily sorted + deduped (last write wins — matching
    ``tsd.storage.fix_duplicates`` semantics, CompactionQueue.java) the
    first time it is read after a write.
    """

    __slots__ = ("ts", "vals", "is_int", "n", "_sorted", "lock",
                 "_ts_base", "_ts_scale")

    def __init__(self) -> None:
        self.ts = np.empty(_INITIAL_CAPACITY, dtype=np.int64)
        self.vals = np.empty(_INITIAL_CAPACITY, dtype=np.float64)
        self.is_int = np.empty(_INITIAL_CAPACITY, dtype=bool)
        self.n = 0
        self._sorted = True
        self.lock = threading.Lock()
        # packed-timestamp compaction state (see compact()): when
        # _ts_scale > 0, ``ts`` holds int32 offsets and the true ms
        # value is _ts_base + ts[i] * _ts_scale. Readers materialize
        # int64 through _ts64_locked(); writers unpack first.
        self._ts_base = 0
        self._ts_scale = 0

    def append(self, ts_ms: int, value: float, is_int: bool) -> None:
        with self.lock:
            self._unpack_locked()
            if self.n == len(self.ts):
                # max() guards a compacted-empty buffer (capacity 0)
                new_cap = max(self.n * 2, _INITIAL_CAPACITY)
                self.ts = np.resize(self.ts, new_cap)
                self.vals = np.resize(self.vals, new_cap)
                self.is_int = np.resize(self.is_int, new_cap)
            i = self.n
            self.ts[i] = ts_ms
            self.vals[i] = value
            self.is_int[i] = is_int
            if self._sorted and i > 0 and ts_ms <= self.ts[i - 1]:
                self._sorted = False
            self.n = i + 1

    def append_many(self, ts_ms: np.ndarray, values: np.ndarray,
                    is_int: np.ndarray | bool = False) -> None:
        """Bulk append (import path). Arrays must be 1-D, same length."""
        k = len(ts_ms)
        if k == 0:
            return
        with self.lock:
            self._unpack_locked()
            need = self.n + k
            if need > len(self.ts):
                new_cap = max(need, len(self.ts) * 2)
                self.ts = np.resize(self.ts, new_cap)
                self.vals = np.resize(self.vals, new_cap)
                self.is_int = np.resize(self.is_int, new_cap)
            self.ts[self.n:need] = ts_ms
            self.vals[self.n:need] = values
            self.is_int[self.n:need] = is_int
            if self._sorted:
                first = ts_ms[0]
                if (self.n > 0 and first <= self.ts[self.n - 1]) or \
                        k > 1 and bool(np.any(np.diff(ts_ms) <= 0)):
                    self._sorted = False
            self.n = need

    def _unpack_locked(self) -> None:
        """Restore the plain int64 timestamp column before a mutation
        (packed buffers are immutable snapshots of compacted data)."""
        if self._ts_scale:
            self.ts = (self._ts_base
                       + self.ts[:self.n].astype(np.int64)
                       * self._ts_scale)
            self._ts_base = 0
            self._ts_scale = 0

    def _ts64_locked(self) -> np.ndarray:
        """The live timestamps as int64 ms (materialized when packed;
        a view otherwise). Caller holds ``lock``."""
        if self._ts_scale:
            return (self._ts_base
                    + self.ts[:self.n].astype(np.int64)
                    * self._ts_scale)
        return self.ts[:self.n]

    def _ensure_sorted_locked(self) -> None:
        if self._sorted:
            return
        ts = self.ts[:self.n]
        order = np.argsort(ts, kind="stable")
        ts_sorted = ts[order]
        vals_sorted = self.vals[:self.n][order]
        ints_sorted = self.is_int[:self.n][order]
        # dedupe: last write wins (stable sort keeps write order per ts)
        if self.n > 1:
            keep = np.empty(self.n, dtype=bool)
            keep[:-1] = ts_sorted[1:] != ts_sorted[:-1]
            keep[-1] = True
            if not keep.all():
                ts_sorted = ts_sorted[keep]
                vals_sorted = vals_sorted[keep]
                ints_sorted = ints_sorted[keep]
        m = len(ts_sorted)
        self.ts[:m] = ts_sorted
        self.vals[:m] = vals_sorted
        self.is_int[:m] = ints_sorted
        self.n = m
        self._sorted = True

    def view(self) -> tuple[np.ndarray, np.ndarray]:
        """Sorted, deduped (ts, vals) views. Do not mutate."""
        with self.lock:
            self._ensure_sorted_locked()
            return self._ts64_locked(), self.vals[:self.n]

    def view_full(self) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
        with self.lock:
            self._ensure_sorted_locked()
            return (self._ts64_locked(), self.vals[:self.n],
                    self.is_int[:self.n])

    def slice_range(self, start_ms: int, end_ms: int) -> tuple[np.ndarray,
                                                               np.ndarray]:
        """Points with start_ms <= ts <= end_ms (inclusive ends, matching
        the reference's getScanEndTimeSeconds semantics)."""
        ts, vals = self.view()
        lo = np.searchsorted(ts, start_ms, side="left")
        hi = np.searchsorted(ts, end_ms, side="right")
        return ts[lo:hi], vals[lo:hi]

    def delete_range(self, start_ms: int, end_ms: int) -> int:
        """Remove points with start_ms <= ts <= end_ms; returns how many
        (ref: TsdbQuery delete=true issuing DeleteRequests per scanned
        row)."""
        with self.lock:
            self._ensure_sorted_locked()
            ts = self._ts64_locked()
            lo = int(np.searchsorted(ts, start_ms, side="left"))
            hi = int(np.searchsorted(ts, end_ms, side="right"))
            k = hi - lo
            if k <= 0:
                return 0
            self._unpack_locked()
            self.ts[lo:self.n - k] = self.ts[hi:self.n]
            self.vals[lo:self.n - k] = self.vals[hi:self.n]
            self.is_int[lo:self.n - k] = self.is_int[hi:self.n]
            self.n -= k
            return k

    def compact(self, pack_ts: bool = True,
                pack_before_ms: int | None = None) -> int:
        """Lifecycle compaction: sort/dedupe, shrink the columns to
        exactly ``n`` elements (growth doubling can strand ~2x dead
        capacity), and — when ``pack_ts`` and lossless — pack the
        timestamp column to int32 offsets from the first timestamp
        (scale 1000 when every ts is second-aligned, else 1), halving
        its resident bytes. Packing is transparent: readers
        materialize int64 on access, the first write unpacks.

        ``pack_before_ms`` restricts packing to COLD buffers (newest
        point older than the horizon): packing a buffer that is still
        being written just buys a full unpack copy on its next append.
        A buffer that is already exact-capacity and either packed or
        ineligible for packing returns 0 without copying anything —
        repeat sweeps over compacted data are free. Returns bytes
        reclaimed."""
        with self.lock:
            before = (self.ts.nbytes + self.vals.nbytes
                      + self.is_int.nbytes)
            self._ensure_sorted_locked()
            n = self.n
            want_pack = (pack_ts and n > 0 and self._ts_scale == 0)
            if want_pack and pack_before_ms is not None:
                # self.ts is plain int64 here (_ts_scale == 0)
                want_pack = int(self.ts[n - 1]) < pack_before_ms
            if want_pack and (int(self.ts[n - 1]) - int(self.ts[0])
                              > np.iinfo(np.int32).max * 1000):
                want_pack = False  # unpackable at any scale
            if not want_pack and len(self.vals) == n:
                return 0  # already compact: no copies
            self.vals = self.vals[:n].copy()
            self.is_int = self.is_int[:n].copy()
            ts = self._ts64_locked()
            packed = self._ts_scale > 0
            if want_pack:
                base = int(ts[0])
                scale = 1000 if (base % 1000 == 0
                                 and not (ts % 1000).any()) else 1
                span = (int(ts[-1]) - base) // scale
                if span <= np.iinfo(np.int32).max:
                    self.ts = ((ts - base) // scale).astype(np.int32)
                    self._ts_base = base
                    self._ts_scale = scale
                    packed = True
            if not packed:
                self.ts = ts[:n].copy()
                self._ts_base = 0
                self._ts_scale = 0
            after = (self.ts.nbytes + self.vals.nbytes
                     + self.is_int.nbytes)
            return max(before - after, 0)

    @property
    def resident_bytes(self) -> int:
        """Allocated column bytes (capacity-based, all three columns)."""
        return self.ts.nbytes + self.vals.nbytes + self.is_int.nbytes

    @property
    def live_bytes(self) -> int:
        """Bytes the ``n`` live points occupy in the CURRENT
        representation (packed timestamps count their packed width)."""
        return self.n * (self.ts.itemsize + self.vals.itemsize
                         + self.is_int.itemsize)

    def __len__(self) -> int:
        return self.n


class SeriesRecord(NamedTuple):
    series_id: int
    metric_id: int
    tags: tuple[tuple[int, int], ...]  # ((tagk_id, tagv_id), ...) sorted
    shard: int
    buffer: SeriesBuffer


class PointBatch(NamedTuple):
    """Flat materialized points for a set of series — the device-upload
    format consumed by :mod:`opentsdb_tpu.ops.pipeline`.

    ``series_idx[i]`` indexes into ``series_ids`` (dense 0..S-1), NOT the
    global series id — so the array program sees a compact series axis.
    """
    series_ids: np.ndarray    # int64 [S] global series ids
    series_idx: np.ndarray    # int32 [N] dense position of each point
    ts_ms: np.ndarray         # int64 [N]
    values: np.ndarray        # float64 [N]

    @property
    def num_series(self) -> int:
        return len(self.series_ids)

    @property
    def num_points(self) -> int:
        return len(self.ts_ms)


def pad_mask(counts: np.ndarray, pmax: int) -> np.ndarray:
    """Boolean [S, Pmax] mask of PAD cells (col >= row count) — the one
    place the padding convention is written down."""
    return np.arange(pmax)[None, :] >= counts[:, None]


class PaddedBatch(NamedTuple):
    """Row-padded materialized points: series i's points occupy columns
    ``0..counts[i]-1`` of row i, time-ascending; the rest is NaN padding.

    This is the TPU-preferred layout — the ragged->dense transposition
    happens during materialization (one contiguous write per series, no
    extra pass), and downstream bucketization needs no scatter at all
    (see :func:`opentsdb_tpu.ops.downsample.bucketize_padded`).
    """
    series_ids: np.ndarray    # int64 [S] global series ids
    values2d: np.ndarray      # float64 [S, Pmax], NaN-padded
    ts2d: np.ndarray          # int64 [S, Pmax], 0-padded
    counts: np.ndarray        # int64 [S] points per row

    @property
    def num_series(self) -> int:
        return len(self.series_ids)

    @property
    def num_points(self) -> int:
        return int(self.counts.sum())


def padded_from_batch(batch: PointBatch) -> PaddedBatch:
    """Row-pad a flat :class:`PointBatch` (series_idx grouped,
    per-series time-ascending — the materialize contract). Shared by
    the read views that build their padded form from a merged flat
    batch (stitched store, cold stat view)."""
    s = len(batch.series_ids)
    counts = np.bincount(batch.series_idx, minlength=s) \
        .astype(np.int64) if s else np.empty(0, dtype=np.int64)
    pmax = max(1, int(counts.max())) if s else 1
    values2d = np.full((s, pmax), np.nan)
    ts2d = np.zeros((s, pmax), dtype=np.int64)
    if batch.num_points:
        row_starts = np.zeros(s, dtype=np.int64)
        np.cumsum(counts[:-1], out=row_starts[1:])
        col = np.arange(batch.num_points, dtype=np.int64) \
            - np.repeat(row_starts, counts)
        values2d[batch.series_idx, col] = batch.values
        ts2d[batch.series_idx, col] = batch.ts_ms
    return PaddedBatch(batch.series_ids, values2d, ts2d, counts)


class StorageBackend(Protocol):
    """The storage swap point (ref: build-bigtable.sh / build-cassandra.sh)."""

    def get_or_create_series(self, metric_id: int,
                             tags: Sequence[tuple[int, int]]) -> int: ...
    def append(self, series_id: int, ts_ms: int, value: float,
               is_int: bool) -> None: ...
    def materialize(self, series_ids: Sequence[int], start_ms: int,
                    end_ms: int) -> PointBatch: ...
    def count_range(self, series_ids: Sequence[int], start_ms: int,
                    end_ms: int) -> np.ndarray: ...
    def materialize_padded(self, series_ids: Sequence[int],
                           start_ms: int, end_ms: int) -> PaddedBatch: ...


class MetricIndex:
    """Per-metric vectorized tag index.

    The reference filters series by compiling literal tag filters into
    scanner row-key regexes and running the rest post-scan
    (TsdbQuery.findSpans :804, SaltScanner:660). Here every metric keeps
    columnar arrays (series_id, tagk_id, tagv_id triples) so a filter
    evaluates as numpy set/mask operations over all series of the metric
    at once.
    """

    def __init__(self, metric_id: int):
        self.metric_id = metric_id
        # tsdlint: allow[unbounded-growth] the store's own series
        # index — bounded by live series cardinality (lifecycle
        # releases the BUFFERS; index-row reclamation rides the
        # demotion-aware UID reclamation ROADMAP item)
        self.series_ids: list[int] = []
        # tsdlint: allow[unbounded-growth] see series_ids
        self._tag_rows: list[tuple[int, int, int]] = []  # (sid, tagk, tagv)
        self._dirty = False
        self._sid_arr = np.empty(0, dtype=np.int64)
        self._tags_arr = np.empty((0, 3), dtype=np.int64)

    def add(self, series_id: int, tags: Sequence[tuple[int, int]]) -> None:
        self.series_ids.append(series_id)
        for tagk, tagv in tags:
            self._tag_rows.append((series_id, tagk, tagv))
        self._dirty = True

    def add_bulk(self, series_ids: Sequence[int],
                 tags_list: Sequence[Sequence[tuple[int, int]]]) -> None:
        """Bulk twin of :meth:`add`: one list extend instead of N calls."""
        self.series_ids.extend(series_ids)
        self._tag_rows.extend(
            (sid, tagk, tagv)
            for sid, tags in zip(series_ids, tags_list)
            for tagk, tagv in tags)
        self._dirty = True

    def arrays(self) -> tuple[np.ndarray, np.ndarray]:
        """(sids[int64 S], tag_triples[int64 T x 3]) snapshot."""
        if self._dirty:
            self._sid_arr = np.asarray(self.series_ids, dtype=np.int64)
            self._tags_arr = (np.asarray(self._tag_rows, dtype=np.int64)
                              .reshape(-1, 3))
            self._dirty = False
        return self._sid_arr, self._tags_arr


# process-wide monotonic store instance ids (shared with the native
# backend): cache keys built from them can never alias a freed store
# the way id(store) could after address reuse
import itertools as _itertools

STORE_INSTANCE_IDS = _itertools.count()


#: what ``oldest_written_since`` answers where the store no longer
#: knows ("everything"): older than any timestamp, so that a reader's
#: ``oldest > hi_ms`` needs no case of its own
ALL = float("-inf")


class WrittenLog:
    """Where in time a store's recent appends landed: what
    ``oldest_written_since`` answers from.

    One entry an append call, ``(points_written after it, the oldest
    timestamp it wrote)``, pushed in the critical section that bumps
    the counter (:attr:`lock`, held by the caller of :meth:`note`).
    What a reader asks is a suffix minimum, so an entry no newer call
    undercuts is all that is kept: a push first pops the entries whose
    timestamp is not older than its own (every suffix that held them
    holds the new one too), and the log ascends in both columns.
    Appends that share a timestamp, a fleet reporting one minute, are
    one entry however many they are. At most :data:`MAX_ENTRIES`:
    the oldest entry beyond that is folded into
    :attr:`floor_version`, and a question about a version under the
    floor is answered :data:`ALL`."""

    MAX_ENTRIES = 4096

    __slots__ = ("lock", "_versions", "_oldest", "floor_version")

    def __init__(self) -> None:
        self.lock = threading.Lock()
        self._versions: list[int] = []
        self._oldest: list[int] = []
        self.floor_version = 0

    def __len__(self) -> int:
        return len(self._versions)

    def note(self, version: int, oldest_ts: int) -> None:
        """An append brought ``points_written`` to ``version``, its
        oldest point at ``oldest_ts``. The caller holds :attr:`lock`
        across the counter's bump and this."""
        while self._oldest and self._oldest[-1] >= oldest_ts:
            self._oldest.pop()
            self._versions.pop()
        self._versions.append(version)
        self._oldest.append(oldest_ts)
        if len(self._versions) > self.MAX_ENTRIES:
            self.floor_version = self._versions.pop(0)
            self._oldest.pop(0)

    def oldest_since(self, version: int):
        with self.lock:
            if version < self.floor_version:
                return ALL
            # ascending in both columns: the first entry past
            # ``version`` is the oldest of all that follow it
            i = bisect.bisect_right(self._versions, version)
            return self._oldest[i] if i < len(self._oldest) else None


class TimeSeriesStore:
    """In-memory storage engine: all series of all metrics.

    Concurrency: a single writer lock guards series creation and index
    updates; per-series appends take only the series' own lock. Readers
    snapshot indices without blocking writes (numpy arrays are replaced,
    never mutated in place once published).
    """

    # fault-injection hook for the scan path (tsd.faults.store_*);
    # set by the owning TSDB, None everywhere else. Rollup tier /
    # preagg stores override fault_site with "rollup.store" so a
    # degraded tier is armable/observable independently of the raw
    # store (tsd.faults.rollup.store_*).
    fault_injector = None
    fault_site = "store"

    def __init__(self, num_shards: int | None = None):
        self.instance_id = next(STORE_INSTANCE_IDS)
        self.num_shards = num_shards or const.salt_buckets()
        self._lock = threading.Lock()
        # tsdlint: allow[unbounded-growth] THE in-RAM store: bounded
        # by live series cardinality; retention/demotion release and
        # shrink the buffers, full row reclamation is the ROADMAP
        # UID-reclamation item
        self._series: list[SeriesRecord] = []
        # tsdlint: allow[unbounded-growth] see _series
        self._key_to_sid: dict[tuple, int] = {}
        # tsdlint: allow[unbounded-growth] see _series
        self._metric_index: dict[int, MetricIndex] = {}
        self.points_written = 0
        # where in time the recent appends landed
        # (oldest_written_since), pushed where the counter is bumped
        self._written = WrittenLog()
        # bumped on destructive ops (delete_range); together with
        # points_written it versions the store for read-side caches
        self.mutation_epoch = 0
        # bumped by compact_series (resident bytes changed without a
        # data change — versions the memory_info cache only)
        self.compactions = 0
        self._memory_info_cache: tuple | None = None

    # -- write path -------------------------------------------------------

    def get_or_create_series(self, metric_id: int,
                             tags: Sequence[tuple[int, int]]) -> int:
        key = (metric_id, tuple(sorted(tags)))
        sid = self._key_to_sid.get(key)
        if sid is not None:
            return sid
        with self._lock:
            sid = self._key_to_sid.get(key)
            if sid is not None:
                return sid
            sid = len(self._series)
            shard = self._shard_for(metric_id, key[1])
            rec = SeriesRecord(sid, metric_id, key[1], shard, SeriesBuffer())
            self._series.append(rec)
            idx = self._metric_index.get(metric_id)
            if idx is None:
                idx = self._metric_index[metric_id] = MetricIndex(metric_id)
            idx.add(sid, key[1])
            self._key_to_sid[key] = sid
            return sid

    def get_or_create_series_bulk(
            self, metric_id: int,
            tags_list: Sequence[Sequence[tuple[int, int]]]) -> np.ndarray:
        """Vectorized get_or_create_series for N series of one metric.

        One lock take and one index update for the whole batch instead
        of N — the write-path analogue of the reference's batched
        ``IncomingDataPoints`` row-template reuse
        (src/core/BatchedDataPoints.java:34). Essential on a 1-CPU host
        where 100k+ per-series Python calls dominate bulk ingest.
        """
        keys = [(metric_id, tuple(sorted(t))) for t in tags_list]
        out = np.empty(len(keys), dtype=np.int64)
        missing: list[int] = []
        get = self._key_to_sid.get
        for i, key in enumerate(keys):
            sid = get(key)
            if sid is None:
                missing.append(i)
                out[i] = -1
            else:
                out[i] = sid
        if not missing:
            return out
        with self._lock:
            new_sids: list[int] = []
            new_tags: list[tuple[tuple[int, int], ...]] = []
            idx = self._metric_index.get(metric_id)
            if idx is None:
                idx = self._metric_index[metric_id] = MetricIndex(metric_id)
            for i in missing:
                key = keys[i]
                sid = self._key_to_sid.get(key)
                if sid is None:
                    sid = len(self._series)
                    shard = self._shard_for(metric_id, key[1])
                    self._series.append(SeriesRecord(
                        sid, metric_id, key[1], shard, SeriesBuffer()))
                    self._key_to_sid[key] = sid
                    new_sids.append(sid)
                    new_tags.append(key[1])
                out[i] = sid
            if new_sids:
                idx.add_bulk(new_sids, new_tags)
        return out

    def _shard_for(self, metric_id: int,
                   tags: tuple[tuple[int, int], ...]) -> int:
        # Same hash family as the salt bucket (RowKey.java:141): series of
        # one metric+tags always land on the same shard/device.
        h = hash((metric_id, tags))
        return h % self.num_shards

    def append(self, series_id: int, ts_ms: int, value: float,
               is_int: bool = False) -> None:
        self._series[series_id].buffer.append(ts_ms, value, is_int)
        self._note_written(1, ts_ms)

    def append_many(self, series_id: int, ts_ms: np.ndarray,
                    values: np.ndarray,
                    is_int: np.ndarray | bool = False) -> None:
        self._series[series_id].buffer.append_many(ts_ms, values, is_int)
        if len(ts_ms):
            self._note_written(len(ts_ms), int(np.min(ts_ms)))

    def append_grid(self, series_ids, bucket_ts: np.ndarray,
                    grid: np.ndarray, mask: np.ndarray) -> int:
        """Bulk write one [S, B] grid: mask-selected cells of row i
        append onto series_ids[i] (portable twin of the native store's
        threaded ``tss_append_grid``)."""
        sids = np.asarray(series_ids, dtype=np.int64)
        if len(sids) and ((sids < 0) | (sids >= len(self._series))).any():
            raise IndexError("invalid series id in append_grid")
        written = 0
        oldest = None
        for i, sid in enumerate(sids):
            m = mask[i]
            if not m.any():
                continue
            ts = bucket_ts[m]
            self._series[sid].buffer.append_many(ts, grid[i][m])
            written += len(ts)
            first = int(ts.min())
            oldest = first if oldest is None else min(oldest, first)
        if written:
            self._note_written(written, oldest)
        return written

    def _note_written(self, n: int, oldest_ts: int) -> None:
        """``n`` points are in their buffers, the oldest of them at
        ``oldest_ts``: count them and log where they landed, in ONE
        critical section, after the points became readable. A reader
        that read ``points_written`` before this call's bump gets the
        call from :meth:`oldest_written_since` whether or not its scan
        saw the points; one that read it after has seen them."""
        log = self._written
        with log.lock:
            self.points_written += n
            log.note(self.points_written, int(oldest_ts))

    def oldest_written_since(self, points_written: int):
        """The smallest timestamp (ms) any append has written since
        ``points_written`` read that value; None if nothing was
        written; :data:`ALL` where the log no longer reaches back that
        far. What lets a reader keep what it built from a span of time
        no write has touched (:mod:`opentsdb_tpu.query.device_cache`,
        "The version rule"). Deletes and repairs are not appends:
        they bump ``mutation_epoch``, which no log refines."""
        return self._written.oldest_since(points_written)

    def delete_range(self, series_ids: Sequence[int], start_ms: int,
                     end_ms: int) -> int:
        """Delete all points of ``series_ids`` within the inclusive
        range; returns the number removed."""
        deleted = 0
        for sid in series_ids:
            deleted += self._series[int(sid)].buffer.delete_range(
                start_ms, end_ms)
        if deleted:
            self.mutation_epoch += 1
        return deleted

    def repair_series(self, series_id: int, min_ts: int, max_ts: int,
                      drop_nonfinite: bool = True) -> int:
        """fsck in-place repair (ref: Fsck.java:99-119): drop points
        with out-of-range timestamps and (optionally) non-finite
        values. Returns points removed."""
        buf = self._series[series_id].buffer
        with buf.lock:
            buf._ensure_sorted_locked()
            buf._unpack_locked()
            m = buf.n
            keep = (buf.ts[:m] >= min_ts) & (buf.ts[:m] <= max_ts)
            if drop_nonfinite:
                keep &= np.isfinite(buf.vals[:m])
            kept = int(keep.sum())
            if kept != m:
                buf.ts[:kept] = buf.ts[:m][keep]
                buf.vals[:kept] = buf.vals[:m][keep]
                buf.is_int[:kept] = buf.is_int[:m][keep]
                buf.n = kept
        removed = m - kept
        if removed:
            self.mutation_epoch += 1
        return removed

    def patch_value(self, series_id: int, ts_ms: int, value: float,
                    is_int: bool = False) -> None:
        """fsck in-place repair: overwrite the value at an exact
        timestamp (raises KeyError when absent)."""
        buf = self._series[series_id].buffer
        with buf.lock:
            buf._ensure_sorted_locked()
            buf._unpack_locked()
            i = int(np.searchsorted(buf.ts[:buf.n], ts_ms))
            if i >= buf.n or buf.ts[i] != ts_ms:
                raise KeyError(f"series {series_id} has no point at "
                               f"{ts_ms}")
            buf.vals[i] = value
            buf.is_int[i] = is_int
        self.mutation_epoch += 1

    # -- read path --------------------------------------------------------

    def series(self, series_id: int) -> SeriesRecord:
        return self._series[series_id]

    def num_series(self) -> int:
        return len(self._series)

    def metric_ids(self) -> list[int]:
        with self._lock:
            return list(self._metric_index)

    def metric_index(self, metric_id: int) -> MetricIndex | None:
        return self._metric_index.get(metric_id)

    def series_ids_for_metric(self, metric_id: int) -> np.ndarray:
        idx = self._metric_index.get(metric_id)
        if idx is None:
            return np.empty(0, dtype=np.int64)
        sids, _ = idx.arrays()
        return sids

    def materialize(self, series_ids: Sequence[int], start_ms: int,
                    end_ms: int) -> PointBatch:
        """Gather all points of ``series_ids`` in [start_ms, end_ms].

        This is the moral equivalent of the reference's 20-way SaltScanner
        fan-out + Span assembly (SaltScanner.java:269) — except the output
        is a flat columnar batch, not a tree of iterators.
        """
        if self.fault_injector is not None:
            self.fault_injector.check(self.fault_site)
        sids = np.asarray(series_ids, dtype=np.int64)
        ts_parts: list[np.ndarray] = []
        val_parts: list[np.ndarray] = []
        counts = np.empty(len(sids), dtype=np.int64)
        for i, sid in enumerate(sids):
            ts, vals = self._series[sid].buffer.slice_range(start_ms, end_ms)
            counts[i] = len(ts)
            if len(ts):
                ts_parts.append(ts)
                val_parts.append(vals)
        if ts_parts:
            all_ts = np.concatenate(ts_parts)
            all_vals = np.concatenate(val_parts)
        else:
            all_ts = np.empty(0, dtype=np.int64)
            all_vals = np.empty(0, dtype=np.float64)
        series_idx = np.repeat(
            np.arange(len(sids), dtype=np.int32), counts)
        return PointBatch(sids, series_idx, all_ts, all_vals)

    def count_range(self, series_ids: Sequence[int], start_ms: int,
                    end_ms: int) -> np.ndarray:
        """Points per series in [start_ms, end_ms] without copying them
        — lets the engine judge padding skew before materializing."""
        out = np.empty(len(series_ids), dtype=np.int64)
        for i, sid in enumerate(np.asarray(series_ids, dtype=np.int64)):
            ts, _ = self._series[sid].buffer.view()
            lo = np.searchsorted(ts, start_ms, side="left")
            hi = np.searchsorted(ts, end_ms, side="right")
            out[i] = hi - lo
        return out

    def materialize_padded(self, series_ids: Sequence[int],
                           start_ms: int, end_ms: int) -> PaddedBatch:
        """Row-padded variant of :meth:`materialize` — same per-series
        slice cost, but each series lands in its own row."""
        if self.fault_injector is not None:
            self.fault_injector.check(self.fault_site)
        sids = np.asarray(series_ids, dtype=np.int64)
        slices = [self._series[sid].buffer.slice_range(start_ms, end_ms)
                  for sid in sids]
        counts = np.asarray([len(ts) for ts, _ in slices],
                            dtype=np.int64)
        pmax = max(1, int(counts.max())) if len(counts) else 1
        values2d = np.full((len(sids), pmax), np.nan)
        ts2d = np.zeros((len(sids), pmax), dtype=np.int64)
        for i, (ts, vals) in enumerate(slices):
            n = len(ts)
            if n:
                ts2d[i, :n] = ts
                values2d[i, :n] = vals
        return PaddedBatch(sids, values2d, ts2d, counts)

    def append_lines(self, sids, ts_ms, values, is_int) -> int:
        """Portable twin of the native scatter-append: element i lands
        on series ``sids[i]`` (negative skips)."""
        sid_arr = np.asarray(sids, dtype=np.int64)
        ts_arr = np.asarray(ts_ms, dtype=np.int64)
        val_arr = np.asarray(values, dtype=np.float64)
        int_arr = np.asarray(is_int, dtype=bool)
        # one kept-and-sorted index, applied once per array
        kept = np.flatnonzero(sid_arr >= 0)
        idx = kept[np.argsort(sid_arr[kept], kind="stable")]
        sid_s = sid_arr[idx]
        ts_s, val_s, int_s = ts_arr[idx], val_arr[idx], int_arr[idx]
        bounds = np.nonzero(np.diff(sid_s))[0] + 1
        written = 0
        for lo, hi in zip(np.r_[0, bounds], np.r_[bounds, len(sid_s)]):
            if lo == hi:
                continue
            self.append_many(int(sid_s[lo]), ts_s[lo:hi], val_s[lo:hi],
                             int_s[lo:hi])
            written += hi - lo
        return written

    def bucket_reduce(self, series_ids, start_ms: int, end_ms: int,
                      t0: int, interval_ms: int, nbuckets: int,
                      want_minmax: bool = False):
        """Portable twin of the native store's fused range-scan +
        fixed-interval pre-reduction: [S, B] sum/count (+min/max)
        grids over [start_ms, end_ms], bucket = (ts - t0)//interval_ms.
        NaN stored values are skipped like the device bucketize."""
        batch = self.materialize(series_ids, start_ms, end_ms)
        s = len(batch.series_ids)
        b = (batch.ts_ms - t0) // interval_ms
        ok = (b >= 0) & (b < nbuckets) & ~np.isnan(batch.values)
        seg = batch.series_idx[ok].astype(np.int64) * nbuckets + b[ok]
        vals = batch.values[ok]
        n = s * nbuckets
        sums = np.bincount(seg, weights=vals, minlength=n).reshape(
            s, nbuckets)
        cnts = np.bincount(seg, minlength=n).astype(np.float64) \
            .reshape(s, nbuckets)
        mins = maxs = None
        if want_minmax:
            mins = np.full(n, np.inf)
            np.minimum.at(mins, seg, vals)
            maxs = np.full(n, -np.inf)
            np.maximum.at(maxs, seg, vals)
            mins = mins.reshape(s, nbuckets)
            maxs = maxs.reshape(s, nbuckets)
        return sums, cnts, mins, maxs

    def shards_of(self, series_ids: Iterable[int]) -> np.ndarray:
        return np.asarray([self._series[s].shard for s in series_ids],
                          dtype=np.int32)

    def total_points(self) -> int:
        return sum(len(rec.buffer) for rec in self._series)

    # -- lifecycle surface -------------------------------------------------

    def compact_series(self, series_ids: Sequence[int] | None = None,
                       pack_ts: bool = True,
                       pack_before_ms: int | None = None
                       ) -> tuple[int, int]:
        """Compact the given series' buffers (all series when None):
        sort/dedupe/shrink-to-fit + lossless timestamp packing (see
        :meth:`SeriesBuffer.compact`; ``pack_before_ms`` limits
        packing to cold buffers). Returns (bytes reclaimed, series
        released) where released = buffers that compacted down to
        zero live points (ghost series keep their sid — numbering is
        positional — but their columns are freed)."""
        if series_ids is None:
            series_ids = range(len(self._series))
        reclaimed = 0
        released = 0
        for sid in series_ids:
            buf = self._series[int(sid)].buffer
            got = buf.compact(pack_ts=pack_ts,
                              pack_before_ms=pack_before_ms)
            reclaimed += got
            if got and buf.n == 0:
                released += 1
        if reclaimed:
            self.compactions += 1
        return reclaimed, released

    def memory_info(self) -> dict:
        """Resident/live/dead column bytes + series/point counts for
        the /api/health and /api/stats memory-footprint report. Cached
        on the store's write/delete/compaction counters so health
        polls do not re-walk a million buffers."""
        key = (self.points_written, self.mutation_epoch,
               len(self._series), self.compactions)
        cached = self._memory_info_cache
        if cached is not None and cached[0] == key:
            return cached[1]
        resident = live = points = 0
        for rec in self._series:
            buf = rec.buffer
            resident += buf.resident_bytes
            live += buf.live_bytes
            points += buf.n
        info = {"series": len(self._series), "points": points,
                "resident_bytes": resident, "live_bytes": live,
                "dead_bytes": max(resident - live, 0)}
        self._memory_info_cache = (key, info)
        return info

    def collect_stats(self, collector) -> None:
        collector.record("storage.series.count", self.num_series())
        collector.record("storage.points.written", self.points_written)
        collector.record("storage.written_log.entries",
                         len(self._written))
        collector.record("storage.written_log.floor_version",
                         self._written.floor_version)
        collector.record("storage.shards", self.num_shards)
        mi = self.memory_info()
        collector.record("storage.resident_bytes",
                         mi["resident_bytes"])
        collector.record("storage.live_bytes", mi["live_bytes"])
        collector.record("storage.dead_bytes", mi["dead_bytes"])
