"""ctypes bindings for the native C++ column store.

Drop-in storage backend (``tsd.storage.backend = native``): same
interface as :class:`opentsdb_tpu.core.store.TimeSeriesStore`, with
point columns living in the C++ arena (``tsdbstore.cc``) and series
identity / tag indexing staying in Python (they need UID strings
anyway). Built on demand with g++; transparently falls back to the
Python backend when no compiler is available.
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import threading
from typing import Iterable, Sequence

import numpy as np

from opentsdb_tpu.core import const
from opentsdb_tpu.core.store import (ALL, MetricIndex, PaddedBatch,
                                     PointBatch)

_INT64_MAX = np.iinfo(np.int64).max
_INT64_MIN = np.iinfo(np.int64).min

_SRC = os.path.join(os.path.dirname(__file__), "tsdbstore.cc")
_LIB_DIR = os.path.dirname(__file__)
_CXX = "g++"
_CXXFLAGS = ("-O3", "-march=native", "-shared", "-fPIC", "-std=c++17",
             "-pthread")
_lib = None
_build_error: str | None = None  # negative cache for failed builds
_lib_lock = threading.Lock()


class NativeBuildError(RuntimeError):
    pass


def _build_key() -> str:
    """Content hash of what the library is made from: the C++ source,
    the flags, and what ``-march=native`` resolves to on THIS machine
    (the compiler's own account of its target). The key is part of the
    library's file name, so a library carried over from another
    machine, an older source or other flags is never loaded — mtimes
    say nothing about any of those."""
    try:
        target = subprocess.run(
            [_CXX, *_CXXFLAGS, "-Q", "--help=target"],
            capture_output=True, text=True, timeout=60)
    except (OSError, subprocess.TimeoutExpired) as e:
        raise NativeBuildError(f"{_CXX} unavailable: {e}") from e
    if target.returncode != 0:
        raise NativeBuildError(
            f"{_CXX} cannot report its target:\n{target.stderr}")
    h = hashlib.sha256()
    with open(_SRC, "rb") as fh:
        h.update(fh.read())
    h.update(" ".join(_CXXFLAGS).encode())
    h.update(target.stdout.encode())
    return h.hexdigest()[:16]


def build_library() -> str:
    """Compile the library for this machine if the one made from the
    current source, flags and CPU is not on disk; returns its path
    (``libtsdbstore.<key>.so``, see :func:`_build_key`)."""
    lib_path = os.path.join(_LIB_DIR, f"libtsdbstore.{_build_key()}.so")
    if os.path.isfile(lib_path):
        return lib_path
    # build beside the target and rename: a concurrent process (a
    # router and its shards boot together) sees no library or a whole
    # one, never a half-written file
    tmp_path = f"{lib_path}.{os.getpid()}.tmp"
    try:
        try:
            proc = subprocess.run(
                [_CXX, *_CXXFLAGS, _SRC, "-o", tmp_path],
                capture_output=True, text=True, timeout=180)
        except (OSError, subprocess.TimeoutExpired) as e:
            raise NativeBuildError(f"{_CXX} unavailable: {e}") from e
        if proc.returncode != 0:
            raise NativeBuildError(
                f"native build failed:\n{proc.stderr}")
        os.replace(tmp_path, lib_path)
    finally:
        if os.path.exists(tmp_path):
            os.unlink(tmp_path)
    # libraries of other keys can never be loaded again from here
    for name in os.listdir(_LIB_DIR):
        if name.startswith("libtsdbstore") and name.endswith(".so") \
                and os.path.join(_LIB_DIR, name) != lib_path:
            try:
                os.unlink(os.path.join(_LIB_DIR, name))
            except OSError:
                # tsdlint: allow[swallow] best-effort tidy-up; another
                # process may have removed it or still map it
                pass
    return lib_path


def load_library():
    global _lib, _build_error
    with _lib_lock:
        if _lib is not None:
            return _lib
        if _build_error is not None:
            # negative cache: without it every probe re-runs g++ —
            # seconds per call on a toolchain-less host
            raise NativeBuildError(_build_error)
        try:
            path = build_library()
        except NativeBuildError as e:
            _build_error = str(e)
            raise
        try:
            lib = ctypes.CDLL(path)
        except OSError as e:
            # a stale/corrupt/ABI-incompatible cached .so must behave
            # exactly like a failed build: negative-cached (CDLL is
            # retried per call otherwise) and surfaced as
            # NativeBuildError so every caller's fallback engages
            _build_error = f"cannot load {path}: {e}"
            raise NativeBuildError(_build_error) from e
        lib.tss_create.restype = ctypes.c_void_p
        lib.tss_destroy.argtypes = [ctypes.c_void_p]
        lib.tss_add_series.argtypes = [ctypes.c_void_p]
        lib.tss_add_series.restype = ctypes.c_int64
        lib.tss_add_series_n.argtypes = [ctypes.c_void_p, ctypes.c_int64]
        lib.tss_add_series_n.restype = ctypes.c_int64
        lib.tss_series_count.argtypes = [ctypes.c_void_p]
        lib.tss_series_count.restype = ctypes.c_int64
        lib.tss_append.argtypes = [ctypes.c_void_p, ctypes.c_int64,
                                   ctypes.c_int64, ctypes.c_double,
                                   ctypes.c_int]
        lib.tss_append_many.argtypes = [
            ctypes.c_void_p, ctypes.c_int64, ctypes.c_int64,
            ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p]
        lib.tss_points_written.argtypes = [ctypes.c_void_p]
        lib.tss_points_written.restype = ctypes.c_int64
        lib.tss_oldest_written_since.argtypes = [ctypes.c_void_p,
                                                 ctypes.c_int64]
        lib.tss_oldest_written_since.restype = ctypes.c_int64
        lib.tss_written_log_stats.argtypes = [ctypes.c_void_p,
                                              ctypes.c_void_p]
        lib.tss_repair_series.argtypes = [ctypes.c_void_p,
                                          ctypes.c_int64, ctypes.c_int64,
                                          ctypes.c_int64, ctypes.c_int]
        lib.tss_repair_series.restype = ctypes.c_int64
        lib.tss_patch_value.argtypes = [ctypes.c_void_p, ctypes.c_int64,
                                        ctypes.c_int64, ctypes.c_double,
                                        ctypes.c_int]
        lib.tss_patch_value.restype = ctypes.c_int
        lib.tss_append_grid.argtypes = [
            ctypes.c_void_p, ctypes.c_void_p, ctypes.c_int64,
            ctypes.c_void_p, ctypes.c_int64, ctypes.c_void_p,
            ctypes.c_void_p, ctypes.c_int]
        lib.tss_append_grid.restype = ctypes.c_int64
        lib.tss_delete_range.argtypes = [ctypes.c_void_p, ctypes.c_int64,
                                         ctypes.c_int64, ctypes.c_int64]
        lib.tss_delete_range.restype = ctypes.c_int64
        lib.tss_series_length.argtypes = [ctypes.c_void_p,
                                          ctypes.c_int64]
        lib.tss_series_length.restype = ctypes.c_int64
        lib.tss_read_series.argtypes = [
            ctypes.c_void_p, ctypes.c_int64, ctypes.c_int64,
            ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p]
        lib.tss_read_series.restype = ctypes.c_int64
        lib.tss_count_range.argtypes = [
            ctypes.c_void_p, ctypes.c_void_p, ctypes.c_int64,
            ctypes.c_int64, ctypes.c_int64, ctypes.c_void_p,
            ctypes.c_int]
        lib.tss_fill_range.argtypes = [
            ctypes.c_void_p, ctypes.c_void_p, ctypes.c_int64,
            ctypes.c_int64, ctypes.c_int64, ctypes.c_void_p,
            ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p,
            ctypes.c_void_p, ctypes.c_int]
        lib.tss_bucket_reduce.argtypes = [
            ctypes.c_void_p, ctypes.c_void_p, ctypes.c_int64,
            ctypes.c_int64, ctypes.c_int64, ctypes.c_int64,
            ctypes.c_int64, ctypes.c_int64, ctypes.c_void_p,
            ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p,
            ctypes.c_int]
        lib.tss_bucket_reduce.restype = ctypes.c_int
        try:
            bucket_grid = lib.tss_bucket_grid
        except AttributeError as e:
            # the file name is a content hash of the source, so this is
            # a library that was not made from tsdbstore.cc: the same
            # failure as one that cannot be loaded, never a fallback
            _build_error = f"{path} lacks tss_bucket_grid: {e}"
            raise NativeBuildError(_build_error) from e
        bucket_grid.argtypes = [
            ctypes.c_void_p, ctypes.c_void_p, ctypes.c_int64,
            ctypes.c_int64, ctypes.c_int64, ctypes.c_int64,
            ctypes.c_int64, ctypes.c_int64, ctypes.c_int,
            ctypes.c_int64, ctypes.c_int64, ctypes.c_int,
            ctypes.c_void_p, ctypes.c_void_p, ctypes.c_int]
        bucket_grid.restype = ctypes.c_int64
        lib.tss_bucket_columns.argtypes = [
            ctypes.c_void_p, ctypes.c_void_p, ctypes.c_int64,
            ctypes.c_int64, ctypes.c_int64, ctypes.c_int64,
            ctypes.c_int64, ctypes.c_int64, ctypes.c_int,
            ctypes.c_void_p, ctypes.c_int64, ctypes.c_int64,
            ctypes.c_int, ctypes.c_void_p, ctypes.c_void_p,
            ctypes.c_void_p, ctypes.c_int]
        lib.tss_bucket_columns.restype = ctypes.c_int
        lib.tss_parse_import.argtypes = [
            ctypes.c_char_p, ctypes.c_int64, ctypes.c_void_p,
            ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p,
            ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p,
            ctypes.c_int64, ctypes.c_void_p, ctypes.c_int]
        lib.tss_parse_import.restype = ctypes.c_int64
        lib.tss_count_lines.argtypes = [ctypes.c_char_p,
                                        ctypes.c_int64]
        lib.tss_count_lines.restype = ctypes.c_int64
        lib.tss_append_lines.argtypes = [
            ctypes.c_void_p, ctypes.c_void_p, ctypes.c_int64,
            ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p]
        lib.tss_append_lines.restype = ctypes.c_int64
        lib.tss_format_dps.argtypes = [
            ctypes.c_void_p, ctypes.c_void_p, ctypes.c_int64,
            ctypes.c_int, ctypes.c_int, ctypes.c_char_p,
            ctypes.c_int64]
        lib.tss_format_dps.restype = ctypes.c_int64
        lib.tss_fmt_fast.argtypes = []
        lib.tss_fmt_fast.restype = ctypes.c_int64
        lib.tss_pool_stats.argtypes = [ctypes.c_void_p]
        lib.tss_pool_stats.restype = None
        _lib = lib
        return lib


def _ptr(arr: np.ndarray):
    return arr.ctypes.data_as(ctypes.c_void_p)


def pool_stats() -> dict:
    """The process's worker pool (tsdbstore.cc ``WorkerPool``): parallel
    passes that ran on their caller alone (``inline``), passes that woke
    helpers (``pooled``), and the threads the pool has created since the
    process began (``threads``): constant once the server is warm, since
    no pass creates a thread of its own."""
    out = np.zeros(3, dtype=np.int64)
    load_library().tss_pool_stats(_ptr(out))
    return {"inline": int(out[0]), "pooled": int(out[1]),
            "threads": int(out[2])}


#: tss_bucket_grid's ``fn`` (tsdbstore.cc ``GridFn``)
_GRID_FN_CODES = {"sum": 0, "count": 1, "avg": 2, "min": 3, "max": 4}


class _NativeSeriesView:
    """Buffer-compatible facade over one native series (read side)."""

    def __init__(self, store: "NativeTimeSeriesStore", sid: int):
        self._store = store
        self._sid = sid

    def view(self):
        ts, vals, _ = self.view_full()
        return ts, vals

    def view_full(self):
        lib = self._store._lib
        n = lib.tss_series_length(self._store._h, self._sid)
        ts = np.empty(n, dtype=np.int64)
        vals = np.empty(n, dtype=np.float64)
        ints = np.empty(n, dtype=np.uint8)
        if n:
            # the copy is capped at n and returns the actual count:
            # concurrent appends/deletes between the length call and
            # the read can change the buffer (trim to what was copied)
            got = lib.tss_read_series(self._store._h, self._sid, n,
                                      _ptr(ts), _ptr(vals), _ptr(ints))
            if got < n:
                got = max(got, 0)
                ts, vals, ints = ts[:got], vals[:got], ints[:got]
        return ts, vals, ints.astype(bool)

    def slice_range(self, start_ms: int, end_ms: int):
        ts, vals = self.view()
        lo = np.searchsorted(ts, start_ms, side="left")
        hi = np.searchsorted(ts, end_ms, side="right")
        return ts[lo:hi], vals[lo:hi]

    def __len__(self):
        return int(self._store._lib.tss_series_length(self._store._h,
                                                      self._sid))


class _NativeSeriesRecord:
    __slots__ = ("series_id", "metric_id", "tags", "shard", "buffer")

    def __init__(self, series_id, metric_id, tags, shard, buffer):
        self.series_id = series_id
        self.metric_id = metric_id
        self.tags = tags
        self.shard = shard
        self.buffer = buffer


class NativeTimeSeriesStore:
    """C++-backed TimeSeriesStore (same duck-typed interface)."""

    # fault-injection hook for the scan path (tsd.faults.store_*);
    # set by the owning TSDB, None everywhere else; rollup tier /
    # preagg instances override fault_site with "rollup.store"
    fault_injector = None
    fault_site = "store"

    def __init__(self, num_shards: int | None = None,
                 materialize_threads: int | None = None):
        from opentsdb_tpu.core.store import STORE_INSTANCE_IDS
        self.instance_id = next(STORE_INSTANCE_IDS)
        self._lib = load_library()
        self._h = ctypes.c_void_p(self._lib.tss_create())
        self.num_shards = num_shards or const.salt_buckets()
        self.threads = materialize_threads or min(
            16, os.cpu_count() or 4)
        self._lock = threading.Lock()
        # tsdlint: allow[unbounded-growth] the native backend's store
        # index — live-series-bounded like the Python twin (core/
        # store.py _series); reclamation is the ROADMAP UID item
        self._records: list[_NativeSeriesRecord] = []
        # tsdlint: allow[unbounded-growth] see _records
        self._key_to_sid: dict[tuple, int] = {}
        # tsdlint: allow[unbounded-growth] see _records
        self._metric_index: dict[int, MetricIndex] = {}
        # destructive-op version for read-side caches (cf. the Python
        # backend's counterpart)
        self.mutation_epoch = 0

    def __del__(self):
        try:
            if self._h:
                self._lib.tss_destroy(self._h)
        except Exception:  # noqa: BLE001
            # tsdlint: allow[swallow] a destructor must never raise
            # (interpreter teardown may have torn the lib down first)
            pass

    # -- write path ---------------------------------------------------

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
            native_sid = self._lib.tss_add_series(self._h)
            assert native_sid == len(self._records)
            shard = hash((metric_id, key[1])) % self.num_shards
            rec = _NativeSeriesRecord(
                native_sid, metric_id, key[1], shard,
                _NativeSeriesView(self, native_sid))
            self._records.append(rec)
            idx = self._metric_index.get(metric_id)
            if idx is None:
                idx = self._metric_index[metric_id] = MetricIndex(
                    metric_id)
            idx.add(native_sid, key[1])
            self._key_to_sid[key] = native_sid
            return native_sid

    def get_or_create_series_bulk(self, metric_id: int,
                                  tags_list) -> np.ndarray:
        """Vectorized get_or_create_series: one native bulk allocation
        (``tss_add_series_n``) + one directory/index update per batch
        (see the Python backend's docstring for rationale)."""
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
            # re-check under the lock, then allocate the still-missing
            # contiguously in one native call
            fresh = [i for i in missing
                     if self._key_to_sid.get(keys[i]) is None]
            # dedupe identical keys inside the batch (first wins)
            seen: dict[tuple, int] = {}
            alloc: list[int] = []
            for i in fresh:
                if keys[i] not in seen:
                    seen[keys[i]] = -1
                    alloc.append(i)
            if alloc:
                first = self._lib.tss_add_series_n(self._h, len(alloc))
                assert first == len(self._records)
                idx = self._metric_index.get(metric_id)
                if idx is None:
                    idx = self._metric_index[metric_id] = MetricIndex(
                        metric_id)
                new_sids: list[int] = []
                new_tags: list[tuple[tuple[int, int], ...]] = []
                for j, i in enumerate(alloc):
                    sid = first + j
                    key = keys[i]
                    self._records.append(_NativeSeriesRecord(
                        sid, metric_id, key[1],
                        hash((metric_id, key[1])) % self.num_shards,
                        _NativeSeriesView(self, sid)))
                    self._key_to_sid[key] = sid
                    new_sids.append(sid)
                    new_tags.append(key[1])
                idx.add_bulk(new_sids, new_tags)
            for i in missing:
                out[i] = self._key_to_sid[keys[i]]
        return out

    def append(self, series_id: int, ts_ms: int, value: float,
               is_int: bool = False) -> None:
        rc = self._lib.tss_append(self._h, series_id, ts_ms, value,
                                  int(is_int))
        if rc != 0:
            raise IndexError(f"no such series {series_id}")

    def append_many(self, series_id: int, ts_ms, values,
                    is_int=False) -> None:
        ts = np.ascontiguousarray(ts_ms, dtype=np.int64)
        vals = np.ascontiguousarray(values, dtype=np.float64)
        if isinstance(is_int, np.ndarray):
            ints = np.ascontiguousarray(is_int, dtype=np.uint8)
        else:
            ints = np.full(len(ts), int(bool(is_int)), dtype=np.uint8)
        rc = self._lib.tss_append_many(self._h, series_id, len(ts),
                                       _ptr(ts), _ptr(vals), _ptr(ints))
        if rc != 0:
            raise IndexError(f"no such series {series_id}")

    # -- read path ----------------------------------------------------

    @property
    def points_written(self) -> int:
        return int(self._lib.tss_points_written(self._h))

    def oldest_written_since(self, points_written: int):
        """The smallest timestamp (ms) any append has written since
        ``points_written`` read that value, None if nothing was
        written, :data:`~opentsdb_tpu.core.store.ALL` where the log no
        longer reaches back that far: ``TimeSeriesStore``'s answer,
        from the log ``tsdbstore.cc`` keeps where it bumps the counter
        (``Store::note_written``, every append path's one way to it)."""
        ts = int(self._lib.tss_oldest_written_since(self._h,
                                                    points_written))
        if ts == _INT64_MAX:
            return None
        return ALL if ts == _INT64_MIN else ts

    def series(self, series_id: int) -> _NativeSeriesRecord:
        return self._records[series_id]

    def num_series(self) -> int:
        return len(self._records)

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
        if self.fault_injector is not None:
            self.fault_injector.check(self.fault_site)
        sids = np.ascontiguousarray(series_ids, dtype=np.int64)
        counts = np.empty(len(sids), dtype=np.int64)
        rc = self._lib.tss_count_range(self._h, _ptr(sids), len(sids),
                                       start_ms, end_ms, _ptr(counts),
                                       self.threads)
        if rc != 0:
            raise IndexError("invalid series id in materialize")
        offsets = np.zeros(len(sids), dtype=np.int64)
        np.cumsum(counts[:-1], out=offsets[1:]) if len(sids) > 1 else None
        total = int(counts.sum())
        ts_out = np.empty(total, dtype=np.int64)
        vals_out = np.empty(total, dtype=np.float64)
        sidx_out = np.empty(total, dtype=np.int32)
        if total:
            self._lib.tss_fill_range(
                self._h, _ptr(sids), len(sids), start_ms, end_ms,
                _ptr(offsets), _ptr(counts), _ptr(ts_out),
                _ptr(vals_out), _ptr(sidx_out), self.threads)
        return PointBatch(sids, sidx_out, ts_out, vals_out)

    def append_grid(self, series_ids, bucket_ts: np.ndarray,
                    grid: np.ndarray, mask: np.ndarray) -> int:
        """Bulk write one [S, B] grid: mask-selected cells of row i
        append onto series_ids[i]. C++ thread pool, one lock take per
        row — the rollup job's output path."""
        sids = np.ascontiguousarray(series_ids, dtype=np.int64)
        bts = np.ascontiguousarray(bucket_ts, dtype=np.int64)
        g = np.ascontiguousarray(grid, dtype=np.float64)
        m = np.ascontiguousarray(mask, dtype=np.uint8)
        n = self._lib.tss_append_grid(
            self._h, _ptr(sids), len(sids), _ptr(bts), g.shape[1],
            _ptr(g), _ptr(m), self.threads)
        if n < 0:
            raise IndexError("invalid series id in append_grid")
        return int(n)

    def repair_series(self, series_id: int, min_ts: int, max_ts: int,
                      drop_nonfinite: bool = True) -> int:
        """fsck in-place repair: drop out-of-range timestamps and
        (optionally) non-finite values. Returns points removed."""
        n = self._lib.tss_repair_series(self._h, series_id, min_ts,
                                        max_ts, int(drop_nonfinite))
        if n < 0:
            raise IndexError(f"no such series {series_id}")
        if n:
            self.mutation_epoch += 1
        return int(n)

    def patch_value(self, series_id: int, ts_ms: int, value: float,
                    is_int: bool = False) -> None:
        """fsck in-place repair: overwrite the value at an exact
        timestamp (raises KeyError when absent)."""
        rc = self._lib.tss_patch_value(self._h, series_id, ts_ms,
                                       float(value), int(is_int))
        if rc == -1:
            raise IndexError(f"no such series {series_id}")
        if rc == -2:
            raise KeyError(f"series {series_id} has no point at "
                           f"{ts_ms}")
        self.mutation_epoch += 1

    def count_range(self, series_ids: Sequence[int], start_ms: int,
                    end_ms: int) -> np.ndarray:
        sids = np.ascontiguousarray(series_ids, dtype=np.int64)
        counts = np.empty(len(sids), dtype=np.int64)
        rc = self._lib.tss_count_range(self._h, _ptr(sids), len(sids),
                                       start_ms, end_ms, _ptr(counts),
                                       self.threads)
        if rc != 0:
            raise IndexError("invalid series id in count_range")
        return counts

    def materialize_padded(self, series_ids: Sequence[int],
                           start_ms: int, end_ms: int) -> PaddedBatch:
        """Row-padded materialize: reuses ``tss_fill_range`` by passing
        per-row offsets ``i * Pmax`` — each series' contiguous run lands
        in its own row of the padded buffers, no extra pass."""
        if self.fault_injector is not None:
            self.fault_injector.check(self.fault_site)
        sids = np.ascontiguousarray(series_ids, dtype=np.int64)
        counts = np.empty(len(sids), dtype=np.int64)
        rc = self._lib.tss_count_range(self._h, _ptr(sids), len(sids),
                                       start_ms, end_ms, _ptr(counts),
                                       self.threads)
        if rc != 0:
            raise IndexError("invalid series id in materialize")
        pmax = max(1, int(counts.max())) if len(sids) else 1
        values2d = np.full(len(sids) * pmax, np.nan)
        ts2d = np.zeros(len(sids) * pmax, dtype=np.int64)
        if counts.sum():
            offsets = np.arange(len(sids), dtype=np.int64) * pmax
            sidx_scratch = np.empty(len(sids) * pmax, dtype=np.int32)
            # fill writes counts[i] elements at offsets[i]; sidx output
            # is positional scratch we don't need in the padded layout
            self._lib.tss_fill_range(
                self._h, _ptr(sids), len(sids), start_ms, end_ms,
                _ptr(offsets), _ptr(counts), _ptr(ts2d),
                _ptr(values2d), _ptr(sidx_scratch), self.threads)
        return PaddedBatch(sids, values2d.reshape(len(sids), pmax),
                           ts2d.reshape(len(sids), pmax), counts)

    def append_lines(self, sids, ts_ms, values, is_int) -> int:
        """Scatter-append: element i lands on series ``sids[i]``
        (negative skips). One native call for a whole import buffer."""
        sid_arr = np.ascontiguousarray(sids, dtype=np.int64)
        ts_arr = np.ascontiguousarray(ts_ms, dtype=np.int64)
        val_arr = np.ascontiguousarray(values, dtype=np.float64)
        int_arr = np.ascontiguousarray(is_int, dtype=np.uint8)
        n = self._lib.tss_append_lines(self._h, _ptr(sid_arr),
                                       len(sid_arr), _ptr(ts_arr),
                                       _ptr(val_arr), _ptr(int_arr))
        if n < 0:
            raise IndexError("invalid series id in append_lines")
        return int(n)

    def bucket_reduce(self, series_ids, start_ms: int, end_ms: int,
                      t0: int, interval_ms: int, nbuckets: int,
                      want_minmax: bool = False):
        """Fused range-scan + fixed-interval pre-reduction: one C++
        pass returns [S, B] sum/count (and min/max on request) grids —
        the device then starts at the grid stage of the pipeline
        instead of receiving every point (SURVEY §7: HBM bandwidth is
        the bottleneck; don't ship what the host can pre-reduce 60x)."""
        if self.fault_injector is not None:
            self.fault_injector.check(self.fault_site)
        sids = np.ascontiguousarray(series_ids, dtype=np.int64)
        s = len(sids)
        sums = np.empty((s, nbuckets), dtype=np.float64)
        cnts = np.empty((s, nbuckets), dtype=np.float64)
        mins = maxs = None
        pmin = pmax = None
        if want_minmax:
            mins = np.empty((s, nbuckets), dtype=np.float64)
            maxs = np.empty((s, nbuckets), dtype=np.float64)
            pmin, pmax = _ptr(mins), _ptr(maxs)
        rc = self._lib.tss_bucket_reduce(
            self._h, _ptr(sids), s, start_ms, end_ms, t0, interval_ms,
            nbuckets, _ptr(sums), _ptr(cnts), pmin, pmax, self.threads)
        if rc != 0:
            raise IndexError("invalid series id in bucket_reduce")
        return sums, cnts, mins, maxs

    def bucket_grid(self, series_ids, start_ms: int, end_ms: int,
                    t0: int, interval_ms: int, nbuckets: int, fn: str,
                    grid: np.ndarray, has_data: np.ndarray) -> int:
        """:meth:`bucket_reduce`'s pass, finished where the tail
        program reads it: statistic ``fn`` (sum | count | avg | min |
        max) of every bucket written ONCE into the caller's padded
        ``grid`` ([s_pad, b_pad], float32 or float64) with the presence
        mask ``has_data`` beside it; empty buckets and both pads hold
        NaN / False. avg divides in f64 and rounds to the grid's type
        once, so the cells are the bits ``fill_padded_grid`` (the
        engine's statement of the contract) writes from
        ``bucket_reduce``'s grids. Returns the points reduced."""
        if self.fault_injector is not None:
            self.fault_injector.check(self.fault_site)
        sids = np.ascontiguousarray(series_ids, dtype=np.int64)
        if grid.dtype not in (np.float32, np.float64) \
                or has_data.dtype != np.bool_ \
                or grid.ndim != 2 or grid.shape != has_data.shape \
                or not grid.flags.c_contiguous \
                or not has_data.flags.c_contiguous \
                or not (grid.flags.writeable and has_data.flags.writeable):
            raise ValueError(
                "bucket_grid writes a C-contiguous float32/float64 grid "
                "and a bool mask of one [s_pad, b_pad] shape")
        s_pad, b_pad = grid.shape
        if s_pad < len(sids) or b_pad < nbuckets:
            raise ValueError(
                f"a [{s_pad}, {b_pad}] grid cannot hold "
                f"{len(sids)} series x {nbuckets} buckets")
        num_points = self._lib.tss_bucket_grid(
            self._h, _ptr(sids), len(sids), start_ms, end_ms, t0,
            interval_ms, nbuckets, _GRID_FN_CODES[fn], s_pad, b_pad,
            int(grid.dtype == np.float64), _ptr(grid), _ptr(has_data),
            self.threads)
        if num_points < 0:
            raise IndexError("invalid series id in bucket_grid")
        return int(num_points)

    def bucket_columns(self, series_ids, start_ms: int, end_ms: int,
                       t0: int, interval_ms: int, nbuckets: int, fn: str,
                       wanted, cols: np.ndarray,
                       masks: np.ndarray) -> np.ndarray:
        """:meth:`bucket_grid`'s cells a bucket at a time, for a caller
        that keeps a metric's whole buckets and lacks a few: bucket
        ``wanted[w]`` of the window (indexes, strictly rising) is
        written into ``cols[w]`` and ``masks[w]`` (``[len(wanted),
        s_pad]``, float32 or float64, and bool), the bits
        :meth:`bucket_grid` writes into that column of its grid; a
        bucket the window cuts holds the points inside ``[start_ms,
        end_ms]`` alone. One walk, one lock take a series, whatever is
        wanted; the same walk returns each series' point count of the
        whole window (:meth:`count_range`'s, NaN points counted)."""
        if self.fault_injector is not None:
            self.fault_injector.check(self.fault_site)
        sids = np.ascontiguousarray(series_ids, dtype=np.int64)
        wanted = np.ascontiguousarray(wanted, dtype=np.int64)
        if cols.dtype not in (np.float32, np.float64) \
                or masks.dtype != np.bool_ \
                or cols.ndim != 2 or cols.shape != masks.shape \
                or not cols.flags.c_contiguous \
                or not masks.flags.c_contiguous \
                or not (cols.flags.writeable and masks.flags.writeable):
            raise ValueError(
                "bucket_columns writes C-contiguous float32/float64 "
                "columns and bool masks of one [wanted, s_pad] shape")
        if cols.shape[0] != len(wanted) or cols.shape[1] < len(sids):
            raise ValueError(
                f"{cols.shape} columns cannot hold {len(wanted)} "
                f"buckets of {len(sids)} series")
        counts = np.empty(len(sids), dtype=np.int64)
        rc = self._lib.tss_bucket_columns(
            self._h, _ptr(sids), len(sids), start_ms, end_ms, t0,
            interval_ms, nbuckets, _GRID_FN_CODES[fn], _ptr(wanted),
            len(wanted), cols.shape[1], int(cols.dtype == np.float64),
            _ptr(cols), _ptr(masks), _ptr(counts), self.threads)
        if rc != 0:
            raise IndexError(
                "invalid series id or bucket in bucket_columns")
        return counts

    def shards_of(self, series_ids: Iterable[int]) -> np.ndarray:
        return np.asarray([self._records[s].shard for s in series_ids],
                          dtype=np.int32)

    def delete_range(self, series_ids, start_ms: int,
                     end_ms: int) -> int:
        deleted = 0
        for sid in series_ids:
            n = int(self._lib.tss_delete_range(self._h, int(sid),
                                               start_ms, end_ms))
            if n > 0:
                deleted += n
        if deleted:
            self.mutation_epoch += 1
        return deleted

    def total_points(self) -> int:
        return sum(int(self._lib.tss_series_length(self._h, sid))
                   for sid in range(len(self._records)))

    def memory_info(self) -> dict:
        """Memory-footprint report (health/stats). The C++ arena does
        not expose per-series capacity, so resident is estimated as
        live bytes (17 bytes/point: int64 ts + float64 value + flag);
        cached on the write/delete counters like the Python twin."""
        key = (self.points_written, self.mutation_epoch,
               len(self._records))
        cached = getattr(self, "_memory_info_cache", None)
        if cached is not None and cached[0] == key:
            return cached[1]
        points = self.total_points()
        info = {"series": len(self._records), "points": points,
                "resident_bytes": points * 17, "live_bytes": points * 17,
                "dead_bytes": 0, "estimated": True}
        self._memory_info_cache = (key, info)
        return info

    def collect_stats(self, collector) -> None:
        collector.record("storage.series.count", self.num_series())
        collector.record("storage.points.written", self.points_written)
        log = np.zeros(2, dtype=np.int64)
        self._lib.tss_written_log_stats(self._h, _ptr(log))
        collector.record("storage.written_log.entries", int(log[0]))
        collector.record("storage.written_log.floor_version",
                         int(log[1]))
        collector.record("storage.shards", self.num_shards)
        collector.record("storage.backend", 1, backend="native")
        mi = self.memory_info()
        collector.record("storage.resident_bytes",
                         mi["resident_bytes"])
        collector.record("storage.live_bytes", mi["live_bytes"])
        collector.record("storage.dead_bytes", mi["dead_bytes"])
        # the process's pool, not this store's (the stores share it)
        ps = pool_stats()
        collector.record("storage.native.passes", ps["inline"],
                         mode="inline")
        collector.record("storage.native.passes", ps["pooled"],
                         mode="pooled")
        collector.record("storage.native.pool_threads", ps["threads"])


IMPORT_ERRORS = {
    1: "too few fields (metric ts value tag=value...)",
    2: "invalid timestamp",
    3: "invalid value",
    4: "malformed tag (need tagk=tagv) or too many tags",
    5: "invalid character in metric or tag",
}


class ParsedImport:
    """Columnar result of one native import-buffer parse.

    ``group_ids[i]`` labels line i with its distinct (metric, sorted
    tags) key (-1 for errors/blanks); ``rep_lines[g]`` is group g's
    first line as bytes, so the caller resolves metric/tag strings and
    UIDs once per distinct series instead of once per point (the whole
    point of the bulk path — ref: TextImporter.java:40 importing via
    per-series WritableDataPoints batches)."""

    __slots__ = ("ts", "values", "is_int", "group_ids", "errors",
                 "rep_lines", "num_groups", "num_lines")

    def __init__(self, ts, values, is_int, group_ids, errors,
                 rep_lines, num_groups, num_lines):
        self.ts = ts                  # int64 [L] raw (s or ms)
        self.values = values          # float64 [L]
        self.is_int = is_int          # uint8 [L]
        self.group_ids = group_ids    # int64 [L], -1 = error/blank
        self.errors = errors          # int32 [L], 0 ok / -1 blank / >0
        self.rep_lines = rep_lines    # list[bytes], one per group
        self.num_groups = num_groups
        self.num_lines = num_lines


# byte classes mirrored from tsdbstore.cc's parser: names allow the
# reference's charset (alnum -_./ plus UTF-8 lead/continuation bytes,
# re-validated python-side for non-ASCII); values allow the decimal
# float shape ONLY — strtod leniency (nan/inf/hex) and python
# int()/float() leniency (underscores, unicode digits) must both be
# rejected or a malformed value silently stores the wrong number
_NAME_BYTES = frozenset(
    b"abcdefghijklmnopqrstuvwxyz"
    b"ABCDEFGHIJKLMNOPQRSTUVWXYZ0123456789-_./")
_FLOAT_BYTES = frozenset(b"0123456789.+-eE")


def _py_valid_name(tok: bytes) -> bool:
    return bool(tok) and all(c in _NAME_BYTES or c >= 0x80
                             for c in tok)


def _parse_import_py(buf: bytes) -> ParsedImport:
    """Pure-Python twin of ``tss_parse_import`` for toolchain-less
    hosts (numpy column outputs, same error codes / strict value
    shape / grouping semantics) — the columnar ingest decode must not
    depend on a C++ compiler being present."""
    lines = buf.split(b"\n")
    if buf.endswith(b"\n"):
        lines.pop()
    n = len(lines)
    ts = np.zeros(n, dtype=np.int64)
    vals = np.zeros(n, dtype=np.float64)
    ints = np.zeros(n, dtype=np.uint8)
    gids = np.full(n, -1, dtype=np.int64)
    errs = np.zeros(n, dtype=np.int32)
    group_map: dict[bytes, int] = {}
    reps: list[bytes] = []
    prev_key = None
    prev_gid = -1
    max_ts = 1 << 47
    for i, line in enumerate(lines):
        if line.endswith(b"\r"):
            line = line[:-1]
        stripped = line.strip()
        if not stripped or stripped.startswith(b"#"):
            errs[i] = -1
            continue
        toks = line.replace(b"\t", b" ").split()
        if len(toks) < 4:
            errs[i] = 1
            continue
        if len(toks) > 16:
            errs[i] = 4
            continue
        if not _py_valid_name(toks[0]):
            errs[i] = 5
            continue
        t = toks[1]
        if not (0 < len(t) < 15 and t.isdigit()):
            errs[i] = 2
            continue
        tval = int(t)
        if tval <= 0 or tval > max_ts:
            errs[i] = 2
            continue
        ts[i] = tval
        v = toks[2]
        st = 1 if v[:1] in (b"-", b"+") else 0
        digits = v[st:]
        if digits and len(digits) < 19 and digits.isdigit():
            acc = int(digits)
            vals[i] = -float(acc) if v[:1] == b"-" else float(acc)
            ints[i] = 1
        else:
            ok = 0 < len(v) < 64 and all(c in _FLOAT_BYTES for c in v)
            if ok:
                try:
                    fv = float(v)
                    ok = fv == fv  # strtod parity: NaN rejected
                except ValueError:
                    ok = False
            if not ok:
                errs[i] = 3
                continue
            vals[i] = fv
            ints[i] = 0
        tags = toks[3:]
        if len(tags) > 8:  # the reference's hard tag cap
            errs[i] = 4
            continue
        bad = 0
        for tag in tags:
            eq = tag.find(b"=")
            if eq <= 0 or eq == len(tag) - 1:
                bad = 4
                break
            if not _py_valid_name(tag[:eq]) or \
                    not _py_valid_name(tag[eq + 1:]):
                bad = 5
                break
        if bad:
            errs[i] = bad
            continue
        key = toks[0] + b" " + b" ".join(sorted(tags))
        if prev_gid >= 0 and key == prev_key:
            gid = prev_gid
        else:
            gid = group_map.get(key)
            if gid is None:
                gid = len(group_map)
                group_map[key] = gid
                reps.append(line)
            prev_key, prev_gid = key, gid
        gids[i] = gid
    return ParsedImport(ts, vals, ints, gids, errs, reps,
                        len(group_map), n)


def parse_import_buffer(buf: bytes,
                        threads: int | None = None) -> ParsedImport:
    """Parse a whole import text buffer in one native pass, parallel
    over newline-aligned chunks (pure-Python columnar fallback when
    the native library cannot build)."""
    if not buf:
        e = np.empty(0, dtype=np.int64)
        return ParsedImport(e, np.empty(0), np.empty(0, np.uint8),
                            e.copy(), np.empty(0, np.int32), [], 0, 0)
    try:
        lib = load_library()
    except NativeBuildError:
        return _parse_import_py(buf)
    if threads is None:
        threads = min(16, os.cpu_count() or 1)
    nl = lib.tss_count_lines(buf, len(buf))
    ts = np.empty(nl, dtype=np.int64)
    vals = np.empty(nl, dtype=np.float64)
    ints = np.empty(nl, dtype=np.uint8)
    gids = np.empty(nl, dtype=np.int64)
    errs = np.empty(nl, dtype=np.int32)
    rep_off = np.empty(nl, dtype=np.int64)
    rep_len = np.empty(nl, dtype=np.int64)
    nlines = ctypes.c_int64(0)
    ng = lib.tss_parse_import(
        buf, len(buf), _ptr(ts), _ptr(vals), _ptr(ints), _ptr(gids),
        _ptr(errs), _ptr(rep_off), _ptr(rep_len), nl,
        ctypes.byref(nlines), threads)
    if ng < 0:
        raise RuntimeError("import parse overflow")
    n = nlines.value
    reps = [bytes(buf[rep_off[g]:rep_off[g] + rep_len[g]])
            for g in range(ng)]
    return ParsedImport(ts[:n], vals[:n], ints[:n], gids[:n], errs[:n],
                        reps, int(ng), n)


def format_dps_is_fast() -> bool:
    """True when the native dps formatter writes doubles through real
    ``std::to_chars`` (libstdc++ >= 11). On gcc-10 hosts the library
    builds (the formatter falls back to a verified %g precision walk,
    value-identical output) but that walk is SLOWER than the Python
    columnar bulk formatter, so serializers should skip native
    formatting there. Raises NativeBuildError when no library."""
    return bool(load_library().tss_fmt_fast())


def format_dps(ts_ms: np.ndarray, vals: np.ndarray, seconds: bool,
               as_arrays: bool) -> bytes:
    """JSON-format one series' dps natively (comma-joined entries, no
    envelope) — ~20x the Python per-point formatting rate. Raises
    NativeBuildError when no compiler exists (callers fall back)."""
    lib = load_library()
    ts_arr = np.ascontiguousarray(ts_ms, dtype=np.int64)
    val_arr = np.ascontiguousarray(vals, dtype=np.float64)
    cap = len(ts_arr) * 64 + 64
    buf = ctypes.create_string_buffer(cap)
    n = lib.tss_format_dps(_ptr(ts_arr), _ptr(val_arr), len(ts_arr),
                           int(seconds), int(as_arrays), buf, cap)
    if n < 0:
        raise RuntimeError("format_dps buffer overflow")
    return buf.raw[:n]


def make_store(config, num_shards: int | None = None):
    """Storage backend factory honoring ``tsd.storage.backend``.

    Defaults to the C++ engine (libtsdbstore) — the production path,
    preserving the reference's swappable-storage-client shape
    (asynchbase/asyncbigtable/asynccassandra, SURVEY.md §5.8); set
    ``tsd.storage.backend=memory`` for the pure-Python twin, e.g. where
    no compiler exists. Falls back automatically if the build fails.
    """
    backend = config.get_string("tsd.storage.backend", "native")
    if backend == "native":
        try:
            return NativeTimeSeriesStore(num_shards=num_shards)
        except NativeBuildError as e:
            import logging
            logging.getLogger(__name__).warning(
                "native store unavailable (%s); using memory backend", e)
    from opentsdb_tpu.core.store import TimeSeriesStore
    return TimeSeriesStore(num_shards=num_shards)
