"""Histogram / sketch datapoints
(ref: ``src/core/SimpleHistogram.java``, ``HistogramCodecManager.java``).

Distribution-valued series: each datapoint is a bucketed histogram blob.
Query-time aggregation merges histograms bucket-wise (SUM — the only
aggregation the reference supports, ``HistogramAggregation.java:20``)
then extracts percentiles (``SimpleHistogram.percentile`` :133). On the
TPU path a column of histograms becomes a dense ``[series, buckets]``
matrix so merge is a segment-sum and percentile extraction a vectorized
cumsum-searchsorted — see :mod:`opentsdb_tpu.ops.percentile`.

Wire format: first byte of the stored blob is the codec id (matching the
reference's ``HistogramDataPointCodecManager`` contract); the built-in
:class:`SimpleHistogramCodec` (id 0x01) encodes bucket bounds + counts
with struct packing (the reference uses Kryo, a Java-only serde; the
framing byte and semantics are preserved, the payload encoding is not
Java-compatible by construction).
"""

from __future__ import annotations

import struct
import threading
from typing import Sequence

import numpy as np


class SimpleHistogram:
    """Explicit-bucket histogram (ref: SimpleHistogram.java:43).

    Buckets are [lo, hi) pairs with counts, plus underflow/overflow
    counters. Percentile uses linear interpolation position = rank
    weighted into the bucket, matching the reference's midpoint
    convention (SimpleHistogram.java:133-170: the bucket whose cumulative
    count crosses the rank contributes its midpoint).
    """

    def __init__(self, bounds: Sequence[float] | None = None):
        # bounds: ascending edges; bucket i = [bounds[i], bounds[i+1])
        self.bounds: list[float] = list(bounds) if bounds is not None else []
        n = max(0, len(self.bounds) - 1)
        self.counts: list[int] = [0] * n
        self.underflow = 0
        self.overflow = 0
        # query-path caches (the engine walks hundreds of thousands of
        # stored histograms per cold query; recomputing these per point
        # dominated that walk). Mutators reset them.
        self._row: np.ndarray | None = None
        self._bkey: tuple | None = None

    def add(self, value: float, count: int = 1) -> None:
        if not self.bounds:
            raise ValueError("histogram has no buckets")
        if value < self.bounds[0]:
            self.underflow += count
            return
        if value >= self.bounds[-1]:
            self.overflow += count
            return
        idx = int(np.searchsorted(self.bounds, value, side="right")) - 1
        self.counts[idx] += count
        self._invalidate()

    def set_bucket(self, lo: float, hi: float, count: int) -> None:
        """Set a bucket count by its bounds, adding the bucket if new."""
        self._invalidate()
        if not self.bounds:
            self.bounds = [lo, hi]
            self.counts = [count]
            return
        for i in range(len(self.counts)):
            if self.bounds[i] == lo and self.bounds[i + 1] == hi:
                self.counts[i] = count
                return
        if lo >= self.bounds[-1]:
            if lo != self.bounds[-1]:
                self.bounds.append(lo)
                self.counts.append(0)
            self.bounds.append(hi)
            self.counts.append(count)
        elif hi <= self.bounds[0]:
            if hi != self.bounds[0]:
                self.bounds.insert(0, hi)
                self.counts.insert(0, 0)
            self.bounds.insert(0, lo)
            self.counts.insert(0, count)
        else:
            raise ValueError(
                f"bucket [{lo},{hi}) overlaps existing bounds {self.bounds}")

    def total_count(self) -> int:
        return sum(self.counts) + self.underflow + self.overflow

    def merge(self, other: "SimpleHistogram") -> None:
        """Bucket-wise SUM (ref: HistogramAggregation SUM)."""
        if self.bounds and other.bounds and self.bounds != other.bounds:
            raise ValueError("cannot merge histograms with different buckets")
        if not self.bounds:
            self.bounds = list(other.bounds)
            self.counts = list(other.counts)
        else:
            for i, c in enumerate(other.counts):
                self.counts[i] += c
        self.underflow += other.underflow
        self.overflow += other.overflow
        self._invalidate()

    def percentile(self, perc: float) -> float:
        """(ref: SimpleHistogram.percentile :133) Returns the midpoint of
        the bucket containing the requested rank; overflow returns the
        top bound, underflow the bottom."""
        if not 0 <= perc <= 100:
            raise ValueError(f"invalid percentile {perc}")
        total = self.total_count()
        if total == 0:
            return 0.0
        target = total * perc / 100.0
        acc = self.underflow
        if acc >= target and self.underflow:
            return float(self.bounds[0]) if self.bounds else 0.0
        for i, c in enumerate(self.counts):
            acc += c
            if acc >= target:
                return (self.bounds[i] + self.bounds[i + 1]) / 2.0
        return float(self.bounds[-1]) if self.bounds else 0.0

    # -- vector form for the TPU path ----------------------------------

    def counts_array(self) -> np.ndarray:
        if self._row is None:
            self._row = np.asarray(self.counts, dtype=np.float64)
        return self._row

    def bounds_key(self) -> tuple:
        """Hashable bounds identity (cached) for uniformity checks."""
        if self._bkey is None:
            self._bkey = tuple(self.bounds)
        return self._bkey

    def _invalidate(self) -> None:
        self._row = None
        self._bkey = None

    def to_json(self) -> dict:
        return {
            "buckets": {f"{self.bounds[i]},{self.bounds[i+1]}": c
                        for i, c in enumerate(self.counts)},
            "underflow": self.underflow,
            "overflow": self.overflow,
        }


class HistogramStats:
    """Counters of the histogram write and query paths, exported at
    ``/api/stats`` by :meth:`TSDB.collect_stats`."""

    def __init__(self):
        self._lock = threading.Lock()
        self.bulk_points = 0     # landed by the columnar decode
        self.slow_points = 0     # landed a point at a time
        self.query_points = 0    # stored points merged by requests
        self.upload_bytes = 0    # bytes device_put by requests
        self.wide_counts = 0     # requests answered again in float64

    def add(self, **grown: int) -> None:
        """Grow counters by name; writers and query workers call side
        by side, and ``+=`` alone would lose an update."""
        with self._lock:
            for name, n in grown.items():
                setattr(self, name, getattr(self, name) + n)

    def collect_stats(self, collector, cache) -> None:
        collector.record("histogram.bulk_points", self.bulk_points)
        collector.record("histogram.slow_points", self.slow_points)
        collector.record("query.histogram.points", self.query_points)
        collector.record("query.histogram.upload_bytes",
                         self.upload_bytes)
        collector.record("query.histogram.wide_counts",
                         self.wide_counts)
        collector.record(
            "query.histogram.resident_bytes",
            cache.bytes_of(RESIDENT_KEY) if cache is not None else 0)


#: first element of a resident entry's key in the HBM cache
RESIDENT_KEY = "hist"


class HistogramArena:
    """Columnar store of one metric's histogram points.

    The reference keeps histogram cells beside scalar cells and walks
    them through HistogramSpan/HistogramRowSeq iterators; the first
    TPU build mirrored that with per-series Python lists of
    ``SimpleHistogram`` objects, which made a 200k-point cold query
    spend ~1.6s in a per-point host loop. Here points append into flat
    parallel arrays (ts, series id, counts row) grouped by bucket
    bounds — a query slices with vectorized masks, no per-point (or
    per-series) Python at all. One sub-arena per distinct bounds
    tuple: the uniform fast path is ``len(groups) == 1``.
    """

    class _Sub:
        __slots__ = ("bounds", "ts", "sid", "rows", "under", "over",
                     "n")

        def __init__(self, bounds: tuple, nb: int):
            self.bounds = bounds
            cap = 1024
            self.ts = np.empty(cap, dtype=np.int64)
            self.sid = np.empty(cap, dtype=np.int64)
            # float64 rows: exact for counts up to 2^53 (the codec's
            # u64 realistic range); float32 would silently round past
            # 2^24. The device layout is float32, and the query
            # program reports a merged total that reaches 2^24: such a
            # request is answered from these rows
            # (query/histogram_engine.py).
            self.rows = np.empty((cap, nb), dtype=np.float64)
            self.under = np.empty(cap, dtype=np.int64)
            self.over = np.empty(cap, dtype=np.int64)
            self.n = 0

        def _grow(self, need: int) -> None:
            """Capacity for ``need`` points, doubling. An array nobody
            holds a view of grows where it stands (``ndarray.resize``:
            ``realloc``, which moves a large block by remapping its
            pages, so the arena never holds two copies of itself: at
            12M points x 64 bins the float64 rows are 6.1 GB). One
            that a captured snapshot still reads cannot (numpy refuses
            to resize it) and is REPLACED by a larger copy as before,
            which leaves the snapshot intact."""
            cap = max(need, len(self.ts) * 2)
            for name in ("ts", "sid", "rows", "under", "over"):
                shape = (cap,) + getattr(self, name).shape[1:]
                try:
                    # on the attribute itself: a local name for the
                    # array would be one reference too many for numpy
                    getattr(self, name).resize(shape)
                except ValueError:
                    old = getattr(self, name)
                    new = np.empty(shape, dtype=old.dtype)
                    new[:self.n] = old[:self.n]
                    setattr(self, name, new)

        def append(self, ts_ms: int, sid: int, row: np.ndarray,
                   under: int = 0, over: int = 0) -> None:
            if self.n == len(self.ts):
                self._grow(self.n + 1)
            self.ts[self.n] = ts_ms
            self.sid[self.n] = sid
            self.rows[self.n] = row
            self.under[self.n] = under
            self.over[self.n] = over
            self.n += 1

        def append_many(self, ts: np.ndarray, sid: np.ndarray,
                        rows: np.ndarray, under=None, over=None) -> None:
            k = len(ts)
            need = self.n + k
            if need > len(self.ts):
                self._grow(need)
            self.ts[self.n:need] = ts
            self.sid[self.n:need] = sid
            self.rows[self.n:need] = rows
            self.under[self.n:need] = 0 if under is None else under
            self.over[self.n:need] = 0 if over is None else over
            self.n = need

        def snapshot(self):
            """(ts[n], sid[n], rows[n, NB]) — stable views.

            MUST be captured under the owning TSDB's _histogram_lock
            (appends run under it): the refs + n are read atomically,
            and append-only semantics mean rows [0, n) of the captured
            arrays never mutate afterwards (growth REPLACES an array
            that a snapshot still reads, see :meth:`_grow`)."""
            ts, sid, rows, n = self.ts, self.sid, self.rows, self.n
            return ts[:n], sid[:n], rows[:n]

        def view(self):
            """Alias of :meth:`snapshot` (same locking contract)."""
            return self.snapshot()

    def __init__(self):
        self.groups: dict[tuple, HistogramArena._Sub] = {}
        self.total_points = 0

    def append(self, ts_ms: int, sid: int,
               hist: SimpleHistogram) -> None:
        key = hist.bounds_key()
        sub = self.groups.get(key)
        if sub is None:
            sub = self.groups[key] = HistogramArena._Sub(
                key, max(1, len(key) - 1))
        sub.append(ts_ms, sid, hist.counts_array(),
                   hist.underflow, hist.overflow)
        self.total_points += 1

    def append_run(self, ts_ms: np.ndarray, sid: int, bounds: tuple,
                   counts: np.ndarray, under: np.ndarray,
                   over: np.ndarray) -> None:
        """The points of one series that :func:`decode_simple_run`
        decoded together, landed by one ``append_many``."""
        sub = self.groups.get(bounds)
        if sub is None:
            sub = self.groups[bounds] = HistogramArena._Sub(
                bounds, max(1, len(bounds) - 1))
        sub.append_many(ts_ms, sid, counts, under, over)
        self.total_points += len(ts_ms)

    def iter_points(self):
        """(ts, sid, bounds, counts_row) over every point — the slow
        generic walk, for persistence and small admin paths."""
        for sub in self.groups.values():
            ts, sid, rows = sub.view()
            for i in range(sub.n):
                yield int(ts[i]), int(sid[i]), sub.bounds, rows[i]

    def purge_before(self, cutoff_ms: int) -> int:
        """Lifecycle retention: drop every point with ts < cutoff_ms,
        shrinking the arrays to fit. Returns points removed. MUST run
        under the owning TSDB's ``_histogram_lock`` (the same contract
        as append/snapshot); the filtered arrays REPLACE the old ones,
        so previously captured snapshot views stay intact."""
        removed = 0
        for key in list(self.groups):
            sub = self.groups[key]
            keep = sub.ts[:sub.n] >= cutoff_ms
            kept = int(keep.sum())
            if kept == sub.n:
                continue
            removed += sub.n - kept
            if kept == 0:
                del self.groups[key]
                continue
            sub.ts = sub.ts[:sub.n][keep].copy()
            sub.sid = sub.sid[:sub.n][keep].copy()
            sub.rows = sub.rows[:sub.n][keep].copy()
            sub.under = sub.under[:sub.n][keep].copy()
            sub.over = sub.over[:sub.n][keep].copy()
            sub.n = kept
        self.total_points -= removed
        return removed


class HistogramCodec:
    """Codec ABI (ref: ``HistogramDataPointCodec.java``)."""

    id: int = 0

    def encode(self, hist: SimpleHistogram, include_id: bool) -> bytes:
        raise NotImplementedError

    def decode(self, data: bytes, includes_id: bool) -> SimpleHistogram:
        raise NotImplementedError


class SimpleHistogramCodec(HistogramCodec):
    """Built-in codec, id 0x01. Payload: u16 n_edges, f64*edges,
    u64*counts(n_edges-1), u64 underflow, u64 overflow."""

    id = 0x01

    def encode(self, hist: SimpleHistogram, include_id: bool = True) -> bytes:
        n = len(hist.bounds)
        out = bytearray()
        if include_id:
            out.append(self.id)
        out += struct.pack(">H", n)
        out += struct.pack(f">{n}d", *hist.bounds)
        out += struct.pack(f">{max(0, n - 1)}Q", *hist.counts)
        out += struct.pack(">QQ", hist.underflow, hist.overflow)
        return bytes(out)

    def decode(self, data: bytes, includes_id: bool = True) -> SimpleHistogram:
        pos = 1 if includes_id else 0
        (n,) = struct.unpack_from(">H", data, pos)
        pos += 2
        bounds = struct.unpack_from(f">{n}d", data, pos)
        pos += 8 * n
        counts = struct.unpack_from(f">{max(0, n - 1)}Q", data, pos)
        pos += 8 * max(0, n - 1)
        under, over = struct.unpack_from(">QQ", data, pos)
        hist = SimpleHistogram(bounds)
        hist.counts = list(counts)
        hist.underflow = under
        hist.overflow = over
        return hist


def decode_simple_run(blobs: Sequence[bytes]):
    """Blobs of the built-in codec (id 0x01, the id byte included)
    that share one bounds header, decoded by one ``np.frombuffer``:
    ``(bounds, counts [k, buckets] float64, underflow [k] int64,
    overflow [k] int64)``, value for value what
    :meth:`SimpleHistogramCodec.decode` gives each blob. None where
    the blobs are no such run (another codec, another length or
    header, a truncated or over-long blob, fewer than two edges, a NaN
    edge, a counter past int64): the caller then decodes them one by
    one, which also says what is wrong with which."""
    first = blobs[0]
    size = len(first)
    if size < 3 or first[0] != SimpleHistogramCodec.id:
        return None
    edges = (first[1] << 8) | first[2]
    head = 3 + 8 * edges
    if edges < 2 or size != head + 8 * (edges - 1) + 16:
        return None
    header = first[:head]
    if not all(len(b) == size and b.startswith(header) for b in blobs):
        return None
    bounds = struct.unpack_from(f">{edges}d", first, 3)
    if any(b != b for b in bounds):
        return None
    body = np.frombuffer(b"".join(blobs), dtype=np.uint8) \
        .reshape(len(blobs), size)[:, head:]
    words = np.ascontiguousarray(body).view(">u8")
    tail = words[:, edges - 1:]
    if (tail >> np.uint64(63)).any():
        return None
    return (bounds, words[:, :edges - 1].astype(np.float64),
            tail[:, 0].astype(np.int64), tail[:, 1].astype(np.int64))


class HistogramCodecManager:
    """id -> codec registry (ref: HistogramCodecManager.java:47).

    Configured via ``tsd.core.histograms.config`` as a JSON map of
    ``{"dotted.CodecClass": id}`` like the reference; the built-in simple
    codec is always registered at id 1.
    """

    def __init__(self, config=None):
        self._by_id: dict[int, HistogramCodec] = {}
        self.register(SimpleHistogramCodec())
        if config is not None:
            spec = config.get_string("tsd.core.histograms.config", "")
            if spec:
                import json
                from opentsdb_tpu.utils.plugin import load_class
                mapping = json.loads(spec)
                for path, codec_id in mapping.items():
                    codec = load_class(path)()
                    codec.id = int(codec_id)
                    self.register(codec)

    def register(self, codec: HistogramCodec) -> None:
        self._by_id[codec.id] = codec

    def codec(self, codec_id: int) -> HistogramCodec:
        try:
            return self._by_id[codec_id]
        except KeyError:
            raise ValueError(f"no histogram codec with id {codec_id}") from None

    def decode(self, blob: bytes) -> SimpleHistogram:
        if not blob:
            raise ValueError("empty histogram blob")
        return self.codec(blob[0]).decode(blob, includes_id=True)

    def encode(self, hist: SimpleHistogram, codec_id: int = 1) -> bytes:
        return self.codec(codec_id).encode(hist, include_id=True)
