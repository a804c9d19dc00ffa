"""The loader of a deployment that stores histograms (``hist-200k``),
named in its ``tsd.rpc.plugin`` beside ``tsd_plugin.Bench``: like
``tsd_plugin.Loader`` it reads the deployment's history from the
process's standard input before the server binds its socket, without a
WAL, and prints the line ``tsdproc.Tsd.loaded_points`` waits for.

What it reads are ``generators/histogram_points.py``'s frames (a line
of JSON, the kept-point mask, the counts as uint16). What it hands the
program is what ``/api/histogram`` would hand it after parsing a body:
``(metric, timestamp, blob, tags)`` a point, the blob the program's own
``SimpleHistogramCodec`` (id 0x01: the id, u16 edges, f64 bounds, u64
counts, u64 underflow and overflow, all big-endian), through
``TSDB.add_histogram_batch``: nothing beneath the TSDB facade is
called. The blobs of a frame are laid out with NumPy and cut into
``bytes``; the first of every frame is checked against the codec's own
``encode``, byte for byte.
"""

from __future__ import annotations

import json
import struct
import sys
import time

import numpy as np

from opentsdb_tpu.core.histogram import SimpleHistogram
from opentsdb_tpu.plugins import RpcPlugin


_BLOCK_SERIES = 100


def _read(stream, n: int) -> bytes:
    buf = stream.read(n)
    if len(buf) != n:
        raise RuntimeError(f"benchmark load: a frame ends after "
                           f"{len(buf)} of {n} bytes")
    return buf


def frame_points(tsdb, head: dict, present: np.ndarray,
                 counts: np.ndarray) -> list[tuple]:
    """One frame as ``add_histogram_batch`` wants it."""
    n, p, b = head["series"], head["points"], head["buckets"]
    bounds = head["bounds"]
    prefix = bytes([1]) + struct.pack(">H", b + 1) \
        + struct.pack(f">{b + 1}d", *bounds)
    size = len(prefix) + 8 * b + 16
    mat = np.zeros((n * p, size), dtype=np.uint8)
    mat[:, :len(prefix)] = np.frombuffer(prefix, dtype=np.uint8)
    mat[:, len(prefix):len(prefix) + 8 * b] = \
        counts.reshape(n * p, b).astype(">u8").view(np.uint8)
    raw = mat.tobytes()
    first = int(np.flatnonzero(present.reshape(-1))[0])
    hist = SimpleHistogram(bounds)
    hist.counts = counts.reshape(n * p, b)[first].tolist()
    if tsdb.histogram_manager.encode(hist) \
            != raw[first * size:(first + 1) * size]:
        raise RuntimeError("benchmark load: the blob laid out here is "
                           "not the codec's own")
    metric, stamps = head["metric"], head["timestamps"]
    tags = [dict(zip(head["tagk"], row)) for row in zip(*head["tagv"])]
    rows, cols = (a.tolist() for a in np.nonzero(present))
    return [(metric, stamps[j], raw[(i * p + j) * size:
                                    (i * p + j + 1) * size], tags[i])
            for i, j in zip(rows, cols)]


class Loader(RpcPlugin):
    def initialize(self, tsdb) -> None:
        stats = getattr(tsdb, "histogram_stats", None)
        if stats is None:
            # a program from before PR 42: it would decode 12M blobs a
            # point at a time for minutes and then cannot merge them
            # (its merge is one-hot over points x segments)
            raise RuntimeError(
                "benchmark load: this program has no columnar "
                "histogram write path (TSDB.histogram_stats): it "
                "cannot run the deployment")
        t0 = time.monotonic()
        total = 0
        errors: list[str] = []
        stdin = sys.stdin.buffer
        while True:
            line = stdin.readline()
            if not line:
                break
            head = json.loads(line)
            n, p, b = head["series"], head["points"], head["buckets"]
            present = np.frombuffer(_read(stdin, n * p),
                                    dtype=np.uint8).reshape(n, p)
            counts = np.frombuffer(_read(stdin, n * p * b * 2),
                                   dtype="<u2").reshape(n, p, b)
            # a block of series at a time: its blobs (1 KB a point)
            # are gone before the next block's are made
            for lo in range(0, n, _BLOCK_SERIES):
                at = slice(lo, lo + _BLOCK_SERIES)
                block = dict(head, series=len(present[at]),
                             tagv=[v[at] for v in head["tagv"]])
                written, errs = tsdb.add_histogram_batch(
                    frame_points(tsdb, block, present[at], counts[at]))
                total += written
                errors += errs[:10]
        # the harness waits for this line and checks the count
        print(f"benchmark-loader: imported {total} data points in "
              f"{time.monotonic() - t0:.1f}s, {len(errors)} errors "
              f"{errors[:3]}", flush=True)
        if errors:
            raise RuntimeError(f"benchmark load failed: {errors[:3]}")
        if stats.slow_points:
            raise RuntimeError(
                f"benchmark load: {stats.slow_points} points were "
                f"decoded one at a time, not as runs of a series")
