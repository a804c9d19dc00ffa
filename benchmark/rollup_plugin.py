"""The loader of a deployment that holds a rollup tier
(``rollup-100k``), named in its ``tsd.rpc.plugin`` beside
``tsd_plugin.Bench``: like ``tsd_plugin.Loader`` it reads the
deployment's history from the process's standard input before the
server binds its socket, without a WAL, and prints the line
``tsdproc.Tsd.loaded_points`` waits for.

What it reads are ``generators/rollup_tiers.py``'s frames (a line of
JSON, the kept-cell mask, the SUM cells as int32 cents, the COUNT
cells as uint16). What it hands the program is what ``/api/rollup``
hands it after parsing a body: runs of ``(interval, aggregator,
metric, tags, timestamps, values)``, a series' SUM cells and then its
COUNT cells, through ``TSDB.add_aggregate_batch``: nothing beneath the
TSDB facade is called.
"""

from __future__ import annotations

import json
import sys
import time

import numpy as np

from opentsdb_tpu.plugins import RpcPlugin


def _read(stream, n: int) -> bytes:
    buf = stream.read(n)
    if len(buf) != n:
        raise RuntimeError(f"benchmark load: a frame ends after "
                           f"{len(buf)} of {n} bytes")
    return buf


def frame_runs(head: dict, present: np.ndarray, sums: np.ndarray,
               counts: np.ndarray):
    """One frame as ``add_aggregate_batch`` wants it."""
    metric, interval = head["metric"], head["interval"]
    stamps = np.asarray(head["timestamps"], dtype=np.int64)
    for i, row in enumerate(zip(*head["tagv"])):
        tags = dict(zip(head["tagk"], row))
        kept = present[i]
        ts = stamps[kept]
        yield interval, "sum", metric, tags, ts, sums[i, kept] / 100.0
        yield interval, "count", metric, tags, ts, \
            counts[i, kept].astype(np.float64)


class Loader(RpcPlugin):
    def initialize(self, tsdb) -> None:
        if not callable(getattr(tsdb, "add_aggregate_batch", None)):
            # a program from before PR 48: it would resolve, append
            # and sync 144M cells one at a time for an hour, and then
            # build and upload 0.7 GB a request
            raise RuntimeError(
                "benchmark load: this program has no columnar rollup "
                "write path (TSDB.add_aggregate_batch): it cannot run "
                "the deployment")
        t0 = time.monotonic()
        total = 0
        errors: list[str] = []
        stdin = sys.stdin.buffer
        while True:
            line = stdin.readline()
            if not line:
                break
            head = json.loads(line)
            n, p = head["series"], head["points"]
            present = np.frombuffer(_read(stdin, n * p),
                                    dtype=np.uint8).reshape(n, p) \
                .astype(bool)
            sums = np.frombuffer(_read(stdin, n * p * 4),
                                 dtype="<i4").reshape(n, p)
            counts = np.frombuffer(_read(stdin, n * p * 2),
                                   dtype="<u2").reshape(n, p)
            written, errs = tsdb.add_aggregate_batch(
                frame_runs(head, present, sums, counts))
            total += written
            errors += errs[:10]
        # the harness waits for this line and checks the count
        print(f"benchmark-loader: imported {total} data points in "
              f"{time.monotonic() - t0:.1f}s, {len(errors)} errors "
              f"{errors[:3]}", flush=True)
        if errors:
            raise RuntimeError(f"benchmark load failed: {errors[:3]}")
        slow = tsdb.rollup_store.stats.slow_points
        if slow:
            raise RuntimeError(
                f"benchmark load: {slow} cells were landed one at a "
                f"time, not as runs of a series")
