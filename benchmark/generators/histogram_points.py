"""The generator of a deployment that stores latency histograms
(``hist-200k``): ``gen.py``'s fleet (the same tags from the series
index alone, the same gappy tenth) whose every point is a histogram of
``buckets`` counts over log-spaced bounds, as a service that reports
its request latency once a ``cadence_s`` writes it through OpenTSDB
2.4's ``/api/histogram``.

**Counts.** A point holds about ``observations`` requests, their
latency log-normal: ``log10(ms)`` is normal with deviation ``sigma``
decades around a location that moves with the datacentre, the minute
and the host,

    mu = mu_lo + mu_dc * dc / dcs + mu_minute * minute + N(0, mu_host),

so that the p99 and p99.9 of different groups and of different
five-minute buckets fall in different buckets of the histogram (a
uniform draw would put every percentile in the last bucket and judge
nothing). The count of bucket ``j`` is a Poisson draw around
``observations`` times the normal mass between its two bounds, the
mass beyond the first and the last bound counted into them (underflow
and overflow stay 0), and no count passes ``count_max``. The expected
masses are read from a table over ``mu`` in steps of 1/1024 decade.
The seed sets the draws and which points drop; every count of series,
gappy series and group sizes is the same for every seed.

**What comes out.** ``generate(data, seed, on_text)`` returns
``(values, points)``: ``values`` the counts, uint16 ``[series, points,
buckets]``, which the judge is built over, and ``points`` the
histogram points written. A dropped point is all zero, and that is its
flag: a kept point never is (it holds about ``observations`` counts;
one that drew none is given one in its likeliest bucket).
``on_text(bytes)`` gets, chunk by chunk and in order, frames for
``benchmark/hist_plugin.py Loader``: one line of JSON (the chunk's
first series and count, the deployment's metric, bounds, timestamps
and tag keys, each series' tag values), then the chunk's ``present``
as bytes and its ``counts`` as little-endian uint16. The loader makes
the codec's blobs of them inside the server; the wire is the
benchmark's own and no part of the program.

Nothing here imports JAX or ``opentsdb_tpu``.
"""

from __future__ import annotations

import collections
import json
import math
import multiprocessing
import os
from concurrent.futures import ProcessPoolExecutor

import numpy as np

import gen

MU_STEPS = 1024         # table rows a decade of mu


class Data(gen.Data):
    """The ``data`` section of ``hist-200k.json``."""

    def __init__(self, spec: dict):
        # gen.Data's keys and checks; a histogram has no scalar value
        super().__init__({**spec, "cents_lo": 0, "cents_hi": 0})
        self.buckets = int(spec["buckets"])
        self.bound_lo_log10 = float(spec["bound_lo_log10"])
        self.bound_hi_log10 = float(spec["bound_hi_log10"])
        self.observations = float(spec["observations"])
        self.count_max = int(spec["count_max"])
        self.sigma = float(spec["sigma"])
        self.mu_lo = float(spec["mu_lo"])
        self.mu_dc = float(spec["mu_dc"])
        self.mu_minute = float(spec["mu_minute"])
        self.mu_host = float(spec["mu_host"])
        if not 0 < self.count_max <= 65_535:
            raise ValueError("counts travel and are judged as uint16")

    @property
    def bounds(self) -> np.ndarray:
        """[buckets + 1] ascending bucket bounds, in ms."""
        return np.logspace(self.bound_lo_log10, self.bound_hi_log10,
                           self.buckets + 1)

    @property
    def timestamps(self) -> np.ndarray:
        return self.t0 + self.cadence_s * np.arange(self.points,
                                                    dtype=np.int64)


def _phi(x: float) -> float:
    return 0.5 * (1.0 + math.erf(x / math.sqrt(2.0)))


def mass_table(data: Data):
    """(mu of row 0, [rows, buckets] expected counts): row ``r`` is the
    profile at ``mu0 + r / MU_STEPS``, over every ``mu`` the
    deployment can draw (the host's share out to six deviations)."""
    lo = data.mu_lo - 6 * data.mu_host
    hi = data.mu_lo + data.mu_dc + data.mu_minute * data.points \
        * data.cadence_s / 60.0 + 6 * data.mu_host
    rows = int(math.ceil((hi - lo) * MU_STEPS)) + 1
    edges = np.log10(data.bounds)
    table = np.empty((rows, data.buckets))
    for r in range(rows):
        mu = lo + r / MU_STEPS
        cdf = [_phi((e - mu) / data.sigma) for e in edges]
        cdf[0], cdf[-1] = 0.0, 1.0      # the tails into the end buckets
        table[r] = np.diff(cdf)
    return lo, table * data.observations


def chunk_counts(data: Data, seed: int, chunk: int):
    """Series ``[chunk * chunk_series, ...)``: global index, counts
    uint16 ``[n, points, buckets]`` (a dropped point all zero) and the
    kept-point mask ``[n, points]``."""
    lo = chunk * data.chunk_series
    hi = min(lo + data.chunk_series, data.series)
    n = hi - lo
    rng = np.random.default_rng([seed, chunk, 42])
    idx = np.arange(lo, hi)
    minute = np.arange(data.points) * (data.cadence_s / 60.0)
    mu = data.mu_lo + data.mu_dc * data.tag_ids("dc", idx)[:, None] \
        / data.dcs + data.mu_minute * minute[None, :] \
        + np.clip(rng.normal(0.0, data.mu_host, size=(n, 1)),
                  -6 * data.mu_host, 6 * data.mu_host)
    mu0, table = mass_table(data)
    row = np.rint((mu - mu0) * MU_STEPS).astype(np.int64)
    counts = np.minimum(rng.poisson(table[row]), data.count_max) \
        .astype(np.uint16)
    empty = ~counts.any(axis=2)
    if empty.any():
        at = np.nonzero(empty)
        counts[at + (table[row[at]].argmax(axis=1),)] = 1
    drop = np.zeros((n, data.points), dtype=bool)
    gappy = np.nonzero(data.is_gappy(idx))[0]
    if len(gappy):
        single = rng.random((len(gappy), data.points)) < data.drop_single
        whole = np.repeat(
            rng.random((len(gappy), data.points // data.block_points))
            < data.drop_block, data.block_points, axis=1)
        drop[gappy] = single | whole
    counts[drop] = 0
    return idx, counts, ~drop


def frame(data: Data, idx: np.ndarray, counts: np.ndarray,
          present: np.ndarray) -> bytes:
    """One chunk as the loader reads it: a line of JSON, then the
    kept-point mask and the counts as bytes."""
    head = {
        "first": int(idx[0]), "series": len(idx),
        "points": data.points, "buckets": data.buckets,
        "metric": data.metric, "bounds": data.bounds.tolist(),
        "timestamps": data.timestamps.tolist(), "tagk": list(data.tags),
        "tagv": [[data.tag_name(k, int(v))
                  for v in data.tag_ids(k, idx)] for k in data.tags]}
    return json.dumps(head, separators=(",", ":")).encode() + b"\n" \
        + present.astype(np.uint8).tobytes() \
        + counts.astype("<u2").tobytes()


def chunk_frame(data: Data, seed: int, chunk: int):
    idx, counts, present = chunk_counts(data, seed, chunk)
    return frame(data, idx, counts, present), counts, present


def chunk_only_counts(data: Data, seed: int, chunk: int):
    """:func:`chunk_frame` without the bytes."""
    _idx, counts, present = chunk_counts(data, seed, chunk)
    return b"", counts, present


def generate(data: Data, seed: int, on_text=None):
    """Every chunk, made by worker processes and handed over in order
    (``gen.generate``'s pool): ``on_text(bytes)`` gets the loader's
    frames where one is given. Returns (the counts, uint16 [series,
    points, buckets]; histogram points written)."""
    values = np.zeros((data.series, data.points, data.buckets),
                      dtype=np.uint16)
    points = 0
    workers = max(1, min(8, (os.cpu_count() or 2) - 2))
    make = chunk_frame if on_text is not None else chunk_only_counts
    pending: collections.deque = collections.deque()
    nxt = 0
    with ProcessPoolExecutor(
            workers,
            mp_context=multiprocessing.get_context("spawn")) as ex:
        while nxt < data.chunks or pending:
            while nxt < data.chunks and len(pending) < 2 * workers:
                pending.append((nxt, ex.submit(make, data, seed, nxt)))
                nxt += 1
            c, fut = pending.popleft()
            text, counts, present = fut.result()
            lo = c * data.chunk_series
            values[lo:lo + len(counts)] = counts
            points += int(present.sum())
            if on_text is not None:
                on_text(text)
    return values, points
