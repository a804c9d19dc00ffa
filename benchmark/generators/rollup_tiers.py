"""The generator of a deployment that holds a rollup tier
(``rollup-100k``): ``gen.py``'s fleet (the same tags from the series
index alone, the same gappy tenth, the same rule for which cells are
missing) whose every point is one cell of the tier's interval in two
tiers, SUM and COUNT, as an external rollup job writes them through
OpenTSDB 2.4's ``/api/rollup`` (``tsd.rollups.config``: an ``avg`` is
read back as SUM over COUNT).

**Cells.** ``points`` cells a series, ``cadence_s`` apart (the tier's
interval), each standing for the raw points of its interval at
``raw_cadence_s``. COUNT is how many of them there were: all
``cadence_s / raw_cadence_s`` for nine series in ten; a gappy series
loses each raw point with probability ``count_loss`` and, in a share
``outage_share`` of its cells, all but a uniform draw of them (a
collector that came back within the hour), never all: a written cell
counts at least one point. SUM is the sum of that many raw values: the
interval's level (``gen.py``'s draw, uniform in ``cents_lo`` ..
``cents_hi``) times the count, plus the scatter of that many points
around it (a rounded normal, ``scatter`` of the level a point), kept
inside ``count`` times the range, in whole cents. A cell ``gen.py``
drops is missing in BOTH tiers. The seed sets the draws and which
cells drop; every count of series, gappy series and group sizes is the
same for every seed.

**What comes out.** ``generate(data, seed, on_text)`` returns
``(values, points)``: ``values`` float64 ``[2, series, points]``, the
SUM cells (in units, cents / 100) and the COUNT cells, NaN where
there is no cell, which the judge is built over; ``points`` the cells
written, both tiers counted. ``on_text(bytes)`` gets, chunk by chunk
and in order, frames for ``benchmark/rollup_plugin.py Loader``: one
line of JSON (the chunk's first series and count, the metric, the
interval's name, the timestamps, the tag keys, each series' tag
values), then the chunk's ``present`` as bytes, its SUM cells as
little-endian int32 cents and its COUNT cells as little-endian uint16.
The wire is the benchmark's own and no part of the program.

Nothing here imports JAX or ``opentsdb_tpu``.
"""

from __future__ import annotations

import collections
import json
import multiprocessing
import os
from concurrent.futures import ProcessPoolExecutor

import numpy as np

import gen

_UNITS = {1: "s", 60: "m", 3600: "h"}


class Data(gen.Data):
    """The ``data`` section of ``rollup-100k.json``."""

    def __init__(self, spec: dict):
        super().__init__(spec)
        self.raw_cadence_s = int(spec["raw_cadence_s"])
        self.count_loss = float(spec["count_loss"])
        self.outage_share = float(spec["outage_share"])
        self.scatter = float(spec["scatter"])
        if self.cadence_s % self.raw_cadence_s or self.t0 % self.cadence_s:
            raise ValueError("a tier's cell holds whole raw intervals "
                             "and starts on its own edge")
        if self.raw_per_cell > 65_535 \
                or self.raw_per_cell * self.cents_hi >= 1 << 31:
            raise ValueError("counts travel as uint16, sums as int32 "
                             "cents")

    @property
    def raw_per_cell(self) -> int:
        return self.cadence_s // self.raw_cadence_s

    @property
    def interval(self) -> str:
        """The tier's name as the program's ``RollupConfig`` has it."""
        unit = max(u for u in _UNITS if self.cadence_s % u == 0)
        return f"{self.cadence_s // unit}{_UNITS[unit]}"

    @property
    def timestamps(self) -> np.ndarray:
        return self.t0 + self.cadence_s * np.arange(self.points,
                                                    dtype=np.int64)


def chunk_cells(data: Data, seed: int, chunk: int):
    """Series ``[chunk * chunk_series, ...)``: global index, SUM cells
    in cents (int64 ``[n, points]``), COUNT cells (int64) and the
    kept-cell mask."""
    idx, level, drop = gen.chunk_values(data, seed, chunk)
    n, full = len(idx), data.raw_per_cell
    rng = np.random.default_rng([seed, chunk, 48])
    counts = np.full((n, data.points), full, dtype=np.int64)
    gappy = np.nonzero(data.is_gappy(idx))[0]
    if len(gappy):
        shape = (len(gappy), data.points)
        kept = full - rng.binomial(full, data.count_loss, size=shape)
        outage = rng.random(shape) < data.outage_share
        kept = np.where(outage, rng.integers(1, full, size=shape), kept)
        counts[gappy] = np.maximum(kept, 1)
    noise = np.rint(rng.standard_normal((n, data.points))
                    * np.sqrt(counts) * data.scatter * level) \
        .astype(np.int64)
    sums = np.clip(counts * level + noise, counts * data.cents_lo,
                   counts * (data.cents_hi - 1))
    return idx, sums, counts, ~drop


def frame(data: Data, idx: np.ndarray, sums: np.ndarray,
          counts: np.ndarray, present: np.ndarray) -> bytes:
    """One chunk as the loader reads it."""
    head = {
        "first": int(idx[0]), "series": len(idx),
        "points": data.points, "metric": data.metric,
        "interval": data.interval,
        "timestamps": data.timestamps.tolist(), "tagk": list(data.tags),
        "tagv": [[data.tag_name(k, int(v))
                  for v in data.tag_ids(k, idx)] for k in data.tags]}
    return json.dumps(head, separators=(",", ":")).encode() + b"\n" \
        + present.astype(np.uint8).tobytes() \
        + sums.astype("<i4").tobytes() + counts.astype("<u2").tobytes()


def as_values(sums, counts, present) -> np.ndarray:
    """What the judge reads of a chunk: ``[2, n, points]`` float64."""
    return np.where(present[None], np.stack([sums / 100.0,
                                             counts.astype(np.float64)]),
                    np.nan)


def chunk_frame(data: Data, seed: int, chunk: int):
    idx, sums, counts, present = chunk_cells(data, seed, chunk)
    return frame(data, idx, sums, counts, present), \
        as_values(sums, counts, present), 2 * int(present.sum())


def chunk_only_values(data: Data, seed: int, chunk: int):
    """:func:`chunk_frame` without the bytes."""
    _idx, sums, counts, present = chunk_cells(data, seed, chunk)
    return b"", as_values(sums, counts, present), 2 * int(present.sum())


def generate(data: Data, seed: int, on_text=None):
    """Every chunk, made by worker processes and handed over in order
    (``gen.generate``'s pool): ``on_text(bytes)`` gets the loader's
    frames where one is given. Returns (the cells, float64 ``[2,
    series, points]``; cells written)."""
    values = np.empty((2, data.series, data.points))
    points = 0
    workers = max(1, min(8, (os.cpu_count() or 2) - 2))
    make = chunk_frame if on_text is not None else chunk_only_values
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
            text, vals, cells = fut.result()
            lo = c * data.chunk_series
            values[:, lo:lo + vals.shape[1]] = vals
            points += cells
            if on_text is not None:
                on_text(text)
    return values, points
