"""Seeded data of one deployment: the generator and what the reference
needs of it. Copied from ``chip_smoke.py`` (PR 21) and made to read its
sizes from the configuration file, so that later PRs may change the
program and its smoke test but not the yardstick.

Nothing here imports JAX or ``opentsdb_tpu``. A configuration file may
name another generator (``deploy.py`` has the contract); this one is
what a file that names none gets.

A deployment is ``series`` series of one metric, ``points`` points each
at ``cadence_s`` from ``t0``, values in cents drawn from the seed. Tags
follow from the series index alone: ``host`` is unique, ``dc`` is
``i % dcs``, ``rack`` is ``i % racks``, ``fleet`` is
``(i // 100) % fleets``. One series in ten (``(i // 100) % 10 == 9``,
spread over every dc) loses about 1% of its points: half as single
points, half as whole blocks of ``block_points``. The seed sets the
values and which points drop; every count of series, gappy series and
group sizes is the same for every seed.
"""

from __future__ import annotations

import collections
import multiprocessing
import os
from concurrent.futures import ProcessPoolExecutor

import numpy as np

_POW10 = 10 ** np.arange(9, -1, -1, dtype=np.int64)


class Data:
    """The ``data`` section of a configuration file."""

    tags = ("host", "dc", "rack", "fleet")     # of every series, in order

    def __init__(self, spec: dict):
        self.metric = spec["metric"]
        self.series = int(spec["series"])
        self.t0 = int(spec["t0"])
        self.cadence_s = int(spec["cadence_s"])
        self.points = int(spec["points"])
        self.dcs = int(spec["dcs"])
        self.racks = int(spec["racks"])
        self.fleets = int(spec["fleets"])
        self.block_points = int(spec["block_points"])
        self.drop_single = float(spec["drop_single"])
        self.drop_block = float(spec["drop_block"])
        self.cents_lo = int(spec["cents_lo"])
        self.cents_hi = int(spec["cents_hi"])
        self.chunk_series = int(spec["chunk_series"])
        if self.points % self.block_points:
            raise ValueError("points must be a multiple of block_points")
        if not 0 < self.fleets <= 26 or self.dcs > 100 \
                or self.racks > 10_000 or self.series > 10_000_000:
            raise ValueError("tag widths of the line template exceeded")
        if len(self.metric.encode()) != len(self.metric):
            raise ValueError("metric must be ASCII")

    @property
    def end(self) -> int:
        """Last second of the data's span."""
        return self.t0 + self.points * self.cadence_s - 1

    @property
    def chunks(self) -> int:
        return -(-self.series // self.chunk_series)

    # tags, from the index alone
    def is_gappy(self, idx: np.ndarray) -> np.ndarray:
        return (idx // 100) % 10 == 9

    def tag_ids(self, tagk: str, idx: np.ndarray) -> np.ndarray:
        if tagk == "host":
            return idx
        if tagk == "dc":
            return idx % self.dcs
        if tagk == "rack":
            return idx % self.racks
        if tagk == "fleet":
            return (idx // 100) % self.fleets
        raise KeyError(f"no tag {tagk!r} in this deployment")

    def tag_name(self, tagk: str, i: int) -> str:
        if tagk == "host":
            return f"h{i:07d}"
        if tagk == "dc":
            return f"d{i:02d}"
        if tagk == "rack":
            return f"r{i:04d}"
        if tagk == "fleet":
            return chr(ord("a") + i)
        raise KeyError(f"no tag {tagk!r} in this deployment")

    def tag_index(self, tagk: str, name: str) -> int:
        """Inverse of :meth:`tag_name`; -1 for a name no series has."""
        try:
            i = ord(name) - ord("a") if tagk == "fleet" \
                else int(name[1:])
        except (ValueError, TypeError):
            return -1
        n = {"host": self.series, "dc": self.dcs, "rack": self.racks,
             "fleet": self.fleets}[tagk]
        return i if 0 <= i < n and self.tag_name(tagk, i) == name \
            else -1

    def tag_count(self, tagk: str) -> int:
        return {"host": self.series, "dc": self.dcs,
                "rack": self.racks, "fleet": self.fleets}[tagk]

    def point_offset_s(self, idx: np.ndarray):
        """Seconds after ``t0 + k * cadence_s`` at which the ``k``-th
        point of each series of ``idx`` lies, in ``[0, cadence_s)``;
        None where every series is in step (here). A generator whose
        series are not overrides it (``deploy.py``)."""
        return None


def chunk_values(data: Data, seed: int, chunk: int):
    """Series ``[chunk * chunk_series, ...)``: global index, values in
    cents ``[n, points]`` and the dropped-point mask."""
    lo = chunk * data.chunk_series
    hi = min(lo + data.chunk_series, data.series)
    rng = np.random.default_rng([seed, chunk])
    cents = rng.integers(data.cents_lo, data.cents_hi,
                         size=(hi - lo, data.points), dtype=np.int64)
    idx = np.arange(lo, hi)
    drop = np.zeros((hi - lo, data.points), dtype=bool)
    gappy = np.nonzero(data.is_gappy(idx))[0]
    if len(gappy):
        single = rng.random((len(gappy), data.points)) < data.drop_single
        whole = np.repeat(
            rng.random((len(gappy), data.points // data.block_points))
            < data.drop_block, data.block_points, axis=1)
        drop[gappy] = single | whole
    return idx, cents, drop


def _digits(x: np.ndarray, width: int) -> np.ndarray:
    """[n] non-negative ints -> [n, width] zero-padded ASCII digits."""
    return ((x[:, None] // _POW10[10 - width:]) % 10 + 48) \
        .astype(np.uint8)


def chunk_lines(data: Data, seed: int, chunk: int):
    """One chunk as ``tsdb import`` text (series-major, the order of a
    ``tsdb scan --import`` dump) and the same points as the reference
    wants them: values in float64 ``[n, points]`` with NaN where a
    point was dropped. Returns (text, values, points written)."""
    idx, cents, drop = chunk_values(data, seed, chunk)
    n = len(idx)
    head = data.metric.encode() + b" "
    line = head + (b"0000000000 0000.00 host=h0000000 dc=d00 "
                   b"rack=r0000 fleet=a\n")
    o = len(head)
    buf = np.empty((n, data.points, len(line)), dtype=np.uint8)
    buf[:] = np.frombuffer(line, dtype=np.uint8)
    ts = data.t0 + data.cadence_s * np.arange(data.points,
                                              dtype=np.int64)
    off = data.point_offset_s(idx)
    if off is None:
        buf[:, :, o:o + 10] = _digits(ts, 10)[None]
    else:
        buf[:, :, o:o + 10] = _digits(
            (ts[None, :] + off[:, None]).reshape(-1), 10) \
            .reshape(n, data.points, 10)
    d = _digits(cents.reshape(-1), 6).reshape(n, data.points, 6)
    buf[:, :, o + 11:o + 15] = d[:, :, :4]
    buf[:, :, o + 16:o + 18] = d[:, :, 4:]
    buf[:, :, o + 25:o + 32] = _digits(idx, 7)[:, None, :]
    buf[:, :, o + 37:o + 39] = _digits(
        data.tag_ids("dc", idx), 2)[:, None, :]
    buf[:, :, o + 46:o + 50] = _digits(
        data.tag_ids("rack", idx), 4)[:, None, :]
    buf[:, :, o + 57] = (data.tag_ids("fleet", idx) + ord("a")) \
        .astype(np.uint8)[:, None]
    values = np.where(drop, np.nan, cents / 100.0)
    return buf[~drop].tobytes(), values, int((~drop).sum())


def chunk_only_values(data: Data, seed: int, chunk: int):
    """:func:`chunk_lines` without the text."""
    _idx, cents, drop = chunk_values(data, seed, chunk)
    return b"", np.where(drop, np.nan, cents / 100.0), int((~drop).sum())


def generate(data: Data, seed: int, on_text=None, lines=None):
    """Every chunk, made by worker processes and handed over in order:
    ``on_text(bytes)`` gets the import text where one is given. Returns
    the values the reference wants ([series, points] float64, NaN where
    a point was dropped) and the number of points. ``lines`` is for a
    generator that builds on this one: its own :func:`chunk_lines`, a
    function of its module, which the workers import by name."""
    values = np.empty((data.series, data.points))
    points = 0
    workers = max(1, min(8, (os.cpu_count() or 2) - 2))
    make = (lines or chunk_lines) if on_text is not None \
        else chunk_only_values
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
            text, vals, pts = fut.result()
            lo = c * data.chunk_series
            values[lo:lo + len(vals)] = vals
            points += pts
            if on_text is not None:
                on_text(text)
    return values, points
