"""The judge of a deployment that asks for percentiles: ``reference.py``
and the six order statistics of OpenTSDB 2.4's percentile family,
``p50``, ``p75``, ``p90``, ``p95``, ``p99`` and ``p999`` (ref:
``src/core/Aggregators.java``, ``PercentileAgg`` over commons-math's
``Percentile`` with LEGACY estimation; interpolation LERP).

Per bucket a member of a group counts where it has a real or a
linearly interpolated value, as ``AggregationIterator`` feeds
``PercentileAgg.runDouble``. Of the group's ``n`` such values, sorted
as ``x[0] <= ... <= x[n - 1]``, the ``p``-th percentile (``p`` = 0.95
for ``p95``, 0.999 for ``p999``) stands at the position
``h = p (n + 1)``, counted from one: below 1 it is the minimum, at or
above ``n`` the maximum, and otherwise

    x[floor(h) - 1] + (h - floor(h)) (x[floor(h)] - x[floor(h) - 1])

(commons-math ``Percentile.evaluate``, ``EstimationType.LEGACY``). All
of it in float64 over one ``lexsort`` a bucket: the exact order
statistic, no sketch and no bins. A rate under a percentile stays
``Unsupported``.

A percentile carries the float32 rounding of one value, like max and
min: its cells have ``scale`` 0 and are held to ``rank_atol``, beyond
one stated allowance. The configuration states float32, and float32
resolves the position ``h`` to one part in 2**23: near 9,500 (a
datacentre of 10,000 hosts under ``p95``) neighbouring positions lie
2**-10 apart, so the weight ``h - floor(h)`` of the straight line is
known to about 1e-3 and the value to 1e-3 of the gap between the two
neighbours, which in the tail of 10,000 values reaches 5 to 10 where
the values themselves are rounded to 5e-4 (PERF.md section 2 has the
chip's readings). ``Cells.atol`` of such a cell is that spacing of
``h`` times that gap, :func:`compare` takes a ranked cell's error
beyond it, and a cell of max, min or a group's end (``h < 1``,
``h >= n``: one value, no line) has none. The bfloat16 control moves a
neighbour by 16 to 32 and fails as before. Everything else
(``rows_to_grid``, ``Cells``, ``Verdict``) is ``reference.py``'s, which
the loader falls back on.
"""

import numpy as np

import reference

PERCENTILES = {"p50": 0.5, "p75": 0.75, "p90": 0.9, "p95": 0.95,
               "p99": 0.99, "p999": 0.999}


def float32_spacing(h: np.ndarray) -> np.ndarray:
    """The distance between neighbouring float32 numbers at ``h``
    (``h`` >= 1): what the stated precision cannot resolve of it."""
    return 2.0 ** (np.floor(np.log2(h)) - 23)


def compare(got: np.ndarray, stray: int, cells) -> reference.Verdict:
    """``reference.compare`` with the error of a ranked cell (``scale``
    0) taken beyond its ``atol``, as a summed cell's is."""
    off = got - cells.want
    ranked = (cells.scale == 0) & np.isfinite(off)
    within = np.clip(off, -cells.atol, cells.atol)
    return reference.compare(np.where(ranked, got - within, got), stray,
                             cells)


class Reference(reference.Reference):
    aggregators = reference.Reference.aggregators + tuple(PERCENTILES)

    @classmethod
    def supports(cls, sub: dict, d, window=None):
        reference.span_only(d, window)      # no window but the span
        return super().supports(sub, d)

    def _reduce(self, grid, ties, gids, g, agg, secs, rate, counter_max):
        p = PERCENTILES.get(agg)
        if p is None:
            return super()._reduce(grid, ties, gids, g, agg, secs, rate,
                                   counter_max)
        b = grid.shape[1]
        out = reference.Cells(g, b)
        filled = reference.lerp_fill(grid)
        for j in range(b):
            col = filled[:, j]
            ok = ~np.isnan(col)
            out.emitted[:, j] = np.bincount(
                gids, weights=~np.isnan(grid[:, j]), minlength=g) > 0
            ids, vals = gids[ok], col[ok]
            n = np.bincount(ids, minlength=g)
            x = vals[np.lexsort((vals, ids))]    # by group, then value
            if not len(x):
                continue
            first = np.cumsum(n) - n
            last = len(x) - 1
            h = p * (n + 1)
            line = (h >= 1) & (h < n)      # else a group's end
            k = np.where(line, np.floor(h).astype(np.int64), 1)
            lo = x[np.minimum(first + k - 1, last)]
            hi = x[np.minimum(first + k, last)]
            end = x[np.clip(np.where(h < 1, first, first + n - 1), 0,
                            last)]
            want = np.where(line, lo + (h - k) * (hi - lo), end)
            out.want[:, j] = np.where(n > 0, want, np.nan)
            out.atol[:, j] = np.where(
                line, float32_spacing(np.maximum(h, 1.0)) * (hi - lo),
                0.0)
        return out
