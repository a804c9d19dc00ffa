"""The judge of a deployment that answers from a rollup tier
(``rollup-100k``): an ``avg`` downsample read back from SUM and COUNT
cells, in float64 NumPy, taking nothing of the program (OpenTSDB 2.4
``user_guide/rollups.html``: "avg" is not stored, it is the SUM tier
over the COUNT tier; ``RollupSpan`` reads both qualifiers of one row;
this repository's ``tests/oracle.py`` states the same rule).

**What a request asks.** ``<agg>:<n>h-avg`` (or ``<n>d-avg``) of the metric over the
deployment's whole span, a whole multiple of the tier's interval.
For each series and bucket the value is

    (sum of the SUM cells of the bucket) / (sum of its COUNT cells)

the average of the raw points the cells stand for, each cell weighing
as many points as it counted: never a mean of the cells' own averages,
never SUM over the number of cells. A bucket in which either tier has
no cell (or counts nothing) has no value: the aggregator interpolates
across it as across a raw gap. From there on the answer is
``reference.py``'s own: its interpolation, its group sum (or max /
min), its limits and its compare. :meth:`Reference.supports` refuses
any window but the span, any downsample but ``avg`` at a multiple of
the tier's interval, and a rate.

**Fast enough for a window.** The requests of a list differ in the
racks they leave out. For a ``sum`` whose only exclusions name one tag,
the fleet's answer is made once and a request subtracts the partial
sums of what it leaves out, kept once a (group, value of that tag)
pair (a rack lies in one datacentre: 2,000 pairs of 50 series):
milliseconds an answer. Anything else goes the way ``reference.py``
goes, through the groups an exclusion touches.

``values`` is what ``generators/rollup_tiers.py`` returns: ``[2,
series, points]`` float64, the SUM and the COUNT cells, NaN where
there is none.
"""

from __future__ import annotations

import re

import numpy as np

import reference
from reference import Cells, Unsupported, lerp_fill

_DAYS = re.compile(r"^(\d+)d-avg$")
_ROWS = 8192            # series interpolated at a time
_PARTS = 4              # sum, sum of magnitudes, members, real members


class Reference(reference.Reference):

    def __init__(self, data, values: np.ndarray, limits: dict):
        super().__init__(data, None, limits)
        self.sums, self.counts = values
        self._partials: dict = {}

    @classmethod
    def supports(cls, sub: dict, d, window=None):
        reference.span_only(d, window)
        days = _DAYS.match(sub.get("downsample") or "")
        if days:        # ``reference.py`` reads s, m and h
            sub = dict(sub, downsample=f"{24 * int(days[1])}h-avg")
        parsed = super().supports(sub, d)
        _agg, _secs, fn, rate = parsed[:4]
        if fn != "avg":
            raise Unsupported(f"downsample function {fn!r}: this judge "
                              f"divides the SUM tier by the COUNT tier")
        if rate:
            raise Unsupported("a rate over a tier's average")
        return parsed

    def series_grid(self, secs: int, fn: str, rate: bool,
                    counter_max):
        """([series, buckets] weighted averages, None), cached."""
        key = (secs, fn)
        hit = self._grids.get(key)
        if hit is None:
            d = self.data
            k = secs // d.cadence_s
            shape = (d.series, d.points // k, k)
            has = ~np.isnan(self.sums.reshape(shape)), \
                ~np.isnan(self.counts.reshape(shape))
            total = np.nansum(self.sums.reshape(shape), axis=2)
            count = np.nansum(self.counts.reshape(shape), axis=2)
            ok = has[0].any(axis=2) & has[1].any(axis=2) & (count > 0)
            hit = self._grids[key] = np.where(
                ok, total / np.where(ok, count, 1.0), np.nan), None
        return hit

    # -- a sum that leaves out values of one tag ------------------------

    def _partial(self, secs: int, group_tag: str, tagk: str):
        """(group names, whole fleet's parts ``[PARTS, groups,
        buckets]``, each pair's group, each pair's value of ``tagk``,
        the pairs' parts ``[PARTS, pairs, buckets]``), or None where
        the pairs are too many to be worth keeping."""
        key = (secs, group_tag, tagk)
        if key in self._partials:
            return self._partials[key]
        d = self.data
        rows, gids, names = self._selection([], [], group_tag)
        xids = d.tag_ids(tagk, rows)
        pairs, pid = np.unique(gids * d.tag_count(tagk) + xids,
                               return_inverse=True)
        out = None
        if 8 * len(pairs) <= d.series:
            grid = self.series_grid(secs, "avg", False, None)[0]
            parts = np.zeros((_PARTS, len(pairs), grid.shape[1]))
            for lo in range(0, d.series, _ROWS):
                real = grid[lo:lo + _ROWS]
                filled = lerp_fill(real)
                ok = ~np.isnan(filled)
                v = np.where(ok, filled, 0.0)
                order = np.argsort(pid[lo:lo + _ROWS], kind="stable")
                into = pid[lo:lo + _ROWS][order]
                starts = np.flatnonzero(np.diff(into, prepend=-1))
                for part, col in zip(parts, (
                        v, np.abs(v), ok.astype(np.float64),
                        (~np.isnan(real)).astype(np.float64))):
                    part[into[starts]] += np.add.reduceat(
                        col[order], starts, axis=0)
            pair_group = pairs // d.tag_count(tagk)
            whole = np.zeros((_PARTS, len(names), grid.shape[1]))
            for part, total in zip(parts, whole):
                np.add.at(total, pair_group, part)
            out = (names, whole, pair_group,
                   pairs % d.tag_count(tagk), parts)
        self._partials[key] = out
        return out

    def answer(self, sub: dict, window=None):
        d = self.data
        agg, secs, _fn, _rate, _cm, include, exclude, group_tag \
            = self.supports(sub, d, window)
        tags = {tagk for tagk, _ in exclude}
        partial = None
        if agg == "sum" and not include and len(tags) == 1:
            (tagk,) = tags
            partial = self._partial(secs, group_tag, tagk)
        if partial is None:
            return super().answer(sub)
        names, whole, pair_group, pair_value, parts = partial
        gone = np.isin(pair_value, [d.tag_index(tagk, v)
                                    for _, vals in exclude for v in vals])
        left = whole.copy()
        for total, part in zip(left, parts):
            np.subtract.at(total, pair_group[gone], part[gone])
        cells = Cells(*left.shape[1:])
        members = np.rint(left[2])
        cells.want[:] = np.where(members > 0, left[0], np.nan)
        cells.scale[:] = np.where(members > 0, left[1], 0.0)
        cells.atol[:] = self.value_atol * members
        cells.emitted[:] = np.rint(left[3]) > 0
        return group_tag, names, secs, cells
