"""The judge of a deployment that stores histograms (``hist-200k``):
OpenTSDB 2.4's histogram query in integer arithmetic, taking nothing
of the program (ref: ``TsdbQuery.isHistogramQuery`` :776,
``HistogramAggregationIterator.java:319``, ``HistogramDownsampler``,
``HistogramAggregation.java:20``: SUM is the only merge,
``SimpleHistogram.percentile`` :133).

**What a request asks.** A sub-query with ``percentiles`` over one
metric: the histogram points of every selected series are merged
bucket-wise by SUM, across the series of a group and across the points
of a downsample bucket, and each percentile ``q`` is read off the
merged histogram as the midpoint of the bucket whose cumulative count
first reaches ``total * q / 100`` (the buckets with ``cum < target``
counted, the program's rule and this file's alike). One row a group
and percentile, named ``<metric>_pct_<q>``; a (group, bucket) is
emitted where a selected series has a point in it. The judge answers
``aggregator`` ``sum`` with ``percentiles``, a ``<n>[sm]-sum``
downsample that tiles the data, and the filters ``reference.py``
knows (``wildcard(*)``, ``literal_or``, ``not_literal_or``, at most
one group-by tag); anything else is :class:`reference.Unsupported`.

**Exact, and why.** Counts are integers (uint16 a point); sums are
int64; ``cum * 100 < total * q`` is decided in integers with ``q`` as
the exact decimal fraction its JSON text states. So a cell has ONE
right bucket, and the program is held to its midpoint but for the
float32 rounding of the midpoint itself (``rank_atol``: half an ulp of
a midpoint under 10**4 is 4.9e-4; neighbouring midpoints lie 15% apart,
0.16 at the least).

**Ties.** The configuration states float32: the program knows
``total * q / 100`` to a few roundings of 2**-24, so where a
cumulative count lies that close to the target, ``cum < target`` may
come out either way and the program's bucket is a neighbour of the
exact one. ``tie_rtol`` (the configuration's, relative to the target)
is that allowance: a cell whose nearest cumulative count is within it
accepts every bucket the comparison could reach inside it, and no
other cell accepts anything but the exact midpoint. The share of an
answer's cells that used the allowance is held to ``tie_share``; cells
beyond it count as ``shape_errors``. A merge in lower precision
(counts rounded to bfloat16 move a cumulative count by up to 0.4%)
moves whole buckets in most cells and fails ``rank_abs_err`` by orders
of magnitude.

**Fast enough for a window.** A thousand requests differ in one
excluded rack: the whole fleet's merged ``[group, bucket of time,
bucket]`` is made once a (group-by tag, downsample), and a request
subtracts what it does not select (integers: exact) where that is the
smaller part, and sums what it selects where that is.

``values`` is what ``generators/histogram_points.py`` returns: counts
``[series, points, buckets]``, a dropped point all zero (no kept point
is). A float array of whole numbers (``control.py``'s bfloat16 copy)
is taken as it is.
"""

from __future__ import annotations

import math
from fractions import Fraction

import numpy as np

import reference
from reference import Unsupported, parse_downsample

_CHUNK = 8192           # series merged at a time


def _must_tile(d, secs: int) -> None:
    if secs % d.cadence_s or (d.points * d.cadence_s) % secs \
            or d.t0 % secs:
        raise Unsupported(f"{secs}s buckets do not tile the data")


def q_name(group: str, q: float) -> str:
    """One row of an answer: its group's tag value and percentile."""
    return f"{group}|{q:g}"


class Cells:
    """What one answer should be, a (group, percentile) a row: the
    exact midpoint, whether the cell is emitted, the lowest and the
    highest midpoint a float32 comparison may reach (``want`` itself
    in all but tie cells), and the share of the answer's cells that
    may use that reach."""

    def __init__(self, want, lo, hi, emitted, tie_share: float):
        self.want, self.lo, self.hi = want, lo, hi
        self.emitted = emitted
        self.tie_share = tie_share


class Verdict(reference.Verdict):
    """``reference.Verdict`` (``run.py`` reads its three numbers) and
    the tie cells that used their allowance."""

    def __init__(self):
        super().__init__()
        self.ties = 0


class Reference:

    def __init__(self, data, values: np.ndarray, limits: dict):
        self.data = data
        self.counts = values
        self.tie_rtol = float(limits["tie_rtol"])
        self.tie_share = float(limits["tie_share"])
        b = np.asarray(data.bounds, dtype=np.float64)
        self.mids = (b[:-1] + b[1:]) / 2.0
        self._bases: dict = {}

    # -- parsing ---------------------------------------------------------

    @classmethod
    def supports(cls, sub: dict, d, window=None):
        """Raises :class:`Unsupported` for a sub-query this judge does
        not answer over the deployment ``d``; returns what it parsed:
        (bucket seconds, percentiles, include, exclude, group-by tag).
        """
        reference.span_only(d, window)      # no window but the span
        if sub.get("metric") != d.metric:
            raise Unsupported(f"metric {sub.get('metric')!r}")
        if sub.get("aggregator") != "sum":
            raise Unsupported(f"aggregator {sub.get('aggregator')!r}: "
                              f"histograms merge by sum alone")
        qs = sub.get("percentiles")
        if not isinstance(qs, list) or not qs or not all(
                isinstance(q, (int, float)) and 0 < q <= 100
                for q in qs):
            raise Unsupported(f"percentiles {qs!r}")
        if sub.get("rate") or sub.get("tsuids"):
            raise Unsupported("a rate or tsuids under percentiles")
        secs, fn = parse_downsample(sub.get("downsample") or "")
        if fn != "sum":
            raise Unsupported(f"downsample function {fn!r}: histograms "
                              f"merge by sum alone")
        _must_tile(d, secs)
        include, exclude, group_tag = [], [], ""
        for f in sub.get("filters") or []:
            kind, tagk = f.get("type"), f.get("tagk")
            if kind == "wildcard" and f.get("filter") == "*":
                pass
            elif kind == "literal_or":
                include.append((tagk, f["filter"].split("|")))
            elif kind == "not_literal_or":
                exclude.append((tagk, f["filter"].split("|")))
            else:
                raise Unsupported(f"filter {f!r}")
            try:
                d.tag_count(tagk)
            except KeyError:
                raise Unsupported(f"filter {f!r}: the deployment has "
                                  f"no tag {tagk!r}") from None
            if f.get("groupBy"):
                if group_tag and group_tag != tagk:
                    raise Unsupported("two group-by tags")
                group_tag = tagk
        return secs, [float(q) for q in qs], include, exclude, group_tag

    def _keep(self, include, exclude) -> np.ndarray:
        d = self.data
        idx = np.arange(d.series)
        keep = np.ones(d.series, dtype=bool)
        for way, filters in ((True, include), (False, exclude)):
            for tagk, vals in filters:
                ids = np.array([d.tag_index(tagk, v) for v in vals])
                hit = np.isin(d.tag_ids(tagk, idx), ids[ids >= 0])
                keep &= hit if way else ~hit
        return keep

    def selected(self, sub: dict) -> int:
        """How many series the sub-query selects."""
        _secs, _qs, include, exclude, _tag = self.supports(sub,
                                                           self.data)
        return int(self._keep(include, exclude).sum())

    # -- merging ---------------------------------------------------------

    def _merge(self, rows: np.ndarray, gids: np.ndarray, g: int,
               k: int):
        """Series ``rows`` into ``g`` groups by ``gids``, ``k`` points
        a bucket of time: (counts int64 [g, T, buckets], points int64
        [g, T])."""
        d = self.data
        t = d.points // k
        merged = np.zeros((g, t, d.buckets), dtype=np.int64)
        points = np.zeros((g, t), dtype=np.int64)
        for lo in range(0, len(rows), _CHUNK):
            sel = rows[lo:lo + _CHUNK]
            order = np.argsort(gids[lo:lo + _CHUNK], kind="stable")
            into = gids[lo:lo + _CHUNK][order]
            starts = np.flatnonzero(np.diff(into, prepend=-1))
            block = self.counts[sel[order]]
            kept = block.any(axis=2).reshape(len(sel), t, k).sum(axis=2)
            block = block.reshape(len(sel), t, k, d.buckets) \
                .sum(axis=2, dtype=np.int64)
            merged[into[starts]] += np.add.reduceat(block, starts,
                                                    axis=0)
            points[into[starts]] += np.add.reduceat(kept, starts,
                                                    axis=0)
        return merged, points

    def _base(self, group_tag: str, k: int):
        """The whole fleet merged by ``group_tag``: (group id a series,
        counts, points, series a group)."""
        key = (group_tag, k)
        base = self._bases.get(key)
        if base is None:
            d = self.data
            idx = np.arange(d.series)
            gids = d.tag_ids(group_tag, idx) if group_tag \
                else np.zeros(d.series, dtype=np.int64)
            g = d.tag_count(group_tag) if group_tag else 1
            base = self._bases[key] = (
                gids, *self._merge(idx, gids, g, k),
                np.bincount(gids, minlength=g))
        return base

    def merged(self, sub: dict):
        """What the sub-query merges: (bucket seconds, percentiles,
        group-by tag, the names of the groups that keep a series,
        counts int64 [groups, T, buckets], points int64 [groups, T])."""
        d = self.data
        secs, qs, include, exclude, group_tag = self.supports(sub, d)
        k = secs // d.cadence_s
        gids, merged, points, members = self._base(group_tag, k)
        keep = self._keep(include, exclude)
        if not keep.all():
            gone = np.flatnonzero(~keep)
            if 2 * len(gone) <= d.series:
                less, fewer = self._merge(gone, gids[gone],
                                          len(members), k)
                merged, points = merged - less, points - fewer
            else:
                rows = np.flatnonzero(keep)
                merged, points = self._merge(rows, gids[rows],
                                             len(members), k)
            members = np.bincount(gids[keep], minlength=len(members))
        live = np.flatnonzero(members)
        groups = [d.tag_name(group_tag, int(i)) if group_tag else ""
                  for i in live]
        return secs, qs, group_tag, groups, merged[live], points[live]

    def answer(self, sub: dict):
        """(group-by tag or '', one name a (group, percentile), bucket
        seconds, :class:`Cells`)."""
        d = self.data
        secs, qs, group_tag, groups, merged, points = self.merged(sub)
        names = [q_name(g, q) for g in groups for q in qs]
        total = merged.sum(axis=2)
        cum = np.cumsum(merged, axis=2)
        shape = (len(groups), len(qs), merged.shape[1])
        want, lo, hi = (np.empty(shape) for _ in range(3))
        last = d.buckets - 1
        for qi, q in enumerate(qs):
            frac = Fraction(repr(q))
            # cum * 100 < total * q in integers, and the two ends of
            # what a comparison within tie_rtol of the target reaches
            left = cum * (100 * frac.denominator)
            right = total * frac.numerator
            tol = np.ceil(right * self.tie_rtol).astype(np.int64)
            for out, below in (
                    (want, left < right[..., None]),
                    (lo, left < (right - tol)[..., None]),
                    (hi, left <= (right + tol)[..., None])):
                out[:, qi] = np.where(
                    total > 0,
                    self.mids[np.minimum(below.sum(axis=2), last)], 0.0)
        emitted = np.repeat(points[:, None, :] > 0, len(qs), axis=1)
        flat = (len(names), merged.shape[1])
        return group_tag, names, secs, Cells(
            want.reshape(flat), lo.reshape(flat), hi.reshape(flat),
            emitted.reshape(flat), self.tie_share)


    def lowered(self, sub: dict, lower) -> np.ndarray:
        """The control: the answer of a program that merges and ranks
        in less than the stated precision, as ``rows_to_grid`` gives a
        served one ([names, T], NaN where nothing is emitted).
        ``lower`` rounds what such a program holds after reading the
        counts: the merged counts, their total and their cumulative
        sums (to bfloat16, say, the MXU's input type: a cumulative
        count then moves by up to 0.4% against the 0.1% p99.9
        resolves); the rule is the program's, in float64. Stored
        counts of one point are under 256 and exact in bfloat16, so
        lowering ``values`` (``control.py``) says nothing of this
        deployment: the merge is where precision is lost."""
        _secs, qs, _tag, groups, merged, points = self.merged(sub)
        merged = lower(merged.astype(np.float64)).astype(np.float64)
        total = lower(merged.sum(axis=2)).astype(np.float64)
        cum = lower(np.cumsum(merged, axis=2)).astype(np.float64)
        got = np.empty((len(groups), len(qs), merged.shape[1]))
        for qi, q in enumerate(qs):
            below = cum < (total * (q / 100.0))[..., None]
            got[:, qi] = np.where(points > 0, self.mids[np.minimum(
                below.sum(axis=2), self.data.buckets - 1)], np.nan)
        return got.reshape(len(groups) * len(qs), -1)


# ---------------------------------------------------------------------
# comparing an answer
# ---------------------------------------------------------------------

def rows_to_grid(rows, tagk: str, names: list[str], t0: int,
                 n_buckets: int, step: int, metric: str):
    """/api/query rows -> ([names, buckets] with NaN where no dp,
    count of rows or datapoints that should not be there). A row's
    name is its group's tag value and the percentile its metric
    states after ``<metric>_pct_``."""
    out = np.full((len(names), n_buckets), np.nan)
    pos = {name: i for i, name in enumerate(names)}
    prefix = metric + "_pct_"
    stray = 0
    for row in rows:
        name = str(row.get("metric", ""))
        group = row.get("tags", {}).get(tagk) if tagk else ""
        gi = pos.get(f"{group}|{name[len(prefix):]}") \
            if name.startswith(prefix) and group is not None else None
        if gi is None:
            stray += 1
            continue
        for ts, v in row["dps"].items():
            j, rem = divmod(int(ts) - t0, step)
            if rem or not 0 <= j < n_buckets:
                stray += 1
                continue
            out[gi, j] = np.nan if v is None else float(v)
    return out, stray


def compare(got: np.ndarray, stray: int, cells: Cells) -> Verdict:
    """The answer ``got`` against ``cells``: ``rank_abs_err`` is the
    largest distance of an emitted cell from the exact midpoint or, in
    a tie cell, from the nearer end of its reach; ``shape_errors``
    counts cells emitted on one side only, strays, and the tie cells
    beyond the share an answer may have."""
    v = Verdict()
    want = np.where(cells.emitted, cells.want, np.nan)
    mism = np.isnan(got) != np.isnan(want)
    v.shape_errors = stray + int(mism.sum())
    if mism.any():
        g, j = np.argwhere(mism)[0]
        v.note = (f"cell ({g}, {j}) emitted={not np.isnan(got[g, j])},"
                  f" reference emitted={not np.isnan(want[g, j])}")
    ok = ~np.isnan(want) & ~np.isnan(got)
    if not ok.any():
        if not v.shape_errors:
            v.shape_errors = 1
            v.note = "nothing to compare"
        return v
    exact = np.where(ok, np.abs(got - want), 0.0)
    err = np.where(ok, np.minimum(exact, np.minimum(
        np.abs(got - cells.lo), np.abs(got - cells.hi))), 0.0)
    v.ties = int((err < exact).sum())
    over = v.ties - math.ceil(cells.tie_share * ok.sum())
    if over > 0:
        v.shape_errors += over
        v.note = v.note or (f"{v.ties} tie cells of {int(ok.sum())}: "
                            f"more than the share {cells.tie_share:g}")
    v.rank_abs_err = float(err.max())
    if not v.note:
        g, j = np.unravel_index(np.argmax(err), err.shape)
        v.note = (f"cell ({g}, {j}): got {got[g, j]!r} want "
                  f"{want[g, j]!r}")
    return v
