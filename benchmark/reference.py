"""The plain reference: NumPy float64 over the seeded arrays, with the
semantics of the reference implementation's Downsampler, RateSpan and
AggregationIterator. It imports nothing of the program and takes
nothing the program has made. Copied from ``chip_smoke.py`` (PR 21),
where it was cross-checked against ``tests/oracle.py``; the test beside
this file checks it against that oracle again.

It answers the sub-queries the traffic files send: one metric, a
``sum``, ``max`` or ``min`` aggregator, an aligned ``<n>[sm]-<fn>``
downsample with ``fn`` in avg, max, min, sum, an optional (counter)
rate, and filters of the types ``wildcard`` (``*``), ``literal_or`` and
``not_literal_or`` with at most one group-by tag. Anything else raises
:class:`Unsupported`: traffic the reference cannot answer cannot be
judged and must not be sent. :meth:`Reference.supports` says so from
the sub-query and the deployment's parameters alone, and ``run.py``
asks it of every template before the server starts.

A request may ask a window of its own instead of the deployment's
whole span (``traffic.py``'s ``window``: the last hour up to a "now"
that moves). ``answer(sub, window=(start_ms, end_ms))`` answers it, by
OpenTSDB 2.4's rules as this repository's conformance oracle states
them (``tests/oracle.py`` holds the program to the same; nothing of it
is imported here):

- a point counts when ``start_ms <= its timestamp <= end_ms``, to the
  millisecond, and in no other case (``TsdbQuery``'s range);
- buckets are aligned down to the interval (``Downsampler``:
  timestamps modulo the interval), so the first bucket starts at
  ``start_ms - start_ms % interval`` and the last holds ``end_ms``:
  both are partial where the window's ends are off an edge, and fold
  only the points the window holds of them;
- downsample, then rate (``RateSpan`` over the downsampler's values):
  a series' first bucket in the window has no rate, and the rate of
  its second reads the partial first;
- a group's bucket is emitted where a member has a real value there;
  a member counts between its first and its last value in the WINDOW,
  on the straight line across a gap (``AggregationIterator``);
- the first bucket is emitted under its aligned timestamp although
  that lies before ``start_ms``, as the oracle and the program's
  ``fixed_bucket_edges`` have it. The source tree the issue names for
  this one question was not in the builder's sandbox (PERF.md section
  7 has the doubt); no cell depends on it, a rate having no value in
  that bucket either way.

The interior buckets of a window are the span's own. For a ``sum``
they are folded once a pair of first and last bucket, over the series
with a value in every interior bucket; those series' two edge buckets
depend only on how many of the bucket's points the window cuts, which
is one of two neighbouring counts by the series' offset within the
cadence (``data.point_offset_s``), so they come from prefix sums over
the offsets; the few series with a gap inside are folded anew for each
window. Anything else (another aggregator, an exclusion, a window of
under three buckets) folds every selected series anew.

A configuration file may name another judge (``deploy.py``); one that
builds on this file subclasses :class:`Reference`.
"""

from __future__ import annotations

import json

import numpy as np

# The number compared, and why these are the right ones (the values
# live in each configuration file under ``limits``, see PERF.md for the
# readings they were set from).
#
# The server computes in float32 on the chip; this file in float64. A
# group sum over 10,000 series loses up to ~1e-5 of the sum of
# magnitudes to f32 accumulation, one dropped series moves it by 1e-4,
# values stored in bfloat16 move it by more. So a summed cell is held
# to |got - want| <= sum_rtol * (sum over the group's members of
# |term|) + value_atol-per-member (what f32 cannot resolve of ONE value
# of magnitude <= 1e4: half an ulp = 5e-4; a rate's delta has two and
# is divided by dt). An order statistic (max, min) carries only the
# f32 rounding of one value and is held to rank_atol.
#
# A counter rate is discontinuous at delta == 0: where two bucket
# values differ by less than f32 can resolve (counter_tie), float32 and
# float64 may disagree on the sign and one of them adds counter_max /
# dt. Such (series, bucket) pairs widen their cell by that amount.


class Unsupported(Exception):
    """The reference does not answer this sub-query."""


_UNITS = {"s": 1, "m": 60, "h": 3600}
_DS_FNS = ("avg", "max", "min", "sum")
_AGGS = ("sum", "max", "min")


def parse_downsample(spec: str) -> tuple[int, str]:
    """``5m-avg`` -> (300, 'avg')."""
    try:
        span, fn = spec.split("-")
        secs = int(span[:-1]) * _UNITS[span[-1]]
    except (ValueError, KeyError, IndexError):
        raise Unsupported(f"downsample {spec!r}") from None
    if fn not in _DS_FNS or secs <= 0:
        raise Unsupported(f"downsample {spec!r}")
    return secs, fn


def ref_rate(grid: np.ndarray, dt_s: float,
             counter_max: float | None, counter_tie: float):
    """Per-series rate over the bucket grid (ref: RateSpan): each
    present bucket against the PREVIOUS PRESENT one, dv / dt seconds;
    a series' first present bucket emits nothing. Returns (rate grid
    with NaN where nothing is emitted, near-tie deltas per cell)."""
    s, b = grid.shape
    present = ~np.isnan(grid)
    cols = np.arange(b)
    last = np.maximum.accumulate(
        np.where(present, cols[None, :], -1), axis=1)
    prev = np.concatenate([np.full((s, 1), -1), last[:, :-1]], axis=1)
    ok = present & (prev >= 0)
    pv = np.take_along_axis(grid, np.maximum(prev, 0), axis=1)
    delta = grid - pv
    ties = np.zeros((s, b), dtype=bool)
    if counter_max is not None:
        ties = ok & (np.abs(delta) <= counter_tie)
        delta = np.where(delta < 0, counter_max - pv + grid, delta)
    dt = (cols[None, :] - prev) * float(dt_s)
    rate = np.where(ok, delta / np.where(ok, dt, 1.0), np.nan)
    return rate, ties


def lerp_fill(grid: np.ndarray) -> np.ndarray:
    """Merge-time interpolation of the lerp aggregators (sum, max,
    min): a missing cell BETWEEN two present ones takes the straight
    line between them; before the first or after the last present cell
    a series contributes nothing (ref: AggregationIterator)."""
    s, b = grid.shape
    present = ~np.isnan(grid)
    if present.all():
        return grid
    cols = np.arange(b)
    prev = np.maximum.accumulate(
        np.where(present, cols[None, :], -1), axis=1)
    nxt = np.minimum.accumulate(
        np.where(present, cols[None, :], b)[:, ::-1], axis=1)[:, ::-1]
    inner = ~present & (prev >= 0) & (nxt < b)
    p = np.clip(prev, 0, b - 1)
    q = np.clip(nxt, 0, b - 1)
    v0 = np.take_along_axis(grid, p, axis=1)
    v1 = np.take_along_axis(grid, q, axis=1)
    w = (cols[None, :] - p) / np.maximum(q - p, 1)
    return np.where(inner, v0 + (v1 - v0) * w, grid)


def fold(v: np.ndarray, fn: str) -> np.ndarray:
    """A downsample function over the last axis, NaN where a bucket
    holds no point."""
    cnt = (~np.isnan(v)).sum(axis=-1)
    with np.errstate(invalid="ignore", divide="ignore"):
        if fn == "avg":
            return np.nansum(v, axis=-1) / cnt
        if fn == "sum":
            return np.where(cnt > 0, np.nansum(v, axis=-1), np.nan)
        if fn == "max":
            return np.where(cnt > 0, np.max(np.where(
                np.isnan(v), -np.inf, v), axis=-1), np.nan)
        return np.where(cnt > 0, np.min(np.where(
            np.isnan(v), np.inf, v), axis=-1), np.nan)


def window_buckets(start_ms: int, end_ms: int, secs: int):
    """(first bucket's timestamp in seconds, number of buckets) of a
    window: aligned down to the interval, the last one holds
    ``end_ms``."""
    first = start_ms - start_ms % (secs * 1000)
    return first // 1000, (end_ms - first) // (secs * 1000) + 1


def span_of(d) -> tuple[int, int]:
    """The window of a request that asks the deployment's whole span."""
    return d.t0 * 1000, d.end * 1000


def span_only(d, window) -> None:
    """For a judge that answers the deployment's span and no other
    window."""
    if window is not None and tuple(window) != span_of(d):
        raise Unsupported(f"the window {tuple(window)} is not the "
                          f"deployment's span {span_of(d)}")


class Cells:
    """What one answer should be: per group and bucket the value, the
    relative scale and the absolute allowance of its comparison, and
    whether the bucket is emitted at all (a bucket exists for a group
    when some member has a REAL value there)."""

    def __init__(self, g: int, b: int):
        self.want = np.full((g, b), np.nan)
        self.scale = np.zeros((g, b))   # sum of |term|; 0 for a rank
        self.atol = np.zeros((g, b))
        self.emitted = np.zeros((g, b), dtype=bool)

    def put(self, gi: int, other: "Cells") -> None:
        self.want[gi] = other.want[0]
        self.scale[gi] = other.scale[0]
        self.atol[gi] = other.atol[0]
        self.emitted[gi] = other.emitted[0]

    def copy(self) -> "Cells":
        out = Cells(*self.want.shape)
        out.want[:] = self.want
        out.scale[:] = self.scale
        out.atol[:] = self.atol
        out.emitted[:] = self.emitted
        return out


def _must_tile(d, secs: int) -> None:
    if secs % d.cadence_s or (d.points * d.cadence_s) % secs \
            or d.t0 % secs:
        raise Unsupported(f"{secs}s buckets do not tile the data")


class Reference:
    aggregators = _AGGS     # a subclass that answers more lists them

    def __init__(self, data, values: np.ndarray, limits: dict):
        """``values``: [series, points] float64, NaN where dropped."""
        self.data = data
        self.values = values
        self.sum_rtol = float(limits["sum_rtol"])
        self.value_atol = float(limits["value_atol"])
        self.rank_atol = float(limits["rank_atol"])
        self.counter_tie = float(limits["counter_tie"])
        self._grids: dict = {}
        self._bases: dict = {}
        self._selections: dict = {}     # the last few, by their filters
        self._edges: dict = {}

    # -- per-series grids ----------------------------------------------

    def series_grid(self, secs: int, fn: str, rate: bool,
                    counter_max: float | None):
        """([series, buckets] grid, ties or None), cached: downsample,
        then rate."""
        key = (secs, fn, rate, counter_max)
        hit = self._grids.get(key)
        if hit is not None:
            return hit
        d = self.data
        _must_tile(d, secs)
        k = secs // d.cadence_s
        if k == 1:
            grid = self.values
        else:
            grid = fold(self.values.reshape(d.series, d.points // k, k),
                        fn)
        ties = None
        if rate:
            grid, ties = ref_rate(grid, secs, counter_max,
                                  self.counter_tie)
        self._grids[key] = (grid, ties)
        return grid, ties

    # -- one group -------------------------------------------------------

    def _reduce(self, grid, ties, gids, g: int, agg: str, secs: int,
                rate: bool, counter_max) -> Cells:
        """Aggregate rows ``grid`` into ``g`` groups."""
        b = grid.shape[1]
        out = Cells(g, b)
        filled = lerp_fill(grid)
        term_atol = 2 * self.value_atol / secs if rate \
            else self.value_atol
        for j in range(b):
            col = filled[:, j]
            ok = ~np.isnan(col)
            out.emitted[:, j] = np.bincount(
                gids, weights=~np.isnan(grid[:, j]), minlength=g) > 0
            n = np.bincount(gids[ok], minlength=g)
            if agg == "sum":
                out.want[:, j] = np.bincount(
                    gids[ok], weights=col[ok], minlength=g)
                out.scale[:, j] = np.bincount(
                    gids[ok], weights=np.abs(col[ok]), minlength=g)
                out.atol[:, j] = term_atol * n
            else:
                fill = -np.inf if agg == "max" else np.inf
                acc = np.full(g, fill)
                op = np.maximum if agg == "max" else np.minimum
                op.at(acc, gids[ok], col[ok])
                out.want[:, j] = np.where(n > 0, acc, np.nan)
            if ties is not None and counter_max is not None:
                out.atol[:, j] += np.bincount(
                    gids, weights=ties[:, j], minlength=g) \
                    * (counter_max / secs)
        return out

    # -- one sub-query ---------------------------------------------------

    @classmethod
    def supports(cls, sub: dict, d, window=None):
        """The parsing half of :meth:`answer`: raises
        :class:`Unsupported` for a sub-query this judge does not
        answer over the deployment ``d`` in the ``window`` asked
        (``(start_ms, end_ms)``; None is the span), from those alone
        (no values), and returns what it parsed."""
        if window is not None and not \
                d.t0 * 1000 <= window[0] <= window[1] < (d.end + 1) * 1000:
            raise Unsupported(f"the window {tuple(window)} leaves the "
                              f"data's span {span_of(d)}")
        if sub.get("metric") != d.metric:
            raise Unsupported(f"metric {sub.get('metric')!r}")
        agg = sub.get("aggregator")
        if agg not in cls.aggregators:
            raise Unsupported(f"aggregator {agg!r}")
        secs, fn = parse_downsample(sub.get("downsample") or "")
        _must_tile(d, secs)
        rate = bool(sub.get("rate"))
        if rate and agg != "sum":
            raise Unsupported("a rate under a rank aggregator")
        opts = sub.get("rateOptions") or {}
        counter_max = float(opts["counterMax"]) \
            if rate and opts.get("counter") else None
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
        return (agg, secs, fn, rate, counter_max, include, exclude,
                group_tag)

    def answer(self, sub: dict, window=None):
        """(group-by tag or '', group names, bucket seconds, Cells).
        ``window`` is ``(start_ms, end_ms)`` of the request; None, or
        the deployment's span, is the whole of the data. The Cells of
        another window lie on :func:`window_buckets` of it."""
        d = self.data
        if window is not None and tuple(window) != span_of(d):
            return self._answer_window(sub, tuple(window))
        agg, secs, fn, rate, counter_max, include, exclude, group_tag \
            = self.supports(sub, d)
        base_key = json.dumps([agg, secs, fn, rate, counter_max,
                               include, group_tag], sort_keys=True)
        base = self._bases.get(base_key)
        if base is None:
            base = self._bases[base_key] = self._base(
                agg, secs, fn, rate, counter_max, include, group_tag)
        rows, gids, order, bounds, names, cells = base
        if not exclude:
            return group_tag, names, secs, cells
        # excluded series change only the groups they belong to:
        # those are computed again from their remaining members
        gone = np.zeros(len(rows), dtype=bool)
        for tagk, vals in exclude:
            ids = np.array([d.tag_index(tagk, v) for v in vals])
            gone |= np.isin(d.tag_ids(tagk, rows), ids[ids >= 0])
        grid, ties = self.series_grid(secs, fn, rate, counter_max)
        out = cells.copy()
        for gi in np.unique(gids[gone]).tolist():
            members = order[bounds[gi]:bounds[gi + 1]]
            members = members[~gone[members]]
            sel = rows[members]
            one = self._reduce(
                grid[sel], None if ties is None else ties[sel],
                np.zeros(len(sel), dtype=np.int64), 1, agg, secs,
                rate, counter_max)
            out.put(gi, one)
        return group_tag, names, secs, out

    def selected(self, sub: dict) -> int:
        """How many series the sub-query selects."""
        d = self.data
        keep = np.ones(d.series, dtype=bool)
        idx = np.arange(d.series)
        for f in sub.get("filters") or []:
            if f["type"] == "wildcard":
                continue
            ids = np.array([d.tag_index(f["tagk"], v)
                            for v in f["filter"].split("|")])
            hit = np.isin(d.tag_ids(f["tagk"], idx), ids[ids >= 0])
            keep &= hit if f["type"] == "literal_or" else ~hit
        return int(keep.sum())

    def _base(self, agg, secs, fn, rate, counter_max, include,
              group_tag):
        d = self.data
        rows, gids, names = self._selection(include, [], group_tag)
        grid, ties = self.series_grid(secs, fn, rate, counter_max)
        g = len(names)
        whole = len(rows) == d.series
        cells = self._reduce(
            grid if whole else grid[rows],
            ties if whole or ties is None else ties[rows],
            gids, g, agg, secs, rate, counter_max)
        order = np.argsort(gids, kind="stable")
        bounds = np.searchsorted(gids[order], np.arange(g + 1))
        return rows, gids, order, bounds, names, cells

    # -- a window of the request's own ----------------------------------

    def _offsets_ms(self, rows: np.ndarray) -> np.ndarray:
        offsets = getattr(self.data, "point_offset_s", None)
        off = offsets(rows) if offsets else None
        return np.zeros(len(rows), dtype=np.int64) if off is None \
            else off.astype(np.int64) * 1000

    def _selection(self, include, exclude, group_tag):
        """(rows, group of each row, group names) of a sub-query's
        filters; the names are those of the groups before the
        exclusion, as :meth:`answer` has them."""
        key = json.dumps([include, exclude, group_tag], sort_keys=True)
        hit = self._selections.get(key)
        if hit is not None:
            return hit
        d = self.data
        rows = None
        for tagk, vals in include:
            ids = np.array(sorted({d.tag_index(tagk, v) for v in vals}
                                  - {-1}), dtype=np.int64)
            if tagk == "host" and rows is None:
                rows = ids          # a host is its series: no pass over all
                continue
            pool = np.arange(d.series) if rows is None else rows
            rows = pool[np.isin(d.tag_ids(tagk, pool), ids)]
        if rows is None:
            rows = np.arange(d.series)
        if group_tag:
            present, gids = np.unique(d.tag_ids(group_tag, rows),
                                      return_inverse=True)
            names = [d.tag_name(group_tag, int(i)) for i in present]
        else:
            gids = np.zeros(len(rows), dtype=np.int64)
            names = [""]
        for tagk, vals in exclude:
            ids = np.array([d.tag_index(tagk, v) for v in vals])
            keep = ~np.isin(d.tag_ids(tagk, rows), ids[ids >= 0])
            rows, gids = rows[keep], gids[keep]
        for old in list(self._selections)[:-3]:
            del self._selections[old]
        self._selections[key] = rows, gids, names
        return rows, gids, names

    def _window_grid(self, rows, secs, fn, rate, counter_max,
                     start_ms: int, end_ms: int):
        """([rows, the window's buckets] grid, ties or None) folded
        anew from the points: those outside ``[start_ms, end_ms]``
        masked, each bucket folded over what is left, then the rate."""
        d = self.data
        k = secs // d.cadence_s
        first_s, nb = window_buckets(start_ms, end_ms, secs)
        j0 = (first_s - d.t0) // secs
        cols = np.arange(j0 * k, (j0 + nb) * k)
        v = self.values[rows[:, None], cols[None, :]]
        ts = (d.t0 + d.cadence_s * cols)[None, :] * 1000 \
            + self._offsets_ms(rows)[:, None]
        v = np.where((ts >= start_ms) & (ts <= end_ms), v, np.nan)
        grid = fold(v.reshape(len(rows), nb, k), fn)
        if not rate:
            return grid, None
        return ref_rate(grid, secs, counter_max, self.counter_tie)

    def _answer_window(self, sub: dict, window: tuple[int, int]):
        d = self.data
        agg, secs, fn, rate, counter_max, include, exclude, group_tag \
            = self.supports(sub, d, window)
        rows, gids, names = self._selection(include, exclude, group_tag)
        start_ms, end_ms = window
        first_s, nb = window_buckets(start_ms, end_ms, secs)
        if agg != "sum" or exclude or nb < 3:
            grid, ties = self._window_grid(rows, secs, fn, rate,
                                           counter_max, start_ms, end_ms)
            return group_tag, names, secs, self._reduce(
                grid, ties, gids, len(names), agg, secs, rate,
                counter_max)
        j0 = (first_s - d.t0) // secs
        key = json.dumps([secs, fn, rate, counter_max, include,
                          group_tag, j0, nb], sort_keys=True)
        edges = self._edges.get(key)
        if edges is None:
            edges = self._edges[key] = _WindowEdges(
                self, rows, gids, len(names), secs, fn, rate,
                counter_max, j0, nb)
        return group_tag, names, secs, edges.cells(start_ms, end_ms)


class _WindowEdges:
    """The windows of one sub-query that share their first and last
    bucket (``j0`` and ``nb`` buckets on, of the span's), summed by
    group: what is the same for all of them once, and a window's two
    edges from tables (the module's docstring has the reasoning).

    A series is REGULAR here when it has a value in every interior
    bucket. Its grid in any such window is: the partial first bucket,
    the span's interior buckets, the partial last bucket; nothing to
    interpolate; under a rate, bucket 1 against the partial first,
    the interior against each other, the last against the last
    interior. The partial first bucket loses the points before
    ``start_ms``: with ``o = start_ms -`` the bucket's edge, ``q, r =
    divmod(o, cadence)``, a series whose offset is at least ``r`` loses
    ``q`` points and any other ``q + 1``; the last bucket keeps ``qe +
    1`` points of a series whose offset is at most ``re`` and ``qe`` of
    any other. So an edge's sums are two slices of two tables, each the
    per-(offset, group) sums for one count, cumulated over the offsets.
    The other series (a gap inside: about one in twenty of the gappy
    tenth) are few and are folded anew."""

    PARTS = 4       # sum, sum of magnitudes, members, near-tie members

    def __init__(self, ref, rows, gids, g, secs, fn, rate, counter_max,
                 j0, nb):
        self.ref, self.g, self.secs, self.fn = ref, g, secs, fn
        self.rate, self.counter_max = rate, counter_max
        self.j0, self.nb = j0, nb
        d = ref.data
        self.k = secs // d.cadence_s
        self.cad_ms = d.cadence_s * 1000
        self.first_ms = (d.t0 + j0 * secs) * 1000
        self.last_ms = self.first_ms + (nb - 1) * secs * 1000
        span = ref.series_grid(secs, fn, False, None)[0]
        inner = span[rows, j0 + 1:j0 + nb - 1]
        regular = ~np.isnan(inner).any(axis=1)
        self.rows, self.gids = rows[regular], gids[regular]
        self.other_rows, self.other_gids = rows[~regular], gids[~regular]
        inner = inner[regular]
        self.before_last = inner[:, -1]
        self.after_first = inner[:, 0]
        self.offsets_ms, self.rank = np.unique(
            ref._offsets_ms(self.rows), return_inverse=True)
        # the interior: columns 1 .. nb - 2 of the window, and under a
        # rate from column 2 on
        self.mid = np.zeros((self.PARTS, g, nb))
        if rate:
            r, ties = ref_rate(inner, secs, counter_max, ref.counter_tie)
            for j in range(1, inner.shape[1]):
                self.mid[:, :, j + 1] = self._parts(
                    r[:, j], ties[:, j], self.gids, g)
        else:
            for j in range(inner.shape[1]):
                self.mid[:, :, j + 1] = self._parts(
                    inner[:, j], None, self.gids, g)
        self._first: dict = {}
        self._last: dict = {}

    def _parts(self, col, ties, keys, n):
        """[PARTS, n] sums of one column by ``keys``."""
        ok = ~np.isnan(col)
        k, v = keys[ok], col[ok]
        out = np.zeros((self.PARTS, n))
        out[0] = np.bincount(k, weights=v, minlength=n)
        out[1] = np.bincount(k, weights=np.abs(v), minlength=n)
        out[2] = np.bincount(k, minlength=n)
        if ties is not None and self.counter_max is not None:
            out[3] = np.bincount(keys, weights=ties, minlength=n)
        return out

    def _table(self, edge):
        """[offsets + 1, PARTS, groups]: the edge bucket's column (under
        a rate its pair of columns) summed by (offset, group) and
        cumulated over the offsets."""
        if self.rate:           # a pair: the bucket before, this one
            r, ties = ref_rate(np.stack(edge, axis=1), self.secs,
                               self.counter_max, self.ref.counter_tie)
            col, ties = r[:, 1], ties[:, 1]
        else:
            col, ties = edge, None
        u = len(self.offsets_ms)
        flat = self._parts(col, ties, self.rank * self.g + self.gids,
                           u * self.g)
        table = flat.reshape(self.PARTS, u, self.g).transpose(1, 0, 2)
        return np.concatenate([np.zeros((1, self.PARTS, self.g)),
                               np.cumsum(table, axis=0)])

    def _nothing(self):
        return np.zeros((len(self.offsets_ms) + 1, self.PARTS, self.g))

    def _first_table(self, cut: int):
        """The first bucket with its first ``cut`` points lost."""
        hit = self._first.get(cut)
        if hit is None:
            if cut >= self.k:
                hit = self._nothing()
            else:
                lo = self.j0 * self.k
                part = fold(self.ref.values[
                    self.rows, lo + cut:lo + self.k], self.fn)
                hit = self._table([part, self.after_first]
                                  if self.rate else part)
            self._first[cut] = hit
        return hit

    def _last_table(self, kept: int):
        """The last bucket with its first ``kept`` points alone."""
        hit = self._last.get(kept)
        if hit is None:
            if kept <= 0:
                hit = self._nothing()
            else:
                lo = (self.j0 + self.nb - 1) * self.k
                part = fold(self.ref.values[self.rows, lo:lo + kept],
                            self.fn)
                hit = self._table([self.before_last, part]
                                  if self.rate else part)
            self._last[kept] = hit
        return hit

    def cells(self, start_ms: int, end_ms: int) -> Cells:
        ref, nb = self.ref, self.nb
        parts = self.mid.copy()
        # the first bucket: offsets under r lose q + 1 points, the
        # others q
        q, r = divmod(start_ms - self.first_ms, self.cad_ms)
        c = int(np.searchsorted(self.offsets_ms, r, "left"))
        few, more = self._first_table(q), self._first_table(q + 1)
        parts[:, :, 1 if self.rate else 0] = few[-1] - few[c] + more[c]
        # the last bucket: offsets up to r keep q + 1 points, the
        # others q
        q, r = divmod(end_ms - self.last_ms, self.cad_ms)
        c = int(np.searchsorted(self.offsets_ms, r, "right"))
        few, more = self._last_table(q), self._last_table(q + 1)
        parts[:, :, nb - 1] = few[-1] - few[c] + more[c]
        out = Cells(self.g, nb)
        term_atol = 2 * ref.value_atol / self.secs if self.rate \
            else ref.value_atol
        out.want[:] = parts[0]
        out.scale[:] = parts[1]
        out.atol[:] = term_atol * parts[2]
        if self.counter_max is not None:
            out.atol += parts[3] * (self.counter_max / self.secs)
        out.emitted[:] = parts[2] > 0
        if len(self.other_rows):
            grid, ties = ref._window_grid(
                self.other_rows, self.secs, self.fn, self.rate,
                self.counter_max, start_ms, end_ms)
            rest = ref._reduce(grid, ties, self.other_gids, self.g,
                               "sum", self.secs, self.rate,
                               self.counter_max)
            out.want += rest.want
            out.scale += rest.scale
            out.atol += rest.atol
            out.emitted |= rest.emitted
        return out


# ---------------------------------------------------------------------
# comparing an answer
# ---------------------------------------------------------------------

class Verdict:
    """The numbers one answer was compared on."""

    def __init__(self):
        self.shape_errors = 0     # limit 0
        self.sum_rel_err = 0.0    # limit sum_rtol
        self.rank_abs_err = 0.0   # limit rank_atol
        self.note = ""

    def ok(self, sum_rtol: float, rank_atol: float) -> bool:
        return self.shape_errors == 0 \
            and self.sum_rel_err <= sum_rtol \
            and self.rank_abs_err <= rank_atol


def rows_to_grid(rows, tagk: str, names: list[str], t0: int,
                 n_buckets: int, step: int, metric: str):
    """/api/query rows -> ([groups, buckets] with NaN where no dp,
    count of rows or datapoints that should not be there)."""
    out = np.full((len(names), n_buckets), np.nan)
    pos = {name: i for i, name in enumerate(names)}
    stray = 0
    for row in rows:
        gi = pos.get(row.get("tags", {}).get(tagk)) if tagk else 0
        if gi is None or row.get("metric") != metric:
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
    err = np.where(ok, np.abs(got - want), 0.0)
    summed = ok & (cells.scale > 0)
    if summed.any():
        rel = np.where(summed, np.maximum(err - cells.atol, 0.0)
                       / np.where(summed, cells.scale, 1.0), 0.0)
        v.sum_rel_err = float(rel.max())
        if not v.note:
            g, j = np.unravel_index(np.argmax(rel), rel.shape)
            v.note = (f"cell ({g}, {j}): got {got[g, j]!r} want "
                      f"{want[g, j]!r}")
    ranked = ok & (cells.scale == 0)
    if ranked.any():
        v.rank_abs_err = float(np.where(ranked, err, 0.0).max())
    return v
