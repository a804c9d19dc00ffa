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
            v = self.values.reshape(d.series, d.points // k, k)
            cnt = (~np.isnan(v)).sum(axis=2)
            with np.errstate(invalid="ignore", divide="ignore"):
                if fn == "avg":
                    grid = np.nansum(v, axis=2) / cnt
                elif fn == "sum":
                    grid = np.where(cnt > 0, np.nansum(v, axis=2),
                                    np.nan)
                elif fn == "max":
                    grid = np.where(cnt > 0, np.max(np.where(
                        np.isnan(v), -np.inf, v), axis=2), np.nan)
                else:
                    grid = np.where(cnt > 0, np.min(np.where(
                        np.isnan(v), np.inf, v), axis=2), np.nan)
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
    def supports(cls, sub: dict, d):
        """The parsing half of :meth:`answer`: raises
        :class:`Unsupported` for a sub-query this judge does not
        answer over the deployment ``d``, from the two alone (no
        values), and returns what it parsed."""
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

    def answer(self, sub: dict):
        """(group-by tag or '', group names, bucket seconds, Cells)."""
        d = self.data
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
        rows = None
        for tagk, vals in include:
            ids = np.array(sorted({d.tag_index(tagk, v) for v in vals}
                                  - {-1}), dtype=np.int64)
            if tagk == "host" and rows is None:
                rows = ids
            else:
                pool = np.arange(d.series) if rows is None else rows
                rows = pool[np.isin(d.tag_ids(tagk, pool), ids)]
        if rows is None:
            rows = np.arange(d.series)
        grid, ties = self.series_grid(secs, fn, rate, counter_max)
        if group_tag:
            raw = d.tag_ids(group_tag, rows)
            present, gids = np.unique(raw, return_inverse=True)
            names = [d.tag_name(group_tag, int(i)) for i in present]
        else:
            gids = np.zeros(len(rows), dtype=np.int64)
            names = [""]
        g = len(names)
        whole = len(rows) == d.series
        cells = self._reduce(
            grid if whole else grid[rows],
            ties if whole or ties is None else ties[rows],
            gids, g, agg, secs, rate, counter_max)
        order = np.argsort(gids, kind="stable")
        bounds = np.searchsorted(gids[order], np.arange(g + 1))
        return rows, gids, order, bounds, names, cells


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
