"""The aggregation engine (ref: ``src/core/Aggregators.java``).

Every reference aggregator — 16 scalar + 12 percentile variants — as a
NaN-aware *vectorized* reduction over the series axis of a
``[series, timebucket]`` array. NaN encodes "no value for this series at
this bucket" and each aggregator carries the interpolation mode the
reference uses at group-merge time (``Aggregators.Interpolation``
:38-44): LERP fills gaps by linear interpolation before reduction, ZIM
substitutes zero, MAX/MIN substitute the type extremes, PREV repeats the
previous value (pfsum). The fill itself happens in
:mod:`opentsdb_tpu.ops.interp`; reductions here just define the
per-bucket math, exactly matching the reference semantics:

- ``sum``/``zimsum``: sum of non-NaN, all-NaN -> NaN (Sum.runDouble)
- ``avg``: mean of non-NaN, all-NaN -> NaN
- ``dev``: *sample* stddev (Welford / n-1), one value -> 0, none -> NaN
- ``median``: upper median sorted[n//2] (Median.runDouble)
- ``diff``: last non-NaN minus first non-NaN, single -> 0 (Diff)
- ``count``: number of non-NaN values (Count.runDouble)
- ``first``/``last``: first/last series (in span order) with a value
- ``multiply``: product; ``squareSum``: sum of squares
- ``p50..p999``: commons-math3 Percentile LEGACY estimation
- ``ep50r3..ep999r7``: estimation types R_3 / R_7 (PercentileAgg :657)
"""

from __future__ import annotations

import re
from dataclasses import dataclass, field
from enum import Enum
from typing import Callable

import jax.numpy as jnp


class Interpolation(Enum):
    """(ref: Aggregators.Interpolation :38-44)"""
    LERP = "lerp"
    ZIM = "zim"    # zero if missing
    MAX = "max"    # type max if missing (used by mimmin)
    MIN = "min"    # type min if missing (used by mimmax)
    PREV = "prev"  # previous value if missing (pfsum)


def _valid(x):
    return ~jnp.isnan(x)


def _nan_where_empty(result, x, axis):
    return jnp.where(jnp.any(_valid(x), axis=axis), result, jnp.nan)


def agg_sum(x, axis=0):
    return _nan_where_empty(jnp.nansum(x, axis=axis), x, axis)


def agg_min(x, axis=0):
    return _nan_where_empty(
        jnp.nanmin(jnp.where(_valid(x), x, jnp.inf), axis=axis), x, axis)


def agg_max(x, axis=0):
    return _nan_where_empty(
        jnp.nanmax(jnp.where(_valid(x), x, -jnp.inf), axis=axis), x, axis)


def agg_avg(x, axis=0):
    cnt = jnp.sum(_valid(x), axis=axis)
    total = jnp.nansum(x, axis=axis)
    return jnp.where(cnt > 0, total / jnp.maximum(cnt, 1), jnp.nan)


def agg_count(x, axis=0):
    return jnp.sum(_valid(x), axis=axis).astype(x.dtype)


def agg_multiply(x, axis=0):
    return _nan_where_empty(
        jnp.prod(jnp.where(_valid(x), x, 1.0), axis=axis), x, axis)


def agg_squaresum(x, axis=0):
    return _nan_where_empty(jnp.nansum(x * x, axis=axis), x, axis)


def agg_dev(x, axis=0):
    """POPULATION standard deviation (divisor n), matching the
    reference exactly: its Welford loop over-increments n by one and
    divides M2 by that, which lands on sigma = sqrt(M2/n) — pinned by
    its own unit tests (TestAggregators.java:82-122 expects
    numpy.std(range(10000)) and {1,2} -> 0.5, both population forms).
    0 for a single value, NaN for none (ref: Aggregators.StdDev :498).
    Computed as the mean-shifted two-pass formula — vectorizable and
    cancellation-safe; clamped at 0 against rounding."""
    cnt = jnp.sum(_valid(x), axis=axis)
    safe_cnt = jnp.maximum(cnt, 1)
    mean = jnp.nansum(x, axis=axis) / safe_cnt
    centered = jnp.where(_valid(x), x - jnp.expand_dims(mean, axis), 0.0)
    m2 = jnp.sum(centered * centered, axis=axis)
    var = m2 / safe_cnt
    dev = jnp.sqrt(jnp.maximum(var, 0.0))
    return jnp.where(cnt == 0, jnp.nan, jnp.where(cnt == 1, 0.0, dev))


def _first_last_positions(x, axis):
    s = x.shape[axis]
    idx_shape = [1] * x.ndim
    idx_shape[axis] = s
    pos = jnp.arange(s).reshape(idx_shape)
    first_pos = jnp.min(jnp.where(_valid(x), pos, s), axis=axis)
    last_pos = jnp.max(jnp.where(_valid(x), pos, -1), axis=axis)
    return first_pos, last_pos


def agg_first(x, axis=0):
    first_pos, _ = _first_last_positions(x, axis)
    safe = jnp.clip(first_pos, 0, x.shape[axis] - 1)
    picked = jnp.take_along_axis(x, jnp.expand_dims(safe, axis),
                                 axis=axis).squeeze(axis)
    return jnp.where(first_pos < x.shape[axis], picked, jnp.nan)


def agg_last(x, axis=0):
    _, last_pos = _first_last_positions(x, axis)
    safe = jnp.clip(last_pos, 0, x.shape[axis] - 1)
    picked = jnp.take_along_axis(x, jnp.expand_dims(safe, axis),
                                 axis=axis).squeeze(axis)
    return jnp.where(last_pos >= 0, picked, jnp.nan)


def agg_diff(x, axis=0):
    """last non-NaN - first non-NaN; exactly one value -> 0; none -> NaN
    (ref: Aggregators.Diff :576)."""
    cnt = jnp.sum(_valid(x), axis=axis)
    d = agg_last(x, axis) - agg_first(x, axis)
    return jnp.where(cnt == 0, jnp.nan, jnp.where(cnt == 1, 0.0, d))


def agg_median(x, axis=0):
    """Upper median: sorted[n // 2] (ref: Aggregators.Median :397)."""
    s = x.shape[axis]
    sorted_x = jnp.sort(x, axis=axis)  # NaNs sort to the end
    cnt = jnp.sum(_valid(x), axis=axis)
    idx = jnp.clip(cnt // 2, 0, s - 1)
    picked = jnp.take_along_axis(sorted_x, jnp.expand_dims(idx, axis),
                                 axis=axis).squeeze(axis)
    return jnp.where(cnt > 0, picked, jnp.nan)


def percentile_along_axis(x, q: float, estimation: str, axis=0):
    """Order statistics with commons-math3 estimation semantics.

    ``legacy``: h = q(n+1)/100, clamp to [min, max], linear interp.
    ``r3``: h = q*n/100, estimate x(ceil(h - 0.5)) — nearest, half down.
    ``r7``: h = (n-1)q/100 + 1, linear interp (numpy 'linear').
    (ref: Aggregators.PercentileAgg :657 + commons-math3 Percentile)
    """
    s = x.shape[axis]
    sorted_x = jnp.sort(x, axis=axis)
    n = jnp.sum(_valid(x), axis=axis).astype(x.dtype)
    p = q / 100.0
    if estimation == "legacy":
        h = p * (n + 1)
    elif estimation == "r3":
        h = jnp.ceil(p * n - 0.5)  # 1-based nearest rank, half rounds down
    elif estimation == "r7":
        h = (n - 1) * p + 1
    else:
        raise ValueError(f"unknown estimation type {estimation!r}")
    h = jnp.clip(h, 1.0, jnp.maximum(n, 1.0))
    h_floor = jnp.floor(h)
    frac = h - h_floor
    lo_idx = jnp.clip(h_floor.astype(jnp.int32) - 1, 0, s - 1)
    hi_idx = jnp.clip(lo_idx + 1,
                      0, jnp.maximum(n.astype(jnp.int32) - 1, 0))
    hi_idx = jnp.clip(hi_idx, 0, s - 1)
    lo = jnp.take_along_axis(sorted_x, jnp.expand_dims(lo_idx, axis),
                             axis=axis).squeeze(axis)
    hi = jnp.take_along_axis(sorted_x, jnp.expand_dims(hi_idx, axis),
                             axis=axis).squeeze(axis)
    out = lo + frac * (hi - lo)
    return jnp.where(n > 0, out, jnp.nan)


@dataclass(frozen=True)
class Aggregator:
    """One aggregation function + its merge-time interpolation mode."""
    name: str
    interpolation: Interpolation
    reduce: Callable  # (x[S,B], axis) -> [B]
    percentile: float | None = None
    estimation: str | None = None

    def __call__(self, x, axis=0):
        return self.reduce(x, axis=axis)

    @property
    def is_percentile(self) -> bool:
        return self.percentile is not None

    @property
    def is_none(self) -> bool:
        return self.name == "none"

    @property
    def rank_class(self) -> bool:
        """median / exact & estimated percentiles: the group stage is
        one sort of the grid along the series axis, not a segment
        reduction. Placement budgets, the prep cache's key and the
        tracing's ``class`` (``rank`` | ``linear``) all read this."""
        return self.name == "median" or self.is_percentile


def _make_percentile(name: str, q: float, estimation: str) -> Aggregator:
    def reduce(x, axis=0, _q=q, _e=estimation):
        return percentile_along_axis(x, _q, _e, axis=axis)
    return Aggregator(name, Interpolation.LERP, reduce,
                      percentile=q, estimation=estimation)


def _agg_none(x, axis=0):
    raise RuntimeError(
        "'none' must not be aggregated; the pipeline emits raw series")


# tsdlint: allow[unbounded-growth] closed import-time registry:
# populated once by the _register decorator walk below, never at
# serve time
_REGISTRY: dict[str, Aggregator] = {}


def _register(agg: Aggregator) -> Aggregator:
    _REGISTRY[agg.name] = agg
    return agg


# Registration mirrors Aggregators.java:47-172 name-for-name.
SUM = _register(Aggregator("sum", Interpolation.LERP, agg_sum))
PFSUM = _register(Aggregator("pfsum", Interpolation.PREV, agg_sum))
MIN = _register(Aggregator("min", Interpolation.LERP, agg_min))
MAX = _register(Aggregator("max", Interpolation.LERP, agg_max))
AVG = _register(Aggregator("avg", Interpolation.LERP, agg_avg))
MEDIAN = _register(Aggregator("median", Interpolation.LERP, agg_median))
NONE = _register(Aggregator("none", Interpolation.ZIM, _agg_none))
MULTIPLY = _register(Aggregator("multiply", Interpolation.LERP, agg_multiply))
# the query-facing registry name is "mult" (Aggregators.java:183 puts
# MULTIPLY under "mult"; its display name is "multiply")
_REGISTRY["mult"] = MULTIPLY
# MovingAverage (Aggregators.java:709) is NOT in the reference registry
# either — it is only reachable through the movingAverage() expression
# function (ExpressionFactory.java:36), provided here by
# opentsdb_tpu.query.expression.core.
DEV = _register(Aggregator("dev", Interpolation.LERP, agg_dev))
DIFF = _register(Aggregator("diff", Interpolation.LERP, agg_diff))
ZIMSUM = _register(Aggregator("zimsum", Interpolation.ZIM, agg_sum))
MIMMIN = _register(Aggregator("mimmin", Interpolation.MAX, agg_min))
MIMMAX = _register(Aggregator("mimmax", Interpolation.MIN, agg_max))
SQUARESUM = _register(Aggregator("squareSum", Interpolation.ZIM,
                                 agg_squaresum))
COUNT = _register(Aggregator("count", Interpolation.ZIM, agg_count))
FIRST = _register(Aggregator("first", Interpolation.ZIM, agg_first))
LAST = _register(Aggregator("last", Interpolation.ZIM, agg_last))

for _q, _name in ((99.9, "p999"), (99.0, "p99"), (95.0, "p95"),
                  (90.0, "p90"), (75.0, "p75"), (50.0, "p50")):
    _register(_make_percentile(_name, _q, "legacy"))
    for _est in ("r3", "r7"):
        _register(_make_percentile(f"e{_name}{_est}", _q, _est))


def get(name: str) -> Aggregator:
    """(ref: Aggregators.get :222)"""
    try:
        return _REGISTRY[name]
    except KeyError:
        raise KeyError(f"No such aggregator: {name}") from None


def names() -> list[str]:
    """Sorted registry names for ``/api/aggregators``."""
    return sorted(_REGISTRY)


def exists(name: str) -> bool:
    return name in _REGISTRY
