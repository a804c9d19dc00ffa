"""Independent reference-semantics oracle for differential testing.

A deliberately naive, per-datapoint Python re-implementation of the
reference's read pipeline — Downsampler window iterator, RateSpan
first-difference, and the AggregationIterator k-way merge with per-
aggregator interpolation (ref: AggregationIterator.java:27-119,
Downsampler.java:295, RateSpan.java:21). Nothing here shares code with
the device kernels, so a differential test against the engine can catch
bugs in the shared XLA pipeline that path-vs-path comparisons cannot.

Scope: fixed-interval downsampling, NONE/ZERO/NAN/SCALAR fills, rate
(plain + counter), the scalar aggregators, the median and the six
legacy percentiles (p50 ... p999), group-by merge.
"""

from __future__ import annotations

import math

import numpy as np

# interpolation mode per aggregator (ref: Aggregators.java:38-44 and
# the registry entries :47-135)
INTERP = {
    "sum": "lerp", "avg": "lerp", "min": "lerp", "max": "lerp",
    "dev": "lerp", "multiply": "lerp",
    "zimsum": "zim", "count": "zim", "squareSum": "zim",
    "mimmin": "max", "mimmax": "min",
    "pfsum": "prev",
    "diff": "lerp", "first": "zim", "last": "zim",
    "median": "lerp",
}

# the percentile family (ref: Aggregators.java PercentileAgg over
# commons-math's Percentile, EstimationType.LEGACY; interpolation
# LERP): registry name -> p
PERCENTILES = {"p50": 0.5, "p75": 0.75, "p90": 0.9, "p95": 0.95,
               "p99": 0.99, "p999": 0.999}
INTERP.update(dict.fromkeys(PERCENTILES, "lerp"))


def legacy_percentile(xs, p):
    """The p-th percentile of the sorted ``xs`` at the one-based
    position h = p (n + 1): the minimum below 1, the maximum at or
    above n, else the straight line between the two neighbours (ref:
    commons-math Percentile.evaluate, LEGACY)."""
    n = len(xs)
    h = p * (n + 1)
    k = math.floor(h)
    if h < 1:
        return xs[0]
    if h >= n:
        return xs[-1]
    return xs[k - 1] + (h - k) * (xs[k] - xs[k - 1])


def downsample_series(ts_ms, vals, interval_ms, function, start_ms,
                      end_ms):
    """One series -> {bucket_start_ms: value} (reference Downsampler:
    modulo-aligned buckets, NaN values skipped)."""
    out = {}
    buckets: dict[int, list] = {}
    for t, v in zip(ts_ms, vals):
        if t < start_ms or t > end_ms or math.isnan(v):
            continue
        b = t - (t % interval_ms)
        buckets.setdefault(b, []).append((t, v))
    for b, pts in buckets.items():
        xs = [v for _, v in sorted(pts)]
        if function == "sum":
            out[b] = sum(xs)
        elif function == "avg":
            out[b] = sum(xs) / len(xs)
        elif function == "min":
            out[b] = min(xs)
        elif function == "max":
            out[b] = max(xs)
        elif function == "count":
            out[b] = float(len(xs))
        elif function == "first":
            out[b] = xs[0]
        elif function == "last":
            out[b] = xs[-1]
        else:
            raise ValueError(function)
    return out


def rate_series(points, counter=False, counter_max=float(2**64 - 1),
                reset_value=0.0, drop_resets=False):
    """{ts: value} -> {ts: rate} (ref: RateSpan dv/dt, counter
    rollover correction, reset suppression). The first point emits
    nothing."""
    out = {}
    items = sorted(points.items())
    for (t0, v0), (t1, v1) in zip(items, items[1:]):
        dt = (t1 - t0) / 1000.0
        if dt <= 0:
            dt = 1.0
        r = (v1 - v0) / dt
        if counter and v1 - v0 < 0:
            r = (counter_max - v0 + v1) / dt
            if drop_resets:
                continue
        if counter and reset_value > 0 and r > reset_value:
            r = 0.0
        out[t1] = r
    return out


def _interp_at(points, t, mode):
    """Value of one series at timestamp t per the aggregator's
    interpolation mode; None = contributes nothing (ref:
    AggregationIterator merge semantics)."""
    if t in points:
        return points[t]
    if mode == "skip":
        return None
    ts = sorted(points)
    if not ts:
        return None
    before = [x for x in ts if x < t]
    after = [x for x in ts if x > t]
    if mode == "zim":
        return 0.0
    if not before or not after:
        if mode == "prev":
            return points[before[-1]] if before else None
        return None  # exhausted / not started: no contribution
    if mode == "lerp":
        t0, t1 = before[-1], after[0]
        v0, v1 = points[t0], points[t1]
        return v0 + (v1 - v0) * (t - t0) / (t1 - t0)
    if mode == "max":
        return float("inf")
    if mode == "min":
        return float("-inf")
    if mode == "prev":
        return points[before[-1]]
    raise ValueError(mode)


def aggregate_group(series_points, agg, interpolate=True):
    """[{ts: value}, ...] -> {ts: aggregate} at the union of the
    group's timestamps with per-aggregator interpolation
    (``interpolate=False``: NaN-fill semantics — a missing series
    simply contributes nothing, ref runDouble's NaN skip)."""
    mode = INTERP[agg] if interpolate else "skip"
    union = sorted({t for p in series_points for t in p})
    out = {}
    for t in union:
        xs = [x for p in series_points
              if (x := _interp_at(p, t, mode)) is not None]
        if not xs:
            continue
        if agg in ("sum", "zimsum", "pfsum"):
            out[t] = sum(xs)
        elif agg == "avg":
            out[t] = sum(xs) / len(xs)
        elif agg in ("min", "mimmin"):
            v = min(xs)
            out[t] = v if math.isfinite(v) else None
        elif agg in ("max", "mimmax"):
            v = max(xs)
            out[t] = v if math.isfinite(v) else None
        elif agg == "count":
            out[t] = float(len(xs))
        elif agg == "multiply":
            out[t] = math.prod(xs)
        elif agg == "squareSum":
            out[t] = sum(x * x for x in xs)
        elif agg == "dev":
            if len(xs) == 1:
                out[t] = 0.0
            else:
                # population std (divisor n): the reference's Welford
                # over-increments n and its own tests expect numpy.std
                # (TestAggregators.java:82-122)
                m = sum(xs) / len(xs)
                out[t] = math.sqrt(
                    sum((x - m) ** 2 for x in xs) / len(xs))
        elif agg == "first":
            out[t] = xs[0]
        elif agg == "last":
            out[t] = xs[-1]
        elif agg == "diff":
            out[t] = 0.0 if len(xs) == 1 else xs[-1] - xs[0]
        elif agg == "median":
            # the upper median (ref: Aggregators.Median.runDouble)
            out[t] = sorted(xs)[len(xs) // 2]
        elif agg in PERCENTILES:
            out[t] = legacy_percentile(sorted(xs), PERCENTILES[agg])
        else:
            raise ValueError(agg)
        if out.get(t) is None:
            del out[t]
    return out


def run_oracle(series, agg, interval_ms, ds_function, start_ms, end_ms,
               rate=False, fill_policy="none", fill_value=float("nan"),
               rate_kwargs=None):
    """Full reference pipeline for ONE group.

    series: list of (ts_ms array, values array). Returns {ts: value}.
    """
    pts = []
    for ts_ms, vals in series:
        p = downsample_series(ts_ms, vals, interval_ms, ds_function,
                              start_ms, end_ms)
        if fill_policy in ("zero", "scalar"):
            sub = 0.0 if fill_policy == "zero" else fill_value
            all_buckets = _group_buckets(series, interval_ms, start_ms,
                                         end_ms)
            p = {b: p.get(b, sub) for b in all_buckets}
        if rate:
            p = rate_series(p, **(rate_kwargs or {}))
        pts.append(p)
    return aggregate_group(pts, agg,
                           interpolate=fill_policy == "none")


def _group_buckets(series, interval_ms, start_ms, end_ms):
    """FillingDownsampler emission grid: every interval bucket over the
    query range."""
    first = start_ms - (start_ms % interval_ms)
    return list(range(first, end_ms + 1, interval_ms))
