"""Multi-chip query pipeline: shard_map over a ('series', 'time') mesh.

The distributed design (SURVEY.md §2.11, §5.8):

- **series axis** — the salt axis. Each device owns a hash-bucket of
  series (exactly the reference's SaltScanner partitioning,
  RowKey.java:141) and bucketizes/rates/fills them locally. Group-by
  aggregation crosses the axis with ``psum``/``pmin``/``pmax`` over ICI
  — replacing the TreeMap merge of 20 scanner callbacks
  (SaltScanner.java:463-536). Order-statistic aggregators (median/
  percentiles/first/last/diff/multiply) ``all_gather`` the filled grid
  instead, paying ICI bandwidth only when the math truly needs global
  order.
- **time axis** — long ranges split into bucket blocks (the analogue of
  sequence/context parallelism). Rate conversion and LERP interpolation
  need the nearest present value *across* block boundaries; these carries
  propagate with a log-step ppermute prefix scan (Hillis-Steele over the
  'time' axis), the TSDB version of ring-attention halo exchange.

The kernels reuse the single-chip segment primitives unchanged — only
the cross-device combines live here.
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import lru_cache, partial

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from opentsdb_tpu.parallel.distributed import to_host as _to_host

from opentsdb_tpu.ops import downsample as ds_mod
from opentsdb_tpu.ops.aggregators import Interpolation
from opentsdb_tpu.ops import aggregators as aggs_mod
from opentsdb_tpu.ops.interp import (_gather_minor, _next_valid_idx,
                                     _prev_valid_idx)
from opentsdb_tpu.obs.trace import trace_span
from opentsdb_tpu.ops.pipeline import PipelineSpec, run_staged

# aggregators whose group reduction crosses the series axis with
# psum/pmin/pmax partials and so keep per-device memory at
# [S_loc, B_loc]
REDUCIBLE_AGGS = frozenset((
    "sum", "zimsum", "pfsum", "avg", "count", "min", "max", "mimmin",
    "mimmax", "squareSum", "dev"))


# [G, B, BINS] histogram cell cap for the distributed percentile path
# (f32: 2^25 cells = 128 MB per device); beyond it the reduction falls
# back to all_gather — with that many groups each group holds few
# series, which is exactly when gathering is the cheaper shape
PERCENTILE_HIST_MAX_CELLS = 1 << 25


def _hist_eligible(num_groups: int, num_buckets: int) -> bool:
    return (num_groups * num_buckets * PERCENTILE_BINS
            <= PERCENTILE_HIST_MAX_CELLS)


def agg_mesh_class(agg_name: str) -> str:
    """Memory class of an aggregator's cross-shard reduction:
    'safe' — per-device O(S_loc x B) (psum partials / edge candidates);
    'pct' — histogram psum, safe iff the [G, B, BINS] partial fits
    (:func:`_hist_eligible` — the per-query shape decides);
    'gather' — all_gathers the series axis (diff/multiply)."""
    if agg_name in REDUCIBLE_AGGS or agg_name in ("first", "last"):
        return "safe"
    if agg_name == "median" or \
            aggs_mod.get(agg_name).percentile is not None:
        return "pct"
    return "gather"


def mesh_memory_safe(agg_name: str, num_groups: int | None = None,
                     num_buckets: int | None = None) -> bool:
    """True when the mesh reduction keeps per-device memory at
    O(S_loc x B) — engine sizing (device-cell budgets) keys off this.
    Percentiles qualify only while their [G, B, BINS] histogram
    partial fits :data:`PERCENTILE_HIST_MAX_CELLS`."""
    cls = agg_mesh_class(agg_name)
    if cls == "safe":
        return True
    if cls == "pct":
        if num_groups is None or num_buckets is None:
            return False  # unknown shape: be conservative
        return _hist_eligible(num_groups + 1, num_buckets)
    return False


# ---------------------------------------------------------------------------
# cross-block carries (time axis)
# ---------------------------------------------------------------------------

def _pad_bts_tail(bts: np.ndarray, target_len: int) -> np.ndarray:
    """Monotonic tail padding of bucket timestamps (extrapolating the
    last step so halo/carry timestamps stay ordered)."""
    bts = np.asarray(bts)
    need = target_len - len(bts)
    if need <= 0:
        return bts
    step = int(bts[-1] - bts[-2]) if len(bts) > 1 else 1000
    extra = bts[-1] + step * np.arange(1, need + 1, dtype=bts.dtype)
    return np.concatenate([bts, extra])


def _scan_boundary(val, ts, present, axis_name: str, n_shards: int,
                   reverse: bool):
    """Exclusive 'nearest-present' scan across mesh axis ``axis_name``.

    Every shard contributes its own boundary candidate (val, ts, present)
    — the last present cell per series for a forward scan, first for a
    reverse scan — and receives the nearest present candidate among all
    shards strictly before (after, if reverse) it. log2(n) ppermute
    rounds (Hillis-Steele).
    """
    if n_shards == 1:
        absent = jnp.zeros_like(present)
        return jnp.zeros_like(val), jnp.zeros_like(ts), absent

    def shift(x, d):
        if reverse:
            perm = [(i, i - d) for i in range(d, n_shards)]
        else:
            perm = [(i, i + d) for i in range(n_shards - d)]
        return jax.lax.ppermute(x, axis_name, perm)

    v, t, p = val, ts, present
    d = 1
    while d < n_shards:
        vin, tin, pin = shift(v, d), shift(t, d), shift(p, d)
        # keep own (nearer) when present, else take incoming (farther)
        v = jnp.where(p, v, vin)
        t = jnp.where(p, t, tin)
        p = p | pin
        d *= 2
    # shift by one to make the scan exclusive
    return shift(v, 1), shift(t, 1), shift(p, 1)


def _block_boundaries(grid, bucket_ts):
    """Per-series (last_val, last_ts, present) and (first_val, first_ts,
    present) of this time block."""
    mask = ~jnp.isnan(grid)
    nb = grid.shape[-1]
    prev_idx = _prev_valid_idx(mask)[:, -1]          # last present idx
    next_idx = _next_valid_idx(mask)[:, 0]           # first present idx
    has_last = prev_idx >= 0
    has_first = next_idx < nb
    lp = jnp.clip(prev_idx, 0, nb - 1)
    fp = jnp.clip(next_idx, 0, nb - 1)
    ts = bucket_ts.astype(grid.dtype)
    ts_row = jnp.broadcast_to(ts[None, :], grid.shape)
    # fused select chains, not per-element TPU gathers (interp._gather_minor)
    return ((_gather_minor(grid, lp[:, None])[:, 0],
             _gather_minor(ts_row, lp[:, None])[:, 0], has_last),
            (_gather_minor(grid, fp[:, None])[:, 0],
             _gather_minor(ts_row, fp[:, None])[:, 0], has_first))


def _fill_with_boundaries(grid, bucket_ts, mode: str,
                          prev_v, prev_t, prev_p,
                          next_v, next_t, next_p):
    """fill_gaps with per-series cross-block boundary carries (the
    block's own carries are interp's sweeps along the buckets)."""
    from opentsdb_tpu.ops.interp import carry_next, carry_prev
    mask = ~jnp.isnan(grid)
    if mode == Interpolation.ZIM.value:
        return jnp.where(mask, grid, 0.0)
    ts = bucket_ts.astype(grid.dtype)
    gz = jnp.where(mask, grid, 0.0)
    v0_l, t0_l, has_lp = carry_prev((gz, ts), mask)
    v0 = jnp.where(has_lp, v0_l, prev_v[:, None])
    t0 = jnp.where(has_lp, t0_l, prev_t[:, None])
    has0 = has_lp | prev_p[:, None]
    if mode == Interpolation.PREV.value:
        return jnp.where(mask, grid, jnp.where(has0, v0, jnp.nan))
    v1_l, t1_l, has_ln = carry_next((gz, ts), mask)
    v1 = jnp.where(has_ln, v1_l, next_v[:, None])
    t1 = jnp.where(has_ln, t1_l, next_t[:, None])
    has1 = has_ln | next_p[:, None]
    in_range = has0 & has1
    if mode in (Interpolation.MAX.value, Interpolation.MIN.value):
        extreme = jnp.inf if mode == Interpolation.MAX.value else -jnp.inf
        return jnp.where(mask, grid, jnp.where(in_range, extreme, jnp.nan))
    if mode != Interpolation.LERP.value:
        raise ValueError(f"unknown interpolation mode {mode!r}")
    t = ts[None, :]
    dt = jnp.where(t1 > t0, t1 - t0, 1.0)
    lerped = v0 + (v1 - v0) * (t - t0) / dt
    return jnp.where(mask, grid, jnp.where(in_range, lerped, jnp.nan))


def _rate_with_boundary(grid, bucket_ts, counter: bool, counter_max,
                        reset_value, drop_resets: bool,
                        carry_v, carry_t, carry_p):
    """Rate kernel with the previous block's last-present carry."""
    from opentsdb_tpu.ops.interp import carry_prev
    mask = ~jnp.isnan(grid)
    ts = bucket_ts.astype(grid.dtype)
    gz = jnp.where(mask, grid, 0.0)
    v_loc, t_loc, has_local = carry_prev((gz, ts), mask,
                                         exclusive=True)
    v_prev = jnp.where(has_local, v_loc, carry_v[:, None])
    t_prev = jnp.where(has_local, t_loc, carry_t[:, None])
    has_prev = has_local | carry_p[:, None]
    dt_sec = (ts[None, :] - t_prev) / 1000.0
    dt_sec = jnp.where(dt_sec > 0, dt_sec, 1.0)
    delta = grid - v_prev
    rate = delta / dt_sec
    if counter:
        rolled = delta < 0
        corrected = (counter_max - v_prev + grid) / dt_sec
        rate = jnp.where(rolled, corrected, rate)
        if drop_resets:
            rate = jnp.where(rolled, jnp.nan, rate)
        rate = jnp.where((reset_value > 0) & (rate > reset_value), 0.0,
                         rate)
    return jnp.where(mask & has_prev, rate, jnp.nan)


# ---------------------------------------------------------------------------
# cross-shard group reduction (series axis)
# ---------------------------------------------------------------------------

def _group_reduce_psum(filled, group_ids, num_groups: int, agg_name: str,
                       axis_name: str):
    """Partial segment reduction per shard + collective combine.

    Per-shard reductions use the single-chip primitives (one-hot MXU
    contraction for sums, chunked broadcast for extrema — both measured
    ~3-40x faster than TPU scatter, see ops.groupby); only the
    psum/pmin/pmax combine is collective."""
    from opentsdb_tpu.ops.groupby import _group_extremum, _group_sum
    valid = ~jnp.isnan(filled)
    x0 = jnp.where(valid, filled, 0.0)

    def seg(x):
        return _group_sum(x, group_ids, num_groups)

    cnt = jax.lax.psum(seg(valid.astype(filled.dtype)), axis_name)
    if agg_name in ("sum", "zimsum", "pfsum"):
        out = jax.lax.psum(seg(x0), axis_name)
    elif agg_name == "avg":
        out = jax.lax.psum(seg(x0), axis_name) / jnp.maximum(cnt, 1)
    elif agg_name == "count":
        out = cnt
    elif agg_name in ("min", "mimmin"):
        part = _group_extremum(jnp.where(valid, filled, jnp.inf),
                               group_ids, num_groups, "min")
        out = jax.lax.pmin(part, axis_name)
        out = jnp.where(jnp.isinf(out) & (out > 0), jnp.nan, out)
    elif agg_name in ("max", "mimmax"):
        part = _group_extremum(jnp.where(valid, filled, -jnp.inf),
                               group_ids, num_groups, "max")
        out = jax.lax.pmax(part, axis_name)
        out = jnp.where(jnp.isinf(out) & (out < 0), jnp.nan, out)
    elif agg_name == "squareSum":
        out = jax.lax.psum(seg(x0 * x0), axis_name)
    elif agg_name == "dev":
        # Two-pass mean-shifted variance, matching the single-chip
        # agg_dev exactly (ops/aggregators.py agg_dev): psum the raw
        # sums for the GLOBAL mean, then psum the locally centered
        # squares.  The one-pass E[x^2]-E[x]^2 form cancels
        # catastrophically in f32 when mean >> std (e.g. counters near
        # 1e7) and diverged from the single-device path.
        s1 = jax.lax.psum(seg(x0), axis_name)
        mean = s1 / jnp.maximum(cnt, 1)                     # [G, B]
        centered = jnp.where(valid, filled - mean[group_ids, :], 0.0)
        m2 = jax.lax.psum(seg(centered * centered), axis_name)
        # population variance (divisor n) to match agg_dev / the
        # reference's own TestAggregators expectations
        var = m2 / jnp.maximum(cnt, 1)
        out = jnp.where(cnt == 1, 0.0, jnp.sqrt(jnp.maximum(var, 0.0)))
    else:
        raise ValueError(f"{agg_name} is not psum-reducible")
    return jnp.where(cnt > 0, out, jnp.nan)


# number of histogram bins for distributed percentile estimation; the
# documented estimator error is (per-group value range) / BINS / 2
PERCENTILE_BINS = 512


def _order_stat_from_hist(counts, cum, lo, width, k):
    """Estimate the k-th (1-based, [G,B]) order statistic from a
    per-cell histogram via grouped-data interpolation: position within
    the rank-crossing bin = (k - cum_before - 0.5) / bin_count."""
    bins = counts.shape[-1]
    kk = jnp.clip(k, 1.0, None)
    idx = jnp.argmax(cum >= kk[..., None], axis=-1)        # [G, B]
    cnt_in = jnp.take_along_axis(counts, idx[..., None],
                                 axis=-1)[..., 0]
    cum_at = jnp.take_along_axis(cum, idx[..., None], axis=-1)[..., 0]
    cum_before = cum_at - cnt_in
    within = jnp.clip((kk - cum_before - 0.5)
                      / jnp.maximum(cnt_in, 1.0), 0.0, 1.0)
    pos = (idx.astype(lo.dtype) + within) / bins
    return lo + pos * width


def _group_percentile_hist(filled, group_ids, num_groups: int, q: float,
                           estimation: str, axis_name: str):
    """Distributed percentile WITHOUT gathering the series axis
    (VERDICT r02 #5): per-shard bucketed histograms + psum, the
    TPU-native translation of the reference's mergeable
    SimpleHistogram.percentile (SimpleHistogram.java:133). Per-device
    memory stays O(S_loc x B + G x B x BINS).

    Bin edges are LINEAR between the group's global min/max per
    (g, b) cell (two cheap psum-combined segment extrema) —
    log-spacing cannot represent arbitrary-sign data. The rank ``h``
    follows the exact path's commons-math3 convention
    (:func:`opentsdb_tpu.ops.aggregators.percentile_along_axis`) and
    the two adjacent order statistics are estimated by grouped-data
    interpolation inside their rank-crossing bins, so the documented
    estimator error is <= the per-cell value range / PERCENTILE_BINS.
    """
    valid = ~jnp.isnan(filled)
    s_loc, b = filled.shape
    from opentsdb_tpu.ops.groupby import _group_extremum, _group_sum
    lo = _group_extremum(jnp.where(valid, filled, jnp.inf),
                         group_ids, num_groups, "min")
    lo = jax.lax.pmin(lo, axis_name)                       # [G, B]
    hi = _group_extremum(jnp.where(valid, filled, -jnp.inf),
                         group_ids, num_groups, "max")
    hi = jax.lax.pmax(hi, axis_name)
    width = jnp.maximum(hi - lo, 1e-30)
    # per-cell bin index under its own group's range
    cell_lo = lo[group_ids]                                # [S_loc, B]
    cell_w = width[group_ids]
    frac = (filled - cell_lo) / cell_w
    bins = jnp.clip((frac * PERCENTILE_BINS).astype(jnp.int32), 0,
                    PERCENTILE_BINS - 1)
    # scatter counts into [G * B * BINS]
    col = jnp.broadcast_to(jnp.arange(b, dtype=jnp.int32)[None, :],
                           filled.shape)
    flat_idx = (group_ids[:, None] * b + col) * PERCENTILE_BINS + bins
    counts = jax.ops.segment_sum(
        valid.reshape(-1).astype(filled.dtype),
        flat_idx.reshape(-1),
        num_segments=num_groups * b * PERCENTILE_BINS)
    counts = jax.lax.psum(
        counts.reshape(num_groups, b, PERCENTILE_BINS), axis_name)
    n = counts.sum(axis=-1)                                # [G, B]
    # rank h per the exact path's estimation convention
    p = q / 100.0
    if estimation == "legacy":
        h = p * (n + 1)
    elif estimation == "r3":
        h = jnp.ceil(p * n - 0.5)
    elif estimation == "upper-median":
        # Aggregators.Median :397 — sorted[n // 2], no interpolation
        h = jnp.floor(n / 2) + 1
    else:  # r7
        h = (n - 1) * p + 1
    h = jnp.clip(h, 1.0, jnp.maximum(n, 1.0))
    h_floor = jnp.floor(h)
    hfrac = h - h_floor
    cum = jnp.cumsum(counts, axis=-1)
    est_lo = _order_stat_from_hist(counts, cum, lo, width, h_floor)
    est_hi = _order_stat_from_hist(counts, cum, lo, width,
                                   jnp.minimum(h_floor + 1, n))
    est = est_lo + hfrac * (est_hi - est_lo)
    # exact degenerate case: zero range
    est = jnp.where(width <= 1e-30, lo, est)
    return jnp.where(n > 0, est, jnp.nan)


def _group_edge_pick(filled, group_ids, num_groups: int, pick: str,
                     s_loc: int, axis_name: str):
    """Distributed first/last: value of the globally lowest/highest
    present series index per (g, b). Each shard reduces to [G, B]
    candidates; the cross-shard combine gathers only those (tiny)."""
    valid = ~jnp.isnan(filled)
    shard = jax.lax.axis_index(axis_name)
    dtype = filled.dtype
    # global series index as float (exact below 2^24 series in f32 —
    # far past the realistic series-axis size of one mesh)
    gidx = (shard * s_loc
            + jnp.arange(s_loc, dtype=jnp.int32))[:, None].astype(dtype)
    gidx = jnp.broadcast_to(gidx, filled.shape)
    from opentsdb_tpu.ops.groupby import _group_extremum, _group_sum
    if pick == "first":
        key = jnp.where(valid, gidx, jnp.inf)
        cand_idx = _group_extremum(key, group_ids, num_groups, "min")
    else:
        key = jnp.where(valid, gidx, -jnp.inf)
        cand_idx = _group_extremum(key, group_ids, num_groups, "max")
    # value at the candidate index: match rows, reduce (match unique)
    match = (gidx == cand_idx[group_ids]) & valid
    cand_val = _group_sum(jnp.where(match, filled, 0.0), group_ids,
                          num_groups)
    # cross-shard: gather [Ds, G, B] candidates, pick best index
    idx_all = jax.lax.all_gather(cand_idx, axis_name, axis=0)
    val_all = jax.lax.all_gather(cand_val, axis_name, axis=0)
    sel = (jnp.argmin(idx_all, axis=0) if pick == "first"
           else jnp.argmax(idx_all, axis=0))
    best = jnp.take_along_axis(idx_all, sel[None], axis=0)[0]
    out = jnp.take_along_axis(val_all, sel[None], axis=0)[0]
    return jnp.where(jnp.isinf(best), jnp.nan, out)


def _group_reduce_distributed(filled, group_ids, num_groups: int,
                              agg_name: str, axis_name: str,
                              s_loc: int | None = None):
    """Cross-shard group reduction for aggregators outside
    REDUCIBLE_AGGS, keeping per-device memory sublinear in the global
    series count wherever the math allows:

    - percentiles (p*/ep*) and median: bucketed-histogram psum
      (documented estimator error, see _group_percentile_hist);
    - first/last: per-shard edge candidates + tiny [Ds, G, B] gather;
    - diff/multiply (rare): all_gather fallback — the only remaining
      full-axis gathers.
    """
    agg = aggs_mod.get(agg_name)
    if (agg.percentile is not None or agg_name == "median") and \
            _hist_eligible(num_groups, filled.shape[-1]):
        q = agg.percentile if agg.percentile is not None else 50.0
        est = ("upper-median" if agg_name == "median"
               else getattr(agg, "estimation", None) or "r7")
        return _group_percentile_hist(filled, group_ids, num_groups,
                                      q, est, axis_name)
    if agg_name in ("first", "last") and s_loc is not None:
        return _group_edge_pick(filled, group_ids, num_groups,
                                agg_name, s_loc, axis_name)
    full = jax.lax.all_gather(filled, axis_name, axis=0, tiled=True)
    gids_full = jax.lax.all_gather(group_ids, axis_name, axis=0,
                                   tiled=True)
    from opentsdb_tpu.ops.groupby import _group_reduce
    return _group_reduce(full, gids_full, num_groups, agg_name)


# ---------------------------------------------------------------------------
# the sharded step
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class ShardedBatch:
    """Host-prepared, device-ready inputs for the sharded pipeline.

    Shapes (Ds = series shards, Dt = time shards):
    - values/series_idx/bucket_idx: [Ds, Dt, Npad] — per-shard point
      lists, padded with bucket_idx == B_loc (a dummy bucket slot)
    - bucket_ts: [B_pad] (split over 'time')
    - group_ids: [Ds * S_loc] (split over 'series'), dummy group == G
    """
    values: np.ndarray
    series_idx: np.ndarray
    bucket_idx: np.ndarray
    bucket_ts: np.ndarray
    group_ids: np.ndarray
    s_loc: int
    b_loc: int
    num_groups: int  # real groups (dummy excluded)


def build_sharded_step(mesh: Mesh, spec: PipelineSpec, s_loc: int,
                       b_loc: int):
    """Compile the multi-chip query step for the given mesh and shapes.

    Returns a jitted fn(values, series_idx, bucket_idx, bucket_ts,
    group_ids, rate_params, fill_value) -> (result[G+1, B_pad],
    emit[G+1, B_pad]) with result sharded over 'time'.
    """
    n_series_shards, n_time_shards = (mesh.shape["series"],
                                      mesh.shape["time"])
    agg = aggs_mod.get(spec.agg_name)
    interp_mode = agg.interpolation.value
    g_padded = spec.num_groups + 1  # trailing dummy group for padding

    def step(values, series_idx, bucket_idx, bucket_ts, group_ids,
             rate_params, fill_value):
        # local blocks: [1, 1, Npad] / [B_loc] / [S_loc]
        vals = values.reshape(-1)
        sidx = series_idx.reshape(-1)
        bidx = bucket_idx.reshape(-1)
        bts = bucket_ts
        gids = group_ids

        # 1. local bucketize into [S_loc, B_loc + 1] (last = padding)
        grid, cnt = ds_mod.bucketize(vals, sidx, bidx, s_loc, b_loc + 1,
                                     spec.ds_function)
        grid = grid[:, :b_loc]
        cnt = cnt[:, :b_loc]
        has_data = cnt > 0

        if spec.fill_policy == ds_mod.FillPolicy.ZERO:
            grid = jnp.where(jnp.isnan(grid), 0.0, grid)
            has_data = jnp.ones_like(has_data)
        elif spec.fill_policy == ds_mod.FillPolicy.SCALAR:
            grid = jnp.where(jnp.isnan(grid), fill_value, grid)
            has_data = jnp.ones_like(has_data)

        # 2. rate with cross-block carry over the 'time' axis
        if spec.rate:
            (lv, lt, lp), _ = _block_boundaries(grid, bts)
            cv, ct, cp = _scan_boundary(lv, lt, lp, "time",
                                        n_time_shards, reverse=False)
            counter_max, reset_value = rate_params
            grid = _rate_with_boundary(
                grid, bts, spec.rate_counter, counter_max, reset_value,
                spec.rate_drop_resets, cv, ct, cp)
            has_data = has_data & ~jnp.isnan(grid)

        if spec.emit_raw:
            return grid, has_data

        # 3. interpolation fill with halo carries both directions.
        # Only fill NONE leaves true gaps that interpolate at merge;
        # NAN/NULL emit explicit NaN points that the reference's merge
        # loop skips WITHOUT interpolating, and ZERO/SCALAR were
        # substituted in step 1 (mirrors pipeline._finish_pipeline).
        if spec.fill_policy == ds_mod.FillPolicy.NONE:
            (lv, lt, lp), (fv, ft, fp) = _block_boundaries(grid, bts)
            pv, pt, pp = _scan_boundary(lv, lt, lp, "time",
                                        n_time_shards, reverse=False)
            nv, nt, npp = _scan_boundary(fv, ft, fp, "time",
                                         n_time_shards, reverse=True)
            filled = _fill_with_boundaries(grid, bts, interp_mode,
                                           pv, pt, pp, nv, nt, npp)
        else:
            filled = grid

        # 4. group aggregation across the 'series' axis
        if spec.agg_name in REDUCIBLE_AGGS:
            result = _group_reduce_psum(filled, gids, g_padded,
                                        spec.agg_name, "series")
        else:
            result = _group_reduce_distributed(
                filled, gids, g_padded, spec.agg_name, "series",
                s_loc=s_loc)

        if spec.fill_policy == ds_mod.FillPolicy.NONE:
            # segment_sum: empty segments give 0 (segment_max gives INT_MIN
            # which breaks the cross-shard psum)
            emit = jax.lax.psum(
                jax.ops.segment_sum(has_data.astype(jnp.int32), gids,
                                    num_segments=g_padded), "series") > 0
        else:
            emit = jnp.ones((g_padded, b_loc), dtype=bool)
        return result, emit

    sharded = shard_map(
        step, mesh=mesh,
        in_specs=(P("series", "time", None), P("series", "time", None),
                  P("series", "time", None), P("time"), P("series"),
                  P(), P()),
        out_specs=(P(None, "time"), P(None, "time"))
        if not spec.emit_raw else (P("series", "time"),
                                   P("series", "time")),
        check_vma=False)
    return jax.jit(sharded)


# ---------------------------------------------------------------------------
# host-side sharding prep
# ---------------------------------------------------------------------------

def prepare_sharded_batch(values: np.ndarray, series_idx: np.ndarray,
                          bucket_idx: np.ndarray, bucket_ts: np.ndarray,
                          group_ids: np.ndarray, num_series: int,
                          num_groups: int, n_series_shards: int,
                          n_time_shards: int) -> ShardedBatch:
    """Partition a flat point batch onto the mesh.

    Series land on series-shards in contiguous *blocks* (shard =
    series_idx // s_loc): after an all_gather over the series axis the
    rows come back in natural series order, which the order-sensitive
    aggregators (first/last/diff pick the lowest/highest series index,
    matching the reference's span order) depend on. Buckets split into
    contiguous time blocks. Point lists are padded per (Ds, Dt) cell to
    the max cell population.
    """
    s_loc = -(-num_series // n_series_shards)
    b = len(bucket_ts)
    b_loc = -(-b // n_time_shards)
    b_pad = b_loc * n_time_shards

    # pad bucket_ts monotonically so halo timestamps stay ordered
    bucket_ts = _pad_bts_tail(bucket_ts, b_pad)

    series_shard = series_idx // s_loc
    local_series = series_idx % s_loc
    time_shard = bucket_idx // b_loc
    local_bucket = bucket_idx % b_loc

    # per-cell padding
    cell_id = series_shard.astype(np.int64) * n_time_shards + time_shard
    order = np.argsort(cell_id, kind="stable")
    counts = np.bincount(cell_id, minlength=n_series_shards * n_time_shards)
    npad = max(int(counts.max()), 1) if len(cell_id) else 1
    ds, dt = n_series_shards, n_time_shards
    pvals = np.zeros((ds, dt, npad), dtype=values.dtype)
    psidx = np.zeros((ds, dt, npad), dtype=np.int32)
    pbidx = np.full((ds, dt, npad), b_loc, dtype=np.int32)  # dummy bucket
    pos = 0
    for cell in range(ds * dt):
        c = counts[cell]
        if c == 0:
            continue
        sel = order[pos:pos + c]
        i, j = divmod(cell, dt)
        pvals[i, j, :c] = values[sel]
        psidx[i, j, :c] = local_series[sel]
        pbidx[i, j, :c] = local_bucket[sel]
        pos += c

    # group ids: [Ds * S_loc]; block layout keeps natural series order
    # (row shard*s_loc+loc == global sid); padding -> dummy group G
    gids = np.full(ds * s_loc, num_groups, dtype=np.int32)
    gids[:num_series] = group_ids

    return ShardedBatch(pvals, psidx, pbidx,
                        bucket_ts.astype(np.int64), gids, s_loc, b_loc,
                        num_groups)


@lru_cache(maxsize=128)
def _compiled_step(mesh: Mesh, spec: PipelineSpec, s_loc: int,
                   b_loc: int):
    """Per-(mesh, spec, shape) cache: build_sharded_step returns a new
    closure every call, so jax.jit alone would re-trace every query."""
    return build_sharded_step(mesh, spec, s_loc, b_loc)


# ---------------------------------------------------------------------------
# blocked (streaming) execution over the mesh — VERDICT r02 #4: the
# carry-chained block scan as a shard_map program, so over-budget long
# ranges keep the fan-out instead of degrading to one device
# ---------------------------------------------------------------------------

def _combine_carry(scan_v, scan_t, scan_p, host_v, host_t, host_p):
    """Nearest-present = the intra-block scan when it found one, else
    the host-chained carry from earlier blocks."""
    v = jnp.where(scan_p, scan_v, host_v)
    t = jnp.where(scan_p, scan_t, host_t)
    return v, t, scan_p | host_p


def _last_across_time(v, t, p, n_time_shards: int):
    """The block-global LAST present candidate per series: each time
    shard contributes its local last; the highest-indexed present shard
    wins. all_gather is fine — candidates are [S_loc] vectors."""
    if n_time_shards == 1:
        return v, t, p
    vs = jax.lax.all_gather(v, "time", axis=0)   # [Dt, S_loc]
    ts = jax.lax.all_gather(t, "time", axis=0)
    ps = jax.lax.all_gather(p, "time", axis=0)
    # scan shards from last to first, keeping the first present
    out_v, out_t, out_p = vs[-1], ts[-1], ps[-1]
    for i in range(n_time_shards - 2, -1, -1):
        out_v = jnp.where(out_p, out_v, vs[i])
        out_t = jnp.where(out_p, out_t, ts[i])
        out_p = out_p | ps[i]
    return out_v, out_t, out_p


def _first_across_time(v, t, p, n_time_shards: int):
    if n_time_shards == 1:
        return v, t, p
    vs = jax.lax.all_gather(v, "time", axis=0)
    ts = jax.lax.all_gather(t, "time", axis=0)
    ps = jax.lax.all_gather(p, "time", axis=0)
    out_v, out_t, out_p = vs[0], ts[0], ps[0]
    for i in range(1, n_time_shards):
        out_v = jnp.where(out_p, out_v, vs[i])
        out_t = jnp.where(out_p, out_t, ts[i])
        out_p = out_p | ps[i]
    return out_v, out_t, out_p


def build_sharded_blocked_step(mesh: Mesh, spec: PipelineSpec,
                               s_loc: int, b_loc: int,
                               summary_only: bool = False):
    """One time-BLOCK of the streaming scan, sharded over the mesh.

    Mirrors ``ops.blocked``'s per-block work (bucketize -> fill policy
    -> rate -> interpolation fill -> group reduce) with three kinds of
    carries composed:
    - intra-block, across 'time' shards: ppermute prefix scans
      (:func:`_scan_boundary`), as in :func:`build_sharded_step`;
    - across blocks: host-chained (prev-rate, prev-fill, next-fill)
      [S]-vectors fed in sharded over 'series' and combined wherever
      the intra-block scan found nothing;
    - outgoing: the block's own boundary summaries (pre-rate last,
      post-rate last, post-rate first), reduced across 'time' shards,
      returned sharded over 'series' for the host to chain.

    ``summary_only`` builds the light pass-1 variant: bucketize +
    rate + boundary summaries with the fill/group-reduce stages
    omitted (the two-pass structure of ``ops.blocked``).

    Returns fn(values, sidx, bidx, bts, gids, rate_params, fill_value,
    rate_carry3, prev_carry3, next_carry3) ->
    (result[G+1, b_pad], emit, pre_last3, post_last3, post_first3),
    with result/emit zero-size placeholders in summary mode.
    """
    n_time_shards = mesh.shape["time"]
    agg = aggs_mod.get(spec.agg_name)
    interp_mode = agg.interpolation.value
    g_padded = spec.num_groups + 1

    def step(values, series_idx, bucket_idx, bucket_ts, group_ids,
             rate_params, fill_value, rate_carry, prev_carry,
             next_carry):
        vals = values.reshape(-1)
        sidx = series_idx.reshape(-1)
        bidx = bucket_idx.reshape(-1)
        bts = bucket_ts
        gids = group_ids

        grid, cnt = ds_mod.bucketize(vals, sidx, bidx, s_loc, b_loc + 1,
                                     spec.ds_function)
        grid = grid[:, :b_loc]
        cnt = cnt[:, :b_loc]
        has_data = cnt > 0
        if spec.fill_policy == ds_mod.FillPolicy.ZERO:
            grid = jnp.where(jnp.isnan(grid), 0.0, grid)
            has_data = jnp.ones_like(has_data)
        elif spec.fill_policy == ds_mod.FillPolicy.SCALAR:
            grid = jnp.where(jnp.isnan(grid), fill_value, grid)
            has_data = jnp.ones_like(has_data)

        # pre-rate block-last summary (chains the NEXT block's rate)
        (plv, plt, plp), _ = _block_boundaries(grid, bts)
        pre_last = _last_across_time(plv, plt, plp, n_time_shards)

        if spec.rate:
            (lv, lt, lp), _ = _block_boundaries(grid, bts)
            sv, st, sp = _scan_boundary(lv, lt, lp, "time",
                                        n_time_shards, reverse=False)
            cv, ct, cp = _combine_carry(sv, st, sp, *rate_carry)
            counter_max, reset_value = rate_params
            grid = _rate_with_boundary(
                grid, bts, spec.rate_counter, counter_max, reset_value,
                spec.rate_drop_resets, cv, ct, cp)
            has_data = has_data & ~jnp.isnan(grid)

        # post-rate boundary summaries for the host chain
        (lv, lt, lp), (fv, ft, fp) = _block_boundaries(grid, bts)
        post_last = _last_across_time(lv, lt, lp, n_time_shards)
        post_first = _first_across_time(fv, ft, fp, n_time_shards)

        if summary_only:
            z = jnp.zeros((g_padded, 0), grid.dtype)
            return (z, z.astype(bool), pre_last, post_last,
                    post_first)

        if spec.fill_policy == ds_mod.FillPolicy.NONE:
            pv, pt, pp = _scan_boundary(lv, lt, lp, "time",
                                        n_time_shards, reverse=False)
            nv, nt, npp = _scan_boundary(fv, ft, fp, "time",
                                         n_time_shards, reverse=True)
            pv, pt, pp = _combine_carry(pv, pt, pp, *prev_carry)
            nv, nt, npp = _combine_carry(nv, nt, npp, *next_carry)
            filled = _fill_with_boundaries(grid, bts, interp_mode,
                                           pv, pt, pp, nv, nt, npp)
        else:
            filled = grid

        if spec.agg_name in REDUCIBLE_AGGS:
            result = _group_reduce_psum(filled, gids, g_padded,
                                        spec.agg_name, "series")
        else:
            result = _group_reduce_distributed(
                filled, gids, g_padded, spec.agg_name, "series",
                s_loc=s_loc)

        if spec.fill_policy == ds_mod.FillPolicy.NONE:
            emit = jax.lax.psum(
                jax.ops.segment_sum(has_data.astype(jnp.int32), gids,
                                    num_segments=g_padded),
                "series") > 0
        else:
            emit = jnp.ones((g_padded, b_loc), dtype=bool)
        return result, emit, pre_last, post_last, post_first

    c3 = (P("series"), P("series"), P("series"))
    sharded = shard_map(
        step, mesh=mesh,
        in_specs=(P("series", "time", None), P("series", "time", None),
                  P("series", "time", None), P("time"), P("series"),
                  P(), P(), c3, c3, c3),
        out_specs=(P(None, "time"), P(None, "time"), c3, c3, c3),
        check_vma=False)
    return jax.jit(sharded)


@lru_cache(maxsize=64)
def _compiled_blocked_step(mesh: Mesh, spec: PipelineSpec, s_loc: int,
                           b_loc: int, summary_only: bool = False):
    return build_sharded_blocked_step(mesh, spec, s_loc, b_loc,
                                      summary_only)


def execute_blocked_sharded(mesh: Mesh, batch_values: np.ndarray,
                            series_idx: np.ndarray,
                            bucket_idx: np.ndarray,
                            bucket_ts: np.ndarray,
                            group_ids: np.ndarray, spec: PipelineSpec,
                            rate_options=None, dtype=None,
                            block_buckets: int | None = None
                            ) -> tuple[np.ndarray, np.ndarray]:
    """Streaming twin of :func:`opentsdb_tpu.ops.blocked.execute_blocked`
    running every block over the mesh: per-DEVICE memory is
    O(S_loc x block), so the budget scales with the fan-out instead of
    collapsing to one device (ref: the 20 SaltScanners stream
    concurrently, SaltScanner.java:463-536).

    Same two-pass structure as ``execute_blocked``: interpolating
    aggregators need each block's NEXT-present carry accumulated over
    ALL later blocks, so a light summary pass (bucketize + rate +
    boundaries, no fill/reduce) sweeps forward first and a backward
    host scan chains the next-carries; non-interpolating aggregators
    skip pass 1 entirely (a single full sweep suffices)."""
    from opentsdb_tpu.ops.blocked import _empty_carry, _merge_carry
    from opentsdb_tpu.ops.pipeline import device_bucket_ts
    from opentsdb_tpu.ops.rate import RateOptions
    if spec.emit_raw:
        raise ValueError("blocked execution aggregates; emit_raw "
                         "queries stream per-series instead")
    if dtype is None:
        dtype = jnp.float64 if jax.config.read("jax_enable_x64") \
            else jnp.float32
    np_dtype = np.dtype(dtype)
    ro = rate_options or RateOptions()
    s, b, g = spec.num_series, spec.num_buckets, spec.num_groups
    ds_shards = mesh.shape["series"]
    dt_shards = mesh.shape["time"]
    s_loc = -(-s // ds_shards)
    s_pad = s_loc * ds_shards
    from opentsdb_tpu.ops.blocked import pick_block_buckets
    # per-device cells = (s_pad/Ds) x (bb/Dt): the global budget for
    # pick_block_buckets scales by the whole mesh
    bb = block_buckets or pick_block_buckets(
        s_pad, b,
        DEFAULT_CELL_BUDGET_PER_DEVICE * ds_shards * dt_shards)
    # block size must split evenly over the time shards
    bb = max(dt_shards, (bb // dt_shards) * dt_shards)
    rate_params = (jnp.asarray(ro.counter_max, dtype),
                   jnp.asarray(ro.reset_value, dtype))
    fv = jnp.asarray(spec.fill_value, dtype)

    bucket_idx = np.asarray(bucket_idx)
    order = np.argsort(bucket_idx, kind="stable")
    sv_ = np.asarray(batch_values, dtype=np_dtype)[order]
    ssi = np.asarray(series_idx, dtype=np.int32)[order]
    sbi = bucket_idx[order]
    dev_bts = np.asarray(device_bucket_ts(bucket_ts))
    starts = [int(np.searchsorted(sbi, b0)) for b0 in range(0, b, bb)]
    starts.append(len(sbi))
    blocks = [(b0, min(b0 + bb, b), starts[i], starts[i + 1])
              for i, b0 in enumerate(range(0, b, bb))]

    agg = aggs_mod.get(spec.agg_name)
    needs_next = agg.interpolation.value in ("lerp", "max", "min")
    b_loc = bb // dt_shards
    step = _compiled_blocked_step(mesh, spec, s_loc, b_loc)

    gids_full = np.full(s_pad, g, dtype=np.int32)
    gids_full[:s] = group_ids

    # memoized per-block batches: the two-pass sweep (needs_next) must
    # not repeat the host-side per-cell packing loop — the memo is the
    # same order of memory as the already-resident sorted point arrays
    _block_memo: dict[int, ShardedBatch] = {}

    def shard_block(i, blk):
        sb = _block_memo.get(i)
        if sb is None:
            b0, b1, p0, p1 = blk
            sb = _block_memo[i] = prepare_sharded_batch(
                sv_[p0:p1], ssi[p0:p1], sbi[p0:p1] - b0,
                _pad_bts_tail(dev_bts[b0:b1], bb),
                gids_full, s_pad, g, ds_shards, dt_shards)
        return sb

    # explicit global uploads so the path works when the mesh spans
    # processes (plain jnp.asarray/jit auto-put would hit device_put's
    # cross-process value check — see distributed.put_global)
    from jax.sharding import NamedSharding
    from opentsdb_tpu.parallel.distributed import put_global
    sh3 = NamedSharding(mesh, P("series", "time", None))
    sht = NamedSharding(mesh, P("time"))
    shs = NamedSharding(mesh, P("series"))

    def carry_dev(c):
        return tuple(put_global(np.asarray(x), shs) for x in c)

    def run(i, blk, which, rate_carry, prev_carry, next_carry):
        sb = shard_block(i, blk)
        return which(
            put_global(np.asarray(sb.values, np_dtype), sh3),
            put_global(sb.series_idx, sh3),
            put_global(sb.bucket_idx, sh3),
            put_global(sb.bucket_ts, sht),
            put_global(gids_full, shs), rate_params, fv,
            carry_dev(rate_carry), carry_dev(prev_carry),
            carry_dev(next_carry))

    empty = _empty_carry(s_pad, np_dtype)
    n_blocks = len(blocks)
    next_carries = [empty] * n_blocks
    if needs_next and n_blocks > 1:
        # pass 1 (light): forward sweep collecting each block's
        # first-present summary, then a backward host scan accumulating
        # the next-carry over ALL later blocks (a gap spanning whole
        # blocks must still interpolate; ops.blocked does the same)
        sstep = _compiled_blocked_step(mesh, spec, s_loc, b_loc,
                                       summary_only=True)
        firsts = []
        rate_carry = empty
        for i, blk in enumerate(blocks):
            _, _, pre_last, _, post_first = run(i, blk, sstep,
                                                rate_carry, empty,
                                                empty)
            firsts.append(tuple(_to_host(x) for x in post_first))
            if spec.rate:
                rate_carry = _merge_carry(
                    tuple(_to_host(x) for x in pre_last), rate_carry)
        nc = empty
        for i in range(n_blocks - 1, -1, -1):
            next_carries[i] = nc
            nc = _merge_carry(firsts[i], nc)

    # pass 2: full sweep with both carries chained
    out = np.empty((g, b), dtype=np_dtype)
    emit_out = np.empty((g, b), dtype=bool)
    rate_carry = empty
    prev_carry = empty
    for i, blk in enumerate(blocks):
        res, emit, pre_last, post_last, _ = run(
            i, blk, step, rate_carry, prev_carry, next_carries[i])
        b0, b1 = blk[0], blk[1]
        nb = b1 - b0
        out[:, b0:b1] = _to_host(res)[:g, :nb]
        emit_out[:, b0:b1] = _to_host(emit)[:g, :nb]
        if spec.rate:
            rate_carry = _merge_carry(
                tuple(_to_host(x) for x in pre_last), rate_carry)
        prev_carry = _merge_carry(
            tuple(_to_host(x) for x in post_last), prev_carry)
    return out, emit_out


# per-DEVICE cell budget for the sharded blocked scan (f32 cells)
DEFAULT_CELL_BUDGET_PER_DEVICE = 1 << 26


def sharded_device_args(mesh: Mesh, batch: ShardedBatch, dtype):
    """Upload a ShardedBatch with its mesh shardings attached, so a
    repeat query can reuse the HBM-resident copies (the mesh twin of
    the single-device prepared-batch cache)."""
    from jax.sharding import NamedSharding
    from opentsdb_tpu.ops.pipeline import device_bucket_ts
    from opentsdb_tpu.parallel.distributed import put_global as put
    s3 = NamedSharding(mesh, P("series", "time", None))
    with trace_span("query.upload"):
        return (put(np.asarray(batch.values, np.dtype(dtype)), s3),
                put(batch.series_idx, s3),
                put(batch.bucket_idx, s3),
                put(device_bucket_ts(batch.bucket_ts),
                    NamedSharding(mesh, P("time"))),
                put(batch.group_ids,
                    NamedSharding(mesh, P("series"))))


def run_sharded_device(mesh: Mesh, spec: PipelineSpec, device_args,
                       s_loc: int, b_loc: int, num_groups: int,
                       rate_options=None, dtype=None):
    """Execute the sharded step over pre-uploaded device args."""
    from opentsdb_tpu.ops.rate import RateOptions
    if dtype is None:
        dtype = jnp.float64 if jax.config.read("jax_enable_x64") \
            else jnp.float32
    ro = rate_options or RateOptions()
    result, emit = run_staged(
        "mesh", _compiled_step(mesh, spec, s_loc, b_loc), lambda: (
            *device_args,
            (jnp.asarray(ro.counter_max, dtype),
             jnp.asarray(ro.reset_value, dtype)),
            jnp.asarray(spec.fill_value, dtype)),
        spec, download=_to_host)
    b = spec.num_buckets
    return result[:num_groups, :b], emit[:num_groups, :b]


def run_sharded(mesh: Mesh, spec: PipelineSpec, batch: ShardedBatch,
                rate_options=None, dtype=None):
    """Execute the sharded step; returns host (result[G,B], emit[G,B])
    trimmed of padding."""
    if dtype is None:
        dtype = jnp.float64 if jax.config.read("jax_enable_x64") \
            else jnp.float32
    # relative ms offsets: absolute epoch-ms int64 would truncate on
    # TPU (no device int64); the kernels only use ts differences
    args = sharded_device_args(mesh, batch, dtype)
    return run_sharded_device(mesh, spec, args, batch.s_loc,
                              batch.b_loc, batch.num_groups,
                              rate_options, dtype)


# ---------------------------------------------------------------------------
# grid-tail step: storage-side bucketized [S, B] grids on the mesh
# (fill -> rate -> interpolate -> reduce; no bucketize)
# ---------------------------------------------------------------------------

def build_sharded_grid_step(mesh: Mesh, spec: PipelineSpec, s_loc: int,
                            b_loc: int):
    """Steps 2-4 of :func:`build_sharded_step` over a pre-bucketized
    grid sharded P('series', 'time') — the mesh twin of
    :func:`opentsdb_tpu.ops.pipeline.run_pipeline_grid`, so the
    storage engine's native [S, B] reduction feeds the mesh directly
    instead of being flattened back to points and re-bucketized."""
    n_time_shards = mesh.shape["time"]
    agg = aggs_mod.get(spec.agg_name)
    interp_mode = agg.interpolation.value
    g_padded = spec.num_groups + 1

    def step(grid, has_data, bucket_ts, group_ids, rate_params,
             fill_value):
        bts = bucket_ts
        gids = group_ids
        if spec.fill_policy == ds_mod.FillPolicy.ZERO:
            grid = jnp.where(jnp.isnan(grid), 0.0, grid)
            has_data = jnp.ones_like(has_data)
        elif spec.fill_policy == ds_mod.FillPolicy.SCALAR:
            grid = jnp.where(jnp.isnan(grid), fill_value, grid)
            has_data = jnp.ones_like(has_data)
        if spec.rate:
            (lv, lt, lp), _ = _block_boundaries(grid, bts)
            cv, ct, cp = _scan_boundary(lv, lt, lp, "time",
                                        n_time_shards, reverse=False)
            counter_max, reset_value = rate_params
            grid = _rate_with_boundary(
                grid, bts, spec.rate_counter, counter_max, reset_value,
                spec.rate_drop_resets, cv, ct, cp)
            has_data = has_data & ~jnp.isnan(grid)
        if spec.emit_raw:
            return grid, has_data
        if spec.fill_policy == ds_mod.FillPolicy.NONE:
            (lv, lt, lp), (fv, ft, fp) = _block_boundaries(grid, bts)
            pv, pt, pp = _scan_boundary(lv, lt, lp, "time",
                                        n_time_shards, reverse=False)
            nv, nt, npp = _scan_boundary(fv, ft, fp, "time",
                                         n_time_shards, reverse=True)
            filled = _fill_with_boundaries(grid, bts, interp_mode,
                                           pv, pt, pp, nv, nt, npp)
        else:
            filled = grid
        if spec.agg_name in REDUCIBLE_AGGS:
            result = _group_reduce_psum(filled, gids, g_padded,
                                        spec.agg_name, "series")
        else:
            result = _group_reduce_distributed(
                filled, gids, g_padded, spec.agg_name, "series",
                s_loc=s_loc)
        if spec.fill_policy == ds_mod.FillPolicy.NONE:
            emit = jax.lax.psum(
                jax.ops.segment_sum(has_data.astype(jnp.int32), gids,
                                    num_segments=g_padded),
                "series") > 0
        else:
            emit = jnp.ones((g_padded, b_loc), dtype=bool)
        return result, emit

    sharded = shard_map(
        step, mesh=mesh,
        in_specs=(P("series", "time"), P("series", "time"), P("time"),
                  P("series"), P(), P()),
        out_specs=(P(None, "time"), P(None, "time"))
        if not spec.emit_raw else (P("series", "time"),
                                   P("series", "time")),
        check_vma=False)
    return jax.jit(sharded)


@lru_cache(maxsize=128)
def _compiled_grid_step(mesh: Mesh, spec: PipelineSpec, s_loc: int,
                        b_loc: int):
    return build_sharded_grid_step(mesh, spec, s_loc, b_loc)


def prepare_sharded_grid(mesh: Mesh, grid: np.ndarray,
                         has_data: np.ndarray, bucket_ts: np.ndarray,
                         dtype=None):
    """Pad + upload a host [S, B] grid with mesh shardings. Returns
    (data_args, s_loc, b_loc, s_pad) for :func:`run_sharded_grid`. The
    device arrays are what the engine's grid cache holds under a mesh
    — HBM-resident AND pre-sharded. Group ids are deliberately NOT
    part of them: the same data answers queries with different
    group-bys (see :func:`sharded_grid_gids`)."""
    from jax.sharding import NamedSharding
    from opentsdb_tpu.ops.pipeline import device_bucket_ts
    if dtype is None:
        dtype = jnp.float64 if jax.config.read("jax_enable_x64") \
            else jnp.float32
    ds_, dt_ = mesh.shape["series"], mesh.shape["time"]
    s, b = grid.shape
    s_loc = -(-s // ds_)
    b_loc = -(-b // dt_)
    s_pad, b_pad = s_loc * ds_, b_loc * dt_
    g = np.full((s_pad, b_pad), np.nan, dtype=np.dtype(dtype))
    g[:s, :b] = grid
    h = np.zeros((s_pad, b_pad), dtype=bool)
    h[:s, :b] = has_data
    bts = _pad_bts_tail(np.asarray(bucket_ts, dtype=np.int64), b_pad)
    from opentsdb_tpu.parallel.distributed import put_global as put
    s2 = NamedSharding(mesh, P("series", "time"))
    with trace_span("query.upload"):
        args = (put(g, s2), put(h, s2),
                put(device_bucket_ts(bts),
                    NamedSharding(mesh, P("time"))))
    return args, s_loc, b_loc, s_pad


def sharded_grid_gids(mesh: Mesh, group_ids: np.ndarray, s_pad: int,
                      num_groups: int):
    """Per-query group-id upload (tiny [S_pad] vector)."""
    from jax.sharding import NamedSharding
    from opentsdb_tpu.parallel.distributed import put_global
    with trace_span("query.upload"):
        gids = np.full(s_pad, num_groups, dtype=np.int32)
        gids[:len(group_ids)] = group_ids
        return put_global(gids, NamedSharding(mesh, P("series")))


def run_sharded_grid(mesh: Mesh, spec: PipelineSpec, device_args,
                     s_loc: int, b_loc: int, num_groups: int,
                     rate_options=None, dtype=None):
    """Execute the grid-tail step over pre-uploaded sharded grids."""
    from opentsdb_tpu.ops.rate import RateOptions
    if dtype is None:
        dtype = jnp.float64 if jax.config.read("jax_enable_x64") \
            else jnp.float32
    ro = rate_options or RateOptions()
    result, emit = run_staged(
        "mesh", _compiled_grid_step(mesh, spec, s_loc, b_loc), lambda: (
            *device_args,
            (jnp.asarray(ro.counter_max, dtype),
             jnp.asarray(ro.reset_value, dtype)),
            jnp.asarray(spec.fill_value, dtype)),
        spec, download=_to_host)
    b = spec.num_buckets
    rows = spec.num_series if spec.emit_raw else num_groups
    return result[:rows, :b], emit[:rows, :b]
