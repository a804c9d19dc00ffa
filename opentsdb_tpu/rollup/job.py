"""The rollup job: batch pre-aggregation of raw data into tiers.

The reference has NO in-repo rollup compactor — rollups are written by
external jobs through the TSD API (SURVEY.md §2.3, TSDB.java:1320).
The TPU build ships one. This is BASELINE.json config 5 ("rollup
compaction job: 24h@1s raw -> 1m/1h tiers across 10M series").

Design (TPU-first):

- the raw window is processed in (series_chunk x time_window) tiles so
  the device working set stays bounded regardless of range length
  (time windows are the job-side analogue of the query path's
  ``ops.blocked`` streaming);
- each tile computes all four rollup aggregations (sum/count/min/max —
  avg derives as sum/count at query time, ref RollupConfig) in ONE
  jitted program over one pass of the data, using the scatter-free
  padded kernel (:func:`opentsdb_tpu.ops.downsample.bucketize_padded`);
- coarser tiers whose interval is a small multiple of the finest
  reduce the finest tier's grids hierarchically on device (1h sum =
  sum of 1m sums, 1h min = min of 1m mins, ...) — no second pass over
  the raw data. Non-nesting or very coarse tiers take their own pass.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from opentsdb_tpu.ops import downsample as ds_mod
from opentsdb_tpu.rollup.config import RollupConfig, RollupInterval

ROLLUP_AGGS = ("sum", "count", "min", "max")

# device cell budget per tile and bucket cap per window. Wider windows
# mean fewer dispatches; the cap bounds the [S, B] output grids and
# the coarsen one-hot.
_TILE_CELL_BUDGET = 64_000_000
_MAX_WINDOW_BUCKETS = 360


@partial(jax.jit, static_argnames=("num_buckets",))
def _rollup_tile(values2d, bucket_idx2d, num_buckets: int):
    """One tile -> stacked [4, S, B] grids (sum/count/min/max order).
    XLA dedupes the shared count contraction across the four calls."""
    grids = [ds_mod.bucketize_padded(values2d, bucket_idx2d,
                                     num_buckets, agg)[0]
             for agg in ROLLUP_AGGS]
    return jnp.stack(grids)


@partial(jax.jit, static_argnames=("num_buckets", "k"))
def _rollup_tile_dense(values2d, num_buckets: int, k: int):
    """Regular-cadence tile (every row full, k points per bucket): all
    four aggregations from [S, B, k] reshape reductions — no bucket
    compare tensor, one pass over the data per statistic. This is the
    fixed-collection-interval common case and the BASELINE config-5
    shape."""
    s = values2d.shape[0]
    x = values2d.reshape(s, num_buckets, k)
    valid = ~jnp.isnan(x)
    cnt = jnp.sum(valid, axis=-1).astype(values2d.dtype)
    sums = jnp.nansum(x, axis=-1)
    mins = jnp.min(jnp.where(valid, x, jnp.inf), axis=-1)
    maxs = jnp.max(jnp.where(valid, x, -jnp.inf), axis=-1)
    empty = cnt == 0
    return jnp.stack([
        jnp.where(empty, jnp.nan, sums),
        jnp.where(empty, jnp.nan, cnt),
        jnp.where(empty, jnp.nan, mins),
        jnp.where(empty, jnp.nan, maxs),
    ])


@partial(jax.jit, static_argnames=("num_coarse",))
def _coarsen(grids, coarse_idx, num_coarse: int):
    """[4, S, Bf] + fine->coarse bucket map [Bf] -> [4, S, Bc].

    Hierarchical reduction: coarse sum = sum of fine sums, count = sum
    of counts, min = min of mins, max = max of maxes. The mapping is
    host-computed from bucket timestamps, so coarse buckets stay
    aligned to their own interval and partial buckets at the window
    edges still materialize. NaN marks empty fine buckets.
    """
    onehot = jax.nn.one_hot(coarse_idx, num_coarse, dtype=grids.dtype)
    hi = jax.lax.Precision.HIGHEST

    def csum(x):
        return jnp.einsum("sb,bc->sc", jnp.where(jnp.isnan(x), 0.0, x),
                          onehot, precision=hi)

    sums = csum(grids[0])
    cnts = csum(grids[1])
    # broadcast membership [Bf, Bc] -> one fused reduce per extremum
    # (a per-coarse-bucket Python loop unrolls Bc passes)
    eq = coarse_idx[:, None] == jnp.arange(num_coarse,
                                           dtype=coarse_idx.dtype)[None, :]
    m_min = eq[None, :, :] & ~jnp.isnan(grids[2])[:, :, None]
    mins = jnp.min(jnp.where(m_min, grids[2][:, :, None], jnp.inf),
                   axis=1)
    m_max = eq[None, :, :] & ~jnp.isnan(grids[3])[:, :, None]
    maxs = jnp.max(jnp.where(m_max, grids[3][:, :, None], -jnp.inf),
                   axis=1)
    empty = cnts == 0
    nan = jnp.nan
    return jnp.stack([
        jnp.where(empty, nan, sums),
        jnp.where(empty, nan, cnts),
        jnp.where(empty, nan, mins),
        jnp.where(empty, nan, maxs),
    ])


def _chunk_tier_sids(tsdb, tiers: list[RollupInterval], chunk
                     ) -> dict[tuple[str, str], np.ndarray]:
    """Raw sid -> tier-store sid for every (tier, agg), computed ONCE
    per series chunk (the mapping is window-invariant, so the window
    loop must not pay per-series Python work)."""
    recs = [tsdb.store.series(int(sid)) for sid in chunk]
    out = {}
    for tier in tiers:
        for agg in ROLLUP_AGGS:
            store = tsdb.rollup_store.tier(tier.interval, agg)
            out[(tier.interval, agg)] = np.fromiter(
                (store.get_or_create_series(r.metric_id, r.tags)
                 for r in recs), dtype=np.int64, count=len(recs))
    return out


def _write_outs(tsdb, rsid_map, outs, written: dict[str, int]) -> None:
    """Fetch dispatched device grids and write them to the tier
    stores. Kept separate from dispatch so the NEXT window's device
    work is already in flight while this one's results download and
    write (the fetch is the only blocking step)."""
    for tier, bucket_ts, g_dev, row_off in outs:
        _write_grids(tsdb, tier, rsid_map, bucket_ts,
                     np.asarray(g_dev), row_off, written)


def _write_grids(tsdb, tier: RollupInterval, rsid_map, bucket_ts,
                 grids: np.ndarray, row_off: int,
                 written: dict[str, int]) -> None:
    """Bulk-write all four aggregations (store.append_grid: one C++
    threaded pass per agg on the native backend). All four grids share
    one NaN pattern (a bucket is NaN iff its count is 0), so a single
    [S, B] mask serves every agg. ``row_off`` positions grid row 0
    within the sweep's chunk (series-split tiles cover a sub-range)."""
    mask = ~np.isnan(grids[1])  # count grid
    any_rows = mask.any(axis=1)
    if not any_rows.any():
        return
    rows = np.nonzero(any_rows)[0]
    sub_mask = mask[rows]
    for ai, agg in enumerate(ROLLUP_AGGS):
        store = tsdb.rollup_store.tier(tier.interval, agg)
        rsids = rsid_map[(tier.interval, agg)][row_off + rows]
        n = store.append_grid(rsids, np.asarray(bucket_ts),
                              grids[ai][rows], sub_mask)
        written[tier.interval] += n


# the irregular tile reduces a broadcast [S, P, B] membership tensor,
# so its cell count stays bounded by splitting wide windows (or, when
# the nested-tier lcm forbids narrower windows, the series axis)
_PADDED_TILE_MAX_CELLS = 500_000_000
# sub-window bucket cap used when re-tiling an oversized irregular tile
_SPLIT_WINDOW_BUCKETS = 64


def _split_window(tsdb, chunk, row_off: int, start_ms: int,
                  end_ms: int, base: RollupInterval,
                  nested: list[RollupInterval]) -> list:
    """Re-tile an oversized irregular window: narrower coarse-aligned
    sub-windows when the nested-tier lcm allows, else halve the series
    axis (each half may split further)."""
    factors = [t.interval_ms // base.interval_ms for t in nested]
    sub_buckets = _window_buckets(factors, cap=_SPLIT_WINDOW_BUCKETS)
    cur_buckets = (end_ms - start_ms) // base.interval_ms + 1
    outs = []
    if sub_buckets < cur_buckets:
        sub_ms = base.interval_ms * sub_buckets
        t0 = start_ms - (start_ms % sub_ms)
        while t0 <= end_ms:
            outs.extend(_rollup_window(
                tsdb, chunk, row_off, max(t0, start_ms),
                min(t0 + sub_ms - 1, end_ms), base, nested,
                can_split=False))
            t0 += sub_ms
        return outs
    half = len(chunk) // 2
    if half == 0:
        # single series still over the cap: dispatch as-is
        return _rollup_window(tsdb, chunk, row_off, start_ms, end_ms,
                              base, nested, can_split=False)
    outs.extend(_rollup_window(tsdb, chunk[:half], row_off, start_ms,
                               end_ms, base, nested))
    outs.extend(_rollup_window(tsdb, chunk[half:], row_off + half,
                               start_ms, end_ms, base, nested))
    return outs


def _rollup_window(tsdb, chunk, row_off: int, start_ms: int,
                   end_ms: int, base: RollupInterval,
                   nested: list[RollupInterval],
                   can_split: bool = True) -> list:
    """One (series chunk x time window) tile: base tier from raw, then
    nested tiers by on-device coarsening. DISPATCHES the device work
    and returns ``[(tier, bucket_ts, device_grids, row_off), ...]``
    without blocking — the tile grids never round-trip to the host
    between bucketize and coarsen."""
    if can_split:
        # pre-split clearly-irregular oversized windows from counts
        # alone, BEFORE paying the big materialize (equal counts are
        # near-certainly the regular fast path, which builds no
        # membership tensor; the post-detect check below backstops the
        # equal-but-irregular edge)
        counts = tsdb.store.count_range(chunk, start_ms, end_ms)
        pmax = int(counts.max()) if len(counts) else 0
        nb_est = (end_ms - start_ms) // base.interval_ms + 1
        if pmax and int(counts.min()) != pmax and \
                len(chunk) * pmax * nb_est > _PADDED_TILE_MAX_CELLS:
            return _split_window(tsdb, chunk, row_off, start_ms,
                                 end_ms, base, nested)
    padded = tsdb.store.materialize_padded(chunk, start_ms, end_ms)
    if padded.num_points == 0:
        return []
    spec = ds_mod.DownsamplingSpecification(
        interval_ms=base.interval_ms, function="sum")
    bucket_idx2d, bucket_ts = ds_mod.assign_buckets_padded(
        padded.ts2d, padded.counts, spec, start_ms, end_ms)
    dtype = jnp.float64 if jax.config.read("jax_enable_x64") \
        else jnp.float32
    from opentsdb_tpu.ops.pipeline import detect_regular_padded
    k = detect_regular_padded(np.asarray(padded.counts),
                              np.asarray(bucket_idx2d), len(bucket_ts))
    if k is not None:
        g_dev = _rollup_tile_dense(
            jnp.asarray(padded.values2d, dtype=dtype),
            len(bucket_ts), k)
    else:
        cells = (padded.values2d.shape[0] * padded.values2d.shape[1]
                 * len(bucket_ts))
        if can_split and cells > _PADDED_TILE_MAX_CELLS:
            return _split_window(tsdb, chunk, row_off, start_ms,
                                 end_ms, base, nested)
        g_dev = _rollup_tile(
            jnp.asarray(padded.values2d, dtype=dtype),
            jnp.asarray(bucket_idx2d, dtype=jnp.int32), len(bucket_ts))
    outs = [(base, bucket_ts, g_dev, row_off)]
    for tier in nested:
        coarse_edges = ds_mod.fixed_bucket_edges(
            int(bucket_ts[0]), int(bucket_ts[-1]), tier.interval_ms)
        coarse_idx = ((bucket_ts - coarse_edges[0])
                      // tier.interval_ms).astype(np.int32)
        cg_dev = _coarsen(g_dev, jnp.asarray(coarse_idx),
                          len(coarse_edges))
        outs.append((tier, coarse_edges, cg_dev, row_off))
    return outs


def _rollup_window_native(tsdb, chunk, row_off: int, start_ms: int,
                          end_ms: int, base: RollupInterval,
                          nested: list[RollupInterval]) -> list:
    """Storage-side tile: the C++ fused range-scan produces the base
    tier's sum/count/min/max grids directly (``tss_bucket_reduce``),
    and nested tiers coarsen by reshape reductions on the host — the
    raw points never leave the storage arena (the job is a pure
    reduction; there is no reuse to amortize an upload against). Same
    output contract as
    :func:`_rollup_window`."""
    bucket_ts = ds_mod.fixed_bucket_edges(start_ms, end_ms,
                                          base.interval_ms)
    b = len(bucket_ts)
    sums, cnts, mins, maxs = tsdb.store.bucket_reduce(
        chunk, start_ms, end_ms, int(bucket_ts[0]), base.interval_ms,
        b, want_minmax=True)
    if not cnts.any():
        return []
    outs = []

    def finalize(s_, c_, mn_, mx_, tier, bts):
        empty = c_ == 0
        outs.append((tier, bts, np.stack([
            np.where(empty, np.nan, s_), np.where(empty, np.nan, c_),
            np.where(empty, np.nan, mn_),
            np.where(empty, np.nan, mx_)]), row_off))

    finalize(sums, cnts, mins, maxs, base, bucket_ts)
    for tier in nested:
        f = tier.interval_ms // base.interval_ms
        coarse_edges = ds_mod.fixed_bucket_edges(
            int(bucket_ts[0]), int(bucket_ts[-1]), tier.interval_ms)
        # align the base-bucket axis to the coarse grid, pad the tail,
        # and reduce [S, Bc, f]; empty raw cells carry the reduction
        # identities (0 for sum/count, +/-inf for min/max) so they
        # vanish in the coarse cells
        off = int((bucket_ts[0] - coarse_edges[0]) // base.interval_ms)
        pad_hi = len(coarse_edges) * f - (off + b)
        s = len(chunk)

        def pad(a, fill):
            return np.pad(a, ((0, 0), (off, pad_hi)),
                          constant_values=fill)

        finalize(pad(sums, 0.0).reshape(s, -1, f).sum(axis=2),
                 pad(cnts, 0.0).reshape(s, -1, f).sum(axis=2),
                 pad(mins, np.inf).reshape(s, -1, f).min(axis=2),
                 pad(maxs, -np.inf).reshape(s, -1, f).max(axis=2),
                 tier, coarse_edges)
    return outs


def _window_buckets(nested_factors: list[int],
                    cap: int = _MAX_WINDOW_BUCKETS) -> int:
    """Buckets of the base tier per window: a multiple of every nested
    factor (so coarsening never straddles a window edge), capped.
    Sweep callers guarantee lcm(factors) <= _MAX_WINDOW_BUCKETS; with
    a smaller cap (the irregular split) the result may exceed it."""
    lcm = 1
    for f in nested_factors:
        lcm = math.lcm(lcm, f)
    return lcm * max(1, cap // lcm)


def run_rollup_job(tsdb, start_ms: int, end_ms: int,
                   intervals: list[str] | None = None,
                   series_chunk: int | None = None,
                   progress=None,
                   series_ids=None) -> dict[str, int]:
    """Materialize rollup tiers for all raw data in [start_ms, end_ms].

    ``series_ids`` optionally restricts the job to a subset of raw
    series (the lifecycle manager demotes one metric at a time);
    default is every series of every metric.

    Returns {interval: points_written}.
    """
    if tsdb.rollup_store is None:
        raise RuntimeError("rollups are not enabled")
    config: RollupConfig = tsdb.rollup_config
    tiers = ([config.get_interval(iv) for iv in intervals]
             if intervals else config.intervals)
    tiers = sorted(tiers, key=lambda t: t.interval_ms)
    written: dict[str, int] = {iv.interval: 0 for iv in tiers}
    if not tiers:
        return written
    finest = tiers[0]
    # greedily nest coarser tiers under the finest pass while the LCM
    # of their base-interval factors keeps one window within the
    # bucket cap (the padded min/max kernel unrolls per bucket, and
    # chunk sizing assumes the cap); the rest take their own raw pass
    nested: list[RollupInterval] = []
    lcm = 1
    for t in tiers[1:]:
        if t.interval_ms % finest.interval_ms:
            continue
        f = t.interval_ms // finest.interval_ms
        if math.lcm(lcm, f) <= _MAX_WINDOW_BUCKETS:
            nested.append(t)
            lcm = math.lcm(lcm, f)
    direct = [t for t in tiers[1:] if t not in nested]

    if series_ids is not None:
        all_sids = np.asarray(series_ids, dtype=np.int64)
    else:
        all_sids = np.concatenate(
            [tsdb.store.series_ids_for_metric(mid)
             for mid in tsdb.store.metric_ids()]
            or [np.empty(0, dtype=np.int64)])
    if len(all_sids):
        # skip series with no raw data in the job window up front:
        # _chunk_tier_sids get_or_creates a tier series per (tier, agg)
        # per raw series, so a sparse range would otherwise permanently
        # allocate empty tier series (memory + snapshot growth)
        counts = np.asarray(
            tsdb.store.count_range(all_sids, start_ms, end_ms))
        all_sids = all_sids[counts > 0]
    # sweeps: finest pass feeds nested tiers by coarsening; each
    # non-nesting tier scans the raw data itself
    sweeps = [(finest, nested)] + [(t, []) for t in direct]
    total_work = len(all_sids) * len(sweeps)
    done = 0
    # storage-side reduction by default (tss_bucket_reduce — no
    # device transfer); tsd.rollups.job.device forces the device tiles
    use_native = (hasattr(tsdb.store, "bucket_reduce") and not
                  tsdb.config.get_bool("tsd.rollups.job.device"))

    for base, sub in sweeps:
        factors = [t.interval_ms // base.interval_ms for t in sub]
        win_ms = base.interval_ms * _window_buckets(factors)
        if series_chunk is None:
            # size the chunk for THIS sweep's window (direct tiers
            # have wider windows), assuming up to 1s cadence
            win_pts = max(1, win_ms // 1000)
            chunk_sz = max(1, _TILE_CELL_BUDGET // win_pts)
        else:
            chunk_sz = series_chunk
        for lo in range(0, len(all_sids), chunk_sz):
            chunk = all_sids[lo:lo + chunk_sz]
            rsid_map = _chunk_tier_sids(tsdb, [base] + sub, chunk)
            # windows align to their own width (a multiple of every
            # nested tier's interval) so no coarse bucket straddles
            # two windows — a straddle would write the same coarse ts
            # twice and lose one half to last-write-wins dedup.
            # One window's device work stays in flight while the
            # previous window's results download and write.
            pending = None
            t0 = start_ms - (start_ms % win_ms)
            while t0 <= end_ms:
                if use_native:
                    outs = _rollup_window_native(
                        tsdb, chunk, 0, max(t0, start_ms),
                        min(t0 + win_ms - 1, end_ms), base, sub)
                else:
                    outs = _rollup_window(tsdb, chunk, 0,
                                          max(t0, start_ms),
                                          min(t0 + win_ms - 1, end_ms),
                                          base, sub)
                if pending:
                    _write_outs(tsdb, rsid_map, pending, written)
                pending = outs
                t0 += win_ms
            if pending:
                _write_outs(tsdb, rsid_map, pending, written)
            done += len(chunk)
            if progress is not None:
                progress(done, total_work)
    return written
