"""Group-by aggregation over the series axis.

(ref: ``src/core/TsdbQuery.java:916-1045`` GroupByAndAggregateCB builds
SpanGroups keyed by concatenated group-by tagv UIDs; each SpanGroup then
runs the AggregationIterator merge loop lazily during serialization)

Here a group is a segment id per series: after interpolation fill
(:mod:`opentsdb_tpu.ops.interp`), one segment reduction over axis 0 of
the ``[series, bucket]`` grid aggregates every group and every bucket at
once. Order-statistic aggregators (median / percentiles) read the two
neighbours of their position a (group, bucket) one of two ways
(:func:`rank_lowering`): on the device, for float32 cells under 2**24
rows and at most 256 groups, a radix selection that counts the
candidates a key digit with the group-sum's one-hot contraction and
moves no cell (since PR 44); anywhere else (a tail placed on the host
CPU backend, float64 under x64, more groups than the selection wins
at) a
single lexicographic ``lax.sort`` keyed by (group, NaN-last, value) —
the across-series analogue of the bucketize sort path. Both give the
same bits.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from opentsdb_tpu.ops import aggregators as aggs_mod
from opentsdb_tpu.ops.interp import fill_gaps


def _seg(fn, data, ids, num, **kw):
    return fn(data, ids, num_segments=num, indices_are_sorted=False, **kw)


# One-hot matmul budget: the MXU contraction beats segment_sum's
# scatter lowering by ~300x at query shapes (measured 0.03 ms vs
# 9.4 ms on [1e6, 12] -> [100, 12]), but S*G must stay bounded so the
# (fused, never materialized) one-hot contraction doesn't explode.
_MATMUL_GROUP_MAX_ELEMS = 2 * 10**9


def _group_sum(data, group_ids, num_groups: int,
               prefer_segment: bool = False):
    """Segment-sum over the series axis: data[S,B] -> [G,B].

    Lowered as a one-hot MXU contraction when S*G permits; TPU scatter
    (segment_sum) otherwise. ``prefer_segment`` (host-CPU placement)
    forces the scatter lowering: XLA:CPU grinds the one-hot dot at
    cells*groups flops while its segment_sum is a linear pass (the
    whole host tail: 7-15 ns a padded cell, PERF.md section 6, PR 32).
    """
    if prefer_segment:
        return _seg(jax.ops.segment_sum, data, group_ids, num_groups)
    s = data.shape[0]
    if s * num_groups <= _MATMUL_GROUP_MAX_ELEMS:
        onehot = jax.nn.one_hot(group_ids, num_groups, dtype=data.dtype)
        return jax.lax.dot_general(
            onehot, data, (((0,), (0,)), ((), ())),
            precision=jax.lax.Precision.HIGHEST)
    return _seg(jax.ops.segment_sum, data, group_ids, num_groups)


# chunked-broadcast VPU budget: the [C, G, B] masked tensor per chunk
_CHUNK_CELL_BUDGET = 10_000_000

_CHUNK_REDUCERS = {"min": (jnp.min, jnp.inf),
                   "max": (jnp.max, -jnp.inf),
                   "prod": (jnp.prod, 1.0)}


def _group_extremum(data, group_ids, num_groups: int, mode: str,
                    prefer_segment: bool = False):
    """Non-linear segment reduction (min/max/prod) over the series
    axis: data[S,B] -> [G,B], with missing cells pre-filled by the
    caller with the reduction's identity.

    TPU scatter (segment_min/max/prod) serializes per element (~9 ms
    at [1M, 12] -> 100 groups); a chunked broadcast-membership compare
    reduced twice (within chunk, then across chunks) runs ~3-6x faster
    while the total compare count S*G*B stays bounded. Falls back to
    scatter for very large group counts where the broadcast's G-factor
    loses.
    """
    red, fill = _CHUNK_REDUCERS[mode]
    s, b = data.shape
    if prefer_segment or s * num_groups * b > _MATMUL_GROUP_MAX_ELEMS:
        segf = {"min": jax.ops.segment_min,
                "max": jax.ops.segment_max,
                "prod": jax.ops.segment_prod}[mode]
        return _seg(segf, data, group_ids, num_groups)
    c = max(1, min(s, _CHUNK_CELL_BUDGET // max(1, num_groups * b)))
    pad = (-s) % c
    if pad:
        data = jnp.concatenate(
            [data, jnp.full((pad, b), fill, data.dtype)], axis=0)
        group_ids = jnp.concatenate(
            [group_ids,
             jnp.full((pad,), -1, group_ids.dtype)])
    n = data.shape[0]
    dc = data.reshape(n // c, c, b)
    ic = group_ids.reshape(n // c, c)
    eq = ic[:, :, None] == jnp.arange(
        num_groups, dtype=group_ids.dtype)[None, None, :]
    masked = jnp.where(eq[:, :, :, None], dc[:, :, None, :], fill)
    return red(red(masked, axis=1), axis=0)


@partial(jax.jit, static_argnames=("num_groups", "agg_name",
                                   "prefer_segment"))
def _group_reduce(filled, group_ids, num_groups: int, agg_name: str,
                  prefer_segment: bool = False):
    """Aggregate filled[S,B] into [G,B] per ``agg_name``. NaN = missing.

    ``prefer_segment`` routes every segmented reduction through scatter
    lowering (host-CPU placement; see _group_sum)."""
    gsum = partial(_group_sum, prefer_segment=prefer_segment)
    gext = partial(_group_extremum, prefer_segment=prefer_segment)
    valid = ~jnp.isnan(filled)
    x0 = jnp.where(valid, filled, 0.0)
    cnt = gsum(valid.astype(filled.dtype), group_ids, num_groups)
    any_valid = cnt > 0

    if agg_name in ("sum", "zimsum", "pfsum"):
        out = gsum(x0, group_ids, num_groups)
    elif agg_name == "avg":
        out = gsum(x0, group_ids, num_groups) / jnp.maximum(cnt, 1)
    elif agg_name == "count":
        out = cnt
    elif agg_name in ("min", "mimmin"):
        out = gext(jnp.where(valid, filled, jnp.inf),
                   group_ids, num_groups, "min")
        out = jnp.where(jnp.isinf(out) & (out > 0), jnp.nan, out)
        # mimmin holes filled with +inf are valid contributions; a group
        # where *everything* is +inf has no real data
        any_valid = any_valid & ~jnp.isnan(out)
    elif agg_name in ("max", "mimmax"):
        out = gext(jnp.where(valid, filled, -jnp.inf),
                   group_ids, num_groups, "max")
        out = jnp.where(jnp.isinf(out) & (out < 0), jnp.nan, out)
        any_valid = any_valid & ~jnp.isnan(out)
    elif agg_name == "multiply":
        out = gext(jnp.where(valid, filled, 1.0),
                   group_ids, num_groups, "prod")
    elif agg_name == "squareSum":
        out = gsum(x0 * x0, group_ids, num_groups)
    elif agg_name == "dev":
        s1 = gsum(x0, group_ids, num_groups)
        mean = s1 / jnp.maximum(cnt, 1)
        centered = jnp.where(valid, filled - mean[group_ids], 0.0)
        m2 = gsum(centered * centered, group_ids, num_groups)
        # population variance (divisor n) — see agg_dev
        var = m2 / jnp.maximum(cnt, 1)
        out = jnp.where(cnt == 1, 0.0, jnp.sqrt(jnp.maximum(var, 0.0)))
    elif agg_name in ("first", "last", "diff"):
        s = filled.shape[0]
        pos = jnp.arange(s, dtype=jnp.int32)[:, None]
        first_pos = _seg(jax.ops.segment_min,
                         jnp.where(valid, pos, s), group_ids, num_groups)
        last_pos = _seg(jax.ops.segment_max,
                        jnp.where(valid, pos, -1), group_ids, num_groups)
        fsafe = jnp.clip(first_pos, 0, s - 1)
        lsafe = jnp.clip(last_pos, 0, s - 1)
        first_val = jnp.take_along_axis(filled, fsafe, axis=0)
        last_val = jnp.take_along_axis(filled, lsafe, axis=0)
        if agg_name == "first":
            out = first_val
        elif agg_name == "last":
            out = last_val
        else:  # diff: exactly one value -> 0 (ref: Aggregators.Diff)
            out = jnp.where(cnt == 1, 0.0, last_val - first_val)
    else:
        agg = aggs_mod.get(agg_name)
        if agg_name == "median":
            q, est = 50.0, "median"
        elif agg.is_percentile:
            q, est = agg.percentile, agg.estimation
        else:
            raise ValueError(f"unsupported group aggregator {agg_name}")
        with jax.named_scope("tail.group_rank"):
            out = _group_rank(filled, valid, cnt, group_ids, num_groups,
                              q, est, prefer_segment)
    return jnp.where(any_valid, out, jnp.nan)


# Bits of the key a step of the selection resolves: 2**bits digit
# values, one column of counts a (rank, bucket, digit value below the
# top one) on the MXU. The stage alone on a v5e at [1,048,576 x 12],
# 112 groups: 10.9 ms at 2 bits, 13.5 at 1, 12.7 at 4 (PR 44).
_SELECT_DIGIT_BITS = 2


# Past this many (padded) groups the selection's two contractions a
# step cost more than the sort, which does not see the group count:
# the stage alone on a v5e at [1,048,576 x 12], ms, selection against
# sort: 10.9 / 52.8 at 112 groups, 45.2 at 256, 49.6 at 512, 60.8 at
# 1,024, 86.9 / 51.2 at 1,792 (PERF.md section 6, PR 44). Smaller
# grids sort in fewer passes, so the line is drawn on the near side.
_SELECT_MAX_GROUPS = 256


def rank_lowering(num_series: int, num_groups: int, dtype,
                  prefer_segment: bool = False) -> str:
    """Which lowering :func:`_group_rank` takes for a padded shape:
    ``select`` (counting, where :func:`_group_sum` would take its
    one-hot contraction, a count is exact and the groups are few:
    float32 cells, under 2**24 rows, at most
    :data:`_SELECT_MAX_GROUPS` groups) or ``sort`` (a host-placed
    tail, float64 under x64, a group count past the one-hot budget or
    past what the selection wins at)."""
    if not prefer_segment and jnp.dtype(dtype) == jnp.float32 \
            and num_series < (1 << 24) \
            and num_groups <= _SELECT_MAX_GROUPS \
            and num_series * num_groups <= _MATMUL_GROUP_MAX_ELEMS:
        return "select"
    return "sort"


def _group_rank(filled, valid, cnt, group_ids, num_groups, q: float,
                est: str, prefer_segment: bool = False):
    """Order statistics per (group, bucket): the two neighbours of the
    position ``h`` among a group's valid cells of a bucket, read by
    :func:`_ranks_by_selection` or out of :func:`_ranks_by_sort`'s
    sorted copy of the grid (:func:`rank_lowering` says which)."""
    s, _b = filled.shape
    n = cnt  # [G,B] valid counts
    p = q / 100.0
    if est == "median":
        h = jnp.floor(n / 2) + 1
    elif est == "legacy":
        h = jnp.clip(p * (n + 1), 1.0, jnp.maximum(n, 1.0))
    elif est == "r3":
        h = jnp.floor(jnp.clip(jnp.ceil(p * n - 0.5), 1.0,
                               jnp.maximum(n, 1.0)))
    elif est == "r7":
        h = jnp.clip((n - 1) * p + 1, 1.0, jnp.maximum(n, 1.0))
    else:
        raise ValueError(f"unknown estimation {est!r}")
    h_floor = jnp.floor(h)
    frac = (h - h_floor) if est in ("legacy", "r7") else jnp.zeros_like(h)
    lo_off = jnp.clip(h_floor.astype(jnp.int32) - 1, 0, None)
    max_off = jnp.maximum(n.astype(jnp.int32) - 1, 0)
    hi_off = jnp.minimum(lo_off + 1, max_off)
    lo_off = jnp.minimum(lo_off, max_off)
    if rank_lowering(s, num_groups, filled.dtype,
                     prefer_segment) == "select":
        lo, hi = _ranks_by_selection(filled, valid, group_ids,
                                     num_groups, lo_off, hi_off)
    else:
        lo, hi = _ranks_by_sort(filled, group_ids, num_groups, lo_off,
                                hi_off)
    return lo + frac * (hi - lo)


def _ranks_by_sort(filled, group_ids, num_groups, lo_off, hi_off):
    """One lax.sort along the series axis keyed lexicographically by
    (group, NaN-last, value), then one gather a rank."""
    s, b = filled.shape
    gkey = jnp.broadcast_to(group_ids[:, None], (s, b)).astype(jnp.int32)
    # lax.sort's total order puts NaN after every number, so missing
    # cells land at the end of their group without a separate NaN key
    _, sorted_vals = jax.lax.sort((gkey, filled), num_keys=2,
                                  dimension=0)
    sizes = jax.ops.segment_sum(jnp.ones_like(group_ids), group_ids,
                                num_groups)
    starts = jnp.cumsum(sizes) - sizes  # [G]
    lo_row = jnp.clip(starts[:, None] + lo_off, 0, s - 1)
    hi_row = jnp.clip(starts[:, None] + hi_off, 0, s - 1)
    lo = jnp.take_along_axis(sorted_vals, lo_row, axis=0)
    hi = jnp.take_along_axis(sorted_vals, hi_row, axis=0)
    return lo, hi


def _flip_order_bits(bits):
    """int32 bits of a float32 <-> an int32 of the same total order
    (-inf < ... < -0 < +0 < ... < +inf): its own inverse."""
    return bits ^ ((bits >> 31) & jnp.int32(0x7fffffff))


def _ranks_by_selection(filled, valid, group_ids, num_groups, lo_off,
                        hi_off):
    """The same two values by a radix selection: no cell moves.

    A float32 maps to a uint32 key of the same total order
    (:func:`_flip_order_bits`, the sign bit flipped for an unsigned
    walk). The key's digits are walked
    from the top: a step counts, per (rank, group, bucket), the
    candidates whose digit is at most d (a 0/1 indicator contracted
    with the one-hot of the group label over the series axis:
    :func:`_group_sum`'s lowering, bfloat16 operands, float32
    accumulation, exact under 2**24 rows), picks the digit the wanted
    rank falls in, sends it back to the rows (the same one-hot
    contracted the other way) and keeps the rows that agree. The
    walked digits are the value: only flags and counts pass through
    the MXU. The walk is written over [bucket, series], the series
    contracted (the compiled grid lies series-minor already: the
    transposes move nothing); both ranks walk side by side."""
    keys = _flip_order_bits(
        jax.lax.bitcast_convert_type(filled, jnp.int32))
    keys = jax.lax.bitcast_convert_type(keys, jnp.uint32).T \
        ^ jnp.uint32(0x80000000)                        # [B,S]
    want = jnp.stack([lo_off.T, hi_off.T]) + 1          # [2,B,G]
    cand = jnp.broadcast_to(valid.T, (2,) + keys.shape)  # [2,B,S]
    width = 1 << _SELECT_DIGIT_BITS
    below = jnp.arange(width - 1, dtype=jnp.uint32)

    def step(i, carry):
        cand, want, found = carry
        onehot = jax.nn.one_hot(group_ids, num_groups,
                                dtype=jnp.bfloat16)     # [S,G], fused
        shift = (32 - _SELECT_DIGIT_BITS * (i + 1)).astype(jnp.uint32)
        digit = (keys >> shift) & jnp.uint32(width - 1)  # [B,S]
        at_most = cand[:, None] \
            & (digit[None, None] <= below[None, :, None, None])
        counts = jnp.einsum(
            "rdbs,sg->rdbg", at_most.astype(jnp.bfloat16), onehot,
            preferred_element_type=jnp.float32).astype(jnp.int32)
        under = counts < want[:, None]                  # [2,D-1,B,G]
        chosen = jnp.sum(under, axis=1, dtype=jnp.int32)
        want = want - jnp.max(jnp.where(under, counts, 0), axis=1)
        sent = jnp.einsum(
            "rbg,sg->rbs", chosen.astype(jnp.bfloat16), onehot,
            preferred_element_type=jnp.float32)
        cand = cand & (digit[None].astype(jnp.float32) == sent)
        return cand, want, found | (chosen.astype(jnp.uint32) << shift)

    _, _, found = jax.lax.fori_loop(
        0, 32 // _SELECT_DIGIT_BITS, step,
        (cand, want, jnp.zeros(want.shape, jnp.uint32)))
    vals = jax.lax.bitcast_convert_type(
        _flip_order_bits(jax.lax.bitcast_convert_type(
            found ^ jnp.uint32(0x80000000), jnp.int32)), jnp.float32)
    return vals[0].T, vals[1].T


def group_aggregate(grid, bucket_ts, group_ids, num_groups: int,
                    agg: aggs_mod.Aggregator, interpolate: bool = True,
                    prefer_segment: bool = False):
    """The reference's SpanGroup.iterator + AggregationIterator pass:
    interpolation fill per the aggregator's mode, then one segmented
    reduction over the series axis. grid[S,B] -> [G,B].

    ``interpolate=False`` for NAN/NULL downsample fill policies: the
    reference's FillingDownsampler emits explicit NaN points there, so
    the merge loop sees a point (and skips its NaN value) instead of a
    gap — cross-series interpolation never triggers."""
    filled = grid
    if interpolate:
        with jax.named_scope("tail.interpolate"):
            filled = fill_gaps(grid, bucket_ts, agg.interpolation.value)
    with jax.named_scope("tail.group_reduce"):
        return _group_reduce(filled, group_ids, num_groups, agg.name,
                             prefer_segment=prefer_segment)
