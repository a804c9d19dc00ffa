"""Merge-time interpolation as vectorized gap filling.

(ref: ``src/core/AggregationIterator.java:27-119`` — the O(1)-space
k-way merge that linearly interpolates each span at timestamps where
other spans have data)

On the ``[series, bucket]`` grid the same semantics become a masked fill
along the time axis: for every NaN hole *between* a series' first and
last values, substitute per the aggregator's interpolation mode; outside
that range the series contributes nothing (stays NaN), exactly like a
span that is exhausted or not yet started in the reference's merge loop.

What lies before and after a hole comes from one sweep along the
buckets in each direction (:func:`carry_prev`, :func:`carry_next`): the
carry is a ``[series]`` vector an array plus a presence flag, a step
``where(present, cell, carry)``. Where the bucket count is no
multiple of the 128 lanes (the 12-, 14-, 16- and 64-bucket classes)
the compiled grid lies series-minor (the series on the lanes, the
buckets the major dimension), so a step reads one row of the grid and
nothing is reversed, padded, copied or gathered; at 768 buckets the
resident grids lie bucket-minor, and the program turns the grid
series-minor with one grid-sized copy before the sweeps and one after
them.
The sweep takes one of two forms by the padded bucket count alone
(:func:`carry_form`): every step written out, which XLA fuses into a
pass or two over the grid, or a loop of partly unrolled steps. Why
not a parallel prefix (``lax.associative_scan``): the TPU compiler
lowers one to reversed, padded and strided copies of the grid. On a
TPU v5e ``fill_gaps`` (lerp) at [1,048,576 x 12] took 22.0 ms as a
scan and takes 1.6 ms as the sweep; at [114,688 x 768] 97.8 ms and
74 s of compiling against 19.0 ms and 3.5 s (PERF.md section 6,
PR 49, the stage-alone table).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from opentsdb_tpu.ops.aggregators import Interpolation


def _prev_valid_idx(mask):
    """[S,B] -> per cell, index of the nearest valid cell at or before it
    (-1 if none)."""
    b = mask.shape[-1]
    idx = jnp.where(mask, jnp.arange(b, dtype=jnp.int32), -1)
    return jax.lax.cummax(idx, axis=mask.ndim - 1)


def _next_valid_idx(mask):
    """[S,B] -> per cell, index of nearest valid cell at or after it
    (B if none). ``reverse=True`` scans right-to-left in place — the
    flip/scan/flip spelling materializes two reversed copies of the
    grid (measured 4.6 ms vs 0.8 ms at [1M, 12])."""
    b = mask.shape[-1]
    idx = jnp.where(mask, jnp.arange(b, dtype=jnp.int32), b)
    return jax.lax.cummin(idx, axis=mask.ndim - 1, reverse=True)


# Unrolled-select budget: reading the grid B times (one fused pass per
# bucket) beats TPU's per-element gather lowering of take_along_axis by
# ~17x at query shapes, but the S*B*B read traffic must stay bounded.
_SELECT_GATHER_MAX_ELEMS = 2 * 10**8
_SELECT_GATHER_MAX_B = 128


def _gather_minor(grid, idx):
    """``grid[s, idx[s, b]]`` along the minor axis.

    take_along_axis lowers to per-element gathers on TPU (measured
    134 ms on a [1e6, 12] grid vs 8 ms for B fused selects), so small
    bucket counts use an unrolled select chain instead; XLA fuses it
    into one pass over the grid per bucket.

    NOTE: only suitable for cheap lookups (e.g. single-column boundary
    summaries). The hot fill/rate kernels use
    :func:`carry_prev`/:func:`carry_next` instead — the select chain
    stops fusing around B=14 on TPU and falls off a 15x cliff
    (measured 88 ms -> 1.5 s at [1M, 13] -> [1M, 14]).
    """
    s, b = grid.shape
    if b <= _SELECT_GATHER_MAX_B and s * b * b <= _SELECT_GATHER_MAX_ELEMS:
        out = jnp.zeros_like(grid)
        for k in range(b):
            out = jnp.where(idx == k, grid[:, k:k + 1], out)
        return out
    return jnp.take_along_axis(grid, idx, axis=-1)


# The sweep's form, read from the (padded) bucket count alone
# (stage-alone timings of ``fill_gaps`` lerp on a TPU v5e, PERF.md
# section 6, PR 49). Up to :data:`_SWEEP_UNROLL_MAX_B` buckets every
# step is written out and XLA fuses a direction into a pass or two
# over the grid (1.6 ms at [1,048,576 x 12]); past it the steps run as
# a loop. The bound protects XLA:CPU's compile time, not the chip: 64
# written-out steps run in 1.15 ms at [114,688 x 64] where the loop
# takes 1.89, but the host backend, which runs the small tails,
# compiles them in 5.4 s where the loop takes 0.4-0.9. A trip takes
# whole sublane tiles of :data:`_SWEEP_TILE` rows, as few as keep the
# loop within :data:`_SWEEP_MAX_TRIPS` trips and no more than
# :data:`_SWEEP_MAX_ROWS`: at [114,688 x 768] 8 rows a trip (96
# trips) take 21.2 ms, 16 take 19.4, 32 take 19.0, 64 take 20.1 and
# 128 take 27.9; at 64 buckets 8, 16 and 32 a trip take the same
# (1.89, 1.82, 1.96), and there XLA:CPU compiles 8 a trip as fast as
# it did the associative scan (0.4-0.7 s; 0.8-1.7 s at 16).
_SWEEP_UNROLL_MAX_B = 16
_SWEEP_TILE = 8
_SWEEP_MAX_TRIPS = 24
_SWEEP_MAX_ROWS = 32


def carry_form(num_buckets: int) -> str:
    """Which form the sweep along ``num_buckets`` (padded) buckets
    takes: ``unrolled`` | ``loop``. The one predicate: the jitted code
    applies it, ``run_staged`` tags ``query.program`` with it."""
    return "unrolled" if num_buckets <= _SWEEP_UNROLL_MAX_B else "loop"


def _sweep_unroll(num_buckets: int) -> int:
    """Steps of the sweep written out side by side: all of them in
    the ``unrolled`` form, a trip's rows in the ``loop`` form."""
    if carry_form(num_buckets) == "unrolled":
        return max(num_buckets, 1)
    tiles = num_buckets // (_SWEEP_TILE * _SWEEP_MAX_TRIPS)
    return min(max(tiles, 1) * _SWEEP_TILE, _SWEEP_MAX_ROWS)


def _sweep(step, init, rows, reverse: bool):
    """``lax.scan`` of ``step`` over the leading (bucket) axis of
    ``rows``, from the last bucket down when ``reverse``; the unroll is
    :func:`_sweep_unroll`'s."""
    b = jax.tree_util.tree_leaves(rows)[0].shape[0]
    return jax.lax.scan(step, init, rows, reverse=reverse,
                        unroll=_sweep_unroll(b))


def _bucket_major(x):
    """[..., B] -> [B, ...]. Where B is no multiple of the 128 lanes
    the compiled grid lies series-minor already and this is a bitcast;
    at 768 buckets it is a grid-sized copy, and :func:`_bucket_minor`
    another (PERF.md section 7, the layout reading)."""
    return jnp.moveaxis(x, -1, 0)


def _bucket_minor(x):
    """[B, ...] -> [..., B], :func:`_bucket_major`'s way back."""
    return jnp.moveaxis(x, 0, -1)


def _take_present(carry, row):
    """One step of every carry: ``row`` where its flag (the last
    item) says present, else ``carry``."""
    *xs, p = row
    return tuple(jnp.where(p, x, c) for x, c in zip(xs, carry[:-1])) \
        + (carry[-1] | p,)


def _nearest_present(arrays, mask, reverse: bool, exclusive: bool):
    present = _bucket_major(mask)
    rows = tuple(_bucket_major(a) for a in arrays) + (present,)
    first = -1 if reverse else 0
    # a cell with no present cell that way carries the sweep's first
    # cell's values under a False flag (callers mask by the flag)
    init = tuple(jnp.broadcast_to(r[first], present.shape[1:])
                 for r in rows[:-1]) + (jnp.zeros_like(present[0]),)

    def step(carry, row):
        new = _take_present(carry, row)
        return new, (carry if exclusive else new)

    _, out = _sweep(step, init, rows, reverse)
    return tuple(_bucket_minor(o) for o in out)


def carry_prev(arrays, mask, exclusive: bool = False):
    """For each cell along the minor axis: the values of ``arrays`` at
    the nearest PRESENT cell at-or-before it (strictly before it when
    ``exclusive``), plus that presence flag. An array need only
    broadcast against ``mask`` (``bucket_ts`` as it is: no grid-sized
    copy of it is made).

    One sweep along the buckets: the carry is a ``[series]`` vector an
    array and the flag, a step ``where(present, cell, carry)``. No
    gather, no reversed or padded copy of the grid."""
    return _nearest_present(arrays, mask, reverse=False,
                            exclusive=exclusive)


def carry_next(arrays, mask):
    """Twin of :func:`carry_prev` from the last bucket down: nearest
    present cell at-or-after."""
    return _nearest_present(arrays, mask, reverse=True, exclusive=False)


@partial(jax.jit, static_argnames=("mode",))
def fill_gaps(grid, bucket_ts, mode: str):
    """Fill NaN holes of ``grid[S,B]`` per interpolation ``mode``.

    - ``lerp``: linear interpolation against ``bucket_ts`` between each
      series' first and last valid cells; NaN outside.
    - ``zim``: 0 for every hole (ZeroIfMissing, Aggregators ZIM).
    - ``max`` / ``min``: +inf / -inf for holes *between* first and last
      valid (type extremes, used by mimmin/mimmax); NaN outside.
    - ``prev``: repeat previous valid value (PREV / pfsum); NaN before
      the first valid cell.

    Returns the filled grid (still [S,B]); cells a series can never
    contribute to stay NaN so downstream reductions skip them.

    ``prev`` is the forward sweep alone and the extremes need the two
    flags; ``lerp`` sweeps forward for what lies before a cell, then
    backward with what lies after it in the carry, and writes the
    filled row in that backward step: the next-present values, the
    mask and the zero-filled grid are never grid-sized arrays.
    """
    mask = ~jnp.isnan(grid)
    if mode == Interpolation.ZIM.value:
        return jnp.where(mask, grid, 0.0)

    if mode == Interpolation.PREV.value:
        gz = jnp.where(mask, grid, 0.0)  # carry no NaN across a hole
        prev_val, has_prev = carry_prev((gz,), mask)
        return jnp.where(mask, grid,
                         jnp.where(has_prev, prev_val, jnp.nan))

    if mode in (Interpolation.MAX.value, Interpolation.MIN.value):
        extreme = jnp.inf if mode == Interpolation.MAX.value else -jnp.inf
        (has0,), (has1,) = carry_prev((), mask), carry_next((), mask)
        return jnp.where(mask, grid,
                         jnp.where(has0 & has1, extreme, jnp.nan))

    if mode != Interpolation.LERP.value:
        raise ValueError(f"unknown interpolation mode {mode!r}")
    # lerp reads the presence off the grid's own row in each step: no
    # grid-sized mask or zero-filled copy is an operand of the sweeps
    def take(carry, g, t):
        p = ~jnp.isnan(g)
        return _take_present(carry, (jnp.where(p, g, 0.0), t, p)), p

    def forward(carry, row):
        carry, _ = take(carry, *row)
        return carry, carry

    def backward(carry, row):
        g, t, v0, t0, has0 = row
        (v1, t1, has1), p = take(carry, g, t)
        # integer ts diffs before the float cast (exact under int32
        # relative offsets, see pipeline.device_bucket_ts)
        num = (t - t0).astype(g.dtype)
        den = (t1 - t0).astype(g.dtype)
        lerped = v0 + (v1 - v0) * num / jnp.where(den > 0, den, 1.0)
        return (v1, t1, has1), jnp.where(
            p, g, jnp.where(has0 & has1, lerped, jnp.nan))

    rows = (_bucket_major(grid), bucket_ts)
    series = grid.shape[:-1]
    # nothing seen yet; the values under a False flag reach no cell
    init = (jnp.zeros(series, grid.dtype),
            jnp.zeros(series, bucket_ts.dtype), jnp.zeros(series, bool))
    _, before = _sweep(forward, init, rows, reverse=False)
    _, out = _sweep(backward, init, rows + before, reverse=True)
    return _bucket_minor(out)
