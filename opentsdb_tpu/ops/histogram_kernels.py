"""Device kernel for the histogram/percentile query path.

(ref: ``src/core/HistogramAggregationIterator.java:319`` — query-time
bucket-wise SUM merge — ``HistogramDownsampler.java`` and
``SimpleHistogram.percentile`` :133)

A window's histogram points are laid out ``[series x slot x bin]``: a
series a row, a distinct timestamp of the window a slot, a histogram
bucket a bin (``bin`` here, so that ``bucket`` keeps meaning a
downsample bucket as everywhere else in ``ops/``). Time is an AXIS and
not a segment: nothing proportional to points x segments is ever built
(the one-hot-over-segments merge this file had until PR 42 needed a
64 GB operand at 12M points x 1,280 segments).

One program a request, BASELINE.json config 4 (p99/p999, histogram
path):

1. merge along series by group: one contraction over the series axis
   alone, ``[G x series]`` (a one-hot of one int32 label a series,
   like :func:`opentsdb_tpu.ops.groupby._group_sum`) against
   ``[series x slots*bins]``: reads the resident counts once;
2. merge along time: the ``[G x slots x bins]`` result against a
   one-hot ``[slots x buckets]`` of each slot's downsample bucket
   (left out without a downsample): small;
3. percentile: cumulative sum over the bins and a rank compare, the
   midpoint of the bin whose cumulative count first reaches
   ``total * q / 100`` (``cum < target`` counted);
4. ``points``: the stored points merged into each (group, bucket),
   from the same two contractions over the presence mask, so an
   emitted cell is one that holds a point (an all-zero histogram
   included, as the reference emits it);
5. ``widest``: the largest merged total. Counts are integers in
   float32 and every partial sum of non-negative integers is exact
   below 2**24, so the merge is exact unless ``widest`` reaches 2**24:
   the engine then answers from the float64 arena on the host.
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp

#: a merged total at or above this may have been rounded in float32
EXACT_BELOW = float(1 << 24)

_EXACT = jax.lax.Precision.HIGHEST


@dataclass(frozen=True)
class HistogramSpec:
    """Static (trace-time) shape of one percentile program: the
    histogram twin of :class:`opentsdb_tpu.ops.pipeline.PipelineSpec`
    (``run_staged`` reads the same fields of both). All dims padded:
    the last group and the last bucket are the dummies that padded
    rows, excluded series and out-of-range slots merge into."""
    num_series: int
    num_slots: int
    num_buckets: int
    num_groups: int
    num_bins: int
    # placed on the host CPU backend: the group merge lowers to a
    # segment sum (one linear pass) instead of the one-hot contraction
    host: bool = False
    # False without a downsample: a slot is its own bucket
    # (``num_buckets == num_slots``) and the time merge is left out (a
    # one-hot of slots x buckets would be slots squared)
    merge_time: bool = True

    tail_class = "histogram"


def _by_group(x, labels, spec: HistogramSpec):
    """[S, W] -> [G, W]: rows summed into their label's group."""
    if spec.host:
        return jax.ops.segment_sum(x, labels,
                                   num_segments=spec.num_groups)
    onehot = jax.nn.one_hot(labels, spec.num_groups, dtype=x.dtype)
    return jax.lax.dot_general(onehot, x, (((0,), (0,)), ((), ())),
                               precision=_EXACT)


@partial(jax.jit, static_argnames=("spec",))
def histogram_percentiles(counts, present, labels, slot_bucket, mids,
                          fractions, spec: HistogramSpec):
    """counts [S, slots*bins] f32 and present [S, slots] f32 (1 where
    a point is stored), labels [S] i32, slot_bucket [slots] i32,
    mids [bins] bin midpoints, fractions [Q] the percentiles as q / 100
    (divided on the host, in float64: the target here is one float32
    product) -> (values [Q, G, B], points [G, B], widest)."""
    g, p, nb = spec.num_groups, spec.num_slots, spec.num_bins
    with jax.named_scope("hist.merge_series"):
        merged = _by_group(counts, labels, spec).reshape(g, p, nb)
        points = _by_group(present, labels, spec)
    if spec.merge_time:
        with jax.named_scope("hist.merge_time"):
            bucket_of = jax.nn.one_hot(slot_bucket, spec.num_buckets,
                                       dtype=counts.dtype)
            merged = jnp.einsum("gpn,pb->gbn", merged, bucket_of,
                                precision=_EXACT)
            points = jnp.einsum("gp,pb->gb", points, bucket_of,
                                precision=_EXACT)
    with jax.named_scope("hist.percentile"):
        totals = merged.sum(axis=2)                       # [G, B]
        cum = jnp.cumsum(merged, axis=2)                  # [G, B, bins]
        target = totals[None] * fractions[:, None, None]
        idx = jnp.sum(cum[None] < target[..., None], axis=3)
        values = mids[jnp.clip(idx, 0, nb - 1)]
        values = jnp.where(totals[None] > 0, values, 0.0)
    return values, points, totals.max()
