"""Bytes the histogram percentile program has to move, from the
deployment's shapes alone. Kept with the benchmark (beside
``kernels.py``, which a PR may not edit) so that a PR which changes
the kernel or its layout cannot change what its roofline share is
measured against.
"""

from __future__ import annotations

COUNT_BYTES = 2     # uint16: the narrowest type that holds count_max


def hist_merge_bytes(series: int, points: int, buckets: int,
                     groups: int, time_buckets: int,
                     percentiles: int) -> int:
    """The least a request that merges every stored histogram point of
    the deployment must move through HBM: each count read once, in the
    narrowest type that holds the deployment's ``count_max`` (65,535)
    exactly, two bytes, whatever the layout (no padding counted: a
    layout that pads reads more, never less); one int32 group label a
    series (what a request's filter decides); the float32 ``[group x
    bucket of time x percentile]`` result written. The arithmetic is
    one add a count and a compare a merged bucket, far under the
    chip's compute peak a byte: the program is bound by memory
    bandwidth and its roofline is these bytes over HBM bytes/s. The
    float32 counts of PR 42's layout are twice that and padded, so its
    share cannot pass 50%."""
    return series * points * buckets * COUNT_BYTES + series * 4 \
        + groups * time_buckets * percentiles * 4
