"""Operations and bytes of the device programs, from shapes alone, and
the copy of the program's shape bucketing they need. Kept with the
benchmark so that a PR which changes a kernel cannot change what its
roofline share is measured against.
"""

from __future__ import annotations

_FRACTIONS = (4, 5, 6, 7)


def shape_bucket(n: int, min_size: int = 8) -> int:
    """Smallest value >= n of the form {4,5,6,7} * 2^k, floored at
    ``min_size`` (copied from ``opentsdb_tpu/ops/shapes.py``: the
    padded shapes the engine compiles for)."""
    n = max(int(n), min_size)
    if n <= min_size:
        return min_size
    k = max(int(n - 1).bit_length() - 3, 0)
    while True:
        for f in _FRACTIONS:
            cand = f << k
            if cand >= n:
                return cand
        k += 1


def grid_tail_bytes(series: int, buckets: int, groups: int) -> int:
    """The least a grid-tail program (rate, interpolation and the
    group reduction over a resident or uploaded [series x bucket] grid)
    must move through HBM for one request: read the float32 grid and
    its presence mask once, the int32 group id of every series, and
    write the float32 [group x bucket] result and its mask. Shapes are
    the padded ones. The arithmetic is a few operations per cell, far
    under the chip's compute peak per byte: the program is bound by
    memory bandwidth, and its roofline is bytes over HBM bytes/s."""
    s, b = shape_bucket(series), shape_bucket(buckets)
    g = shape_bucket(groups + 1)
    return s * b * (4 + 1) + s * 4 + g * b * (4 + 1)
