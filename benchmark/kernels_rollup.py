"""Bytes the rollup-average program has to move, from the deployment's
shapes alone. Kept with the benchmark (beside ``kernels.py``, which a
PR may not edit) so that a PR which changes the program or its layout
cannot change what its roofline share is measured against.
"""

from __future__ import annotations

CELL_BYTES = 4      # float32: the precision the configuration states


def avg_div_bytes(series: int, buckets: int, groups: int) -> int:
    """The least a request that divides a tier's SUM cells by its
    COUNT cells and sums the quotients by group must move through
    HBM: both ``[series x bucket]`` grids read once at four bytes a
    cell, no padding and no mask counted (a layout that pads or keeps
    a mask reads more, never less); one int32 group label a series
    (what a request's filter decides); the float32 ``[group x bucket]``
    result and its mask written. The arithmetic is a division, an
    interpolation and an add a cell, far under the chip's compute peak
    a byte: the program is bound by memory bandwidth and its roofline
    is these bytes over HBM bytes/s. A program that makes temporaries
    of the grid's size between its stages moves several times this."""
    return 2 * series * buckets * CELL_BYTES + series * 4 \
        + groups * buckets * (CELL_BYTES + 1)
