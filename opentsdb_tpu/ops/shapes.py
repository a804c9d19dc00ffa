"""Geometric shape bucketing: a bounded XLA compile space.

Every jitted pipeline entry specializes on (S, B, G, N); production
traffic varies all four continuously, so without bucketing each new
series count or window length pays a multi-second XLA compile
mid-query (r02's BENCH_E2E max_ms hit 16 s against p50s of hundreds of
ms). Rounding each dimension UP to the next value of the form
``{1, 1.25, 1.5, 1.75} x 2^k`` caps the distinct programs per
dimension at ~4 log2(range) (~80 for a 1M span) while wasting at most
25% padding — the same trick as bucketed sequence lengths in serving
stacks.

Padded series rows are NaN (no contribution) and belong to a dummy
trailing group; padded buckets extend bucket_ts monotonically and trim
off the result. Callers slice back to the true (G, B) so bucketing is
invisible to everything above.
"""

from __future__ import annotations

import numpy as np

_FRACTIONS = (4, 5, 6, 7)  # x/4: 1, 1.25, 1.5, 1.75


def shape_bucket(n: int, min_size: int = 8) -> int:
    """Smallest value >= n of the form {4,5,6,7} * 2^k (k >= 0),
    floored at ``min_size``."""
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


def pad_bucket_ts(bucket_ts: np.ndarray, target: int) -> np.ndarray:
    """Monotonic tail extension (same contract as the sharded
    pipeline's halo padding)."""
    bts = np.asarray(bucket_ts)
    need = target - len(bts)
    if need <= 0:
        return bts
    step = int(bts[-1] - bts[-2]) if len(bts) > 1 else 1000
    extra = bts[-1] + step * np.arange(1, need + 1, dtype=bts.dtype)
    return np.concatenate([bts, extra])


def pad_2d_host(arr: np.ndarray, s_pad: int, b_pad: int,
                fill) -> np.ndarray:
    """Host-side [S, B] -> [s_pad, b_pad] padding. The engine pads
    grids ONCE when they are built/cached so warm queries touch no
    per-query pad at all."""
    s, b = arr.shape
    if (s_pad, b_pad) == (s, b):
        return arr
    out = np.full((s_pad, b_pad), fill, dtype=arr.dtype)
    out[:s, :b] = arr
    return out


def pad_group_ids(group_ids: np.ndarray, s_pad: int,
                  num_groups: int) -> np.ndarray:
    """Group ids padded with the dummy trailing group."""
    gids = np.full(s_pad, num_groups, dtype=np.int32)
    gids[:len(group_ids)] = group_ids
    return gids
