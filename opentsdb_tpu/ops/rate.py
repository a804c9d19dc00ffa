"""Rate / counter conversion (ref: ``src/core/RateSpan.java:21``,
``RateOptions.java:27``).

First difference dv/dt (per second) between a series' successive
*present* points, vectorized over the ``[series, bucket]`` grid: each
present cell reads the previous present cell of its own series from
one forward sweep along the buckets (``interp.carry_prev``), so holes
(NaN) are skipped exactly like the reference's iterator skips to the
prior datapoint.

Counter semantics (RateOptions):
- ``counter``: negative delta means rollover; corrected rate =
  (counter_max - prev + cur) / dt (RateSpan.java:150-170)
- ``drop_resets``: drop the rolled-over point instead
- ``reset_value``: corrected rates above this emit 0

The first present point of every series has no predecessor and produces
no rate (masked to NaN).
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp

from opentsdb_tpu.ops.interp import carry_prev


@dataclass(frozen=True)
class RateOptions:
    """(ref: RateOptions.java:27-52)"""
    counter: bool = False
    counter_max: float = float(2**64 - 1)  # Long.MAX in ref; u64 here
    reset_value: float = 0.0
    drop_resets: bool = False

    @classmethod
    def parse(cls, spec: str | None) -> "RateOptions":
        """Parse the query-string form ``rate{counter[,max[,reset]]}``
        (ref: QueryRpc parseRateOptions)."""
        if not spec or spec == "rate":
            return cls()
        if not (spec.startswith("rate{") and spec.endswith("}")):
            raise ValueError(f"invalid rate options: {spec}")
        parts = spec[5:-1].split(",")
        counter = parts[0] in ("counter", "dropcounter")
        drop = parts[0] == "dropcounter"
        counter_max = float(2**64 - 1)
        reset = 0.0
        if len(parts) >= 2 and parts[1]:
            # tsdlint: allow[kernel-hygiene] rate-SPEC string parse
            # (once per query), not an array element pull
            counter_max = float(parts[1])
        if len(parts) >= 3 and parts[2]:
            # tsdlint: allow[kernel-hygiene] spec parse, see above
            reset = float(parts[2])
        return cls(counter=counter, counter_max=counter_max,
                   reset_value=reset, drop_resets=drop)

    def to_json(self) -> dict:
        return {"counter": self.counter, "counterMax": self.counter_max,
                "resetValue": self.reset_value,
                "dropResets": self.drop_resets}


@partial(jax.jit, static_argnames=("counter", "drop_resets"))
def _rate_kernel(grid, bucket_ts, counter: bool, counter_max,
                 reset_value, drop_resets: bool):
    mask = ~jnp.isnan(grid)
    # previous present cell, *strictly* before each cell: the forward
    # sweep's carry as it stood when the step reached the cell
    t_cur = bucket_ts[None, :]
    gz = jnp.where(mask, grid, 0.0)
    v_prev, t_prev, has_prev = carry_prev((gz, t_cur), mask,
                                          exclusive=True)
    # difference timestamps BEFORE any float cast: bucket_ts arrives as
    # small relative offsets (device_bucket_ts) so integer diffs are
    # exact even on TPU where int64/float64 are unavailable
    dt_sec = (t_cur - t_prev).astype(grid.dtype) / 1000.0
    dt_sec = jnp.where(dt_sec > 0, dt_sec, 1.0)
    delta = grid - v_prev
    rate = delta / dt_sec
    if counter:
        rolled = delta < 0
        corrected = (counter_max - v_prev + grid) / dt_sec
        rate = jnp.where(rolled, corrected, rate)
        if drop_resets:
            rate = jnp.where(rolled, jnp.nan, rate)
        # reset_value: corrected rates above threshold emit 0
        rate = jnp.where(
            (reset_value > 0) & (rate > reset_value), 0.0, rate)
    return jnp.where(mask & has_prev, rate, jnp.nan)


def compute_rate(grid, bucket_ts, options: RateOptions):
    """Apply rate conversion to a [S,B] grid. Returns a same-shape grid;
    the first present point of each series becomes NaN (dropped)."""
    return _rate_kernel(grid, bucket_ts, options.counter,
                        jnp.asarray(options.counter_max, grid.dtype),
                        jnp.asarray(options.reset_value, grid.dtype),
                        options.drop_resets)
