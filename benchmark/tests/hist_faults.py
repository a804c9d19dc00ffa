"""Faults for ``test_hist_cell.py`` to find, each loaded into the TSD
through ``tsd.rpc.plugin`` beside the loader: a merge done in less
than the stated precision, a dropped series, a dropped bucket of
time."""

import numpy as np

from opentsdb_tpu.plugins import RpcPlugin


def to_bfloat16(x: np.ndarray) -> np.ndarray:
    """The nearest bfloat16 of each value (round to nearest even on
    the float32 bit pattern), as float32."""
    bits = np.ascontiguousarray(x, dtype=np.float32).view(np.uint32)
    rounded = (bits + np.uint32(0x7FFF)
               + ((bits >> np.uint32(16)) & np.uint32(1))) \
        & np.uint32(0xFFFF0000)
    return rounded.view(np.float32)


class Bfloat16Merge(RpcPlugin):
    """The merge along series comes out of its contraction in
    bfloat16 (8 bits of mantissa: what the MXU gives a program that
    does not ask for more): a merged count above 256 is rounded, and a
    cumulative count moves by up to 0.4%. A point's own counts are
    under 256, so rounding what is STORED would change nothing."""

    def initialize(self, tsdb) -> None:
        import jax.numpy as jnp

        from opentsdb_tpu.ops import histogram_kernels
        real = histogram_kernels._by_group

        def rounded(x, labels, spec):
            return real(x, labels, spec).astype(jnp.bfloat16) \
                .astype(x.dtype)

        histogram_kernels._by_group = rounded


class DroppedSeries(RpcPlugin):
    """The plan loses the last series it selected."""

    def initialize(self, tsdb) -> None:
        from opentsdb_tpu.query.engine import QueryEngine
        real = QueryEngine._apply_filters

        def short(self, store, sub, sids):
            sids, tags, plan_tags = real(self, store, sub, sids)
            if not sub.percentiles:
                return sids, tags, plan_tags
            keep = np.arange(len(sids) - 1)
            return sids[keep], tags.select(keep), plan_tags

        QueryEngine._apply_filters = short


class DroppedBucket(RpcPlugin):
    """The last bucket of time is merged into nothing."""

    def initialize(self, tsdb) -> None:
        from opentsdb_tpu.query import histogram_engine
        real = histogram_engine._time_axis

        def short(point_ts, tsq, sub):
            time_idx, ts_out, in_range = real(point_ts, tsq, sub)
            return time_idx, ts_out, in_range & (
                time_idx < len(ts_out) - 1)

        histogram_engine._time_axis = short
