"""The rank tail's share of its roofline: the least time the chip
could take for one request's bytes, whatever implements the
percentile (``kernels.grid_tail_bytes`` of the first sub-query's shape
over the HBM bandwidth of ``peaks.json``: the grid and its mask read
once, the ids, the ``[group x bucket]`` result written; a selection
needs no less), over the mean device time of one program execution in
the traced stretch. Since PR 44 the group stage selects by counting
(16 steps over the keys) where it sorted, and ``fill_gaps`` still moves
the grid through HBM many times over, so the share is small by
construction: how small is the finding."""
import kernels
import readers


def read(ctx):
    n, secs = readers.program_modules(ctx)
    if not n or not ctx.peaks or not ctx.first_shape:
        return None
    least = kernels.grid_tail_bytes(*ctx.first_shape) \
        / ctx.peaks["hbm_bytes_per_s"]
    return 100.0 * least / (secs / n)
