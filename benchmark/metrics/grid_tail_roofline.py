"""The grid-tail program's share of its roofline: the least time the
chip could take for one request's bytes (``kernels.grid_tail_bytes``
over the HBM bandwidth of ``peaks.json``; the program is memory-bound)
over the mean device time of one program execution in the trace. The
shape is the first sub-query's and the mean is over every program of
the traced stretch: a live request runs two programs over the same
grid, ``sum`` and ``max`` (2.8 and 4.6 ms, PERF.md), and the share is
that of their mean."""
import kernels
import readers


def read(ctx):
    n, secs = readers.program_modules(ctx)
    if not n or not ctx.peaks or not ctx.first_shape:
        return None
    least = kernels.grid_tail_bytes(*ctx.first_shape) \
        / ctx.peaks["hbm_bytes_per_s"]
    return 100.0 * least / (secs / n)
