"""The rollup-average program's share of its roofline: the least time
the chip could take for one request (``kernels_rollup.avg_div_bytes``
of the deployment's series, the first request's buckets and groups,
over the HBM bandwidth of ``peaks.json``: both grids once at four
bytes a cell, a label a series, the result) over the mean device time
of one ``run_pipeline_avg_div`` execution in the traced stretch."""
import kernels_rollup
import rollupreaders


def read(ctx):
    n, secs = rollupreaders.program_modules(ctx)
    if not n or not ctx.peaks or not ctx.first_shape:
        return None
    _selected, buckets, groups = ctx.first_shape
    least = kernels_rollup.avg_div_bytes(
        ctx.config["data"]["series"], buckets, groups) \
        / ctx.peaks["hbm_bytes_per_s"]
    return 100.0 * least / (secs / n)
