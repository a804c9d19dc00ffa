"""The histogram merge's share of its roofline: the least time the
chip could take for one request (``kernels_hist.hist_merge_bytes`` of
the deployment's shapes and the first request's groups, buckets and
percentiles, over the HBM bandwidth of ``peaks.json``: every count
once at two bytes, a label a series, the result) over the mean device
time of one ``histogram_percentiles`` execution in the traced
stretch. The resident counts are float32 today, four bytes a count and
padded, so the share can reach 50% at most."""
import histreaders
import kernels_hist


def read(ctx):
    n, secs = histreaders.merge_modules(ctx)
    if not n or not ctx.peaks or not ctx.first_shape:
        return None
    d = ctx.config["data"]
    first = ctx.results[0].request.doc["queries"][0]
    _series, time_buckets, rows = ctx.first_shape
    qs = len(first["percentiles"])
    least = kernels_hist.hist_merge_bytes(
        d["series"], d["points"], d["buckets"], rows // qs,
        time_buckets, qs) / ctx.peaks["hbm_bytes_per_s"]
    return 100.0 * least / (secs / n)
