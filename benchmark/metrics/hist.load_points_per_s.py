"""Histogram points the loader landed a second, from its own line in
the server's log (``benchmark-loader: imported <n> data points in
<t>s``: standard input read, blobs laid out, ``add_histogram_batch``).
"""
import histreaders


def read(ctx):
    return histreaders.load_points_per_s(ctx)
