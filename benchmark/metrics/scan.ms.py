"""Mean ``queryScanTime`` (the storage scan into the bucket grid) of
the window's last requests, from ``/api/stats/query``."""
import readers


def read(ctx):
    return readers.mean_query_stat(ctx, "queryScanTime")
