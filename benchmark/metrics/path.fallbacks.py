"""Stages of the window that went a slow way: plans outside or before
the index, walked filters, host-filled grids, group tags from the
request's own rows (``envreaders.FALLBACKS``). 0 in a healthy cell."""
import envreaders


def read(ctx):
    return envreaders.fallbacks(ctx)
