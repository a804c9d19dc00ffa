"""Self time of the ``query.http`` root: what no stage under it
names (the body's parse, the cache and streaming look-ups, the
statistics)."""
import spanreaders


def read(ctx):
    return spanreaders.self_mean_ms(ctx, "query.http")
