"""Share of HBM grid-cache lookups in the window that hit. Only a
device-placed tail consults that cache: no lookups, nothing to read."""


def read(ctx):
    hits = ctx.counter_delta("tsd.query.devicecache.hits")
    misses = ctx.counter_delta("tsd.query.devicecache.misses")
    return hits / (hits + misses) if hits + misses > 0 else None
