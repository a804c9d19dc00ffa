"""Device busy time per timed request in the traced stretch."""


def read(ctx):
    if not ctx.trace or not ctx.trace_queries:
        return None
    return ctx.trace["busy_s"] * 1000.0 / ctx.trace_queries
