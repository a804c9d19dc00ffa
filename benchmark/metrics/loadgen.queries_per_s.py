"""Requests answered per second of the window."""


def read(ctx):
    if not ctx.results:
        return None
    span = max(r.done for r in ctx.results) - ctx.window_t0
    return len(ctx.results) / span
