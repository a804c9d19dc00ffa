"""Mean of the ``query.assemble`` stage: result rows from arrays."""


def read(ctx):
    return ctx.stage_mean_ms("query.assemble")
