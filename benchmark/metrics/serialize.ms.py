"""Mean of the ``query.serialize`` stage: rows to JSON bytes."""


def read(ctx):
    return ctx.stage_mean_ms("query.serialize")
