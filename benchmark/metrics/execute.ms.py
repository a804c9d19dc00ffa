"""Mean of the ``query.execute`` stage: scan, upload and program."""


def read(ctx):
    return ctx.stage_mean_ms("query.execute")
