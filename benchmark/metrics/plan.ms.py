"""Mean of the ``query.plan`` stage: filters, tag index, grouping."""


def read(ctx):
    return ctx.stage_mean_ms("query.plan")
