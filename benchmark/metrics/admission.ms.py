"""Mean of the ``query.admission`` stage: from the request parsed to
the root's start in a worker (admission, the worker queue, the
router's dispatch)."""


def read(ctx):
    return ctx.stage_mean_ms("query.admission")
