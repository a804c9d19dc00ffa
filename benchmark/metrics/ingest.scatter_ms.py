"""Mean of the ``store.scatter`` stage per put body: the store's
appends, a series at a time, with the WAL's framing inside."""


def read(ctx):
    return ctx.stage_mean_ms("store.scatter")
