"""Mean of the ``ingest.decode`` stage per put body: JSON parse,
validation and grouping by series."""


def read(ctx):
    return ctx.stage_mean_ms("ingest.decode")
