"""Mean of the ``query.receive`` stage: from a request's first bytes
in the server's buffer to the request parsed (headers, body)."""


def read(ctx):
    return ctx.stage_mean_ms("query.receive")
