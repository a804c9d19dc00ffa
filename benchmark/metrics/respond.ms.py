"""Mean of the ``query.respond`` stage: from the handler's return in
the worker to the response's last byte drained (the loop's wake-up,
the latency and SLO feeds, gzip, the write)."""


def read(ctx):
    return ctx.stage_mean_ms("query.respond")
