"""Names of stored tag values the window's filters read, a sub-query:
growth of ``tsd.query.filter.names_read`` over the count of
``query.execute``. 0 where every filter resolves through ids; the
key's distinct count for each filter that walks its names. A program
without the counter gives None."""
import spanreaders


def read(ctx):
    grown = spanreaders.counter_delta(ctx, "tsd.query.filter.names_read")
    n0, _ = spanreaders.histogram(ctx.before, "tsd_stage_latency_ms",
                                  "query.execute")
    n1, _ = spanreaders.histogram(ctx.after, "tsd_stage_latency_ms",
                                  "query.execute")
    return grown / (n1 - n0) if grown is not None and n1 > n0 else None
