"""What ``Tracer.finish`` costs a served query, on the worker before
the response goes back (``tsd.trace.finish_ms``)."""
import envreaders


def read(ctx):
    return envreaders.per_query(ctx, "tsd.trace.finish_ms")
