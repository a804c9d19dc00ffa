"""Milliseconds a sub-query spent turning its value filters into tagv
ids: the ``query.filter_resolve`` stage (a child of ``query.plan``, one
span a filter resolved the ``ids`` or the ``walk`` way) over the count
of ``query.execute``. A program without the stage gives None."""
import spanreaders


def read(ctx):
    return spanreaders.per_execute_ms(ctx, "query.filter_resolve")
