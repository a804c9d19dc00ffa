"""What the readers of the request's envelope and of the tracer's own
counters share (PR 36): the stages outside ``query.http``
(``query.receive``, ``query.admission``, ``query.respond``), the CPU
time of the threads that serve queries (``tsd.runtime.thread_cpu_ms``),
what ``Tracer.finish`` costs, and the counters that say which way a
request went. Like ``spanreaders.py``: ``ctx`` is
``run.Context``; a program without the span or the counter (the parent
of the PR that brought it) gives None, never an error.
"""

from __future__ import annotations

import spanreaders

# the thread groups a served query runs on: the server's query pool
# and the pool a request's independent sub-queries fan out onto
SERVING_THREADS = ("tsd-query", "tsd-subq")

# (counter, tag, value) of every slow way a request can fall to: a
# plan outside the index or one that had to build it, a filter walked
# over its key's names, a grid filled on the host, group tags sorted
# from the request's own rows
FALLBACKS = (("tsd.query.plan", "index", "bypass"),
             ("tsd.query.plan", "index", "built"),
             ("tsd.query.filter", "resolve", "walk"),
             ("tsd.query.grid_build", "mode", "host"),
             ("tsd.query.assemble", "tags", "matrix"))


def queries(ctx) -> int:
    """Served queries finished between the snapshots: growth of the
    count of ``query.http`` roots."""
    n0, _ = spanreaders.histogram(ctx.before, "tsd_stage_latency_ms",
                                  "query.http")
    n1, _ = spanreaders.histogram(ctx.after, "tsd_stage_latency_ms",
                                  "query.http")
    return n1 - n0


def per_query(ctx, metric: str):
    """Growth of a counter of the process over the served queries of
    the window."""
    grown = spanreaders.counter_delta(ctx, metric)
    n = queries(ctx)
    return grown / n if grown is not None and n > 0 else None


def serving_cpu_ms_per_query(ctx):
    """CPU time the kernel charged to ``SERVING_THREADS`` in the
    window, a served query; None where the program exports no thread's
    (the parent of PR 36, a host without procfs)."""
    grown = [spanreaders.counter_delta(
        ctx, "tsd.runtime.thread_cpu_ms", thread=group)
        for group in SERVING_THREADS]
    seen = [g for g in grown if g is not None]
    n = queries(ctx)
    return sum(seen) / n if seen and n > 0 else None


def fallbacks(ctx):
    """Requests' stages that went a slow way in the window, summed
    over ``FALLBACKS``; None where the program exports none of the
    counters."""
    grown = [spanreaders.counter_delta(ctx, metric, **{tag: value})
             for metric, tag, value in FALLBACKS]
    seen = [g for g in grown if g is not None]
    return sum(seen) if seen else None
