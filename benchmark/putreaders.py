"""What the readers of the put path's metrics share (PR 31). Like
``spanreaders.py``: ``ctx`` is ``run.Context``; a program or a cell
with nothing to read gives None or an empty list, never an error."""

from __future__ import annotations

import spanreaders


def acks_ms(ctx) -> list[float]:
    """Every scheduled /api/put body's acknowledgement on the load
    generator's clock: from the instant the body was due (open loop)
    to the last byte of its answer, the wait for one of the writers'
    connections included."""
    return [r.latency_ms for r in ctx.write_results]


def bodies(ctx) -> int:
    """Put bodies the server finished between the snapshots: growth of
    the count of ``ingest.put`` roots."""
    n0, _ = spanreaders.histogram(ctx.before, "tsd_stage_latency_ms",
                                  "ingest.put")
    n1, _ = spanreaders.histogram(ctx.after, "tsd_stage_latency_ms",
                                  "ingest.put")
    return n1 - n0
