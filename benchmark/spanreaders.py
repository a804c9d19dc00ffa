"""What the readers of the program's nested stage spans and of its
device, runtime and start-up counters share (PR 24). Like
``readers.py``: ``ctx`` is ``run.Context``, whose ``before`` and
``after`` hold ``/api/stats/raw`` and ``/api/health`` at the window's
two ends. A program without the span or the counter (the parent of the
PR that brought it) gives None, never an error.
"""

from __future__ import annotations


def histogram(snap, family: str, stage: str):
    for h in snap["stats"]["histograms"]:
        if h["name"] == family and h["labels"].get("stage") == stage:
            return h["count"], h["sum"]
    return 0, 0.0


def stage_sum_ms(ctx, stage: str, family: str = "tsd_stage_latency_ms"):
    """Growth of one stage histogram's sum over the window, or None
    where the stage never ran in it."""
    n0, s0 = histogram(ctx.before, family, stage)
    n1, s1 = histogram(ctx.after, family, stage)
    return s1 - s0 if n1 > n0 else None


def per_execute_ms(ctx, stage: str):
    """Milliseconds of ``stage`` per ``query.execute`` (one
    sub-query): a stage may open more than once inside one execution
    (the wide cell uploads twice), so its own count is not the
    divisor."""
    total = stage_sum_ms(ctx, stage)
    n0, _ = histogram(ctx.before, "tsd_stage_latency_ms",
                       "query.execute")
    n1, _ = histogram(ctx.after, "tsd_stage_latency_ms",
                       "query.execute")
    return total / (n1 - n0) if total is not None and n1 > n0 else None


def self_mean_ms(ctx, stage: str):
    """Mean self time of a stage that has children: its duration less
    the union of its children's (``tsd_stage_self_ms``)."""
    n0, s0 = histogram(ctx.before, "tsd_stage_self_ms", stage)
    n1, s1 = histogram(ctx.after, "tsd_stage_self_ms", stage)
    return (s1 - s0) / (n1 - n0) if n1 > n0 else None


def records(snap, metric: str, **tags) -> list[dict]:
    return [r for r in snap["stats"]["records"]
            if r["metric"] == metric
            and all(r["tags"].get(k) == v for k, v in tags.items())]


def counter_delta(ctx, metric: str, **tags):
    """Growth of the counter's records that carry ``tags``, summed;
    None where the program exports no such record."""
    after = records(ctx.after, metric, **tags)
    if not after:
        return None
    return sum(r["value"] for r in after) \
        - sum(r["value"] for r in records(ctx.before, metric, **tags))


def wall_ms(ctx) -> float:
    """The harness's clock between the two snapshots."""
    return (ctx.after["at"] - ctx.before["at"]) * 1000.0
