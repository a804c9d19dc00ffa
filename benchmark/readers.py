"""What the per-layer readers under ``benchmark/metrics/`` share. A
reader is ``read(ctx) -> number or None``; ``ctx`` is ``run.Context``.
A reader that finds nothing to read returns None and the metric is
left out of the result line.
"""

from __future__ import annotations

import math
import statistics


def percentile(values: list[float], q: float) -> float:
    """Nearest-rank percentile of all the values."""
    s = sorted(values)
    return s[max(0, min(len(s) - 1, math.ceil(q / 100 * len(s)) - 1))]


def mean_query_stat(ctx, key: str):
    """Mean of one ``QueryStat`` over the window's requests that
    ``/api/stats/query`` still holds (it keeps the last 50)."""
    vals = [q["stats"][key] for q in ctx.query_stats
            if key in q.get("stats", {})]
    return statistics.fmean(vals) if vals else None


def program_modules(ctx):
    """(executions, seconds) of compiled programs in the traced
    stretch, from the trace's ``XLA Modules`` line."""
    if not ctx.trace:
        return 0, 0.0
    n = sum(m[1] for m in ctx.trace["modules"])
    return n, sum(m[2] for m in ctx.trace["modules"])
