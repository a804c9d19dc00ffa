"""What the readers of the rollup cell's per-layer metrics share
(PR 48). Like ``spanreaders.py``: ``ctx`` is ``run.Context``; a
program without the module, the counter or the line gives None (or
nothing to divide), never an error.
"""

from __future__ import annotations

import spanreaders

PROGRAM_MODULE = "run_pipeline_avg_div"
SOURCES = ("raw", "tier", "fallback")


def program_modules(ctx):
    """(executions, seconds) in the traced stretch of the compiled
    programs whose name holds :data:`PROGRAM_MODULE` (``jax.jit`` names
    a module after its function)."""
    if not ctx.trace:
        return 0, 0.0
    mine = [m for m in ctx.trace["modules"] if PROGRAM_MODULE in m[0]]
    return sum(m[1] for m in mine), sum(m[2] for m in mine)


def tier_share(ctx):
    """Percent of the window's plan stages that tier selection sent to
    a rollup tier: ``tsd.query.rollup{source=tier}`` over all
    sources."""
    grown = {s: spanreaders.counter_delta(ctx, "tsd.query.rollup",
                                          source=s) for s in SOURCES}
    total = sum(v for v in grown.values() if v)
    return 100.0 * (grown["tier"] or 0) / total if total else None
