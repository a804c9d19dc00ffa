"""What the readers of the histogram cell's per-layer metrics share
(PR 42). Like ``spanreaders.py``: ``ctx`` is ``run.Context``; a
program without the module, the counter or the line gives None (or
nothing to divide), never an error.
"""

from __future__ import annotations

import os
import re

HERE = os.path.dirname(os.path.abspath(__file__))
MERGE_MODULE = "histogram_percentiles"
_LOADED = re.compile(r"^benchmark-loader: imported (\d+) data points "
                     r"in ([0-9.]+)s")


def merge_modules(ctx):
    """(executions, seconds) in the traced stretch of the compiled
    programs whose name holds :data:`MERGE_MODULE`."""
    if not ctx.trace:
        return 0, 0.0
    mine = [m for m in ctx.trace["modules"] if MERGE_MODULE in m[0]]
    return sum(m[1] for m in mine), sum(m[2] for m in mine)


def load_points_per_s(ctx):
    """From the loader's line in the log of the run's server
    (``run.py`` keeps it under ``.bench/work/<cell>/tsd.log``)."""
    log = os.path.join(os.path.dirname(HERE), ".bench", "work",
                       ctx.workload["name"], "tsd.log")
    try:
        with open(log, "r", errors="replace") as fh:
            for line in fh:
                m = _LOADED.match(line)
                if m:
                    secs = float(m.group(2))
                    return int(m.group(1)) / secs if secs > 0 else None
    except OSError:
        pass
    return None
