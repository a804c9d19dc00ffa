"""How late the load generator sent, on its own clock: mean of (first
byte written - instant due) over the window. A starved generator must
not be read as a fast server."""
import statistics


def read(ctx):
    late = [r.late_ms for r in ctx.results]
    return statistics.fmean(late) if late else None
