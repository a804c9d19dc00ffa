"""How late the load generator sent its /api/put bodies: mean of
(first byte written - instant due). A body waits only for one of the
writers' connections, so this grows with a backlog of acknowledgements
as well as with a starved generator."""
import statistics


def read(ctx):
    late = [r.late_ms for r in ctx.write_results]
    return statistics.fmean(late) if late else None
