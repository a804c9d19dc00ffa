"""Share of the window in which the server held no request: it
waited for the client (1 - the ``query.http`` stage's time over the
wall between the snapshots; a closed loop of one client)."""
import spanreaders


def read(ctx):
    busy = spanreaders.stage_sum_ms(ctx, "query.http")
    if busy is None:
        return None
    return 100.0 * (1.0 - busy / spanreaders.wall_ms(ctx))
