"""Share of the window spent inside ``ingest.put`` roots, in
thread-ms over the wall between the snapshots: how much of the one
interpreter the writers take from the queries beside them. Bodies that
overlap count side by side, so a backlog reads above 100."""
import spanreaders


def read(ctx):
    busy = spanreaders.stage_sum_ms(ctx, "ingest.put")
    if busy is None:
        return None
    return 100.0 * busy / spanreaders.wall_ms(ctx)
