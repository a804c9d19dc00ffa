"""Points the store took a second: growth of ``tsd.datapoints.added``
over the wall between the two snapshots. Under the offered rate when a
backlog grows."""
import spanreaders


def read(ctx):
    added = spanreaders.counter_delta(ctx, "tsd.datapoints.added")
    if added is None:
        return None
    return added / (spanreaders.wall_ms(ctx) / 1000.0)
