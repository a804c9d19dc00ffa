"""Share of the window's rank-class programs (a percentile or the
median: one sort of the grid along the series axis) placed on the
device and not on the host CPU backend: ``tsd.query.tail`` by
``class`` and ``placement``. A program that does not label its tails
by class gives None."""
import spanreaders


def read(ctx):
    total = spanreaders.counter_delta(ctx, "tsd.query.tail",
                                      **{"class": "rank"})
    if not total:
        return None
    on_device = spanreaders.counter_delta(
        ctx, "tsd.query.tail", placement="device",
        **{"class": "rank"}) or 0
    return 100.0 * on_device / total
