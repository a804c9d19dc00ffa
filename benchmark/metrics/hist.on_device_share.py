"""Share of the window's histogram percentile programs (``class`` =
``histogram``: the merge of the resident counts and the percentile's
rank compare) placed on the device, not answered from the float64
arena on the host: ``tsd.query.tail`` by ``class`` and ``placement``.
A program that does not run histograms through ``run_staged`` (the
parent of PR 42) gives None."""
import spanreaders


def read(ctx):
    total = spanreaders.counter_delta(ctx, "tsd.query.tail",
                                      **{"class": "histogram"})
    if not total:
        return None
    on_device = spanreaders.counter_delta(
        ctx, "tsd.query.tail", placement="device",
        **{"class": "histogram"}) or 0
    return 100.0 * on_device / total
