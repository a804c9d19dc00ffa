"""Series the store gained inside the window: growth of
``tsd.storage.series.count``, whoever made them. The plan index is
versioned by that count, so each one drops its metric's index whole
and the next request pays the rebuild: 0 in a cell whose writers only
append."""
import spanreaders


def read(ctx):
    return spanreaders.counter_delta(ctx, "tsd.storage.series.count")
