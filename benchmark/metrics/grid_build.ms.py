"""Host NumPy between scan and upload, per sub-query: cache digest,
the NaN fill and both pads of the grid (``query.grid_build``)."""
import spanreaders


def read(ctx):
    return spanreaders.per_execute_ms(ctx, "query.grid_build")
