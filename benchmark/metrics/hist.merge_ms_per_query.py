"""Device time of the histogram percentile program a request: the
executions in the traced stretch of the compiled programs named
``histogram_percentiles`` (``jax.jit`` names a module after its
function), their mean."""
import histreaders


def read(ctx):
    n, secs = histreaders.merge_modules(ctx)
    return secs * 1000.0 / n if n else None
