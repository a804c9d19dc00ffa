"""Self time of ``query.execute``: what none of its child stages
names (``tsd_stage_self_ms``)."""
import spanreaders


def read(ctx):
    return spanreaders.self_mean_ms(ctx, "query.execute")
