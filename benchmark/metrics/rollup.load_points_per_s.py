"""Rollup cells the loader landed a second, both tiers counted, from
its own line in the server's log (``benchmark-loader: imported <n>
data points in <t>s``: standard input read, the runs laid out,
``add_aggregate_batch``)."""
import histreaders


def read(ctx):
    return histreaders.load_points_per_s(ctx)
