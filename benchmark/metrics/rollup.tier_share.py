"""Share of the window's sub-queries that tier selection answered from
a rollup tier (``tsd.query.rollup`` by ``source``: raw, tier, or raw as
the fallback of an empty tier), in percent. The cell's request has its
best match in the 1h tier: anything under 100 is a request that read
another store. A program without the counter (the parent of PR 48)
gives None."""
import rollupreaders


def read(ctx):
    return rollupreaders.tier_share(ctx)
