"""Put bodies a physical fsync round carried: the count of
``ingest.put`` roots over the growth of ``tsd.wal.group_syncs``. 1
when every body pays its own fsync; above it when bodies overlap and
the group commit shares one."""
import putreaders
import spanreaders


def read(ctx):
    syncs = spanreaders.counter_delta(ctx, "tsd.wal.group_syncs")
    return putreaders.bodies(ctx) / syncs if syncs else None
