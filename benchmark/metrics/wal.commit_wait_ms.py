"""Milliseconds a put body waited for its group-committed fsync: the
``wal.commit_wait`` stage's time over the count of ``ingest.put``
roots (a body that found its records already synced opens no span)."""
import putreaders
import spanreaders


def read(ctx):
    waited = spanreaders.stage_sum_ms(ctx, "wal.commit_wait")
    n = putreaders.bodies(ctx)
    return waited / n if waited is not None and n > 0 else None
