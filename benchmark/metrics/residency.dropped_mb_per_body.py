"""Resident bytes the window's writes cost its readers, a put body:
growth of ``tsd.query.residency.dropped_bytes`` (the bytes of every
entry of the HBM cache that a look-up dropped because the store had
changed under it, PR 51) over the count of ``ingest.put`` roots, in MB
of 1e6 bytes. ~0 where the writes land beyond what is resident; the
metric's whole grid and its columns (63 MB + 63 MB at ``fleet-1m``) a
body where every write drops everything. A program without the counter
(the parent of PR 51) gives None."""
import putreaders
import spanreaders


def read(ctx):
    dropped = spanreaders.counter_delta(
        ctx, "tsd.query.residency.dropped_bytes")
    bodies = putreaders.bodies(ctx)
    if dropped is None or bodies <= 0:
        return None
    return dropped / bodies / 1e6
