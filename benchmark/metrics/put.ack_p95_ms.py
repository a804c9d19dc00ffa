"""95th percentile (nearest rank) of the window's /api/put
acknowledgements (``putreaders.acks_ms``: due to the last byte of the
204)."""
import putreaders
import readers


def read(ctx):
    acks = putreaders.acks_ms(ctx)
    return readers.percentile(acks, 95) if acks else None
