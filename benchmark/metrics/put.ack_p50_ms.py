"""Median of the window's /api/put acknowledgements
(``putreaders.acks_ms``: due to the last byte of the 204)."""
import statistics

import putreaders


def read(ctx):
    acks = putreaders.acks_ms(ctx)
    return statistics.median(acks) if acks else None
