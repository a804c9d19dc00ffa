"""Bytes histogram requests put on the device, a served query
(``tsd.query.histogram.upload_bytes`` over the count of ``query.http``
roots), in MB of 1e6 bytes: one int32 label a resident row and a few
small vectors where the counts stayed resident (0.9), the counts
themselves where they did not (3,800)."""
import envreaders


def read(ctx):
    grown = envreaders.per_query(ctx, "tsd.query.histogram.upload_bytes")
    return grown / 1e6 if grown is not None else None
