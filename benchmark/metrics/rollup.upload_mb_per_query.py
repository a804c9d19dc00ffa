"""Bytes rollup-average requests put on the device, a served query
(``tsd.query.rollup.upload_bytes`` over the count of ``query.http``
roots), in MB of 1e6 bytes: one int32 label a resident row where the
tier pair stayed resident (0.4), both grids where it did not (705)."""
import envreaders


def read(ctx):
    grown = envreaders.per_query(ctx, "tsd.query.rollup.upload_bytes")
    return grown / 1e6 if grown is not None else None
