"""Bytes of rollup tier pairs (a SUM and a COUNT grid) the HBM cache
holds when the window closes (the gauge
``tsd.query.rollup.resident_bytes``), in MB of 1e6 bytes."""
import spanreaders


def read(ctx):
    held = spanreaders.records(ctx.after,
                               "tsd.query.rollup.resident_bytes")
    return sum(r["value"] for r in held) / 1e6 if held else None
