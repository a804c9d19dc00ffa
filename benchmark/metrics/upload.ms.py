"""Operand casts, pads and ``device_put`` calls per sub-query, host
side (``query.upload``; nothing in it waits for a transfer)."""
import spanreaders


def read(ctx):
    return spanreaders.per_execute_ms(ctx, "query.upload")
