"""Bytes the HBM grid cache holds when the window closes
(``/api/health`` ``device.resident``), in MB of 1e6 bytes."""


def read(ctx):
    by_dev = ctx.after["health"]["device"]["resident"]["bytes_by_device"]
    return sum(by_dev.values()) / 1e6 if by_dev else None
