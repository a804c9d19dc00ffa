"""90th percentile of request latency, where the window holds too few
requests to judge a 95th: a statistic beside the judged median."""
import readers


def read(ctx):
    lat = ctx.latencies_ms()
    return readers.percentile(lat, 90) if lat else None
