"""Of the thread-milliseconds the request stages spent with the
device unoccupied, the share inside ``query.execute`` itself that no
child stage names (``tsd.device.idle_stage_ms``)."""
import spanreaders


def read(ctx):
    stages = {r["tags"]["stage"] for r in spanreaders.records(
        ctx.after, "tsd.device.idle_stage_ms")
        if r["tags"].get("stage", "").startswith("query.")}
    idle = {s: spanreaders.counter_delta(
        ctx, "tsd.device.idle_stage_ms", stage=s) for s in stages}
    total = sum(idle.values())
    if total <= 0:
        return None
    return 100.0 * idle.get("query.execute", 0.0) / total
