"""UID resolution and series lookup per distinct series during the
bulk load: the ``ingest.resolve`` stage summed since process start."""
import spanreaders


def read(ctx):
    _n, total_ms = spanreaders.histogram(
        ctx.after, "tsd_stage_latency_ms", "ingest.resolve")
    return total_ms / 1000.0 if total_ms else None
