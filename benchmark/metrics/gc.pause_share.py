"""Share of the window the collector held the interpreter
(``tsd.runtime.gc_pause_ms``, all generations)."""
import spanreaders


def read(ctx):
    paused = spanreaders.counter_delta(ctx, "tsd.runtime.gc_pause_ms")
    if paused is None:
        return None
    return 100.0 * paused / spanreaders.wall_ms(ctx)
