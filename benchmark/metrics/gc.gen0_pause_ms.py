"""Mean pause of a generation-0 collection (``tsd.runtime.gc_pause_ms``
over ``gc_collections``, ``gen=0``): the same few hundred young
objects every time, so its cost is the speed of the process's memory
(five times the usual at the wide cell's upper level, PR 36)."""
import spanreaders


def read(ctx):
    paused = spanreaders.counter_delta(ctx, "tsd.runtime.gc_pause_ms",
                                       gen="0")
    n = spanreaders.counter_delta(ctx, "tsd.runtime.gc_collections",
                                  gen="0")
    return paused / n if paused is not None and n else None
