"""Share of the window with no program in flight on the device, by
the program's own occupancy clock (dispatch to ready), over the
whole window; beside the trace's ``device.idle_share``."""
import spanreaders


def read(ctx):
    occupied = spanreaders.counter_delta(ctx, "tsd.device.occupied_ms")
    if occupied is None:
        return None
    return 100.0 * (1.0 - occupied / spanreaders.wall_ms(ctx))
