"""Share of the traced stretch in which no operation ran on the
device, averaged over the chips used."""


def read(ctx):
    if not ctx.trace or ctx.trace_window_s <= 0:
        return None
    return 100.0 * (1.0 - ctx.trace["busy_s"] / ctx.trace_window_s)
