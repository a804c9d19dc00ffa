"""Share of the window's programs placed on the device and not
on the host CPU backend (``tsd.query.tail`` by ``placement``)."""
import spanreaders


def read(ctx):
    total = spanreaders.counter_delta(ctx, "tsd.query.tail")
    if not total:
        return None
    on_device = spanreaders.counter_delta(ctx, "tsd.query.tail",
                                          placement="device") or 0
    return 100.0 * on_device / total
