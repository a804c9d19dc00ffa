"""Compilations the program itself counted in the window
(``tsd.device.compiles``: fresh or from the persistent cache); must
equal the plugin's ``window.compiles``: 0."""
import spanreaders


def read(ctx):
    return spanreaders.counter_delta(ctx, "tsd.device.compiles")
