"""Compilations JAX was asked for inside the window (fresh or from the
persistent cache). Every shape is warmed before it: must be 0."""


def read(ctx):
    return ctx.window_compiles
