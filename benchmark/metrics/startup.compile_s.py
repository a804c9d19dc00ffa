"""The warm-up stretch: each template sent a fixed number of times,
the first of which compiles or loads the program from the cache."""


def read(ctx):
    return ctx.setup.get("warm_s")
