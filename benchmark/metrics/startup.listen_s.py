"""Process start until the server listens: generate, load, TPU client."""


def read(ctx):
    return ctx.setup.get("listen_s")
