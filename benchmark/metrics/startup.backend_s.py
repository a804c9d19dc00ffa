"""JAX's client and the device, timed inside the server
(``/api/health`` ``startup.backend``)."""


def read(ctx):
    return ctx.after["health"].get("startup", {}).get("backend")
