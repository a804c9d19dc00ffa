"""Device time of the sort per timed request in the traced stretch:
the operations of the trace whose name begins ``sort`` (the
instruction ``jax.lax.sort`` compiles to, whatever number the compiler
gives it), over the timed requests that ended in the stretch."""


def read(ctx):
    if not ctx.trace or not ctx.trace_queries:
        return None
    secs = [s for name, s in ctx.trace["ops"]
            if name.lstrip("%").startswith("sort")]
    if not secs:
        return None
    return sum(secs) * 1000.0 / ctx.trace_queries
