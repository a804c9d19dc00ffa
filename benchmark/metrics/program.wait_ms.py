"""Dispatch to ready per sub-query on the host clock
(``query.program``): the launch, the transfer still in flight and the
kernels; beside the device trace's ``program.busy_ms_per_query``."""
import spanreaders


def read(ctx):
    return spanreaders.per_execute_ms(ctx, "query.program")
