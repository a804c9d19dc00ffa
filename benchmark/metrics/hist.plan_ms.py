"""The ``query.plan`` stage of a histogram request, a sub-query: the
histogram store's plan index (filters, group labels). A program whose
histogram engine opens no span (the parent of PR 42) gives None."""
import spanreaders


def read(ctx):
    return spanreaders.per_execute_ms(ctx, "query.plan")
