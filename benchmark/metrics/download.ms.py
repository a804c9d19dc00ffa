"""``np.asarray`` of the ready results per sub-query
(``query.download``)."""
import spanreaders


def read(ctx):
    return spanreaders.per_execute_ms(ctx, "query.download")
