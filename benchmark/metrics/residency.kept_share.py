"""Share of the HBM cache's look-ups that met a NEWER version of the
store than their entry's and kept the entry all the same
(``tsd.query.residency`` by ``outcome``: kept over kept + dropped,
PR 51): the store said that nothing written since the entry's version
lies inside the span of time the entry covers. 1.0 where every write
lands beyond what is resident (appends at the head of the store after
the window's end); 0 where every write drops what it finds. A program
without the counter (the parent of PR 51, where any newer version
drops the entry uncounted), or a window in which no look-up met a
newer version, gives None."""
import spanreaders


def read(ctx):
    kept = spanreaders.counter_delta(ctx, "tsd.query.residency",
                                     outcome="kept")
    dropped = spanreaders.counter_delta(ctx, "tsd.query.residency",
                                        outcome="dropped")
    if kept is None or dropped is None or kept + dropped <= 0:
        return None
    return kept / (kept + dropped)
