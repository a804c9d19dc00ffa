"""A fault for ``test_live_fleet_cell.py`` to find, loaded into the
TSD through ``tsd.rpc.plugin`` beside the loader: a server that keeps
what a write touched."""

from opentsdb_tpu.plugins import RpcPlugin


class KeepsWhatAWriteTouched(RpcPlugin):
    """The store answers "nothing was written" whatever was: what a
    write path that forgot to say where its points landed, or a cache
    that trusted an entry without asking, would serve. A column of
    the metric stays resident over a bucket that has gained a point,
    and so does the window's grid put together from it."""

    def initialize(self, tsdb) -> None:
        tsdb.store.oldest_written_since = lambda points_written: None
