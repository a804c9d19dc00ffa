"""Faults for ``test_refresh_cell.py`` to find, each loaded into the
TSD through ``tsd.rpc.plugin`` beside the loader: the answer of
another window, however near, where the request's own was asked."""

from opentsdb_tpu.plugins import RpcPlugin


class RoundedWindow(RpcPlugin):
    """The window is rounded down to the minute before anything reads
    it: what a cache keyed by a window rounded to the cadence would
    serve. Every series whose second of the minute lies between the
    rounded edge and the real one gains a point at one end and loses
    one at the other."""

    def initialize(self, tsdb) -> None:
        from opentsdb_tpu.query.model import TSQuery
        real = TSQuery.validate

        def rounded(self, now_ms=None):
            out = real(self, now_ms)
            self.start_ms -= self.start_ms % 60_000
            self.end_ms -= self.end_ms % 60_000
            return out

        TSQuery.validate = rounded


class StaleWindow(RpcPlugin):
    """Every request is answered for the window the request before it
    asked (the first for its own): what a cache that finds "the last
    hour" resident and does not look at its ends would serve."""

    def initialize(self, tsdb) -> None:
        from opentsdb_tpu.query.model import TSQuery
        real = TSQuery.validate
        last: list = []

        def stale(self, now_ms=None):
            out = real(self, now_ms)
            mine = (self.start_ms, self.end_ms)
            if last:
                self.start_ms, self.end_ms = last[0]
            last[:] = [mine]
            return out

        TSQuery.validate = stale
