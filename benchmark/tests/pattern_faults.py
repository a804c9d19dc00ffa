"""A fault for ``test_wildcard_cell.py`` to find: the walk of a key's
names loses the last value it matched, so a pattern's selection is one
host short. Loaded into the TSD through ``tsd.rpc.plugin`` beside the
loader."""

from opentsdb_tpu.plugins import RpcPlugin


class DroppedHost(RpcPlugin):
    def initialize(self, tsdb) -> None:
        from opentsdb_tpu.query.filters import FilterEvaluator
        real = FilterEvaluator.matching_tagv_ids

        def short(self, filt, candidate_ids):
            return real(self, filt, candidate_ids)[:-1]

        FilterEvaluator.matching_tagv_ids = short
