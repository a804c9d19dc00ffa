"""A fault for ``test_wildcard_cell.py`` to find: a filter that
matches a stored name loses the last value it matched, so a pattern's
selection is one host short. Planted where such a filter becomes its
tagv ids whichever way it goes (``FilterEvaluator.stored_name_ids``:
the plan index's name table since PR 41, the walk of a key's names
before and where there is no table). Loaded into the TSD through
``tsd.rpc.plugin`` beside the loader."""

from opentsdb_tpu.plugins import RpcPlugin


class DroppedHost(RpcPlugin):
    def initialize(self, tsdb) -> None:
        from opentsdb_tpu.query.filters import FilterEvaluator
        real = FilterEvaluator.stored_name_ids

        def short(self, filt, tags, kid):
            ids, negated, said = real(self, filt, tags, kid)
            return ids[:-1], negated, said

        FilterEvaluator.stored_name_ids = short
