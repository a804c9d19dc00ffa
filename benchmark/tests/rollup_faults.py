"""Faults for ``test_rollup_cell.py`` to find, each loaded into the
TSD through ``tsd.rpc.plugin`` beside the loader: an altered answer,
SUM cells served undivided, SUM over the number of cells (COUNT
ignored), the answer of another pair of racks."""

from opentsdb_tpu.plugins import RpcPlugin


class AlteredAnswer(RpcPlugin):
    """One group's row a thousandth off where it is produced."""

    def initialize(self, tsdb) -> None:
        from opentsdb_tpu.query import engine
        real = engine.execute_avg_divide

        def wrong(*args, **kwargs):
            result, emit = real(*args, **kwargs)
            result = result.copy()
            result[0] *= 1.001
            return result, emit

        engine.execute_avg_divide = wrong


class Undivided(RpcPlugin):
    """The SUM tier's cells go on as if they were the averages."""

    def initialize(self, tsdb) -> None:
        from opentsdb_tpu.ops import pipeline
        real = pipeline.avg_divide_grid

        def undivided(grid_sum, grid_cnt, xp=None):
            _grid, valid = real(grid_sum, grid_cnt, **(
                {} if xp is None else {"xp": xp}))
            return grid_sum, valid

        pipeline.avg_divide_grid = undivided


class CellsNotCounts(RpcPlugin):
    """A bucket's SUM cells over how many cells there were, the COUNT
    tier's values ignored: the mean of the hours' sums."""

    def initialize(self, tsdb) -> None:
        from opentsdb_tpu.query.engine import QueryEngine
        real = QueryEngine._reduce_to_grid
        counts = {id(store) for (_iv, agg), store
                  in tsdb.rollup_store._tiers.items() if agg == "count"}

        def cells(self, store, sids, tsq, bucket_ts, interval_ms, stat,
                  scanned):
            if id(store) in counts:
                stat = "count"
            return real(self, store, sids, tsq, bucket_ts, interval_ms,
                        stat, scanned)

        QueryEngine._reduce_to_grid = cells


class OtherRacks(RpcPlugin):
    """Every request is answered for the racks next to those it left
    out: what a cache keyed by less than the selection would serve."""

    def initialize(self, tsdb) -> None:
        from opentsdb_tpu.query import filters
        real = filters.build_filter

        def shifted(f):
            if isinstance(f, dict) and f.get("tagk") == "rack" \
                    and f.get("type") == "not_literal_or":
                f = dict(f, filter="|".join(
                    f"r{(int(v[1:]) + 1) % 2000:04d}"
                    for v in f["filter"].split("|")))
            return real(f)

        filters.build_filter = shifted
