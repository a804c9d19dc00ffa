"""A fault for ``test_harness.py`` to find: the served path with one
group's answer altered where it is produced. Loaded into the TSD
through ``tsd.rpc.plugin`` beside the loader."""

from opentsdb_tpu.plugins import RpcPlugin


class AlteredAnswer(RpcPlugin):
    def initialize(self, tsdb) -> None:
        from opentsdb_tpu.ops import pipeline
        real = pipeline.execute_grid

        def wrong(*args, **kwargs):
            result, emit = real(*args, **kwargs)
            result = result.copy()
            result[0] *= 1.001
            return result, emit

        pipeline.execute_grid = wrong
