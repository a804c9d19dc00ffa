"""Device-resident grid cache: HBM as the tier/block cache.

The reference keeps hot HBase blocks in the region server's block
cache so repeated scans don't touch disk; the TPU-native analogue
keeps pre-bucketized ``[S, B]`` grids resident in device HBM so
queries over the same window don't re-scan the host store or
re-upload.

What an entry is, by its key's first item:

``metricgrid`` (``engine._resident_grid``, PR 43): a METRIC's whole
padded grid and presence mask for one (store, metric, plan-index
version = the metric's series count, ``start_ms``, ``end_ms``, first
bucket, interval, buckets, downsample function): scalars only, no
digest. A request's filter is not in the key: it goes up as one int32
group label a resident row, excluded rows on the dummy trailing group
that padded rows already have (``ops/shapes.pad_group_ids``), so no
program changes and every panel of a dashboard, every rule of an
evaluator's pass and both sub-queries of a ``sum`` + ``max`` request
read the entry the first one built (one build at a time, under
``TSDB._resident_grid_lock``). Its meta holds each row's point count
of the window (the store's ``count_range``), so the limits' check and
the scan's stat points stay the selection's. Taken for a device-placed
tail over a selection the plan index planned, no mesh, not
``aggregator=none``, not ``delete``, the metric's grid within the cell
budget and this cache's bytes, and a selection of at least half of the
metric's rows (``engine.RESIDENT_GRID_MIN_SHARE``): the tail program
costs the METRIC's rows over a resident grid and the scan, digest and
upload it replaces cost the SELECTION's, so a tenth of a metric is
cheaper scanned, and from a half up the two padded shapes are within
one doubling and the resident grid wins. What it does not give is a
window that moves: the window is in the key, so ``end=now`` traffic
builds anew when it changes (time-blocked columns are the next step).

``grid`` (``engine._grid_pipeline``): the grid of one request's own
rows, keyed by a digest of its series ids: every other device-placed
grid request, and the mesh twin's pre-sharded operands. ``avgdiv``
(the rollup average's divided grid), ``prep`` (the point path's
prepared batch) and ``hist`` (a histogram metric's window of counts,
``histogram_engine``) keep their own keys.

Every entry is stamped with the store's mutation version,
``(points_written, mutation_epoch)`` read BEFORE the store was (every
write, delete or lifecycle sweep bumps it), and dropped by the first
look-up that reads another: a hit holds the cells a fresh scan would
write. Bounded LRU by device bytes (``tsd.query.device_cache_mb``); an
entry larger than the whole cache is never kept, and with the key at 0
nothing is resident and every request scans.
"""

from __future__ import annotations

import hashlib
import threading
from collections import OrderedDict
from typing import Any


def array_digest(arr) -> bytes:
    """Content fingerprint of an index array (sids, group_ids)."""
    return hashlib.blake2b(memoryview(arr), digest_size=16).digest()


class DeviceGridCache:
    """LRU of device arrays keyed by (reduction params, store version).

    Also reused (with ``stat_prefix``) as the host-RAM prepared-batch
    cache for host-tail queries — same keying/invalidations, separate
    byte pool."""

    def __init__(self, max_bytes: int, stat_prefix: str =
                 "query.devicecache"):
        self.stat_prefix = stat_prefix
        self.max_bytes = max_bytes
        self._lock = threading.Lock()
        # key -> (version, arrays: tuple, meta: dict, nbytes: int)
        self._entries: OrderedDict[Any, tuple] = OrderedDict()
        self._bytes = 0
        self.hits = 0
        self.misses = 0

    def get(self, key, version):
        """(arrays, meta) on hit with a matching version, else None."""
        with self._lock:
            entry = self._entries.get(key)
            if entry is None or entry[0] != version:
                if entry is not None:  # stale: the store changed
                    self._bytes -= entry[3]
                    del self._entries[key]
                self.misses += 1
                return None
            self._entries.move_to_end(key)
            self.hits += 1
            return entry[1], entry[2]

    @staticmethod
    def _entry_nbytes(a) -> int:
        if a is None:
            return 0
        inner = getattr(a, "arrays", None)  # PreparedBatch
        if inner is not None:
            return sum(getattr(x, "nbytes", 0) for x in inner)
        return getattr(a, "nbytes", 0)

    def put(self, key, version, arrays: tuple, meta: dict) -> None:
        nbytes = sum(self._entry_nbytes(a) for a in arrays)
        if nbytes > self.max_bytes:
            return  # larger than the whole cache: don't thrash
        with self._lock:
            old = self._entries.pop(key, None)
            if old is not None:
                self._bytes -= old[3]
            self._entries[key] = (version, arrays, meta, nbytes)
            self._bytes += nbytes
            while self._bytes > self.max_bytes and self._entries:
                _, (_, _, _, nb) = self._entries.popitem(last=False)
                self._bytes -= nb

    def bytes_of(self, kind) -> int:
        """Bytes of the entries whose key begins with ``kind``."""
        with self._lock:
            return sum(e[3] for k, e in self._entries.items()
                       if k[0] == kind)

    def clear(self) -> None:
        with self._lock:
            self._entries.clear()
            self._bytes = 0

    def placement(self) -> dict[str, Any]:
        """Where the resident arrays really sit: bytes per device over
        every cached entry's shards. A mesh that claims four devices
        while its grids sit on the first shows up here."""
        by_device: dict[str, int] = {}
        with self._lock:
            entries = [e[1] for e in self._entries.values()]
        for arrays in entries:
            for a in arrays:
                for x in getattr(a, "arrays", None) or (a,):
                    for shard in getattr(x, "addressable_shards", ()):
                        name = str(shard.device)
                        by_device[name] = by_device.get(name, 0) \
                            + shard.data.nbytes
        return {"entries": len(entries), "bytes_by_device": by_device}

    def collect_stats(self, collector) -> None:
        collector.record(f"{self.stat_prefix}.bytes", self._bytes)
        collector.record(f"{self.stat_prefix}.entries",
                         len(self._entries))
        collector.record(f"{self.stat_prefix}.hits", self.hits)
        collector.record(f"{self.stat_prefix}.misses", self.misses)
