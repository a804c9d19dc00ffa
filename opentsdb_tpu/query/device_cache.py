"""What is resident in HBM: under which key, valid until when, built
by whom.

The reference keeps hot HBase blocks in the region server's block
cache so repeated scans don't touch disk; here a window's operands
stay in device HBM so that queries over it neither scan the host store
nor upload again. :meth:`DeviceGridCache.resident` is the one door:
every kind of entry is looked up, built, kept and dropped by the same
four rules.

**The version rule.** An entry is stamped with the version of what it
was built from, read BEFORE the build read it, and dropped by the
first look-up that reads another: a write during the build leaves an
entry the next request will not trust, and a hit holds what a fresh
build would. ``resident`` calls ``version_of()`` and then ``build()``,
so the order is the order of two calls in one function and no caller
can get it wrong. For a store the version is :func:`store_version`,
``(points_written, mutation_epoch)`` of each store read (every write,
delete and lifecycle sweep bumps one of them); the histogram arenas
have ``TSDB._histogram_version``. **Unless what was written since
did not touch what the entry covers:** an entry may be kept with the
span of time it covers of ONE store, the first its version reads
(``covers=(lo_ms, hi_ms)``, both inclusive; absent: everything). A
look-up that reads a newer version keeps such an entry iff nothing
but that store's ``points_written`` moved (its epoch stands: nothing
but appends happened) and the store's word, ``oldest_written_since(the
entry's points_written)``, is None or greater than ``hi_ms``; the entry is
then re-stamped with the version just read, so the store's bounded
log of recent writes need only reach back to an entry's last use.
The store pushes an append into that log in the critical section
that bumps ``points_written``, after the points became readable: a
reader that read the counter before the bump gets the write as
"since", whether or not its scan saw the points; one that read it
after has seen them. Only the OLDEST timestamp is known, so a
backfill behind an entry (older than ``lo_ms``) drops it needlessly:
conservative, and exact. A store without the method (the cold store,
the stitched and rollup wrappers, the histogram arenas), a log that
no longer reaches back (``ALL``), an epoch that moved, an entry
without a span: any write drops it, as before.

**The flight.** One build a key at a time: the first caller builds,
callers of the same key wait for it and hit, callers of another key
do not wait. A build that raises keeps nothing and lets the waiters
through, and the next of them builds. An entry that is there is read
under the cache's one lock, without a flight.

**The turn.** The builds of a kind in :data:`SERIAL_BUILD_KINDS` run
one at a time whatever their keys, with or without a cache: what
bounds HBM's peak where one layout is a large share of the chip. The
window is in a key, so ``end=now`` requests have a key each and no
flight makes one wait for another; eight query workers laying out
3.8 GB each (``hist`` at 12M points) would not fit a 16 GB chip,
while one after another they peak at two entries, the one the LRU is
about to drop and the one being built.

**The bytes.** LRU by the bytes of the arrays
(``tsd.query.device_cache_mb``). ``build`` returns ``(arrays, meta)``;
``arrays`` None says there is nothing to keep (an empty window), an
entry larger than the whole cache is answered and not kept, and with
no cache at all (the key at 0, or a tail placed on the host, which
must neither evict HBM's entries nor count as device bytes) the
module's :func:`resident` just builds.

**The kinds**, by the first item of the key:

``metricgrid`` (``QueryEngine._resident_grid``): a METRIC's whole
padded ``[series x bucket]`` grid and presence mask of one (store,
metric, plan-index version = the metric's series count, window, first
bucket, interval, buckets, downsample function): scalars only, no
digest. A request's filter is not in the key: it goes up as one int32
group label a resident row, excluded rows on the dummy trailing group
that padded rows already have (``ops/shapes.pad_group_ids``), so every
panel of a dashboard, every rule of an evaluator's pass and both
sub-queries of a ``sum`` + ``max`` request read the entry the first
one built. Its meta holds each row's point count of the window, so the
limits' check and the scan's stat points stay the selection's. Taken
for a device-placed tail over a selection the plan index planned, no
mesh, not ``aggregator=none``, not ``delete``, the grid within the
cell budget and this cache's bytes, and at least half of the metric's
rows selected (``engine.RESIDENT_GRID_MIN_SHARE``: the tail costs the
METRIC's rows, the scan, digest and upload it replaces the
SELECTION's; the measured crossover is in PERF.md section 5).

The kind has two levels, so that a window asked again and a window
that moves both find what they can reuse. The first look-up is the
window's key above, and a hit is the whole story: one look-up, the
resident grid, the grid program. Only its miss goes to the second
level, ``metriccol`` (``QueryEngine._metric_columns``): ONE bucket of
the metric, the statistic of each padded row and its presence mask as
two ``[series]`` vectors, under (store, metric, plan-index version,
interval, downsample function, the bucket's start in ms) and NO
window. Buckets are aligned to the epoch, so a bucket that lies whole
inside a window holds the same cells in every window that holds it
whole: ``end=now`` traffic finds all of its buckets but the two its
window cuts. Those are computed from the points inside ``[start,
end]`` by the request itself and never kept, by the same storage pass
(``bucket_columns``, one walk a request) that builds whatever whole
bucket was not there and counts every row's points of the window. The
columns of one (metric, interval, function) are looked up and built
under ONE flight (:meth:`DeviceGridCache.resident_columns`), not a
flight a column: requests whose windows share some columns cannot each
hold a few and wait for the other's; with every column there, nobody
waits. The version is read once, before any column is looked up or
the store is scanned, and stamps every column built; each column
counts as a hit or a miss of this cache like any entry. The window's
own entry is first the columns themselves (``ops.pipeline
.GridColumns``: the resident ones and the two cut ones, so the second
sub-query of a request waits for the first's build and hits, as
ever); the program that runs over them assembles the padded grid in
the same module as the tail and hands it back, and that grid takes
the columns' place under the window's key (:meth:`DeviceGridCache
.replace`: no bytes move). A store without the pass, or a window of
more than ``engine.RESIDENT_COLUMNS_MAX_BUCKETS`` buckets (a column
is an operand and an entry each), builds the window whole in one
row-major pass, as before the second level. **What a write drops:**
what it touched. Both levels pass their span (the window ``[start_ms,
end_ms]``; the bucket ``[start, start + interval_ms - 1]``), so an
append at the head of the store, after the window's end, leaves the
window's grid and every column resident, and the request behind it
is the hit it would have been; an append inside a bucket drops the
window's grid, that bucket's column and (the oldest timestamp being
all the store keeps) the columns after it, and the next request
builds those in one pass over the older ones that stayed; a delete,
a lifecycle sweep and a repair (``mutation_epoch``) drop everything
of the store. The look-up spans say which (``stale`` = kept, dropped
or none), and the cache counts them (``tsd.query.residency``).

``grid`` (``QueryEngine._grid_pipeline``): the grid of one request's
own rows, keyed by a digest of its series ids: every other
device-placed grid request. Under a mesh the same key (the mesh in it)
holds the pre-sharded operands. This kind and the three below pass no
span: any write to what they were built from drops them.

``avgdiv`` (``QueryEngine._avg_rollup_pipeline``): the sum and count
grids a rollup average divides, versioned by both tiers' stores.

``hist`` (``histogram_engine``): a histogram metric's window of counts
(``ResidentCounts``), by (metric, window), versioned by
``TSDB._histogram_version``. Its builds take turns.

``prep`` (``QueryEngine._run_sub``): the point path's prepared batch,
in this cache or, for a host-placed tail, in its host-RAM twin
(``tsd.query.host_cache_mb``, ``stat_prefix`` ``query.hostcache``: the
same class, a pool of its own). The one kind that does not come
through ``resident``: its look-up reads two pools, an open breaker
skips the device's, a hit that fails on the device falls back to the
cold path, and its put happens inside the dispatch the breaker guards,
so it calls :meth:`~DeviceGridCache.get` and
:meth:`~DeviceGridCache.put`, the two halves ``resident`` is made of,
with :func:`store_version` read before its scan.
"""

from __future__ import annotations

import contextlib
import hashlib
import threading
from collections import OrderedDict
from typing import Any

from opentsdb_tpu.core.store import ALL


#: what :func:`resident` says of the arrays it returns: they were
#: there, this call built and kept them, or it built them and nothing
#: is kept (no cache, nothing to keep, or larger than the cache)
HIT, BUILT, NOT_KEPT = "hit", "built", "not_kept"


def array_digest(arr) -> bytes:
    """Content fingerprint of an index array (sids, group_ids)."""
    return hashlib.blake2b(memoryview(arr), digest_size=16).digest()


def store_version(*stores) -> tuple:
    """The version of what a build reads from ``stores``: each one's
    ``(points_written, mutation_epoch)``, in order."""
    return tuple(v for store in stores
                 for v in (store.points_written,
                           getattr(store, "mutation_epoch", 0)))


class Lookup:
    """One request's account of the entries it found under an OLDER
    version: how many it kept and dropped (:attr:`stale`, the look-up
    span's tag), and, given the ONE ``store`` whose span of time the
    entries cover, that store's word on what was written since a
    version (``oldest_written_since``, asked once a version however
    many entries carry it; a store without the method, or no store,
    answers ``ALL``: today's whole-store rule). The store is asked
    inside a look-up, so after that look-up's version was read: an
    answer remembered here is never older than the version an entry
    is re-stamped with, and no write falls between the two (where
    ``resident`` reads the version again under the flight, what is
    remembered of the first look-up can only be an answer that
    dropped the entry). One request, one of these."""

    __slots__ = ("store", "kept", "dropped", "_since")

    def __init__(self, store=None):
        self.store = store
        self.kept = self.dropped = 0
        self._since: dict = {}

    def written_since(self, points_written: int):
        if points_written not in self._since:
            ask = getattr(self.store, "oldest_written_since", None)
            self._since[points_written] = ALL if ask is None \
                else ask(points_written)
        return self._since[points_written]

    @property
    def stale(self) -> str:
        return "dropped" if self.dropped else \
            "kept" if self.kept else "none"


#: the kinds whose builds take turns (the module's docstring, "The
#: turn"): one lock a process, since the chip is one a process
SERIAL_BUILD_KINDS = frozenset({"hist"})
_SERIAL_BUILD = threading.Lock()


def _build_turn(key):
    """What a build of ``key`` holds while it runs."""
    if key is not None and key[0] in SERIAL_BUILD_KINDS:
        return _SERIAL_BUILD
    return contextlib.nullcontext()


def resident(cache: "DeviceGridCache | None", key, version_of, build,
             covers=None, lookup: Lookup | None = None):
    """:meth:`DeviceGridCache.resident` of ``cache``; with no cache,
    what ``build`` makes (in its turn, where ``key``'s kind takes
    turns), and nothing kept."""
    if cache is None:
        with _build_turn(key):
            return (*build(), NOT_KEPT)
    return cache.resident(key, version_of, build, covers, lookup)


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
        # key -> (version, arrays: tuple, meta: dict, nbytes: int,
        # covers: (lo_ms, hi_ms) | None)
        self._entries: OrderedDict[Any, tuple] = OrderedDict()
        # key -> [its flight's lock, the calls inside resident() for
        # it]: an entry a key somebody is looking up or building now
        self._flights: dict[Any, list] = {}
        self._bytes = 0
        self.hits = 0
        self.misses = 0
        # look-ups that met a newer version than their entry's, by
        # what became of the entry, and the bytes of the dropped ones
        self.stale_kept = 0
        self.stale_dropped = 0
        self.stale_dropped_bytes = 0

    def resident(self, key, version_of, build, covers=None,
                 lookup: Lookup | None = None):
        """``(arrays, meta, how)``: the entry under ``key`` if it is of
        the version ``version_of()`` reads now (:data:`HIT`), else
        what ``build()`` makes, ``(arrays: tuple | None, meta)``, kept
        under that version (:data:`BUILT`) unless ``arrays`` is None
        or larger than the whole cache (:data:`NOT_KEPT`).

        ``version_of`` is called before ``build``, both under the
        key's flight (and the kind's turn, where it takes turns):
        whoever else asks for ``key`` meanwhile waits, then reads the
        version for itself and hits. A ``build`` that raises keeps
        nothing and the waiters go on. An entry that is there costs a
        hit what it always did, one lock and no flight.

        ``covers``: the span of time ``(lo_ms, hi_ms)``, both
        inclusive, that what ``build`` makes covers of the ONE store
        ``version_of`` reads (``lookup.store``), kept with the entry;
        absent, everything. ``lookup`` takes the tally of what an
        older version's entry came to, and gives the store's word
        ("The version rule")."""
        hit = self._hit(key, version_of(), lookup)
        if hit is not None:
            return (*hit, HIT)
        with self._flight(key), _build_turn(key):
            version = version_of()
            hit = self.get(key, version, lookup)
            if hit is not None:
                return (*hit, HIT)
            arrays, meta = build()
            kept = arrays is not None \
                and self.put(key, version, arrays, meta, covers)
            return arrays, meta, BUILT if kept else NOT_KEPT

    def resident_columns(self, flight_key, keys, version, build,
                         covers=None, lookup: Lookup | None = None):
        """``(columns, rest)``: the arrays under each of ``keys`` (one
        (metric, interval, function)'s per-bucket columns), every one
        of ``version``, and what ``build`` made beside them.

        ``build(missing) -> (built, rest)`` makes the arrays of the
        keys at the indexes ``missing`` (what is not there, or is of
        another version) in ONE pass, with whatever else the caller
        wants of that pass (``rest``: a moving window's cut buckets
        and counts); each is kept under its key. All of it happens
        under the ONE flight ``flight_key``, not a flight a column:
        two requests whose windows share some columns cannot each
        hold a few and wait for the other's. With every key there,
        nobody waits: ``build(())`` runs outside the flight. Each key
        counts as a hit or a miss, like any entry's. ``covers`` has
        the span of time each key's column covers, ``lookup`` as in
        :meth:`resident`."""
        def arrays_of(hit):
            return None if hit is None else hit[0]

        found = [arrays_of(self._hit(key, version, lookup))
                 for key in keys]
        if None in found:
            with self._flight(flight_key):
                missing = []
                for i, key in enumerate(keys):
                    if found[i] is None:
                        found[i] = arrays_of(
                            self.get(key, version, lookup))
                        if found[i] is None:
                            missing.append(i)
                if missing:
                    built, rest = build(tuple(missing))
                    for i, arrays in zip(missing, built):
                        self.put(keys[i], version, arrays, {},
                                 covers[i] if covers else None)
                        found[i] = arrays
                    return found, rest
        # every column was there, or another request built the missing
        # ones while this one waited: nothing to build but the rest
        return found, build(())[1]

    @contextlib.contextmanager
    def _flight(self, key):
        """Hold ``key``'s flight: one holder at a time, the others
        wait; the flight is gone when nobody is inside or waiting."""
        with self._lock:
            flight = self._flights.get(key)
            if flight is None:
                flight = self._flights[key] = [threading.Lock(), 0]
            flight[1] += 1
        try:
            with flight[0]:
                yield
        finally:
            with self._lock:
                flight[1] -= 1
                if not flight[1]:
                    del self._flights[key]

    def _good_locked(self, key, entry, version,
                     lookup: Lookup | None) -> bool:
        """Whether ``entry`` holds what a build at ``version`` (read
        just now) would: it is of that version, or ("The version
        rule") it covers a span of time of ``lookup.store``, the
        first store of the version, nothing but appends to that store
        happened since the entry's version (every other item of the
        version stands, its epoch among them), and the oldest of them
        lies after the span's end. Such an entry is re-stamped with
        ``version`` and counted as kept."""
        if entry[0] == version:
            return True
        covers = entry[4]
        if covers is None or lookup is None \
                or entry[0][1:] != version[1:]:
            return False
        oldest = lookup.written_since(entry[0][0])
        if oldest is not None and not oldest > covers[1]:
            return False
        self._entries[key] = (version, *entry[1:])
        self.stale_kept += 1
        lookup.kept += 1
        return True

    def _hit(self, key, version, lookup: Lookup | None = None):
        """(arrays, meta) of a matching entry, counted as a hit; else
        None, and nothing counted or dropped (:meth:`get` does that,
        once the caller holds the key's flight)."""
        with self._lock:
            entry = self._entries.get(key)
            if entry is None or not self._good_locked(
                    key, entry, version, lookup):
                return None
            self._entries.move_to_end(key)
            self.hits += 1
            return entry[1], entry[2]

    def get(self, key, version, lookup: Lookup | None = None):
        """(arrays, meta) of an entry of ``version``, or of an older
        one that no write since has touched ("The version rule");
        else None, the stale entry dropped."""
        with self._lock:
            entry = self._entries.get(key)
            if entry is None or not self._good_locked(
                    key, entry, version, lookup):
                if entry is not None:  # stale: the store changed
                    self._bytes -= entry[3]
                    del self._entries[key]
                    self.stale_dropped += 1
                    self.stale_dropped_bytes += entry[3]
                    if lookup is not None:
                        lookup.dropped += 1
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

    def put(self, key, version, arrays: tuple, meta: dict,
            covers=None) -> bool:
        """Keep ``arrays`` under ``key``; False where they are larger
        than the whole cache (nothing kept: don't thrash)."""
        nbytes = sum(self._entry_nbytes(a) for a in arrays)
        if nbytes > self.max_bytes:
            return False
        with self._lock:
            self._put_locked(key, version, arrays, meta, nbytes, covers)
        return True

    def _put_locked(self, key, version, arrays, meta, nbytes,
                    covers) -> None:
        old = self._entries.pop(key, None)
        if old is not None:
            self._bytes -= old[3]
        self._entries[key] = (version, arrays, meta, nbytes, covers)
        self._bytes += nbytes
        while self._bytes > self.max_bytes and self._entries:
            _, evicted = self._entries.popitem(last=False)
            self._bytes -= evicted[3]

    def replace(self, key, old: tuple, arrays: tuple, meta: dict) -> bool:
        """Put ``arrays`` and ``meta`` where ``key`` holds ``old`` (the
        very tuple), under the version ``old`` was kept with: a form of
        the same operands that is cheaper to read. False, and nothing
        done, where the entry is gone or holds something else (a write
        dropped it, another request rebuilt it)."""
        nbytes = sum(self._entry_nbytes(a) for a in arrays)
        with self._lock:
            entry = self._entries.get(key)
            if entry is None or entry[1] is not old \
                    or nbytes > self.max_bytes:
                return False
            self._put_locked(key, entry[0], arrays, meta, nbytes,
                             entry[4])
        return True

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
        pool = self.stat_prefix.rsplit(".", 1)[-1]
        collector.record("query.residency", self.stale_kept,
                         outcome="kept", cache=pool)
        collector.record("query.residency", self.stale_dropped,
                         outcome="dropped", cache=pool)
        collector.record("query.residency.dropped_bytes",
                         self.stale_dropped_bytes, cache=pool)
