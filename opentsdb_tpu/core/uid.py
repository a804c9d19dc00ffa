"""UID service: bidirectional name <-> fixed-width-UID dictionary.

(ref: ``src/uid/UniqueId.java``) The reference stores the mapping in the
``tsdb-uid`` HBase table and allocates ids with an atomic increment on
MAXID_ROW followed by two CAS writes (UniqueId.java:596-625). The TPU
build keeps the same semantics — monotonically increasing ids per kind,
width-limited, assignment-is-idempotent, pending-assignment dedupe
(UniqueId.java:117) — on top of a process-local store guarded by a lock.
Horizontal scale-out of assignment moves to the storage backend the same
way the reference delegates to HBase atomics.

Also supports random UID assignment for metrics
(ref: ``src/uid/RandomUniqueId.java``) and UID-filter plugins
(ref: ``src/uid/UniqueIdFilterPlugin.java``).
"""

from __future__ import annotations

import random
import threading
from typing import Callable, Iterable

from opentsdb_tpu.core import const

UID_KINDS = ("metric", "tagk", "tagv")


class NoSuchUniqueName(LookupError):
    """Name has no assigned UID (ref: src/uid/NoSuchUniqueName.java)."""

    def __init__(self, kind: str, name: str):
        super().__init__(f"No such name for '{kind}': '{name}'")
        self.kind = kind
        self.name = name


class NoSuchUniqueId(LookupError):
    """UID has no assigned name (ref: src/uid/NoSuchUniqueId.java)."""

    def __init__(self, kind: str, uid: bytes):
        super().__init__(f"No such unique ID for '{kind}': {uid.hex()}")
        self.kind = kind
        self.uid = uid


class FailedToAssignUniqueIdError(RuntimeError):
    """Assignment rejected (filter veto or id space exhausted)
    (ref: src/uid/FailedToAssignUniqueIdException.java)."""


class UniqueId:
    """One UID dictionary for one kind ('metric' | 'tagk' | 'tagv').

    ids are exposed both as ints (used by the array compute path, where a
    series' group-by key is its tagv id) and as big-endian fixed-width
    bytes (the storage codec form). id 0 is never assigned (matches the
    reference, where 0 is reserved).
    """

    def __init__(self, kind: str, width: int = 3,
                 random_ids: bool = False,
                 filter_fn: Callable[[str, str], bool] | None = None):
        if kind not in UID_KINDS:
            raise ValueError(f"unknown UID kind {kind!r}")
        if not 1 <= width <= 8:
            raise ValueError(f"invalid UID width {width}")
        self.kind = kind
        self.width = width
        self.random_ids = random_ids
        self.max_possible_id = (1 << (8 * width)) - 1
        self._filter = filter_fn
        self._lock = threading.Lock()
        self._name_to_id: dict[str, int] = {}
        self._id_to_name: dict[int, str] = {}
        self._sorted_names: list[str] | None = None  # suggest index
        self._max_id = 0
        # bumped, under the lock, by every change of what an ASSIGNED
        # id is called (rename, delete, load): what versions a cache of
        # names by id. An assignment leaves it: the new id is in no
        # series yet
        self._generation = 0
        self._rng = random.Random(0xC0FFEE)
        # cache-statistics parity with UniqueId.java:105-114
        self.cache_hits = 0
        self.cache_misses = 0
        self.random_id_collisions = 0

    # -- lookups ----------------------------------------------------------

    def get_id(self, name: str) -> int:
        with self._lock:
            uid = self._name_to_id.get(name)
        if uid is None:
            self.cache_misses += 1
            raise NoSuchUniqueName(self.kind, name)
        self.cache_hits += 1
        return uid

    def get_name(self, uid: int | bytes) -> str:
        iid = self.uid_to_int(uid) if isinstance(uid, bytes) else uid
        with self._lock:
            name = self._id_to_name.get(iid)
        if name is None:
            raise NoSuchUniqueId(self.kind, self.int_to_uid(iid))
        return name

    def has_name(self, name: str) -> bool:
        with self._lock:
            return name in self._name_to_id

    @property
    def generation(self) -> int:
        """Differs from an earlier reading once an id assigned before
        that reading has changed its name or lost it. Names read by id
        AFTER a reading are good for as long as it repeats."""
        return self._generation

    # -- assignment (ref: UniqueId.java:596-625, :865) --------------------

    def get_or_create_id(self, name: str) -> int:
        with self._lock:
            uid = self._name_to_id.get(name)
            if uid is not None:
                return uid
            return self._assign_locked(name)

    def assign_id(self, name: str) -> int:
        """Explicit assignment (``tsdb mkmetric`` / ``/api/uid/assign``).

        Fails if the name already has a UID (matches UidManager semantics).
        """
        with self._lock:
            if name in self._name_to_id:
                raise FailedToAssignUniqueIdError(
                    f"Name already exists with UID: "
                    f"{self.int_to_uid(self._name_to_id[name]).hex()}")
            return self._assign_locked(name)

    def _assign_locked(self, name: str) -> int:
        if self._filter is not None and not self._filter(self.kind, name):
            raise FailedToAssignUniqueIdError(
                f"UID filter rejected assignment of {self.kind} '{name}'")
        if self.random_ids:
            # ref: RandomUniqueId.java — random id, retry on collision
            for _ in range(10):
                cand = self._rng.randint(1, self.max_possible_id)
                if cand not in self._id_to_name:
                    uid = cand
                    break
                self.random_id_collisions += 1
            else:
                raise FailedToAssignUniqueIdError(
                    f"could not find a free random UID for '{name}'")
        else:
            if self._max_id >= self.max_possible_id:
                raise FailedToAssignUniqueIdError(
                    f"all {self.max_possible_id} UIDs of kind "
                    f"{self.kind} are assigned")
            self._max_id += 1
            uid = self._max_id
        self._name_to_id[name] = uid
        self._id_to_name[uid] = name
        self._sorted_names = None
        return uid

    def rename(self, old_name: str, new_name: str) -> None:
        """(ref: UniqueId.java rename)"""
        with self._lock:
            if old_name not in self._name_to_id:
                raise NoSuchUniqueName(self.kind, old_name)
            if new_name in self._name_to_id:
                raise FailedToAssignUniqueIdError(
                    f"cannot rename to existing name '{new_name}'")
            uid = self._name_to_id.pop(old_name)
            self._name_to_id[new_name] = uid
            self._id_to_name[uid] = new_name
            self._sorted_names = None
            self._generation += 1

    def delete(self, name: str) -> None:
        """(ref: UniqueId.java deleteAsync, 2.2+)"""
        with self._lock:
            if name not in self._name_to_id:
                raise NoSuchUniqueName(self.kind, name)
            uid = self._name_to_id.pop(name)
            self._id_to_name.pop(uid, None)
            self._sorted_names = None
            self._generation += 1

    def load(self, names: dict[str, int], max_id: int) -> None:
        """Replace the whole dictionary (a snapshot's, at start-up)."""
        forward = {name: int(uid) for name, uid in names.items()}
        with self._lock:
            self._name_to_id = forward
            self._id_to_name = {uid: name
                                for name, uid in forward.items()}
            self._max_id = int(max_id)
            self._sorted_names = None
            self._generation += 1

    # -- suggest (ref: UniqueId.java suggest / TSDB.java:1762-1816) -------

    def suggest(self, search: str, max_results: int = 25) -> list[str]:
        """Prefix seek over a cached sorted index — the analogue of the
        reference's scanner with a start row on the sorted name CF
        (sorting all names per keystroke is O(N log N) at 1M+ UIDs)."""
        import bisect
        with self._lock:
            names = self._sorted_names
            if names is None:
                names = self._sorted_names = sorted(self._name_to_id)
            lo = bisect.bisect_left(names, search)
            out = []
            for n in names[lo:lo + max_results]:
                if not n.startswith(search):
                    break
                out.append(n)
        return out

    def grep(self, regex: str) -> list[str]:
        import re
        pat = re.compile(regex)
        with self._lock:
            return sorted(n for n in self._name_to_id if pat.search(n))

    # -- codecs -----------------------------------------------------------

    def int_to_uid(self, uid: int) -> bytes:
        return uid.to_bytes(self.width, "big")

    def uid_to_int(self, uid: bytes) -> int:
        if len(uid) != self.width:
            raise ValueError(
                f"wrong UID length {len(uid)}, expected {self.width}")
        return int.from_bytes(uid, "big")

    # -- introspection ----------------------------------------------------

    def __len__(self) -> int:
        with self._lock:
            return len(self._name_to_id)

    def names(self) -> list[str]:
        with self._lock:
            return list(self._name_to_id)

    def items(self) -> list[tuple[str, int]]:
        with self._lock:
            return list(self._name_to_id.items())

    def max_id(self) -> int:
        with self._lock:
            return self._max_id

    def collect_stats(self, collector) -> None:
        collector.record("uid.cache-hit", self.cache_hits, kind=self.kind)
        collector.record("uid.cache-miss", self.cache_misses, kind=self.kind)
        # (ref: UniqueId.java random_id_collisions stat — bumped here
        # since the random-metric path landed but never exported until
        # tsdlint's counter-export pass flagged it)
        collector.record("uid.random-id-collisions",
                         self.random_id_collisions, kind=self.kind)
        collector.record("uid.cache-size", len(self), kind=self.kind)
        collector.record("uid.ids-used", self.max_id(), kind=self.kind)
        collector.record("uid.ids-available",
                         self.max_possible_id - self.max_id(), kind=self.kind)


class UidRegistry:
    """The three UID dictionaries owned by a TSDB (ref: TSDB.java:125-129)."""

    def __init__(self, metric_width: int = const.METRICS_WIDTH,
                 tagk_width: int = const.TAG_NAME_WIDTH,
                 tagv_width: int = const.TAG_VALUE_WIDTH,
                 random_metrics: bool = False):
        self.metrics = UniqueId("metric", metric_width,
                                random_ids=random_metrics)
        self.tag_names = UniqueId("tagk", tagk_width)
        self.tag_values = UniqueId("tagv", tagv_width)

    def by_kind(self, kind: str) -> UniqueId:
        if kind in ("metric", "metrics"):
            return self.metrics
        if kind == "tagk":
            return self.tag_names
        if kind == "tagv":
            return self.tag_values
        raise ValueError(f"unknown UID kind {kind!r}")

    def tsuid(self, metric_id: int, tags: Iterable[tuple[int, int]]) -> bytes:
        """TSUID bytes = metric uid + (tagk uid + tagv uid) sorted by tagk."""
        out = bytearray(self.metrics.int_to_uid(metric_id))
        for tagk_id, tagv_id in sorted(tags):
            out += self.tag_names.int_to_uid(tagk_id)
            out += self.tag_values.int_to_uid(tagv_id)
        return bytes(out)
