"""The plan's data formats: what the plan and assemble stages of a
query keep of one metric's tag index, and read it by.

:class:`TagMatrix` is the columnar tags of a selection of series,
:class:`PlanIndex` the cached per-(store, metric) structure a
selection is planned from (each key's distinct values and their names,
each group-by key set's labelling and its :class:`GroupLayout`), and
:func:`group_tag_summary` / :func:`_common_tags` the SpanGroup tag
rule read off them. :mod:`~opentsdb_tpu.query.filters` is written
against these formats; the scalar engine, the histogram engine, the
sketch path and the streaming plans all plan and assemble through
them. Nothing here knows an engine: this module imports none.
"""

from __future__ import annotations

import threading
from collections import OrderedDict
from typing import Sequence

import numpy as np

from opentsdb_tpu.query import filters as filters_mod


class TagMatrix:
    """Columnar per-series tags for one sub-query's selected series.

    ``vids[i, j]`` is the tagv id of tag key ``kids[j]`` on series i, or
    -1 when the series lacks that key. Every engine consumer of
    per-series tags (group keys, SpanGroup common-tag semantics,
    explicit_tags, tsuids) reads this matrix with array ops — the
    previous list-of-dicts walk cost ~0.4 s per 200k series and showed
    up directly in the north-star query budget.

    ``origin`` is ``(index, rows)`` on a matrix selected out of a
    cached :class:`PlanIndex`: its row i is row ``rows[i]`` of the
    index (``rows`` None: every row), so what the index keeps per
    series of the metric (group labels, the tag columns group by
    group) is read there instead of derived again. Such a matrix
    gathers its ``vids`` out of the index only when somebody reads
    them.
    """

    __slots__ = ("kids", "_vids", "origin")

    def __init__(self, kids: np.ndarray, vids: np.ndarray | None,
                 origin: "tuple[PlanIndex, np.ndarray | None] | None"
                 = None):
        self.kids = kids        # int64 [K] sorted distinct tagk ids
        self._vids = vids       # int64 [S, K]; -1 = key absent
        self.origin = origin

    @property
    def vids(self) -> np.ndarray:
        if self._vids is None:
            index, rows = self.origin
            self._vids = index.tags.vids[rows]
        return self._vids

    @classmethod
    def from_triples(cls, sids: np.ndarray, triples: np.ndarray,
                     kids: np.ndarray | None = None) -> "TagMatrix":
        """Build from the metric index's (sid, kid, vid) rows; triples
        for sids outside ``sids`` are ignored. ``kids`` optionally fixes
        the column space (for cross-store alignment)."""
        sids = np.asarray(sids, dtype=np.int64)
        if kids is None:
            kids = (np.unique(triples[:, 1]) if len(triples)
                    else np.empty(0, dtype=np.int64))
        vids = np.full((len(sids), len(kids)), -1, dtype=np.int64)
        if len(triples) and len(sids) and len(kids):
            order = np.argsort(sids, kind="stable")
            ssorted = sids[order]
            pos = np.searchsorted(ssorted, triples[:, 0])
            pos = np.minimum(pos, len(ssorted) - 1)
            keep = ssorted[pos] == triples[:, 0]
            kcol = np.searchsorted(kids, triples[:, 1])
            kcol_ok = np.minimum(kcol, len(kids) - 1)
            keep &= kids[kcol_ok] == triples[:, 1]
            rows = order[pos[keep]]
            vids[rows, kcol_ok[keep]] = triples[keep, 2]
        return cls(kids, vids)

    @classmethod
    def from_pairs(cls, tag_tuples: Sequence[Sequence[tuple[int, int]]]
                   ) -> "TagMatrix":
        """Build from per-series ((kid, vid), ...) tuples (small paths:
        tsuid queries, histogram series)."""
        rows = [(i, kid, vid) for i, tags in enumerate(tag_tuples)
                for kid, vid in tags]
        triples = (np.asarray(rows, dtype=np.int64).reshape(-1, 3)
                   if rows else np.empty((0, 3), dtype=np.int64))
        return cls.from_triples(np.arange(len(tag_tuples)), triples)

    @property
    def num_series(self) -> int:
        if self._vids is None:
            return len(self.origin[1])
        return self._vids.shape[0]

    def col(self, kid: int) -> np.ndarray | None:
        """[S] tagv ids for one key (-1 absent), or None if no series
        has the key at all."""
        j = int(np.searchsorted(self.kids, kid))
        if j < len(self.kids) and self.kids[j] == kid:
            return self.vids[:, j]
        return None

    def distinct(self, kid: int) -> np.ndarray:
        """Sorted distinct tagv ids present in one key's column."""
        col = self.col(kid)
        if col is None:
            return np.empty(0, dtype=np.int64)
        return np.unique(col[col >= 0])

    def name_table(self, kid: int, tagv, folded: bool
                   ) -> tuple[None, int, bool]:
        """A matrix of one request has nowhere to keep the names of
        its values (:meth:`PlanIndex.name_table` has): none, no name
        read, nothing built."""
        return None, 0, False

    def select(self, mask_or_idx) -> "TagMatrix":
        origin = self.origin
        if origin is not None:
            index, rows = origin
            if rows is None:
                rows = np.arange(index.num_series)
            origin = (index, rows[mask_or_idx])
        vids = self._vids
        return TagMatrix(self.kids,
                         None if vids is None else vids[mask_or_idx],
                         origin)

    def num_pairs(self) -> int:
        """Present (key, value) pairs over all rows."""
        if self.origin is not None:
            return self.origin[0].num_pairs(self.origin[1])
        return int((self.vids >= 0).sum())

    def tags_of(self, i: int) -> list[tuple[int, int]]:
        """Series i's present (kid, vid) pairs, kid-ascending."""
        row = self.vids[i]
        return [(int(k), int(v)) for k, v in zip(self.kids, row)
                if v >= 0]


def compact_row_labels(mat: np.ndarray) -> tuple[np.ndarray, int]:
    """``np.unique(mat, axis=0, return_inverse=True)`` equivalent via
    per-column factorization — the void-dtype row sort behind
    unique(axis=0) is ~10x slower at 1M rows. Labels preserve the
    lexicographic row order (the reference's ByteMap group-key order).
    """
    n_rows, n_cols = mat.shape
    if n_cols == 0 or n_rows == 0:
        return (np.zeros(n_rows, dtype=np.int32),
                1 if n_rows else 0)
    labels = None
    count = 1
    for j in range(n_cols):
        u, inv = np.unique(mat[:, j], return_inverse=True)
        if labels is None:
            labels, count = inv.astype(np.int64), len(u)
        else:
            # composite stays < count * len(u) <= n_rows^2: int64-safe,
            # re-compacted each step so it never grows further
            labels = labels * len(u) + inv
            u2, labels = np.unique(labels, return_inverse=True)
            count = len(u2)
    return labels.astype(np.int32), count


def group_labels(tags: TagMatrix, gb_kids: Sequence[int]
                 ) -> tuple[np.ndarray, int]:
    """Group label per row of ``tags`` + group count for the group-by
    keys ``gb_kids``: rows with equal tagv-id tuples share a label, and
    labels ascend with the tuple (-1 = key absent sorts first)."""
    mat = np.empty((tags.num_series, len(gb_kids)), dtype=np.int64)
    for j, k in enumerate(gb_kids):
        col = tags.col(k)
        mat[:, j] = col if col is not None else -1
    return compact_row_labels(mat)


class GroupLayout:
    """The rows of a tag matrix group by group, and what the SpanGroup
    tag rule needs of each group: per tag key the minimum and maximum
    tagv id over its members. A minimum below 0 says the key is absent
    on a member (it vanishes), minimum == maximum that all members
    agree (a common tag), anything else that they differ (an
    aggregated tag).

    ``order`` is the stable argsort of a compact labelling (every
    label 0..G-1 has a member), ``starts`` [G + 1] its group
    boundaries, ``cols`` [K, S] the tag columns read in that order, a
    column a row. The
    engine makes one per request from a matrix that came from nowhere,
    and a :class:`PlanIndex` keeps one per cached labelling of the
    whole metric, of which a request reads its selection
    (:meth:`selected`).
    """

    #: members a block of whole groups holds before the next begins:
    #: what :meth:`selected` copies at a time is 512 KB a column, not
    #: the columns of the whole metric
    BLOCK = 1 << 17

    __slots__ = ("order", "starts", "cols", "minv", "maxv")

    def __init__(self, order: np.ndarray, starts: np.ndarray,
                 cols: np.ndarray):
        self.order, self.starts, self.cols = order, starts, cols
        # int64 [G, K] over whole groups
        self.minv, self.maxv = (
            reduce.reduceat(cols, starts[:-1], axis=1).T.astype(np.int64)
            for reduce in (np.minimum, np.maximum))

    def members(self, group: int) -> np.ndarray:
        """The rows of one group, ascending."""
        return self.order[self.starts[group]:self.starts[group + 1]]

    def selected(self, mask: np.ndarray):
        """``(minv, maxv, members)`` over the rows ``mask`` keeps, for
        the groups that keep any, renumbered in label order:
        ``members(g)`` gives the kept rows of the g-th of them.

        A group that keeps every member reads the whole group's
        minimum and maximum; the others are reduced over their kept
        members, a block of whole groups at a time."""
        starts = self.starts
        chosen = mask[self.order]
        kept = np.add.reduceat(chosen, starts[:-1], dtype=np.int64)
        minv, maxv = self.minv.copy(), self.maxv.copy()
        partial = (kept > 0) & (kept < np.diff(starts))
        # the first group to start at or after each multiple of BLOCK
        cuts = np.unique(np.append(
            np.searchsorted(starts,
                            np.arange(0, len(chosen), self.BLOCK)),
            len(kept)))
        for g0, g1 in zip(cuts[:-1], cuts[1:]):
            if not partial[g0:g1].any():
                continue
            lo, hi = starts[g0], starts[g1]
            picked = chosen[lo:hi]
            live = np.flatnonzero(kept[g0:g1])
            seg = (np.cumsum(kept[g0:g1]) - kept[g0:g1])[live]
            live += g0
            for j, col in enumerate(self.cols):
                col = col[lo:hi][picked]
                minv[live, j] = np.minimum.reduceat(col, seg)
                maxv[live, j] = np.maximum.reduceat(col, seg)
        present = np.flatnonzero(kept)

        def members(group: int) -> np.ndarray:
            at = slice(starts[present[group]],
                       starts[present[group] + 1])
            return self.order[at][chosen[at]]

        return minv[present], maxv[present], members


def _group_starts(labels: np.ndarray, count: int) -> np.ndarray:
    """[count + 1] boundaries of the groups of a compact labelling in
    its stable argsort."""
    starts = np.zeros(count + 1, dtype=np.int64)
    np.cumsum(np.bincount(labels, minlength=count), out=starts[1:])
    return starts


class _LabelSet:
    """One cached labelling of a :class:`PlanIndex` and, built by the
    first assemble stage that asks, its :class:`GroupLayout`."""

    __slots__ = ("labels", "count", "layout")

    def __init__(self, labels: np.ndarray, count: int):
        self.labels, self.count = labels, count
        self.layout: GroupLayout | None = None


class PlanIndex:
    """What the plan and assemble stages need of one metric's tag
    index and that depends on nothing else: the metric's whole
    :class:`TagMatrix`, and, built by the first request that asks,
    each tag key's distinct tagv ids, each group-by key set's label
    for every series, and that labelling's :class:`GroupLayout` (the
    member order, the group boundaries and a group-major copy of the
    tag columns, int32 where the ids fit: 4 + 4 K bytes a series).

    The tag index only appends, so its series count versions all of
    it: the engine keeps one per (store, metric) in
    ``tsdb._tagmat_cache`` and drops it whole when ``version`` no
    longer equals the index's length. One part has a second version:
    the NAMES of a key's distinct ids (``name_table``: a
    :class:`~opentsdb_tpu.query.filters.NameTable`, built by the first
    filter that matches stored names of that key), which the series
    count cannot vouch for, since ``rename`` and ``delete`` change a
    name and no series. A table carries the UID dictionary's
    ``generation`` as read before its names were, and a request that
    reads another generation builds it again (one read a name, what
    every such request cost before there was a table). A filter that
    holds exact names reads the live dictionary's forward map and no
    table.

    What a table costs, a key: 8 bytes a name for the list of names
    (references to the dictionary's own strings) and names x longest
    name bytes for the byte matrix, which is refused (the key is then
    walked, a request) where it would exceed
    ``NameArrays.MAX_PAD`` = 8 times the names' own bytes; as much
    again for the case-folded matrix once an ``i`` filter has asked,
    and 4 bytes a name for each matrix's lengths, 8 for its ids where
    the names are not all of one length. At ``fleet-1m``: ``host``
    (1,000,000 names of 8 bytes) 8 MB of list, 8 MB of matrix, 4 MB of
    lengths, 12 MB more once folded; ``dc``, ``rack``, ``fleet`` a few
    KB each. Nothing is built for a key no pattern names.

    The lazy parts build under one lock (two sub-queries of a request
    plan side by side: the second waits and reads what the first
    built). At most :data:`LABEL_SETS` labellings are kept, least
    recently used out, each with its layout (4 bytes a series, and
    4 + 4 K more once assembled from).
    """

    LABEL_SETS = 8

    __slots__ = ("version", "tags", "_keys_of", "_distinct", "_names",
                 "_labels", "_lock")

    def __init__(self, version: int, tags: TagMatrix):
        self.version = version
        self.tags = tags
        # present keys a row, or None where every row holds them all
        present = tags.vids >= 0
        self._keys_of = None if present.all() else \
            present.sum(axis=1, dtype=np.int32)
        # tsdlint: allow[unbounded-growth] keyed by tag key: at most
        # one entry a column of ``tags``; gone with the index
        self._distinct: dict[int, np.ndarray] = {}
        # tsdlint: allow[unbounded-growth] keyed by tag key, like
        # ``_distinct``; an entry is replaced, never added to
        self._names: dict[int, filters_mod.NameTable] = {}
        self._labels: OrderedDict[tuple, _LabelSet] = OrderedDict()
        self._lock = threading.Lock()

    @property
    def num_series(self) -> int:
        return self.tags.num_series

    def col(self, kid: int) -> np.ndarray | None:
        return self.tags.col(kid)

    def distinct(self, kid: int) -> np.ndarray:
        found = self._distinct.get(kid)
        if found is None:
            with self._lock:
                found = self._distinct.get(kid)
                if found is None:
                    found = self._distinct[kid] = self.tags.distinct(kid)
        return found

    def name_table(self, kid: int, tagv, folded: bool
                   ) -> tuple["filters_mod.NameTable", int, bool]:
        """``(table, names read, built)``: the names of
        ``distinct(kid)`` in ``tagv`` (the tagv UID dictionary) as of
        its present generation, with the case-folded arrays when
        ``folded`` asks for them; how many names this call read from
        the dictionary (all of them, or 0), and whether it built
        anything."""
        found = self._names.get(kid)
        if found is not None and found.generation == tagv.generation \
                and found.has(folded):
            return found, 0, False
        ids, read = self.distinct(kid), 0
        with self._lock:
            # read before the names are: a rename during the build
            # leaves a table the next request will not trust
            generation = tagv.generation
            found = self._names.get(kid)
            if found is None or found.generation != generation:
                found = self._names[kid] = filters_mod.NameTable(
                    ids, tagv, generation)
                read = len(ids)
            fold = not found.has(folded)
            if fold:
                found.fold()
        return found, read, read > 0 or fold

    def _label_set(self, key: tuple) -> _LabelSet:
        """Called with the lock held."""
        found = self._labels.get(key)
        if found is None:
            found = self._labels[key] = _LabelSet(
                *group_labels(self.tags, key))
            while len(self._labels) > self.LABEL_SETS:
                self._labels.popitem(last=False)
        else:
            self._labels.move_to_end(key)
        return found

    def labels(self, gb_kids: Sequence[int]) -> tuple[np.ndarray, int]:
        """:func:`group_labels` of the whole metric (int32 [S], count);
        the array is shared between requests: read it, never write."""
        with self._lock:
            found = self._label_set(tuple(gb_kids))
        return found.labels, found.count

    def layout(self, gb_kids: Sequence[int]) -> GroupLayout:
        """The :class:`GroupLayout` of ``labels(gb_kids)`` over the
        whole metric; shared between requests like the labels."""
        with self._lock:
            found = self._label_set(tuple(gb_kids))
            if found.layout is None:
                labels, count = found.labels, found.count
                # 16-bit keys sort by radix: a sixth of the time
                order = np.argsort(
                    labels.astype(np.uint16) if count <= 1 << 16
                    else labels, kind="stable").astype(np.int32)
                vids = self.tags.vids
                if not vids.size or \
                        vids.max() <= np.iinfo(np.int32).max:
                    vids = vids.astype(np.int32)
                cols = np.empty(vids.shape[::-1], dtype=vids.dtype)
                for j, col in enumerate(cols):
                    np.take(vids[:, j], order, out=col)
                found.layout = GroupLayout(
                    order, _group_starts(labels, count), cols)
        return found.layout

    def num_pairs(self, rows: np.ndarray | None) -> int:
        """Present (key, value) pairs over ``rows`` (None: all)."""
        if self._keys_of is None:
            n = self.num_series if rows is None else len(rows)
            return n * len(self.tags.kids)
        return int((self._keys_of if rows is None
                    else self._keys_of[rows]).sum())

    def select(self, rows: np.ndarray | None) -> TagMatrix:
        """The matrix of the index's rows ``rows`` (ascending
        positions; None: all of them), remembering where it came
        from. Its ``vids`` are gathered when first read."""
        if rows is None:
            return TagMatrix(self.tags.kids, self.tags.vids,
                             (self, None))
        return TagMatrix(self.tags.kids, None, (self, rows))


#: a selection of fewer than one row in SMALL_SELECTION of its metric
#: is summarized from its own rows (a sort of n group ids and two
#: gathers, ~0.1 us a selected row) and not from the index's layout (a
#: mask over all S rows read in member order, ~0.005 us a row of the
#: METRIC, and up to as much again for the groups a filter cut): the
#: costs cross near one row in ten. A panel of 8 hosts of a million
#: never reads, or builds, the layout.
SMALL_SELECTION = 8


def group_tag_summary(tags: TagMatrix, group_ids: np.ndarray,
                      num_groups: int, gb_kids: Sequence[int] | None):
    """``(way, minv, maxv, members, source)`` for the groups
    ``group_ids`` makes of the rows of ``tags``: int64 [G, K] minimum
    and maximum tagv id a group and key (:class:`GroupLayout` has the
    rule they decide), ``members(g)`` the rows of ``source`` (a
    :class:`TagMatrix`) in group g, ascending.

    ``way`` says where they were read: ``index`` when ``group_ids``
    are the labels of ``gb_kids`` gathered from the :class:`PlanIndex`
    the matrix was selected from (``gb_kids`` None says they are not),
    and the selection is no :data:`SMALL_SELECTION`: the index's
    cached layout, of which an unfiltered request reads the whole
    groups as they stand. Otherwise a layout of the matrix's own
    rows, made here: ``small`` where that was the cheaper of two ways
    (a :data:`SMALL_SELECTION` of an index), ``matrix`` where there
    was no index to read (``path.fallbacks`` counts it)."""
    way = "matrix"
    if tags.origin is not None and gb_kids is not None:
        index, rows = tags.origin
        if rows is None or len(rows) == index.num_series:
            layout = index.layout(gb_kids)
            return ("index", layout.minv, layout.maxv, layout.members,
                    index.tags)
        if len(rows) * SMALL_SELECTION >= index.num_series:
            mask = np.zeros(index.num_series, dtype=bool)
            mask[rows] = True
            return ("index", *index.layout(gb_kids).selected(mask),
                    index.tags)
        way = "small"
    order = np.argsort(group_ids, kind="stable")
    layout = GroupLayout(order, _group_starts(group_ids, num_groups),
                         tags.vids[order].T)
    return way, layout.minv, layout.maxv, layout.members, tags


def _match_series_by_tags(src_store, dst_store, sids: np.ndarray,
                          metric_id: int) -> np.ndarray:
    """For each src-store series id, the dst-store series id with the
    identical (metric, tags) key, or -1 — fully vectorized (the rollup
    avg path aligns the count tier to the sum tier this way; a
    dict-lookup walk costs seconds at 1M series).

    Exact match: both stores' tag matrices are built over the union key
    space, so equal rows <=> equal tag sets (ref: RollupSpan reading
    sum+count qualifiers of one row — same series identity)."""
    dst_sids = dst_store.series_ids_for_metric(metric_id)
    if len(dst_sids) == 0 or len(sids) == 0:
        return np.full(len(sids), -1, dtype=np.int64)
    _, src_triples = src_store.metric_index(metric_id).arrays()
    _, dst_triples = dst_store.metric_index(metric_id).arrays()
    kids = np.union1d(
        np.unique(src_triples[:, 1]) if len(src_triples)
        else np.empty(0, dtype=np.int64),
        np.unique(dst_triples[:, 1]) if len(dst_triples)
        else np.empty(0, dtype=np.int64))
    a = TagMatrix.from_triples(sids, src_triples, kids=kids).vids
    b = TagMatrix.from_triples(dst_sids, dst_triples, kids=kids).vids
    both = np.concatenate([a, b], axis=0)
    labels, _ = compact_row_labels(both)
    la, lb = labels[:len(a)], labels[len(a):]
    order = np.argsort(lb, kind="stable")
    lb_sorted = lb[order]
    pos = np.searchsorted(lb_sorted, la)
    pos_c = np.minimum(pos, len(lb_sorted) - 1)
    hit = lb_sorted[pos_c] == la
    return np.where(hit, dst_sids[order[pos_c]], -1)


def _common_tags(tags: TagMatrix, members: np.ndarray, uids
                 ) -> tuple[dict[str, str], list[str]]:
    """SpanGroup semantics for ONE group (small paths — the engine's
    main loop computes all groups at once in ``_build_results``):
    ``tags`` = k=v pairs identical across every member series;
    ``aggregateTags`` = keys present everywhere with differing values
    (keys missing from some series vanish)."""
    sub = tags.vids[members]
    out_tags: dict[str, str] = {}
    agg_tags: list[str] = []
    for j, kid in enumerate(tags.kids):
        col = sub[:, j]
        lo = int(col.min()) if len(col) else -1
        if lo < 0:
            continue
        kname = uids.tag_names.get_name(int(kid))
        if lo == int(col.max()):
            out_tags[kname] = uids.tag_values.get_name(lo)
        else:
            agg_tags.append(kname)
    return out_tags, agg_tags


class _UidNameCache:
    """Memoized UID->name lookups for result assembly (one cache per
    query; group loops hit the same few names over and over)."""

    def __init__(self, registry):
        self._reg = registry
        # tsdlint: allow[unbounded-growth] one cache per query,
        # garbage with the query; bounded by its result's UID count
        self._cache: dict[int, str] = {}

    def __call__(self, uid: int) -> str:
        name = self._cache.get(uid)
        if name is None:
            name = self._cache[uid] = self._reg.get_name(uid)
        return name
