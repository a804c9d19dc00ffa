"""Tag-value filters (ref: ``src/query/filter/TagVFilter.java`` and
subclasses).

All 9 reference filter types: literal_or, iliteral_or, not_literal_or,
not_iliteral_or, wildcard, iwildcard, regexp, not_key — with the same
``type(expr)`` shorthand grammar and the old-style tag-map conversion
(``*`` -> wildcard group-by, ``a|b`` -> literal_or group-by, exact value
-> literal_or non-grouping; ref TagVFilter.tagsToFilters).

Evaluation is vectorized: instead of the reference's per-row
``match(tags)`` callbacks post-scan (SaltScanner.java:660-692), a filter
resolves the set of matching tagv UIDs once and then the series mask is
one pass over the metric's tag column.

What is kept between requests, and by whom: the engine caches one
``PlanIndex`` per (store, metric) — the series x tag-key matrix of tagv
ids, each column's distinct ids, the group labels of recent group-by
key sets — versioned by the metric's series count (the tag index only
appends; a new series drops the whole entry). Beside each column's
distinct ids it keeps, once a filter has asked, their NAMES as arrays
(:class:`NameTable`), stamped with the UID dictionary's ``generation``
as read before the names were: a rename, a delete or a snapshot load
moves the generation, the next request sees the difference and reads
the names again, so a renamed value shows in the next request. A
filter becomes its series mask one of four ways (counted by
``tsd.query.filter{resolve=}``):

- ``ids``: a filter whose predicate is membership in a set of exact
  names (``exact_names``: ``literal_or``, ``not_literal_or`` and the
  old-style ``tagk=value`` / ``tagk=a|b``) looks its own names up in the
  dictionary's forward map, a look-up a name it holds, and reads the
  name of no stored value (ref: TagVLiteralOrFilter resolves its
  literals to tagv UIDs when the query is built, TagVFilter.resolveTags);
- ``table``: a filter that matches a stored NAME (``stored_names``:
  ``wildcard``, ``iwildcard``, ``iliteral_or``, ``not_iliteral_or``,
  ``regexp``) over a source that keeps a name table (the plan index)
  matches all of the key's names at once: a pattern a few array
  comparisons a pattern character, a folded literal one comparison a
  character over the names of its length, ``regexp`` its compiled
  pattern over the table's list of names (no dictionary read, no
  lock). The request that finds no table of the dictionary's
  generation reads the key's names once to build it (``table=built``),
  every other reads none (``table=hit``);
- ``walk``: the same filters over a source that has nowhere to keep a
  table (a plain ``TagMatrix``: tsuids, a write between selection and
  plan, the histogram, sketch and streaming planners), or over a key
  whose names are too uneven for one (:data:`NameArrays.MAX_PAD`):
  ``matching_tagv_ids`` reads the NAME of every distinct value of the
  key and runs the predicate on it;
- ``presence``: filters that match every value (``*``, ``.*``) and
  ``not_key`` read the column alone.

Turning one value filter into its tagv ids, the ``ids``, ``table`` or
``walk`` way, is the stage ``query.filter_resolve`` (a child of
``query.plan``; tags ``way``, ``names_read``, ``matched``, and
``table`` = hit or built on the table's way). ``names_read`` is the
names read from the UID dictionary: the table's rows on the request
that builds it, 0 on a hit, the key's distinct count on a walk; they
add up in ``tsd.query.filter.names_read``.
"""

from __future__ import annotations

import re
from collections import Counter
from typing import Sequence

import numpy as np

from opentsdb_tpu.obs.trace import trace_span

_FILTER_RE = re.compile(r"^(\w+)\((.*)\)$", re.DOTALL)


def _utf8(text: str) -> bytes:
    """The bytes a name or a pattern is compared by. UTF-8 is
    self-synchronising, so equal byte runs are equal characters; a lone
    surrogate (JSON can carry one) encodes instead of raising."""
    return text.encode("utf-8", "surrogatepass")


class TagVFilter:
    """(ref: TagVFilter.java:70)"""

    filter_name = ""
    groupby_default = False
    #: True when names are compared by their ``lower()``
    case_insensitive = False
    #: True when every present value matches (``*``, ``.*``): the
    #: evaluator then reads the key's presence and walks no names
    matches_all = False

    def __init__(self, tagk: str, filter_expr: str, group_by: bool = False):
        if not tagk:
            raise ValueError("missing tag key")
        self.tagk = tagk
        self.filter_expr = filter_expr
        self.group_by = group_by or self.groupby_default
        self.post_init()

    def post_init(self) -> None:
        pass

    # string predicate over candidate tag values; None => value-independent
    def match_value(self, value: str) -> bool:
        raise NotImplementedError

    @property
    def match_absent(self) -> bool:
        """True when series *lacking* the tag key match (not_key)."""
        return False

    @property
    def includes_present(self) -> bool:
        """True when series having the key may match."""
        return True

    def exact_names(self) -> tuple[frozenset[str], bool] | None:
        """``(names, negated)`` when the predicate is "the value is
        (``negated``: is not) one of these exact names", which the
        evaluator answers from the names' own UIDs; None when only the
        predicate run over a stored value's name can tell."""
        return None

    def stored_names(self, table: "NameTable"
                     ) -> tuple[np.ndarray, bool] | None:
        """``(ids, negated)`` when the predicate is "the value is
        (``negated``: is not) one of these", the tagv ids of the names
        of ``table`` it picks out with array operations; None when
        only ``match_value`` over one name at a time can tell."""
        return None

    def to_json(self) -> dict:
        return {"tagk": self.tagk, "filter": self.filter_expr,
                "type": self.filter_name, "groupBy": self.group_by}

    def __repr__(self) -> str:
        return (f"{self.filter_name}(tagk={self.tagk}, "
                f"filter={self.filter_expr}, group_by={self.group_by})")

    def __eq__(self, other) -> bool:
        return (type(self) is type(other) and self.tagk == other.tagk
                and self.filter_expr == other.filter_expr
                and self.group_by == other.group_by)

    def __hash__(self) -> int:
        return hash((type(self).__name__, self.tagk, self.filter_expr,
                     self.group_by))


class TagVLiteralOrFilter(TagVFilter):
    """``literal_or(v1|v2)`` (ref: TagVLiteralOrFilter.java:35)"""
    filter_name = "literal_or"
    negated = False

    def post_init(self) -> None:
        if not self.filter_expr:
            raise ValueError("empty literal_or filter")
        values = self.filter_expr.split("|")
        self._literals = frozenset(
            v.lower() if self.case_insensitive else v
            for v in values if v)

    def match_value(self, value: str) -> bool:
        v = value.lower() if self.case_insensitive else value
        return (v in self._literals) != self.negated

    def exact_names(self) -> tuple[frozenset[str], bool] | None:
        # a name's other spellings have UIDs of their own, which only
        # the stored names can tell
        if self.case_insensitive:
            return None
        return self._literals, self.negated

    def stored_names(self, table: "NameTable"
                     ) -> tuple[np.ndarray, bool] | None:
        arrays = table.arrays(self.case_insensitive)
        if arrays is None:
            return None
        return arrays.equal([_utf8(v) for v in self._literals]), \
            self.negated

    @property
    def literals(self) -> set[str]:
        return set(self._literals)


class TagVILiteralOrFilter(TagVLiteralOrFilter):
    filter_name = "iliteral_or"
    case_insensitive = True


class TagVNotLiteralOrFilter(TagVLiteralOrFilter):
    filter_name = "not_literal_or"
    negated = True


class TagVNotILiteralOrFilter(TagVILiteralOrFilter):
    filter_name = "not_iliteral_or"
    negated = True


class TagVWildcardFilter(TagVFilter):
    """``wildcard(*web*)`` — ``*`` globs, case sensitive
    (ref: TagVWildcardFilter.java:34). ``*`` alone is special: ``?``,
    ``[`` and every other character stand for themselves."""
    filter_name = "wildcard"

    def post_init(self) -> None:
        expr = self.filter_expr
        if not expr or "*" not in expr:
            raise ValueError(
                f"wildcard filter must contain '*': {expr!r}")
        if self.case_insensitive:
            expr = expr.lower()
        # anchored by ``match`` and ``\Z``: ``fullmatch`` costs a
        # name 10 ns more, 1% of a walk over a million names
        parts = expr.split("*")
        self._regex = re.compile(
            "(?s:" + ".*".join(map(re.escape, parts)) + r")\Z")
        self.matches_all = not any(parts)
        self._parts = [_utf8(part) for part in parts]

    def match_value(self, value: str) -> bool:
        if self.matches_all:
            return True
        v = value.lower() if self.case_insensitive else value
        return self._regex.match(v) is not None

    def stored_names(self, table: "NameTable"
                     ) -> tuple[np.ndarray, bool] | None:
        arrays = table.arrays(self.case_insensitive)
        if arrays is None:
            return None
        return arrays.glob(self._parts), False


class TagVIWildcardFilter(TagVWildcardFilter):
    filter_name = "iwildcard"
    case_insensitive = True


class TagVRegexFilter(TagVFilter):
    """``regexp(pattern)`` (ref: TagVRegexFilter.java:28)"""
    filter_name = "regexp"

    def post_init(self) -> None:
        self._regex = re.compile(self.filter_expr)
        self.matches_all = self.filter_expr in (".*", "^.*", ".*$", "^.*$")

    def match_value(self, value: str) -> bool:
        return self._regex.match(value) is not None

    def stored_names(self, table: "NameTable"
                     ) -> tuple[np.ndarray, bool] | None:
        # NumPy has no such predicate: the compiled pattern runs over
        # the table's list of names, a name at a time, with no
        # dictionary read and no lock
        return table.matching(self._regex.match), False


class TagVNotKeyFilter(TagVFilter):
    """Matches series that do NOT have the tag key at all
    (ref: TagVNotKeyFilter.java:10). Cannot group by."""
    filter_name = "not_key"

    def post_init(self) -> None:
        if self.filter_expr:
            raise ValueError(
                "Filter value must be null or empty for not_key")
        if self.group_by:
            raise ValueError("cannot group by with a not_key filter")

    def match_value(self, value: str) -> bool:
        return False

    @property
    def match_absent(self) -> bool:
        return True

    @property
    def includes_present(self) -> bool:
        return False


_FILTER_TYPES: dict[str, type[TagVFilter]] = {
    cls.filter_name: cls for cls in (
        TagVLiteralOrFilter, TagVILiteralOrFilter, TagVNotLiteralOrFilter,
        TagVNotILiteralOrFilter, TagVWildcardFilter, TagVIWildcardFilter,
        TagVRegexFilter, TagVNotKeyFilter)
}


def get_filter(tagk: str, expr: str, group_by: bool = False) -> TagVFilter:
    """Parse ``type(value)`` shorthand, or bare value / ``a|b`` / ``*``
    old-style (ref: TagVFilter.getFilter :199-260 + tagsToFilters)."""
    m = _FILTER_RE.match(expr)
    if m:
        ftype, fexpr = m.group(1), m.group(2)
        cls = _FILTER_TYPES.get(ftype)
        if cls is None:
            raise ValueError(f"Unrecognized filter type: {ftype}")
        return cls(tagk, fexpr, group_by)
    # old-style tag values
    if expr == "*" or "*" in expr:
        return TagVIWildcardFilter(tagk, expr, group_by)
    if "|" in expr:
        return TagVLiteralOrFilter(tagk, expr, group_by)
    return TagVLiteralOrFilter(tagk, expr, group_by)


def build_filter(obj: dict) -> TagVFilter:
    """From the 2.x JSON form {type, tagk, filter, groupBy}."""
    ftype = obj.get("type", "")
    cls = _FILTER_TYPES.get(ftype)
    if cls is None:
        raise ValueError(f"Unrecognized filter type: {ftype}")
    return cls(obj.get("tagk", ""), obj.get("filter", ""),
               bool(obj.get("groupBy", False)))


def tags_to_filters(tags: dict[str, str]) -> list[TagVFilter]:
    """Old-style v1 tag map -> filters (ref: TagVFilter.tagsToFilters):
    ``*``/wildcards and ``a|b`` group by; exact values only filter."""
    out = []
    for tagk, expr in tags.items():
        group_by = "*" in expr or "|" in expr or expr.startswith(
            ("wildcard(", "iwildcard(", "literal_or(", "iliteral_or(",
             "regexp("))
        out.append(get_filter(tagk, expr, group_by=group_by))
    return out


def filter_types() -> dict[str, dict]:
    """Metadata for ``/api/config/filters`` (ref: RpcManager)."""
    docs = {
        "literal_or": ("Accepts one or more exact values and matches if "
                       "the series contains any of them. Case sensitive.",
                       "host=literal_or(web01|web02)"),
        "iliteral_or": ("Accepts one or more exact values and matches if "
                        "the series contains any of them. Case insensitive.",
                        "host=iliteral_or(web01|web02)"),
        "not_literal_or": ("Accepts one or more exact values and matches "
                           "if the series does NOT contain any of them. "
                           "Case sensitive.", "host=not_literal_or(web01)"),
        "not_iliteral_or": ("Accepts one or more exact values and matches "
                            "if the series does NOT contain any of them. "
                            "Case insensitive.",
                            "host=not_iliteral_or(web01)"),
        "wildcard": ("Performs pre, post and in-fix glob matching of "
                     "values. Case sensitive.", "host=wildcard(web*)"),
        "iwildcard": ("Performs pre, post and in-fix glob matching of "
                      "values. Case insensitive.", "host=iwildcard(web*)"),
        "regexp": ("Provides full, POSIX compliant regular expression "
                   "using the built in Java Pattern class.",
                   "host=regexp(.*)"),
        "not_key": ("Skips any time series with the given tag key, "
                    "regardless of the value.", "host=not_key()"),
    }
    return {name: {"description": d, "examples": e}
            for name, (d, e) in docs.items()}


def _member_mask(col: np.ndarray, ids, negated: bool) -> np.ndarray:
    """Rows of ``col`` whose tagv id is one of ``ids`` or, ``negated``,
    that hold the key with another value. One gather through a table
    of a byte a tagv id up to the largest asked for (ids are assigned
    in sequence, so at most a byte a name of the dictionary): ``clip``
    sends -1 (key absent) to entry 0, the id no name ever gets, and
    every id past the table to its last entry."""
    ids = np.asarray(ids, dtype=np.int64)
    table = np.full(int(ids.max(initial=0)) + 2, negated, dtype=bool)
    table[0] = False
    table[ids] = not negated
    return np.take(table, col, mode="clip")


class NameArrays:
    """One spelling of a key's names (as stored, or case-folded) as
    arrays a pattern is matched over at once.

    ``chars`` is uint8 ``[width, names]``: row j holds byte j of every
    name (0 past a name's end), so one character position of all the
    names is one contiguous run and comparing it with a pattern's
    character is one pass over it. The names are ordered by byte
    length (the order they came in kept within a length; ``ids`` and
    ``lengths`` follow), so "at least this long" is a suffix of the
    rows and "exactly this long" (``sizes``, ``starts``) a slice of
    them: a part anchored at the names' END compares one row of
    ``chars`` a character and length, each over its own slice.
    """

    #: the matrix pads every name to the longest: it may take up to
    #: this many times the bytes of the names themselves (one 4 KB
    #: name among a million of 8 bytes would make it 4 GB); a key
    #: beyond that gets no arrays and is walked
    MAX_PAD = 8

    __slots__ = ("ids", "chars", "lengths", "sizes", "starts")

    def __init__(self, ids, chars, lengths):
        self.ids = ids              # int64 [N]: the tagv id of a row
        self.chars = chars          # uint8 [width, N]
        self.lengths = lengths      # int32 [N], ascending
        # the distinct lengths, and the first row of each (+ N)
        self.sizes, starts = np.unique(lengths, return_index=True)
        self.starts = np.append(starts, len(lengths))

    @classmethod
    def of(cls, ids: np.ndarray,
           spellings: list[bytes]) -> "NameArrays | None":
        """The arrays of ``spellings`` (the names of ``ids``, in their
        order), or None where padding them costs more than
        :data:`MAX_PAD` times their own bytes."""
        lengths = np.fromiter(map(len, spellings), dtype=np.int32,
                              count=len(spellings))
        width = max(int(lengths.max(initial=0)), 1)
        if len(spellings) * width > \
                cls.MAX_PAD * int(lengths.sum(dtype=np.int64)):
            return None
        rows = np.array(spellings, dtype=f"S{width}") \
            .view(np.uint8).reshape(len(spellings), width)
        order = np.argsort(lengths, kind="stable")
        if (order[1:] > order[:-1]).all():
            return cls(ids, np.ascontiguousarray(rows.T), lengths)
        return cls(ids[order], np.ascontiguousarray(rows[order].T),
                   lengths[order])

    def _first_of(self, length: int) -> int:
        """The first row whose name has ``length`` bytes or more."""
        return int(self.starts[np.searchsorted(self.sizes, length)])

    def glob(self, parts: list[bytes]) -> np.ndarray:
        """The ids of the names that are ``parts`` with any run of
        bytes, none included, between one part and the next (a pattern
        split at its ``*``): the first part at 0, the last at the
        name's end, each part between them at its leftmost place after
        the part before it."""
        first, *middle, last = parts
        middle = [part for part in middle if part]
        lo = self._first_of(len(first) + len(last)
                            + sum(map(len, middle)))
        n = len(self.ids)
        if lo == n:
            return self.ids[:0]
        hit = np.ones(n - lo, dtype=bool)
        same = np.empty(n - lo, dtype=bool)
        for j, byte in enumerate(first):
            hit &= np.equal(self.chars[j, lo:], byte, out=same)
        if last:
            for size, a, b in zip(self.sizes, self.starts[:-1],
                                  self.starts[1:]):
                if b <= lo:
                    continue
                a = max(a, lo)
                for j, byte in enumerate(last, size - len(last)):
                    hit[a - lo:b - lo] &= np.equal(
                        self.chars[j, a:b], byte, out=same[:b - a])
        rows = np.flatnonzero(hit) + lo
        if middle and len(rows):
            rows = rows[self._between(rows, middle, len(first),
                                      len(last))]
        return self.ids[rows]

    def _between(self, rows: np.ndarray, middle: list[bytes],
                 head: int, tail: int) -> np.ndarray:
        """Which of ``rows`` hold the parts of ``middle`` in order
        between byte ``head`` and their last ``tail`` bytes, each at
        its leftmost place (if that one leaves no room for the rest,
        none does)."""
        chars = self.chars[:, rows]
        room = self.lengths[rows] - tail    # a part must end by here
        at = np.full(len(rows), head, dtype=np.int32)
        alive = np.ones(len(rows), dtype=bool)
        for part in middle:
            placed = np.zeros(len(rows), dtype=bool)
            after = at
            for begin in range(head, len(chars) - len(part) + 1):
                here = ~placed & (at <= begin) \
                    & (room >= begin + len(part))
                for j, byte in enumerate(part, begin):
                    here &= chars[j] == byte
                if here.any():
                    placed |= here
                    after = np.where(here, begin + len(part), after)
            alive &= placed
            at = after
            head += len(part)
        return alive

    def equal(self, names: list[bytes]) -> np.ndarray:
        """The ids of the names that are one of ``names``, whole."""
        found = []
        for name in names:
            k = int(np.searchsorted(self.sizes, len(name)))
            if k == len(self.sizes) or self.sizes[k] != len(name):
                continue
            a, b = self.starts[k], self.starts[k + 1]
            hit = np.ones(b - a, dtype=bool)
            for j, byte in enumerate(name):
                hit &= self.chars[j, a:b] == byte
            found.append(np.flatnonzero(hit) + a)
        if not found:
            return np.empty(0, dtype=np.int64)
        return self.ids[np.concatenate(found)]


class NameTable:
    """The names of one tag key's distinct tagv ids, read from the UID
    dictionary once and kept by the plan index for as long as the
    dictionary's ``generation`` is the one read BEFORE them: the list
    of the dictionary's own ``str`` objects (``names``, for ``regexp``
    and for the folded spelling) and their bytes as
    :class:`NameArrays`, as stored and, once an ``i`` filter has
    asked (``fold``, called with the index's lock held), case-folded:
    ``name.lower()`` may change a name's byte length, so the folded
    arrays have lengths and an order of their own."""

    __slots__ = ("generation", "ids", "names", "_arrays")

    def __init__(self, ids: np.ndarray, tagv, generation: int):
        """Reads a name an id through ``tagv.get_name``: an id that
        left the dictionary raises ``NoSuchUniqueId`` as a walk's
        would."""
        self.generation = generation
        self.ids = ids
        self.names = [tagv.get_name(uid) for uid in ids.tolist()]
        # folded? -> arrays, None where they would pad too much
        self._arrays = {False: NameArrays.of(
            ids, [_utf8(name) for name in self.names])}

    def has(self, folded: bool) -> bool:
        return folded in self._arrays

    def fold(self) -> None:
        self._arrays[True] = NameArrays.of(
            self.ids, [_utf8(name.lower()) for name in self.names])

    def arrays(self, folded: bool) -> NameArrays | None:
        return self._arrays[folded]

    def matching(self, predicate) -> np.ndarray:
        """The ids of the names ``predicate`` holds of."""
        return self.ids[[i for i, name in enumerate(self.names)
                         if predicate(name)]]


class FilterEvaluator:
    """Vectorized filter application over a metric's tag columns.

    The columns come from a ``TagMatrix`` (``col(kid)``: the tagv id
    of every series, -1 where the key is absent) or from the engine's
    cached ``PlanIndex`` over one, which also keeps each column's
    distinct tagv ids (``distinct(kid)``) and their names as a
    :class:`NameTable` (``name_table(kid, ...)``), both built by the
    first filter that needs them, for as long as the metric gains no
    series and, the names, the UID dictionary's generation stands. A
    plain matrix computes the distinct ids on the spot and keeps no
    names: over it a filter that matches a stored name reads every
    distinct value's name from the live dictionary, a request. A
    filter that holds exact names reads the dictionary's forward map
    either way.
    """

    def __init__(self, uids):
        self._uids = uids

    def matching_tagv_ids(self, filt: TagVFilter,
                          candidate_ids: np.ndarray) -> np.ndarray:
        """Run the string predicate over distinct candidate tagv ids."""
        tagv = self._uids.tag_values
        keep = [vid for vid in candidate_ids.tolist()
                if filt.match_value(tagv.get_name(int(vid)))]
        return np.asarray(keep, dtype=np.int64)

    def exact_tagv_ids(self, names) -> list[int]:
        """The UIDs of the names that have one. tagv ids are shared
        between keys: whether a key holds one, its column says."""
        tagv = self._uids.tag_values
        ids = []
        for name in names:
            try:
                ids.append(tagv.get_id(name))
            except LookupError:
                pass
        return ids

    def stored_name_ids(self, filt: TagVFilter, tags, kid: int
                        ) -> tuple[np.ndarray, bool, dict]:
        """``(ids, negated, span tags)`` of a filter that matches a
        stored name: its ids from the source's name table where it
        keeps one that can tell (``way=table``), else from a walk of
        the key's distinct values."""
        table, read, built = tags.name_table(
            kid, self._uids.tag_values, filt.case_insensitive)
        found = None if table is None else filt.stored_names(table)
        if found is not None:
            return *found, {"way": "table", "names_read": read,
                            "table": "built" if built else "hit"}
        candidates = tags.distinct(kid)
        return self.matching_tagv_ids(filt, candidates), False, {
            "way": "walk", "names_read": read + len(candidates)}

    def apply(self, filters: Sequence[TagVFilter], tags,
              tally: Counter | None = None) -> np.ndarray:
        """Return the boolean keep-mask over the series of ``tags``.

        Every filter must pass — same-key and cross-key filters all AND
        together (ref: TsdbQuery/SaltScanner filter chain semantics).
        A filter that says it matches every value (``*``, ``.*``) is
        the key's presence; ``not_key`` is its absence; one that names
        exact values (``exact_names``) is the column against their
        UIDs; any other is the column against the ids of the stored
        names it matches (``stored_name_ids``). ``tally`` counts the
        filters evaluated each way (``resolve_ids``, ``resolve_table``,
        ``resolve_walk``, ``resolve_presence``) and the names read from
        the UID dictionary (``names_read``): the ``query.plan`` span's
        tags. A filter that becomes tagv ids (``ids``, ``table``,
        ``walk``) does so inside a ``query.filter_resolve`` span of its
        own.
        """
        if tally is None:
            tally = Counter()
        n = tags.num_series
        keep = np.ones(n, dtype=bool)
        by_key: dict[str, list[TagVFilter]] = {}
        for f in filters:
            by_key.setdefault(f.tagk, []).append(f)
        for tagk, flist in by_key.items():
            try:
                kid = self._uids.tag_names.get_id(tagk)
            except LookupError:
                kid = None
            col = None if kid is None else tags.col(kid)
            if col is None:
                # a key nobody named, or that no series here holds:
                # only not_key filters can match
                tally["resolve_presence"] += len(flist)
                if not all(f.match_absent for f in flist):
                    return np.zeros(n, dtype=bool)
                continue
            for f in flist:
                # same-key filters AND together like the reference's
                # per-key chain (all must pass)
                if f.match_absent and not f.includes_present:
                    tally["resolve_presence"] += 1
                    keep &= col < 0
                    continue
                if f.matches_all:
                    tally["resolve_presence"] += 1
                    keep &= col >= 0
                    continue
                if (exact := f.exact_names()) is not None:
                    tally["resolve_ids"] += 1
                    names, negated = exact
                    with trace_span("query.filter_resolve", way="ids",
                                    names_read=0) as span:
                        ids = self.exact_tagv_ids(names)
                        if span is not None:
                            span.tag(matched=len(ids))
                else:
                    with trace_span("query.filter_resolve") as span:
                        ids, negated, how = self.stored_name_ids(
                            f, tags, kid)
                        if span is not None:
                            span.tag(matched=len(ids), **how)
                    tally["resolve_" + how["way"]] += 1
                    tally["names_read"] += how["names_read"]
                if not len(ids) and not negated:
                    return np.zeros(n, dtype=bool)
                keep &= _member_mask(col, ids, negated)
        return keep
