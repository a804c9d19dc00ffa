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
appends; a new series drops the whole entry). Names are never kept: each
request reads the live UID dictionary, one of three ways (counted by
``tsd.query.filter{resolve=}``), so a renamed value shows in the next
request:

- ``ids``: a filter whose predicate is membership in a set of exact
  names (``exact_names``: ``literal_or``, ``not_literal_or`` and the
  old-style ``tagk=value`` / ``tagk=a|b``) looks its own names up in the
  dictionary's forward map, a look-up a name it holds, and reads the
  name of no stored value (ref: TagVLiteralOrFilter resolves its
  literals to tagv UIDs when the query is built, TagVFilter.resolveTags);
- ``walk``: a filter that cannot say so (``iliteral_or``,
  ``not_iliteral_or``, ``wildcard``, ``iwildcard``, ``regexp``) has
  ``matching_tagv_ids`` read the NAME of every distinct value of its
  key and run its predicate on it: 1,000,000 look-ups for a pattern
  over a key of 1,000,000 hosts;
- ``presence``: filters that match every value (``*``, ``.*``) and
  ``not_key`` read the column alone.

Turning one value filter into its tagv ids, the ``ids`` way or the
``walk`` way, is the stage ``query.filter_resolve`` (a child of
``query.plan``; tags ``way``, ``names_read``, ``matched``), and the
names of stored values read add up in ``tsd.query.filter.names_read``.
"""

from __future__ import annotations

import re
from collections import Counter
from typing import Sequence

import numpy as np

from opentsdb_tpu.obs.trace import trace_span

_FILTER_RE = re.compile(r"^(\w+)\((.*)\)$", re.DOTALL)


class TagVFilter:
    """(ref: TagVFilter.java:70)"""

    filter_name = ""
    groupby_default = False
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
    case_insensitive = False
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
    case_insensitive = False

    def post_init(self) -> None:
        expr = self.filter_expr
        if not expr or "*" not in expr:
            raise ValueError(
                f"wildcard filter must contain '*': {expr!r}")
        if self.case_insensitive:
            expr = expr.lower()
        # anchored by ``match`` and ``\Z``: ``fullmatch`` costs a
        # name 10 ns more, 1% of a walk over a million names
        self._regex = re.compile(
            "(?s:" + ".*".join(re.escape(part)
                               for part in expr.split("*")) + r")\Z")
        self.matches_all = expr.strip("*") == ""

    def match_value(self, value: str) -> bool:
        if self.matches_all:
            return True
        v = value.lower() if self.case_insensitive else value
        return self._regex.match(v) is not None


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


def _member_mask(col: np.ndarray, ids: list[int],
                 negated: bool) -> np.ndarray:
    """Rows of ``col`` whose tagv id is one of ``ids`` or, ``negated``,
    that hold the key with another value. One gather through a table
    of a byte a tagv id up to the largest asked for (ids are assigned
    in sequence, so at most a byte a name of the dictionary): ``clip``
    sends -1 (key absent) to entry 0, the id no name ever gets, and
    every id past the table to its last entry."""
    table = np.full(max(ids, default=0) + 2, negated, dtype=bool)
    table[0] = False
    table[ids] = not negated
    return np.take(table, col, mode="clip")


class FilterEvaluator:
    """Vectorized filter application over a metric's tag columns.

    The columns come from a ``TagMatrix`` (``col(kid)``: the tagv id
    of every series, -1 where the key is absent) or from the engine's
    cached ``PlanIndex`` over one, which also keeps each column's
    distinct tagv ids (``distinct(kid)``, built by the first filter
    that has to walk the key) for as long as the metric gains no
    series; a plain matrix computes them on the spot. Names are never
    cached: every request reads the live UID dictionary, the forward
    map for a filter that holds exact names, the name of every
    distinct value of the key for one that holds a pattern.
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

    def apply(self, filters: Sequence[TagVFilter], tags,
              tally: Counter | None = None) -> np.ndarray:
        """Return the boolean keep-mask over the series of ``tags``.

        Every filter must pass — same-key and cross-key filters all AND
        together (ref: TsdbQuery/SaltScanner filter chain semantics).
        A filter that says it matches every value (``*``, ``.*``) is
        the key's presence; ``not_key`` is its absence; one that names
        exact values (``exact_names``) is the column against their
        UIDs; any other runs its string predicate over the names of
        the column's distinct values. ``tally`` counts the filters
        evaluated each way (``resolve_ids``, ``resolve_walk``,
        ``resolve_presence``) and the names of stored values read
        (``names_read``): the ``query.plan`` span's tags. A filter
        that becomes tagv ids (``ids``, ``walk``) does so inside a
        ``query.filter_resolve`` span of its own.
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
                elif f.matches_all:
                    tally["resolve_presence"] += 1
                    keep &= col >= 0
                elif (exact := f.exact_names()) is not None:
                    tally["resolve_ids"] += 1
                    names, negated = exact
                    with trace_span("query.filter_resolve", way="ids",
                                    names_read=0) as span:
                        ids = self.exact_tagv_ids(names)
                        if span is not None:
                            span.tag(matched=len(ids))
                    if not ids and not negated:
                        return np.zeros(n, dtype=bool)
                    keep &= _member_mask(col, ids, negated)
                else:
                    tally["resolve_walk"] += 1
                    with trace_span("query.filter_resolve",
                                    way="walk") as span:
                        candidates = tags.distinct(kid)
                        ids = self.matching_tagv_ids(f, candidates)
                        if span is not None:
                            span.tag(names_read=len(candidates),
                                     matched=len(ids))
                    tally["names_read"] += len(candidates)
                    keep &= np.isin(col, ids)
        return keep
