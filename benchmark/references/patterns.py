"""The judge of a deployment whose dashboards name their hosts by a
pattern (``fleet-1m-wildcard``): ``reference.py`` and the five value
filters that match a stored NAME and not an id: ``wildcard`` and
``iwildcard`` with any pattern, ``regexp``, ``iliteral_or`` and
``not_iliteral_or`` (ref: OpenTSDB 2.4 ``src/query/filter/``,
``TagVWildcardFilter``, ``TagVRegexFilter``, ``TagVLiteralOrFilter``;
docs ``user_guide/query/filters.html``).

What a pattern matches, as that documentation states it: ``*`` stands
for any run of characters, none included, and is the only special
character; ``?``, ``[`` and every other character stand for
themselves; ``wildcard`` is case sensitive, ``iwildcard`` compares in
lower case. The matcher is this file's own and plain: the pattern is
split on ``*``; its first part has to begin the name unless the
pattern begins with ``*``, its last part has to end it unless the
pattern ends with ``*``, and the parts between are found in order,
each at the leftmost place after the one before. ``regexp`` is
Python's ``re`` ``match``: anchored at the start of the name alone.
``iliteral_or`` holds the names that, in lower case, are one of its
``|``-separated values in lower case, and ``not_iliteral_or`` the
others.

A window may have sent sixteen thousand distinct patterns over a key
of a million names, so the names of a key are held once, as a
``[names x width]`` byte matrix, and a pattern with its ``*`` at one
end is a handful of column comparisons (milliseconds over 1,000,000
names; ``tests/test_pattern_filters_served.py`` holds
:meth:`Names.glob` to a character-by-character matcher). ``regexp`` and the literal pair walk
the names in Python: no cell sends them, the tests do. Names are the
deployment's own (``data.tag_name``), which are ASCII.

Each of the five becomes an INCLUDE of the values it matches
(``not_iliteral_or`` of those it does not: in this deployment every
series carries every key), and from there the answer is
``reference.py``'s: the rows, the groups, the reduction, the limits.
Everything else (``compare``, ``rows_to_grid``, ``Cells``, ``Verdict``)
is ``reference.py``'s, which the loader falls back on.
"""

import re

import numpy as np

import reference

PATTERN_TYPES = ("wildcard", "iwildcard", "regexp", "iliteral_or",
                 "not_iliteral_or")


class Names:
    """The names of one key's values, in the order of their ids."""

    def __init__(self, names: list[str]):
        self.text = names
        raw = [n.encode() for n in names]
        self.length = np.array([len(r) for r in raw], dtype=np.int64)
        self.width = int(self.length.max(initial=0))
        self.lengths = np.unique(self.length).tolist()
        # column-major: one character position of every name is one
        # contiguous run
        self.bytes = np.asfortranarray(np.frombuffer(
            b"".join(r.ljust(self.width, b"\0") for r in raw),
            dtype=np.uint8).reshape(len(raw), self.width))
        self._lower = None

    @property
    def lower(self) -> np.ndarray:
        if self._lower is None:
            upper = (self.bytes >= ord("A")) & (self.bytes <= ord("Z"))
            self._lower = np.asfortranarray(self.bytes + 32 * upper)
        return self._lower

    def _at(self, m: np.ndarray, part: bytes, offset: int) -> np.ndarray:
        """Names that hold ``part`` at ``offset``."""
        if offset + len(part) > self.width:
            return np.zeros(len(m), dtype=bool)
        ok = np.ones(len(m), dtype=bool)
        for j, c in enumerate(part):
            ok &= m[:, offset + j] == c
        return ok

    def _ends_with(self, m: np.ndarray, part: bytes) -> np.ndarray:
        ok = np.zeros(len(m), dtype=bool)
        for ell in self.lengths:
            if ell < len(part):
                continue
            here = self._at(m, part, ell - len(part))
            ok |= here if len(self.lengths) == 1 \
                else here & (self.length == ell)
        return ok

    def glob(self, pattern: str, fold: bool = False) -> np.ndarray:
        """[names] bool: which names the pattern matches; ``fold``
        compares in lower case."""
        m = self.lower if fold else self.bytes
        parts = [p.encode() for p in
                 (pattern.lower() if fold else pattern).split("*")]
        first, last = parts[0], parts[-1]
        if len(parts) == 1:
            return (self.length == len(first)) & self._at(m, first, 0)
        ok = (self.length >= len(first) + len(last)) \
            & self._at(m, first, 0) & self._ends_with(m, last)
        pos = np.full(len(m), len(first))
        limit = self.length - len(last)
        for part in parts[1:-1]:
            if not part:
                continue
            places = max(self.width - len(part) + 1, 0)
            occurs = np.ones((len(m), places), dtype=bool)
            for j, c in enumerate(part):
                occurs &= m[:, j:j + places] == c
            at = np.arange(places)
            occurs &= (at >= pos[:, None]) \
                & (at + len(part) <= limit[:, None])
            ok &= occurs.any(axis=1)
            pos = np.argmax(occurs, axis=1) + len(part)
        return ok

    def regexp(self, pattern: str) -> np.ndarray:
        match = re.compile(pattern).match
        return np.array([match(n) is not None for n in self.text],
                        dtype=bool)

    def one_of(self, values: list[str]) -> np.ndarray:
        """Names that are one of ``values``, whole and in lower case."""
        want = {v.lower() for v in values if v}
        return np.array([n.lower() in want for n in self.text],
                        dtype=bool)


def _pattern_of(f: dict, d):
    """``(tagk, {"type", "filter"})`` of a filter this file adds to
    ``reference.py``'s, or None for one of ``reference.py``'s own."""
    kind, tagk, expr = f.get("type"), f.get("tagk"), f.get("filter")
    if kind not in PATTERN_TYPES \
            or (kind == "wildcard" and expr == "*"):
        return None
    if not isinstance(expr, str) or not expr:
        raise reference.Unsupported(f"filter {f!r}")
    if kind in ("wildcard", "iwildcard") and "*" not in expr:
        raise reference.Unsupported(f"filter {f!r}: no '*' in it")
    if kind == "regexp":
        try:
            re.compile(expr)
        except re.error as e:
            raise reference.Unsupported(f"filter {f!r}: {e}") from None
    try:
        d.tag_count(tagk)
    except KeyError:
        raise reference.Unsupported(f"filter {f!r}: the deployment has "
                                    f"no tag {tagk!r}") from None
    return tagk, {"type": kind, "filter": expr}


class Reference(reference.Reference):

    def __init__(self, data, values, limits):
        super().__init__(data, values, limits)
        self._names: dict = {}

    @classmethod
    def supports(cls, sub: dict, d, window=None):
        """``reference.py``'s, with each of the five name filters
        among ``include`` as ``(tagk, {"type", "filter"})`` beside its
        ``(tagk, [names])``."""
        reference.span_only(d, window)      # no window but the span
        plain, patterns = [], []
        for f in sub.get("filters") or []:
            p = _pattern_of(f, d)
            if p is None:
                plain.append(f)
            else:
                patterns.append((p, bool(f.get("groupBy"))))
        *head, include, exclude, group_tag = super().supports(
            dict(sub, filters=plain), d)
        for (tagk, spec), group_by in patterns:
            include.append((tagk, spec))
            if group_by:
                if group_tag and group_tag != tagk:
                    raise reference.Unsupported("two group-by tags")
                group_tag = tagk
        return (*head, include, exclude, group_tag)

    def names(self, tagk: str) -> Names:
        hit = self._names.get(tagk)
        if hit is None:
            d = self.data
            hit = self._names[tagk] = Names(
                [d.tag_name(tagk, i) for i in range(d.tag_count(tagk))])
        return hit

    def matched(self, tagk: str, spec) -> np.ndarray:
        """[values of the key] bool: which of them an entry of
        ``include`` or ``exclude`` names."""
        d = self.data
        if isinstance(spec, list):
            ids = np.array([d.tag_index(tagk, v) for v in spec],
                           dtype=np.int64)
            hit = np.zeros(d.tag_count(tagk), dtype=bool)
            hit[ids[ids >= 0]] = True
            return hit
        kind, expr = spec["type"], spec["filter"]
        names = self.names(tagk)
        if kind == "regexp":
            return names.regexp(expr)
        if kind in ("wildcard", "iwildcard"):
            return names.glob(expr, fold=kind == "iwildcard")
        hit = names.one_of(expr.split("|"))
        return ~hit if kind == "not_iliteral_or" else hit

    def selected(self, sub: dict) -> int:
        d = self.data
        *_head, include, exclude, _group_tag = self.supports(sub, d)
        idx = np.arange(d.series)
        keep = np.ones(d.series, dtype=bool)
        for tagk, spec in include:
            keep &= self.matched(tagk, spec)[d.tag_ids(tagk, idx)]
        for tagk, spec in exclude:
            keep &= ~self.matched(tagk, spec)[d.tag_ids(tagk, idx)]
        return int(keep.sum())

    def _base(self, agg, secs, fn, rate, counter_max, include,
              group_tag):
        """The include step: a name filter becomes the names it
        matches, and ``reference.py`` takes it from there."""
        d = self.data
        named = [(tagk, spec if isinstance(spec, list) else [
            d.tag_name(tagk, int(i))
            for i in np.flatnonzero(self.matched(tagk, spec))])
            for tagk, spec in include]
        return super()._base(agg, secs, fn, rate, counter_max, named,
                             group_tag)
