"""Tag-value filter tests.

Mirrors the reference suites under ``test/query/filter/``
(TestTagVFilter, TestTagVLiteralOrFilter, TestTagVRegexFilter,
TestTagVWildcardFilter, TestTagVNotLiteralOrFilter,
TestTagVNotKeyFilter; ref: src/query/filter/TagVFilter.java:70).
"""

import json
from collections import Counter

import numpy as np
import pytest

from opentsdb_tpu import TSDB, Config
from opentsdb_tpu.core.uid import NoSuchUniqueId
from opentsdb_tpu.query.plan import PlanIndex, TagMatrix
from opentsdb_tpu.query.filters import (FilterEvaluator, build_filter,
                                        filter_types, get_filter,
                                        tags_to_filters)
from opentsdb_tpu.tsd.http_api import HttpRequest, HttpRpcRouter


# ---------------------------------------------------------------------------
# string predicates per type
# ---------------------------------------------------------------------------

class TestPredicates:
    def test_literal_or(self):
        f = get_filter("host", "literal_or(web01|web02)")
        assert f.match_value("web01")
        assert f.match_value("web02")
        assert not f.match_value("WEB01")
        assert not f.match_value("web03")

    def test_iliteral_or(self):
        f = get_filter("host", "iliteral_or(web01)")
        assert f.match_value("WEB01")
        assert f.match_value("web01")
        assert not f.match_value("web02")

    def test_not_literal_or(self):
        f = get_filter("host", "not_literal_or(web01|web02)")
        assert not f.match_value("web01")
        assert f.match_value("web03")
        assert f.match_value("WEB01")    # case sensitive negation

    def test_not_iliteral_or(self):
        f = get_filter("host", "not_iliteral_or(web01)")
        assert not f.match_value("WEB01")
        assert f.match_value("web02")

    def test_wildcard_pre_post_infix(self):
        assert get_filter("h", "wildcard(web*)").match_value("web01")
        assert get_filter("h", "wildcard(*01)").match_value("web01")
        assert get_filter("h", "wildcard(*eb*)").match_value("web01")
        assert not get_filter("h", "wildcard(web*)").match_value("db01")
        assert not get_filter("h", "wildcard(WEB*)").match_value("web01")

    def test_iwildcard(self):
        assert get_filter("h", "iwildcard(WEB*)").match_value("web01")

    @pytest.mark.parametrize("expr, value, hit", [
        # ``*`` alone is special (ref: TagVWildcardFilter splits on it
        # and compares the parts as they are): a glob's ``?`` and
        # ``[...]`` stand for themselves
        ("web?1*", "webx12", False), ("web?1*", "web?12", True),
        ("h[01]*", "h[01]x", True), ("h[01]*", "h0x", False),
        ("*[!a]", "b", False), ("*[!a]", "x[!a]", True),
        # and so does what a regular expression would read
        ("a.b*", "axb1", False), ("a.b*", "a.b1", True),
        ("*+)", "x+)", True), ("w(eb*", "w(eb1", True),
        ("*\\d", "x\\d", True), ("*\\d", "x7", False),
        # a part with no ``*`` beside it is anchored at its end
        ("web*01", "web5501", True), ("web*01", "xweb01", False),
        ("web*01", "web011", False), ("**web**", "aweba", True),
        ("a*b*c", "abc", True), ("a*b*c", "acb", False),
        ("web*", "web\nx", True)])
    @pytest.mark.parametrize("ftype", ["wildcard", "iwildcard"])
    def test_only_the_star_is_special(self, ftype, expr, value, hit):
        if ftype == "iwildcard":
            expr = expr.upper()
        f = get_filter("h", f"{ftype}({expr})")
        assert f.match_value(value) is hit

    def test_regexp(self):
        f = get_filter("h", "regexp(web\\d+)")
        assert f.match_value("web01")
        assert not f.match_value("webxx")

    def test_regexp_invalid_raises(self):
        with pytest.raises(Exception):
            get_filter("h", "regexp((unclosed)")

    def test_not_key(self):
        f = get_filter("h", "not_key()")
        assert not f.match_value("anything")   # present key -> reject
        assert f.match_absent
        assert not f.includes_present

    def test_unknown_type_raises(self):
        with pytest.raises(ValueError):
            get_filter("h", "bogus_type(x)")


# ---------------------------------------------------------------------------
# parsing forms (ref: TagVFilter.getFilter :199-260, tagsToFilters)
# ---------------------------------------------------------------------------

class TestParsing:
    def test_old_style_star_is_iwildcard_groupby(self):
        fs = tags_to_filters({"host": "*"})
        assert fs[0].group_by
        assert fs[0].match_value("anything")

    def test_old_style_pipe_is_literal_or_groupby(self):
        fs = tags_to_filters({"host": "web01|web02"})
        assert fs[0].group_by
        assert fs[0].match_value("web01")
        assert not fs[0].match_value("web03")

    def test_old_style_exact_value_no_groupby(self):
        fs = tags_to_filters({"host": "web01"})
        assert not fs[0].group_by
        assert fs[0].match_value("web01")

    def test_new_style_in_tag_map_groups_by(self):
        fs = tags_to_filters({"host": "wildcard(web*)"})
        assert fs[0].group_by

    def test_build_filter_json_form(self):
        f = build_filter({"type": "literal_or", "tagk": "host",
                          "filter": "a|b", "groupBy": True})
        assert f.tagk == "host" and f.group_by
        assert f.match_value("a")
        with pytest.raises(ValueError):
            build_filter({"type": "nope", "tagk": "h", "filter": "x"})

    def test_filter_equality_and_hash(self):
        a = get_filter("host", "literal_or(x)")
        b = get_filter("host", "literal_or(x)")
        c = get_filter("host", "literal_or(y)")
        assert a == b and hash(a) == hash(b)
        assert a != c

    def test_filter_types_metadata(self):
        meta = filter_types()
        assert set(meta) == {"literal_or", "iliteral_or",
                             "not_literal_or", "not_iliteral_or",
                             "wildcard", "iwildcard", "regexp",
                             "not_key"}
        assert all("description" in v and "examples" in v
                   for v in meta.values())


# ---------------------------------------------------------------------------
# vectorized evaluation over the columnar tag index
# (ref: SaltScanner post-scan filter application :660-692)
# ---------------------------------------------------------------------------

class TestFilterEvaluator:
    def seed(self, tsdb):
        base = 1356998400
        tsdb.add_point("m", base, 1, {"host": "web01", "dc": "lax"})
        tsdb.add_point("m", base, 2, {"host": "web02", "dc": "lax"})
        tsdb.add_point("m", base, 3, {"host": "db01", "dc": "sjc"})
        tsdb.add_point("m", base, 4, {"dc": "sjc"})  # no host tag
        mid = tsdb.uids.metrics.get_id("m")
        sids = tsdb.store.series_ids_for_metric(mid)
        _, triples = tsdb.store.metric_index(mid).arrays()
        return sids, TagMatrix.from_triples(sids, triples)

    def hosts(self, tsdb, sids, mask):
        out = []
        for s in sids[mask]:
            rec = tsdb.store.series(int(s))
            tags = {tsdb.uids.tag_names.get_name(k):
                    tsdb.uids.tag_values.get_name(v)
                    for k, v in rec.tags}
            out.append(tags.get("host", "<none>"))
        return sorted(out)

    def test_literal_filter(self, tsdb):
        sids, tags = self.seed(tsdb)
        ev = FilterEvaluator(tsdb.uids)
        mask = ev.apply([get_filter("host", "literal_or(web01)")], tags)
        assert self.hosts(tsdb, sids, mask) == ["web01"]

    def test_wildcard_filter(self, tsdb):
        sids, tags = self.seed(tsdb)
        ev = FilterEvaluator(tsdb.uids)
        mask = ev.apply([get_filter("host", "wildcard(web*)")], tags)
        assert self.hosts(tsdb, sids, mask) == ["web01", "web02"]

    def test_missing_tag_never_matches_value_filter(self, tsdb):
        sids, tags = self.seed(tsdb)
        ev = FilterEvaluator(tsdb.uids)
        mask = ev.apply([get_filter("host", "regexp(.*)")], tags)
        # the host-less series must not match
        assert "<none>" not in self.hosts(tsdb, sids, mask)

    def test_not_key_matches_only_absent(self, tsdb):
        sids, tags = self.seed(tsdb)
        ev = FilterEvaluator(tsdb.uids)
        mask = ev.apply([get_filter("host", "not_key()")], tags)
        assert self.hosts(tsdb, sids, mask) == ["<none>"]

    def test_filters_on_same_key_and_together(self, tsdb):
        # every filter must pass, same-key included (reference chain)
        sids, tags = self.seed(tsdb)
        ev = FilterEvaluator(tsdb.uids)
        mask = ev.apply([get_filter("host", "wildcard(web*)"),
                         get_filter("host", "not_literal_or(web02)")],
                        tags)
        assert self.hosts(tsdb, sids, mask) == ["web01"]

    def test_filters_across_keys_and_together(self, tsdb):
        sids, tags = self.seed(tsdb)
        ev = FilterEvaluator(tsdb.uids)
        mask = ev.apply([get_filter("host", "wildcard(*)"),
                         get_filter("dc", "literal_or(lax)")], tags)
        assert self.hosts(tsdb, sids, mask) == ["web01", "web02"]

    def test_unknown_tag_key_matches_nothing(self, tsdb):
        sids, tags = self.seed(tsdb)
        ev = FilterEvaluator(tsdb.uids)
        mask = ev.apply([get_filter("nosuch", "literal_or(x)")], tags)
        assert not mask.any()

    def test_unknown_tag_key_not_key_matches_all(self, tsdb):
        sids, tags = self.seed(tsdb)
        ev = FilterEvaluator(tsdb.uids)
        mask = ev.apply([get_filter("nosuch", "not_key()")], tags)
        assert mask.all()


# ---------------------------------------------------------------------------
# the four ways a filter becomes a mask (PR 35, PR 41) against the
# reference's per-row predicate (ref: TagVFilter.match(tags) post-scan):
# a filter that holds exact names resolves them through the dictionary's
# forward map and reads no stored name; one that matches stored names
# does so over the plan index's name table, or, over a plain matrix,
# walks the key's distinct values; ``*``, ``.*`` and not_key read the
# column alone
# ---------------------------------------------------------------------------

BASE = 1356998400
#: "web01" is a host and a dc; "lax" a dc only; "ghost" has a UID and
#: no series; "nosuch" / "nothere" have none; the fourth series has no
#: host; the sixth differs from the second in case alone
FLEET = [{"host": "web01", "dc": "lax"},
         {"host": "web02", "dc": "lax"},
         {"host": "db01", "dc": "sjc"},
         {"dc": "sjc"},
         {"host": "z\u00fcrich-01", "dc": "m\u00fcnchen"},
         {"host": "WEB02", "dc": "LAX"},
         {"host": "db02", "dc": "web01"}]
LITERALS = ["web01", "web01|nosuch", "lax", "nosuch|nothere",
            "web01||db01", "web01|", "z\u00fcrich-01", "ghost",
            "WEB02|db02", "lax|web01"]
EXPRS = (
    [(t, e) for t in ("literal_or", "iliteral_or", "not_literal_or",
                      "not_iliteral_or") for e in LITERALS]
    + [(t, e) for t in ("wildcard", "iwildcard")
       for e in ("web*", "*", "*01", "*\u00fc*", "W*", "*nosuch*")]
    + [("regexp", e) for e in ("web\\d+", ".*", "^z.*", "nosuch")]
    + [("not_key", "")])
#: two filters on one key, filters on two keys: all must pass
CHAINS = [
    [("host", "literal_or(web01|web02|db01)"),
     ("host", "not_literal_or(web02)")],
    [("host", "not_literal_or(web01)"), ("host", "wildcard(*0*)")],
    [("host", "literal_or(web01|db02)"), ("dc", "literal_or(web01)")],
    [("host", "not_literal_or(db01)"), ("dc", "not_literal_or(lax)")],
    [("host", "not_key()"), ("dc", "literal_or(sjc)")],
    [("host", "literal_or(web01)"), ("rack", "not_key()")],
    [("host", "iliteral_or(web02)"), ("dc", "not_literal_or(LAX)")],
    [("host", "web01|db01"), ("dc", "*")],      # old style
    [("host", "web01")],                        # old style, exact
]
CASES = ([[("host", f"{t}({e})")] for t, e in EXPRS]
         # a key some series lack, a key of every series, an unknown key
         + [[("dc", f"{t}({e})")] for t, e in EXPRS]
         + [[("rack", f"{t}({e})")] for t, e in EXPRS[::7]]
         + CHAINS)


def fleet(tsdb):
    """(uids, the metric's whole TagMatrix, each series' tags by name)"""
    for i, tags in enumerate(FLEET):
        tsdb.add_point("m", BASE, i, tags)
    tsdb.add_point("other", BASE, 1, {"host": "ghost"})
    mid = tsdb.uids.metrics.get_id("m")
    sids = tsdb.store.series_ids_for_metric(mid)
    _, triples = tsdb.store.metric_index(mid).arrays()
    return tsdb.uids, TagMatrix.from_triples(sids, triples), sids


def tags_by_name(tsdb, sids):
    uids = tsdb.uids
    return [{uids.tag_names.get_name(k): uids.tag_values.get_name(v)
             for k, v in tsdb.store.series(int(s)).tags} for s in sids]


def predicate_walk(filters, rows):
    """The reference's per-row chain: every filter must pass; a series
    without the key passes a not_key alone."""
    return np.array([all(
        f.match_value(tags[f.tagk]) if f.tagk in tags else f.match_absent
        for f in filters) for tags in rows], dtype=bool)


SOURCES = {"matrix": lambda tags: tags,
           "index": lambda tags: PlanIndex(tags.num_series, tags)}


@pytest.mark.parametrize("source", sorted(SOURCES))
@pytest.mark.parametrize(
    "case", CASES, ids=[" ".join(f"{k}={e}" for k, e in c) for c in CASES])
def test_every_way_selects_what_the_predicate_selects(tsdb, source, case):
    uids, tags, sids = fleet(tsdb)
    filters = [get_filter(k, e) for k, e in case]
    tally = Counter()
    mask = FilterEvaluator(uids).apply(filters, SOURCES[source](tags),
                                       tally)
    assert mask.dtype == bool and mask.shape == (len(FLEET),)
    assert mask.tolist() == predicate_walk(
        filters, tags_by_name(tsdb, sids)).tolist()
    # each filter evaluated went exactly one way; only a walk and the
    # build of a table (a fresh index's first) read names of stored
    # values, and only an index has a table
    ways = sum(tally[f"resolve_{w}"]
               for w in ("ids", "table", "walk", "presence"))
    assert 1 <= ways <= len(filters)
    by_name = tally["resolve_walk"] + tally["resolve_table"]
    assert (tally["names_read"] > 0) == (by_name > 0)
    assert tally["resolve_table" if source == "matrix"
                 else "resolve_walk"] == 0


@pytest.mark.parametrize("source", sorted(SOURCES))
@pytest.mark.parametrize("ftype", ["literal_or", "not_literal_or",
                                   "iliteral_or", "not_iliteral_or",
                                   "wildcard", "iwildcard", "regexp"])
def test_a_renamed_value_shows_in_the_next_apply(tsdb, source, ftype):
    """Nothing of the dictionary outlives a rename: the same source
    answers by the new name once a value is renamed (a matrix reads
    the live dictionary, an index's name table carries the
    dictionary's generation)."""
    uids, tags, sids = fleet(tsdb)
    src = SOURCES[source](tags)
    ev = FilterEvaluator(uids)
    exprs = {"regexp": ("web01", "web99"),
             "wildcard": ("web01*", "web99*"),
             "iwildcard": ("WEB01*", "WEB99*")}.get(
        ftype, ("web01|db01", "web99|db01"))
    old, new = (get_filter("host", f"{ftype}({e})") for e in exprs)
    before = [ev.apply([f], src).tolist() for f in (old, new)]
    uids.tag_values.rename("web01", "web99")
    rows = tags_by_name(tsdb, sids)
    assert rows[0]["host"] == "web99" and rows[6]["dc"] == "web99"
    after = [ev.apply([f], src).tolist() for f in (old, new)]
    assert after == [predicate_walk([f], rows).tolist()
                     for f in (old, new)]
    assert before != after and before[0][0] != after[0][0]


# ---------------------------------------------------------------------------
# the name table's matcher (PR 41) against ``TagVFilter.match_value`` a
# name: the five filters that match a stored NAME, over names chosen to
# try it
# ---------------------------------------------------------------------------

#: mixed lengths; "a" is shorter than ``first + last`` of ``a*a``;
#: "abc" against ``ab*bc`` would need its ``b`` twice; ``?`` ``[`` ``.``
#: ``*`` as characters of a name; case pairs; "İ" (2 bytes) whose
#: ``lower()`` is "i̇" (3 bytes); "ẞ" (3 bytes) whose
#: ``lower()`` is "ß" (2)
TABLE_NAMES = [
    "a", "b", "ab", "ba", "aa", "abc", "abcabc", "abcbc", "aXa", "axa",
    "abab", "aabb", "bbaa", "web01", "web02", "WEB02", "Web02",
    "web-01.prod", "WEB-01.PROD", "web?1x", "webx12", "h[01]x", "h0x",
    "h.x", "hax", "a*b", "x", "db-01", "zürich-01", "ZÜRICH-01",
    "İstanbul", "i̇stanbul", "istanbul", "straße",
    "STRASSE", "STRAẞE", "a-rather-longer-name-than-the-others.01"]
TABLE_GLOBS = [
    "a*", "*a", "*b*", "a*a", "ab*bc", "ab*ab", "abc*abc", "a*b*c",
    "a**c", "**a", "a**", "*a*b*", "*ab*ab*", "a*b*a*b", "*web*1*",
    "web?1*", "h[01]*", "h.x*", "*.prod", "*.PROD", "W*", "w*02",
    "a\\*b*", "a*\\*b", "*İ*", "i̇*", "*ß*", "*SS*",
    "*ẞ*", "zü*", "*Ü*1", "*nosuch*",
    "*a-rather-longer-name-than-the-others.01-and-then-some",
    "*-name-*-the-*.0*"]
TABLE_LITERALS = [
    "WEB02", "web02|İSTANBUL", "İSTANBUL", "nosuch",
    "a|ab|abc|A", "STRASSE|straße", "STRAẞE", "h[01]x|H.X",
    "a*b|x"]
TABLE_REGEXPS = [
    "web\\d+", "^a.*c$", "[aA][xX]?a$", "\\S+\\s?", ".*\\.prod$",
    "(?i)web02", "h[01]x", "h\\[01\\]x", "[^a]+$", "a\\*b", "İ",
    "nosuch", "a|ab"]
TABLE_CASES = (
    [(t, e) for t in ("wildcard", "iwildcard") for e in TABLE_GLOBS]
    + [(t, e) for t in ("iliteral_or", "not_iliteral_or")
       for e in TABLE_LITERALS]
    + [("regexp", e) for e in TABLE_REGEXPS])
#: two filters on one key, on two keys, the not_ form beside a pattern
TABLE_CHAINS = [
    [("host", "wildcard(a*)"), ("host", "not_iliteral_or(AB|ABC)")],
    [("host", "iwildcard(W*)"), ("host", "regexp(.*2$)")],
    [("host", "wildcard(*b*)"), ("host", "iwildcard(*A*)"),
     ("host", "not_literal_or(abab)")],
    [("host", "iliteral_or(WEB02)"), ("one", "wildcard(on*)")],
    [("host", "regexp(^web)"), ("one", "iliteral_or(NOPE)")],
    [("host", "wildcard(a*)"), ("nokey", "wildcard(a*)")],
    [("host", "wildcard(a*)"), ("elsewhere", "iwildcard(a*)")],
    [("one", "not_iliteral_or(ONLY)")],
    [("one", "iwildcard(*ONLY)")],
    [("some", "wildcard(*a*)")],
]


def name_fleet(names=TABLE_NAMES):
    """(uids, a plan index, each series' tags by name): a series a
    name under ``host``, each under ``one`` with the same single
    value, every third under ``some``, two series with no ``host``;
    ``elsewhere`` is a key with a UID that no series holds, ``nokey``
    has none. Made without a TSDB: a store would refuse ``?``, ``[``
    and ``*`` in a name, the dictionary and a rename do not."""
    from opentsdb_tpu.core.uid import UidRegistry
    uids = UidRegistry()
    rows = [{"host": name, "one": "only"} for name in names]
    for row in rows[::3]:
        row["some"] = row["host"][::-1]
    rows += [{"one": "only"}, {"one": "only", "some": "a"}]
    uids.tag_names.get_or_create_id("elsewhere")
    pairs = [[(uids.tag_names.get_or_create_id(k),
               uids.tag_values.get_or_create_id(v))
              for k, v in row.items()] for row in rows]
    tags = TagMatrix.from_pairs(pairs)
    return uids, PlanIndex(tags.num_series, tags), rows


def assert_the_table_agrees(case):
    uids, index, rows = name_fleet()
    filters = [get_filter(k, e) for k, e in case]
    tally = Counter()
    mask = FilterEvaluator(uids).apply(filters, index, tally)
    assert mask.tolist() == predicate_walk(filters, rows).tolist()
    assert tally["resolve_walk"] == 0
    return tally


@pytest.mark.parametrize(
    "kind, expr", TABLE_CASES, ids=[f"{t}({e})" for t, e in TABLE_CASES])
def test_the_name_table_selects_what_match_value_selects(kind, expr):
    tally = assert_the_table_agrees([("host", f"{kind}({expr})")])
    assert tally["resolve_table"] == 1
    assert tally["names_read"] == len(TABLE_NAMES)


@pytest.mark.parametrize(
    "case", TABLE_CHAINS,
    ids=[" ".join(f"{k}={e}" for k, e in c) for c in TABLE_CHAINS])
def test_filters_over_name_tables_and_together(case):
    assert_the_table_agrees(case)


@pytest.mark.parametrize("kind", ["wildcard", "iwildcard"])
def test_every_small_pattern_over_every_small_name(kind):
    """Every pattern of up to five of ``a``, ``B``, ``*`` with a ``*``
    that is not all ``*``, over every name of up to five of ``a``,
    ``b``, ``B`` (the middle parts' leftmost placement, overlaps, a
    name too short for its pattern), one table for all of them."""
    from itertools import product
    names = ["".join(p) for n in range(1, 6)
             for p in product("abB", repeat=n)]
    uids, index, rows = name_fleet(names)
    ev = FilterEvaluator(uids)
    patterns = ["".join(p) for n in range(2, 6)
                for p in product("aB*", repeat=n)
                if "*" in p and set(p) != {"*"}]
    assert len(patterns) == 296
    for pattern in patterns:
        f = get_filter("host", f"{kind}({pattern})")
        assert ev.apply([f], index).tolist() == \
            predicate_walk([f], rows).tolist(), pattern


def test_cold_tables_are_built_once_by_threads_side_by_side():
    """Eight threads ask a cold index for one key's table, plain and
    folded, while the interpreter switches often: every name is read
    once, every thread gets the one table, every mask is right."""
    import sys
    import threading
    uids, index, rows = name_fleet()
    tagv, reads = uids.tag_values, []
    real = tagv.get_name
    tagv.get_name = lambda uid: reads.append(uid) or real(uid)
    ev = FilterEvaluator(uids)
    exprs = ["wildcard(a*)", "iwildcard(*B*)", "regexp(^web)",
             "iliteral_or(WEB02)"] * 2
    got, tables = {}, []
    start = threading.Barrier(len(exprs))

    def ask(i, expr):
        start.wait(10)
        f = get_filter("host", expr)
        got[i] = (f, ev.apply([f], index).tolist())
        tables.append(index.name_table(
            uids.tag_names.get_id("host"), tagv, True)[0])

    was = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        threads = [threading.Thread(target=ask, args=(i, e))
                   for i, e in enumerate(exprs)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(30)
        assert not any(t.is_alive() for t in threads)
    finally:
        sys.setswitchinterval(was)
    assert len(got) == len(exprs)
    for f, mask in got.values():
        assert mask == predicate_walk([f], rows).tolist()
    assert sorted(reads) == sorted(set(reads))
    assert len(reads) == len(TABLE_NAMES)
    assert all(t is tables[0] for t in tables)


def test_a_key_with_one_very_long_name_is_walked():
    """The byte matrix pads every name to the longest: where that
    costs more than ``NameArrays.MAX_PAD`` times the names' own bytes
    the key gets none and its patterns walk, a request, and say so;
    ``regexp`` needs no matrix."""
    from opentsdb_tpu.query.filters import NameArrays
    names = [f"h{i:03d}" for i in range(100)] + ["h" * 4096]
    assert len(names) * 4096 > NameArrays.MAX_PAD * (400 + 4096)
    uids, index, rows = name_fleet(names)
    ev = FilterEvaluator(uids)
    for expr, way, read in (
            ("wildcard(h00*)", "walk", 2 * len(names)),
            ("iwildcard(H00*)", "walk", len(names)),
            ("iliteral_or(H007)", "walk", len(names)),
            ("regexp(h00)", "table", 0)):
        f, tally = get_filter("host", expr), Counter()
        assert ev.apply([f], index, tally).tolist() == \
            predicate_walk([f], rows).tolist()
        assert tally["resolve_" + way] == 1, expr
        assert tally["names_read"] == read, expr
    # evenly long names are no matter of length
    uids, index, rows = name_fleet(["x" * 4000 + str(i)
                                    for i in range(10)])
    tally = Counter()
    f = get_filter("host", "wildcard(*x7)")
    assert FilterEvaluator(uids).apply([f], index, tally).sum() == 1
    assert tally["resolve_table"] == 1


class TestHowAFilterIsResolved:
    """The exact way reads no stored name, whatever the key holds; a
    pattern reads them once, into the plan index's name table, and
    again only when the dictionary says a name changed."""

    N = 300

    @pytest.fixture
    def served(self):
        tsdb = TSDB(Config(**{
            "tsd.core.auto_create_metrics": "true",
            "tsd.tpu.warmup": "false", "tsd.trace.sample": "1",
            "tsd.query.cache.enable": "false"}))
        text = "".join(
            f"sys.f {BASE + 10 * j} {i + j} host=h{i:03d}x dc=d{i % 3}\n"
            for i in range(self.N) for j in range(3))
        written, errors = tsdb.import_buffer(text.encode(), durable=False)
        assert written == 3 * self.N and not errors
        yield tsdb, HttpRpcRouter(tsdb)
        tsdb.shutdown()

    @staticmethod
    def spy(tsdb, monkeypatch):
        tagv, reads = tsdb.uids.tag_values, []
        real = tagv.get_name
        monkeypatch.setattr(
            tagv, "get_name", lambda uid: reads.append(uid) or real(uid))
        return reads

    @staticmethod
    def post(router, *subs):
        """The response to one request of a sub-query a list of
        ``(tagk, type, expression)``."""
        body = json.dumps({
            "start": BASE * 1000, "end": (BASE + 60) * 1000,
            "queries": [{"metric": "sys.f", "aggregator": "none",
                         "filters": [
                             {"type": t, "tagk": k, "filter": e,
                              "groupBy": False}
                             for k, t, e in filters]}
                        for filters in subs]}).encode()
        return router.handle(HttpRequest(
            method="POST", path="/api/query", params={}, headers={},
            body=body))

    @staticmethod
    def spans(router, resp, name):
        data = router.tsdb.tracer.get(resp.headers["X-TSD-Trace-Id"])
        return [s.tags for s in data.spans if s.name == name]

    @classmethod
    def ask(cls, router, *filters):
        """(hosts answered, the ``query.plan`` span's tags)"""
        resp = cls.post(router, filters)
        assert resp.status == 200, resp.body
        (plan,) = cls.spans(router, resp, "query.plan")
        return sorted(r["tags"]["host"] for r in json.loads(resp.body)), \
            plan

    @classmethod
    def resolve(cls, router, *filters):
        """(hosts answered, the ``query.filter_resolve`` spans' tags)"""
        resp = cls.post(router, filters)
        assert resp.status == 200, resp.body
        return sorted(r["tags"]["host"] for r in json.loads(resp.body)), \
            cls.spans(router, resp, "query.filter_resolve")

    @staticmethod
    def stats(router, metric, tag):
        resp = router.handle(HttpRequest(
            method="GET", path="/api/stats", params={}, headers={},
            body=b""))
        return {r["tags"][tag]: r["value"]
                for r in json.loads(resp.body) if r["metric"] == metric}

    @classmethod
    def resolved(cls, router):
        return cls.stats(router, "tsd.query.filter", "resolve")

    @classmethod
    def tables(cls, router):
        return cls.stats(router, "tsd.query.filter.table", "state")

    def test_exact_names_read_no_stored_name(self, served, monkeypatch):
        tsdb, router = served
        assert self.resolved(router) == {"ids": 0, "table": 0,
                                         "walk": 0, "presence": 0}
        reads = self.spy(tsdb, monkeypatch)
        hosts, plan = self.ask(
            router, ("host", "literal_or", "h007x|h123x|nosuch"))
        assert hosts == ["h007x", "h123x"]
        assert (plan["names_read"], plan["resolve_ids"]) == (0, 1)
        hosts, plan = self.ask(
            router, ("host", "not_literal_or", "h007x|h123x"),
            ("dc", "wildcard", "*"))
        assert len(hosts) == self.N - 2 and "h007x" not in hosts
        assert (plan["names_read"], plan["resolve_ids"],
                plan["resolve_presence"]) == (0, 1, 1)
        assert "resolve_walk" not in plan and "resolve_table" not in plan
        assert self.resolved(router) == {"ids": 2, "table": 0,
                                         "walk": 0, "presence": 1}
        assert self.tables(router) == {"hit": 0, "built": 0}
        # (the serializer names the answered series' tags: not the plan)
        del reads[:]
        mask = FilterEvaluator(tsdb.uids).apply(
            [get_filter("host", "literal_or(h007x|h123x)"),
             get_filter("host", "not_literal_or(h007x)")],
            TagMatrix.from_triples(*self.index_of(tsdb)))
        assert mask.sum() == 1 and reads == []

    @staticmethod
    def index_of(tsdb):
        mid = tsdb.uids.metrics.get_id("sys.f")
        return (tsdb.store.series_ids_for_metric(mid),
                tsdb.store.metric_index(mid).arrays()[1])

    def test_a_pattern_walks_every_distinct_value(self, served,
                                                  monkeypatch):
        """Once: over a plain matrix a pattern walks the key's N names, a
        request; over the plan index the first request reads them
        into the key's table (``table=built``) and the second reads
        none (``table=hit``); the folded arrays are built from the
        table's names, a new key's table from its own."""
        tsdb, router = served
        reads = self.spy(tsdb, monkeypatch)
        matrix = TagMatrix.from_triples(*self.index_of(tsdb))
        for _ in range(2):
            del reads[:]
            tally = Counter()
            mask = FilterEvaluator(tsdb.uids).apply(
                [get_filter("host", "wildcard(*7x*)")], matrix, tally)
            assert mask.sum() == 30 and len(reads) == self.N
            assert tally == {"resolve_walk": 1, "names_read": self.N}
        del reads[:]
        hosts, plan = self.ask(router, ("host", "wildcard", "*7x*"))
        assert len(hosts) == 30
        assert (plan["names_read"], plan["resolve_table"]) == (self.N, 1)
        assert "resolve_ids" not in plan and "resolve_walk" not in plan
        assert len(reads) >= self.N     # and the serializer's own
        del reads[:]
        hosts, found = self.resolve(router, ("host", "wildcard", "*7x*"))
        assert len(hosts) == 30
        assert found == [{"way": "table", "table": "hit",
                          "names_read": 0, "matched": 30}]
        hosts, found = self.resolve(router, ("host", "wildcard", "h00*"))
        assert len(hosts) == 10 and found[0]["table"] == "hit"
        # the serializer names what it answers (30 hosts of 3 dcs,
        # 10 of 3); the plan read nothing
        assert len(reads) == 33 + 13
        hosts, plan = self.ask(router, ("host", "iliteral_or", "H007X"),
                               ("dc", "regexp", "d[01]"))
        assert hosts == ["h007x"]
        assert (plan["names_read"], plan["resolve_table"]) == (3, 2)
        hosts, found = self.resolve(
            router, ("host", "not_iliteral_or", "H007X|h008x"),
            ("dc", "regexp", "d[01]"))
        assert len(hosts) == 199 and "h007x" not in hosts
        assert found == [
            {"way": "table", "table": "hit", "names_read": 0,
             "matched": 2},
            {"way": "table", "table": "hit", "names_read": 0,
             "matched": 2}]
        assert self.resolved(router) == {"ids": 0, "table": 7,
                                         "walk": 0, "presence": 0}
        # the host table, its folded arrays, the dc table
        assert self.tables(router) == {"hit": 4, "built": 3}

    def test_a_deleted_uid_stops_the_walk_alone(self, served):
        """A stored value whose UID left the dictionary: the exact way
        reads no stored name and answers, as the reference's
        literal_or does; a walk of that key raises as it always did,
        and so does the build of its table, whether the delete came
        before the first table or after it."""
        tsdb, _ = served
        tags = TagMatrix.from_triples(*self.index_of(tsdb))
        warm = PlanIndex(tags.num_series, tags)
        ev = FilterEvaluator(tsdb.uids)
        assert ev.apply([get_filter("host", "wildcard(h0*)")],
                        warm).sum() == 100
        tsdb.uids.tag_values.delete("h200x")
        cold = PlanIndex(tags.num_series, tags)
        for source in (tags, cold, warm):
            for expr, count in (("literal_or(h007x|h200x)", 1),
                                ("not_literal_or(h007x|h200x)",
                                 self.N - 1)):
                assert ev.apply([get_filter("host", expr)],
                                source).sum() == count
            for expr in ("wildcard(h0*)", "iliteral_or(h007x)",
                         "regexp(h.*)"):
                with pytest.raises(NoSuchUniqueId):
                    ev.apply([get_filter("host", expr)], source)
            # another key's names are all there
            assert ev.apply([get_filter("dc", "wildcard(*1)")],
                            source).sum() == self.N // 3

    # -- "no stale name table: a renamed value shows in the next
    # request" (benchmark/configs/fleet-1m-wildcard.json, guarantees)

    def test_a_renamed_value_shows_in_the_next_request(self, served):
        tsdb, router = served
        hosts, found = self.resolve(router, ("host", "wildcard", "h00*"))
        assert len(hosts) == 10 and "h007x" in hosts
        assert found[0]["table"] == "built"
        assert self.resolve(router, ("host", "wildcard", "z*")) == ([], [
            {"way": "table", "table": "hit", "names_read": 0,
             "matched": 0}])
        tsdb.uids.tag_values.rename("h007x", "z007x")
        hosts, found = self.resolve(router, ("host", "wildcard", "h00*"))
        assert len(hosts) == 9 and "h007x" not in hosts
        assert found == [{"way": "table", "table": "built",
                          "names_read": self.N, "matched": 9}]
        hosts, found = self.resolve(router, ("host", "iwildcard", "Z*"))
        assert hosts == ["z007x"]
        # the names were read for the rename already: the folded
        # arrays come from them
        assert found == [{"way": "table", "table": "built",
                          "names_read": 0, "matched": 1}]
        tsdb.uids.tag_values.rename("z007x", "H007X")
        for kind, expr, want in (
                ("iwildcard", "H007*", ["H007X"]),
                ("iliteral_or", "h007x", ["H007X"]),
                ("regexp", "[hH]007", ["H007X"]),
                ("wildcard", "h007*", [])):
            hosts, found = self.resolve(router, ("host", kind, expr))
            assert hosts == want, kind
            assert found[0]["names_read"] == \
                (self.N if kind == "iwildcard" else 0)
        assert self.tables(router) == {"hit": 4, "built": 4}

    def test_a_deleted_value_stops_the_next_request(self, served):
        tsdb, router = served
        hosts, _ = self.resolve(router, ("host", "wildcard", "h20*"))
        assert "h200x" in hosts
        tsdb.uids.tag_values.delete("h200x")
        for kind, expr in (("wildcard", "h00*"), ("iwildcard", "H00*"),
                           ("iliteral_or", "H007X"), ("regexp", "h00")):
            resp = self.post(router, [("host", kind, expr)])
            assert resp.status != 200, kind
            assert "No such unique ID" in resp.body.decode(), kind
        # another key's table, and the exact way, are not concerned
        hosts, found = self.resolve(router, ("dc", "wildcard", "*2"),
                                    ("host", "literal_or", "h002x"))
        assert hosts == ["h002x"]
        assert [f["way"] for f in found] == ["table", "ids"]

    def test_a_new_series_shows_in_the_next_request(self, served):
        tsdb, router = served
        hosts, plan = self.ask(router, ("host", "wildcard", "h99*"))
        assert hosts == [] and plan["index"] == "built"
        hosts, plan = self.ask(router, ("host", "wildcard", "h99*"))
        assert hosts == [] and plan["index"] == "hit"
        assert plan["names_read"] == 0
        tsdb.add_point("sys.f", BASE, 1, {"host": "h999new", "dc": "d9"})
        hosts, plan = self.ask(router, ("host", "wildcard", "h99*"))
        assert hosts == ["h999new"]
        # the new tagv id reached the column with a new series: the
        # plan index went whole, its tables with it
        assert (plan["index"], plan["names_read"],
                plan["resolve_table"]) == ("built", self.N + 1, 1)

    def test_two_sub_queries_build_a_cold_table_once(self, served):
        tsdb, router = served
        self.ask(router, ("host", "literal_or", "h007x"))   # the index
        assert self.tables(router) == {"hit": 0, "built": 0}
        resp = self.post(router, [("host", "wildcard", "h00*")],
                         [("host", "iwildcard", "*7X")])
        assert resp.status == 200, resp.body
        assert len(json.loads(resp.body)) == 10 + 30
        found = self.spans(router, resp, "query.filter_resolve")
        assert len(found) == 2
        assert sum(f["names_read"] for f in found) == self.N
        assert self.spans(router, resp, "query.plan")[0]["index"] == "hit"
        # whichever came first read the names; the folded arrays are
        # the second sub-query's to build either way
        assert self.resolved(router)["table"] == 2
        built = self.tables(router)["built"]
        assert built in (1, 2) and self.tables(router)["hit"] == 2 - built

    def test_a_key_with_one_very_long_name_says_walk(self, served):
        tsdb, router = served
        tsdb.uids.tag_values.rename("h299x", "h" * 30000)
        hosts, found = self.resolve(router, ("host", "wildcard", "h00*"))
        assert len(hosts) == 10
        # the names read once to find them too uneven, once to walk
        assert found == [{"way": "walk", "names_read": 2 * self.N,
                          "matched": 10}]
        hosts, found = self.resolve(router, ("host", "wildcard", "*7x"))
        assert len(hosts) == 30
        assert found == [{"way": "walk", "names_read": self.N,
                          "matched": 30}]
        assert self.resolved(router) == {"ids": 0, "table": 0,
                                         "walk": 2, "presence": 0}
        assert self.tables(router) == {"hit": 0, "built": 0}
