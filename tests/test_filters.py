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
from opentsdb_tpu.query.engine import PlanIndex, TagMatrix
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
# the three ways a filter becomes a mask (PR 35) against the reference's
# per-row predicate (ref: TagVFilter.match(tags) post-scan): a filter
# that holds exact names resolves them through the dictionary's forward
# map and reads no stored name; the others walk the key's distinct
# values; ``*``, ``.*`` and not_key read the column alone
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
    # each filter evaluated went exactly one way, and only a walk
    # reads names of stored values
    ways = sum(tally[f"resolve_{w}"] for w in ("ids", "walk", "presence"))
    assert 1 <= ways <= len(filters)
    assert (tally["names_read"] > 0) == (tally["resolve_walk"] > 0)


@pytest.mark.parametrize("source", sorted(SOURCES))
@pytest.mark.parametrize("ftype", ["literal_or", "not_literal_or",
                                   "iliteral_or", "not_iliteral_or",
                                   "wildcard", "iwildcard", "regexp"])
def test_a_renamed_value_shows_in_the_next_apply(tsdb, source, ftype):
    """Nothing of the dictionary is kept between two requests: the
    same source answers by the new name once a value is renamed."""
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


class TestHowAFilterIsResolved:
    """The exact way reads no stored name, whatever the key holds."""

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
    def ask(router, *filters):
        """(hosts answered, the ``query.plan`` span's tags)"""
        body = json.dumps({
            "start": BASE * 1000, "end": (BASE + 60) * 1000,
            "queries": [{"metric": "sys.f", "aggregator": "none",
                         "filters": [
                             {"type": t, "tagk": k, "filter": e,
                              "groupBy": False}
                             for k, t, e in filters]}]}).encode()
        resp = router.handle(HttpRequest(
            method="POST", path="/api/query", params={}, headers={},
            body=body))
        assert resp.status == 200, resp.body
        data = router.tsdb.tracer.get(resp.headers["X-TSD-Trace-Id"])
        (plan,) = [s.tags for s in data.spans if s.name == "query.plan"]
        return sorted(r["tags"]["host"] for r in json.loads(resp.body)), \
            plan

    @staticmethod
    def resolved(router):
        resp = router.handle(HttpRequest(
            method="GET", path="/api/stats", params={}, headers={},
            body=b""))
        return {r["tags"]["resolve"]: r["value"]
                for r in json.loads(resp.body)
                if r["metric"] == "tsd.query.filter"}

    def test_exact_names_read_no_stored_name(self, served, monkeypatch):
        tsdb, router = served
        assert self.resolved(router) == {"ids": 0, "walk": 0,
                                         "presence": 0}
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
        assert "resolve_walk" not in plan
        assert self.resolved(router) == {"ids": 2, "walk": 0,
                                         "presence": 1}
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
        tsdb, router = served
        reads = self.spy(tsdb, monkeypatch)
        mask = FilterEvaluator(tsdb.uids).apply(
            [get_filter("host", "wildcard(*7x*)")],
            TagMatrix.from_triples(*self.index_of(tsdb)))
        assert mask.sum() == 30 and len(reads) == self.N
        hosts, plan = self.ask(router, ("host", "wildcard", "*7x*"))
        assert len(hosts) == 30
        assert (plan["names_read"], plan["resolve_walk"]) == (self.N, 1)
        assert "resolve_ids" not in plan
        hosts, plan = self.ask(router, ("host", "iliteral_or", "H007X"),
                               ("dc", "regexp", "d[01]"))
        assert hosts == ["h007x"]
        assert (plan["names_read"], plan["resolve_walk"]) == \
            (self.N + 3, 2)
        assert self.resolved(router) == {"ids": 0, "walk": 3,
                                         "presence": 0}

    def test_a_deleted_uid_stops_the_walk_alone(self, served):
        """A stored value whose UID left the dictionary: the exact way
        reads no stored name and answers, as the reference's
        literal_or does; a walk of that key raises as it always did."""
        tsdb, _ = served
        tags = TagMatrix.from_triples(*self.index_of(tsdb))
        ev = FilterEvaluator(tsdb.uids)
        tsdb.uids.tag_values.delete("h200x")
        for expr, count in (("literal_or(h007x|h200x)", 1),
                            ("not_literal_or(h007x|h200x)", self.N - 1)):
            assert ev.apply([get_filter("host", expr)],
                            tags).sum() == count
        for expr in ("wildcard(h0*)", "iliteral_or(h007x)", "regexp(h.*)"):
            with pytest.raises(NoSuchUniqueId):
                ev.apply([get_filter("host", expr)], tags)
