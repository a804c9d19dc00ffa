"""Tag-value filter tests.

Mirrors the reference suites under ``test/query/filter/``
(TestTagVFilter, TestTagVLiteralOrFilter, TestTagVRegexFilter,
TestTagVWildcardFilter, TestTagVNotLiteralOrFilter,
TestTagVNotKeyFilter; ref: src/query/filter/TagVFilter.java:70).
"""

import numpy as np
import pytest

from opentsdb_tpu.query.engine import TagMatrix
from opentsdb_tpu.query.filters import (FilterEvaluator, build_filter,
                                        filter_types, get_filter,
                                        tags_to_filters)


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
