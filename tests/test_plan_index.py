"""The plan index (PR 28): what the plan stage derives from a metric's
append-only tag index (the series x key matrix of tagv ids, each
column's distinct ids, the group labels of a group-by key set) is kept
per (store, metric) and versioned by the metric's series count. The
same request through a cold index (built by it), a warm one (hit) and
the path that cannot use it (bypass: the labels come from the selected
columns, as every request's did before) must serialize to the same
bytes, and select the series a walk over the records selects.

PR 30: the assemble stage reads each group's common and aggregated
tags from the index too (a group-major copy of the tag columns beside
each cached labelling), where the request's matrix came out of the
index and selects no small part of it. The index way, the matrix way
(bypass: a sort of the request's own rows) and a walk over the records
must agree on ``tags``, ``aggregateTags``, ``tsuids`` and every byte.
"""

import itertools
import json
import threading

import numpy as np
import pytest

from opentsdb_tpu import TSDB, Config
from opentsdb_tpu.query import plan as plan_mod
from opentsdb_tpu.query.engine import QueryEngine
from opentsdb_tpu.query.plan import (GroupLayout, PlanIndex, TagMatrix,
                                     group_labels, group_tag_summary)
from opentsdb_tpu.query.filters import build_filter
from opentsdb_tpu.tsd.http_api import HttpRequest, HttpRpcRouter

BASE = 1356998400
BACKENDS = ["native", "memory"]
DCS = ["d3", "d0", "d2", "d1"]      # tagv ids ascend in THIS order
SERIES = 48


def lines():
    """48 series of one metric. dc ids ascend against their names; a
    sixth of the series has no rack, every eighth no dc."""
    out = []
    for h in range(SERIES):
        tags = [f"host=h{h:02d}", f"fleet=f{h % 2}"]
        if h % 8 != 7:
            tags.append(f"dc={DCS[h % 4]}")
        if h % 6 != 5:
            tags.append(f"rack=r{h % 5}")
        out += [f"sys.plan {BASE + i * 20} {h + i} {' '.join(tags)}\n"
                for i in range(12)]
    return "".join(out).encode()


class Served:
    """A TSDB behind its HTTP router, with the three ways through the
    plan stage."""

    def __init__(self, backend, monkeypatch):
        self.tsdb = TSDB(Config(**{
            "tsd.core.auto_create_metrics": "true",
            "tsd.tpu.warmup": "false", "tsd.trace.sample": "1",
            "tsd.query.cache.enable": "false",
            "tsd.storage.backend": backend}))
        self.router = HttpRpcRouter(self.tsdb)
        self.monkeypatch = monkeypatch
        for name in DCS + [f"r{i}" for i in range(5)]:
            self.tsdb.uids.tag_values.get_or_create_id(name)
        written, errors = self.tsdb.import_buffer(lines(),
                                                  durable=False)
        assert written == SERIES * 12 and not errors

    def query(self, *subs, **top):
        """(response body, the ``query.plan`` tags of each sub)."""
        body = json.dumps({
            "start": BASE * 1000, "end": (BASE + 240) * 1000,
            "queries": [{"metric": "sys.plan", "aggregator": "sum",
                         "downsample": "1m-avg", **sub}
                        for sub in subs], **top}).encode()
        resp = self.router.handle(HttpRequest(
            method="POST", path="/api/query", params={}, headers={},
            body=body))
        assert resp.status == 200, resp.body
        data = self.tsdb.tracer.get(resp.headers["X-TSD-Trace-Id"])
        plans = sorted((s.tags for s in data.spans
                        if s.name == "query.plan"),
                       key=lambda t: t["sub"])
        self.ways = [t["tags"] for t in sorted(
            (s.tags for s in data.spans if s.name == "query.assemble"),
            key=lambda t: t["sub"])]
        return resp.body, plans

    def cold(self, *subs, **top):
        self.tsdb._tagmat_cache.clear()
        body, plans = self.query(*subs, **top)
        assert "built" in [p["index"] for p in plans], plans
        return body, plans

    def warm(self, *subs, **top):
        body, plans = self.query(*subs, **top)
        assert [p["index"] for p in plans] == ["hit"] * len(plans)
        return body, plans

    def bypass(self, *subs, **top):
        # the selection is a copy of the index's array: not the index
        store = self.tsdb.store
        whole = store.series_ids_for_metric
        with self.monkeypatch.context() as m:
            m.setattr(store, "series_ids_for_metric",
                      lambda mid: whole(mid).copy())
            body, plans = self.query(*subs, **top)
        assert [p["index"] for p in plans] == ["bypass"] * len(plans)
        return body, plans

    def same_three_ways(self, *subs, **top):
        cold, plans = self.cold(*subs, **top)
        warm, warm_plans = self.warm(*subs, **top)
        bypass, bypass_plans = self.bypass(*subs, **top)
        assert cold == warm == bypass
        for a, b, c in zip(plans, warm_plans, bypass_plans):
            assert (a.get("series"), a.get("groups")) == \
                (b.get("series"), b.get("groups")) == \
                (c.get("series"), c.get("groups"))
        return json.loads(cold), plans

    def walked(self, filters, tsuids=False):
        """The series a walk over the records selects: every filter
        must pass on every series, by its own string predicate."""
        uids, store = self.tsdb.uids, self.tsdb.store
        keep = []
        for sid in store.series_ids_for_metric(
                uids.metrics.get_id("sys.plan")):
            rec = store.series(int(sid))
            tags = {uids.tag_names.get_name(k):
                    uids.tag_values.get_name(v) for k, v in rec.tags}
            if all(f.match_value(tags[f.tagk]) if f.tagk in tags
                   else f.match_absent for f in filters):
                keep.append((tags, uids.tsuid(
                    rec.metric_id, rec.tags).hex().upper())
                    if tsuids else tags)
        return keep

    def walked_groups(self, filters, gb):
        """{group-by values: (tags, aggregateTags, tsuids)} by the
        SpanGroup rule, member by member."""
        groups = {}
        for tags, tsuid in self.walked(
                [build_filter(f) for f in filters], tsuids=True):
            groups.setdefault(tuple(tags[k] for k in gb),
                              []).append((tags, tsuid))
        out = {}
        for key, members in groups.items():
            everywhere = set.intersection(
                *(set(tags) for tags, _ in members))
            values = {k: {tags[k] for tags, _ in members}
                      for k in everywhere}
            out[key] = (
                {k: v.pop() for k, v in values.items() if len(v) == 1},
                sorted(k for k, v in values.items() if len(v) > 1),
                [tsuid for _, tsuid in members])
        return out

    def index_matrix_and_walk(self, sub, **top):
        """The rows of one sub-query answered the index way, after the
        matrix way gave the same bytes and the walk the same tags."""
        sub = {"aggregator": "sum", **sub}
        self.query(sub, **top)             # the index and its layout
        body, _ = self.warm(sub, showTSUIDs=True, **top)
        assert self.ways == ["index"]
        assert body == self.bypass(sub, showTSUIDs=True, **top)[0]
        assert self.ways == ["matrix"]
        rows = json.loads(body)
        filters = sub.get("filters", [])
        gb = sorted({f["tagk"] for f in filters if f["groupBy"]})
        want = self.walked_groups(filters, gb)
        got = {tuple(r["tags"][k] for k in gb):
               (r["tags"], sorted(r["aggregateTags"]), r["tsuids"])
               for r in rows}
        assert got == want and len(rows) == len(want)
        return rows


@pytest.fixture
def served(request, monkeypatch):
    s = Served(getattr(request, "param", "native"), monkeypatch)
    yield s
    s.tsdb.shutdown()


def flt(type_, tagk, expr, group_by=False):
    return {"type": type_, "tagk": tagk, "filter": expr,
            "groupBy": group_by}


FILTERS = [("literal_or", "r1|r3"), ("iliteral_or", "R1|r3"),
           ("not_literal_or", "r2"), ("not_iliteral_or", "R2|r0"),
           ("wildcard", "*"), ("iwildcard", "R*3"),
           ("regexp", "r[12]"), ("not_key", "")]


@pytest.mark.parametrize("served", BACKENDS, indirect=True)
@pytest.mark.parametrize("group_by", [True, False],
                         ids=["groupby", "nogroup"])
@pytest.mark.parametrize("type_, expr", FILTERS,
                         ids=[t for t, _ in FILTERS])
def test_cold_warm_and_bypass_answer_the_same_bytes(served, type_,
                                                    expr, group_by):
    if type_ == "not_key":      # cannot group: dc groups beside it
        filters = [flt(type_, "rack", expr)] + \
            ([flt("wildcard", "dc", "*", True)] if group_by else [])
    else:
        filters = [flt(type_, "rack", expr, group_by)]
    rows, (plan,) = served.same_three_ways({"filters": filters})
    want = served.walked([build_filter(f) for f in filters])
    assert plan["series"] == len(want) > 0
    gb = [f["tagk"] for f in filters if f["groupBy"]]
    keys = {tuple(t[k] for k in gb) for t in want}
    assert plan["groups"] == len(rows) == len(keys)
    assert {tuple(r["tags"][k] for k in gb) for r in rows} == keys


def dcs_of(rows):
    return [r["tags"].get("dc") for r in rows]


@pytest.mark.parametrize("served", BACKENDS, indirect=True)
def test_emptied_groups_close_up_in_tagv_id_order(served):
    # all four dcs, in the order their ids were assigned
    rows, (plan,) = served.same_three_ways(
        {"filters": [flt("wildcard", "dc", "*", True)]})
    assert dcs_of(rows) == DCS and plan["groups"] == 4
    # hosts of d0 and d1 alone (h % 4 in 1, 3; h % 8 == 7 has no dc):
    # two groups left, still by id, not by name
    hosts = "|".join(f"h{h:02d}" for h in (1, 3, 5, 9, 11))
    rows, (plan,) = served.same_three_ways(
        {"filters": [flt("wildcard", "dc", "*", True),
                     flt("literal_or", "host", hosts)]})
    assert dcs_of(rows) == ["d0", "d1"]
    assert (plan["series"], plan["groups"]) == (5, 2)


@pytest.mark.parametrize("served", BACKENDS, indirect=True)
def test_two_group_by_keys(served):
    rows, (plan,) = served.same_three_ways(
        {"filters": [flt("wildcard", "dc", "*", True),
                     flt("not_literal_or", "rack", "r4", True)]})
    got = [(r["tags"]["dc"], r["tags"]["rack"]) for r in rows]
    want = {(t["dc"], t["rack"]) for t in served.walked(
        [build_filter(flt("wildcard", "dc", "*")),
         build_filter(flt("not_literal_or", "rack", "r4"))])}
    assert len(got) == len(set(got)) == plan["groups"] and \
        set(got) == want
    # dc-major, by id: the reference's ByteMap order of the pair
    assert [d for d, _ in got] == sorted((d for d, _ in got),
                                         key=DCS.index)
    for dc in DCS:
        racks = [r for d, r in got if d == dc]
        assert racks == sorted(racks)


@pytest.mark.parametrize("served", BACKENDS, indirect=True)
def test_series_lacking_the_key(served):
    # a value filter never takes them; grouping a key some series of
    # the SELECTION lack puts those in the group that sorts first
    rows, (plan,) = served.same_three_ways(
        {"filters": [flt("wildcard", "rack", "*", True)]})
    assert plan["series"] == SERIES - SERIES // 6
    assert [r["tags"]["rack"] for r in rows] == \
        [f"r{i}" for i in range(5)]
    rows, (plan,) = served.same_three_ways(
        {"filters": [flt("wildcard", "dc", "*", True),
                     flt("not_key", "rack", "")]})
    assert plan["series"] == sum(
        1 for h in range(SERIES) if h % 6 == 5 and h % 8 != 7)
    rows, (plan,) = served.same_three_ways(
        {"filters": [flt("wildcard", "host", "h0*", True)],
         "aggregator": "max"})
    assert plan["groups"] == 10


@pytest.mark.parametrize("served", BACKENDS, indirect=True)
def test_an_unknown_tag_key(served):
    rows, (plan,) = served.same_three_ways(
        {"filters": [flt("literal_or", "nosuch", "x")]})
    assert rows == [] and "series" not in plan
    rows, (plan,) = served.same_three_ways(
        {"filters": [flt("not_key", "nosuch", "")]})
    assert plan["series"] == SERIES and len(rows) == 1
    # a known key no series of this metric carries
    served.tsdb.add_point("sys.other", BASE, 1, {"shelf": "s1"})
    rows, (plan,) = served.same_three_ways(
        {"filters": [flt("wildcard", "shelf", "*")]})
    assert rows == []
    rows, (plan,) = served.same_three_ways(
        {"filters": [flt("not_key", "shelf", "")]})
    assert plan["series"] == SERIES


@pytest.mark.parametrize("served", BACKENDS, indirect=True)
def test_two_filters_on_one_key(served):
    filters = [flt("wildcard", "rack", "r*", True),
               flt("not_literal_or", "rack", "r0|r3")]
    rows, (plan,) = served.same_three_ways({"filters": filters})
    assert [r["tags"]["rack"] for r in rows] == ["r1", "r2", "r4"]
    assert plan["series"] == len(served.walked(
        [build_filter(f) for f in filters]))


@pytest.mark.parametrize("served", BACKENDS, indirect=True)
def test_explicit_tags(served):
    # host, fleet and dc filtered: only series with exactly those keys
    filters = [flt("wildcard", "host", "*"),
               flt("wildcard", "fleet", "*"),
               flt("wildcard", "dc", "*", True)]
    rows, (plan,) = served.same_three_ways(
        {"filters": filters, "explicitTags": True})
    assert plan["series"] == sum(
        1 for h in range(SERIES) if h % 6 == 5 and h % 8 != 7)
    assert plan["groups"] == len(rows) > 1
    assert dcs_of(rows) == sorted(dcs_of(rows), key=DCS.index)


@pytest.mark.parametrize("served", BACKENDS, indirect=True)
def test_a_new_series_moves_the_version(served):
    sub = {"filters": [flt("wildcard", "dc", "*", True)],
           "aggregator": "count"}
    _, (plan,) = served.cold(sub)
    before = plan["series"]
    served.warm(sub)
    served.tsdb.add_point("sys.plan", BASE + 30, 7,
                          {"host": "new", "dc": "d9", "fleet": "f0"})
    body, (plan,) = served.query(sub)
    assert (plan["index"], plan["series"], plan["groups"]) == \
        ("built", before + 1, 5)
    assert dcs_of(json.loads(body)) == DCS + ["d9"]
    assert served.warm(sub)[0] == body == served.bypass(sub)[0]
    counts = {r[2]["index"]: r[1] for r in stats_records(served.tsdb)
              if r[0] == "tsd.query.plan"}
    assert counts == {"built": 2, "hit": 2, "bypass": 1}


def stats_records(tsdb):
    from opentsdb_tpu.stats.stats import StatsCollector
    c = StatsCollector("tsd")
    tsdb.tracer.collect_stats(c)
    return c.records


@pytest.mark.parametrize("served", BACKENDS, indirect=True)
def test_a_renamed_tagv_shows_in_the_next_request(served):
    sub = {"filters": [flt("literal_or", "dc", "d2|east", True)]}
    body, _ = served.cold(sub)
    assert dcs_of(json.loads(body)) == ["d2"]
    served.tsdb.uids.tag_values.rename("d0", "east")
    body, _ = served.warm(sub)              # same index, live names
    assert dcs_of(json.loads(body)) == ["east", "d2"]
    assert body == served.bypass(sub)[0]


@pytest.mark.parametrize("served", BACKENDS, indirect=True)
def test_two_sub_queries_on_a_cold_index_from_two_threads(served):
    assert served.tsdb.query_fanout_pool is not None
    subs = [{"filters": [flt("wildcard", "dc", "*", True),
                         flt("not_literal_or", "rack", "r1")],
             "aggregator": agg} for agg in ("sum", "max")]
    want, _ = served.bypass(*subs)
    for _ in range(5):
        body, plans = served.cold(*subs)
        assert body == want
        assert [(p["series"], p["groups"]) for p in plans] == \
            [(plans[0]["series"], 4)] * 2
    assert served.warm(*subs)[0] == want


def test_lazy_parts_are_built_once_under_threads():
    rng = np.random.default_rng(28)
    tags = TagMatrix(np.array([2, 5, 9]),
                     rng.integers(-1, 40, size=(20000, 3)))
    index = PlanIndex(20000, tags)
    barrier = threading.Barrier(4)
    got = []

    def ask():
        barrier.wait()
        got.append((index.labels([5, 9]), index.distinct(5)))

    threads = [threading.Thread(target=ask) for _ in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    (labels, count), distinct = got[0]
    for (other, n), d in got[1:]:
        assert other is labels and n == count and d is distinct
    want, n = group_labels(tags, [5, 9])
    assert n == count and np.array_equal(labels, want)
    assert np.array_equal(distinct, tags.distinct(5))


@pytest.mark.parametrize("served", BACKENDS, indirect=True)
def test_the_label_cache_stays_within_its_bound(served):
    keys = ["host", "dc", "rack", "fleet"]
    sets = [c for n in range(1, 5)
            for c in itertools.combinations(keys, n)]
    assert len(sets) > PlanIndex.LABEL_SETS
    served.tsdb._tagmat_cache.clear()
    for gb in sets + sets[:3]:
        sub = {"filters": [flt("wildcard", k, "*", True) for k in gb]}
        body, _ = served.query(sub)
        assert body == served.bypass(sub)[0]
        (index,) = served.tsdb._tagmat_cache.values()
        assert len(index._labels) <= PlanIndex.LABEL_SETS
    assert len(index._labels) == PlanIndex.LABEL_SETS
    uids = served.tsdb.uids.tag_names
    newest = tuple(sorted(uids.get_id(k) for k in sets[2]))
    assert next(reversed(index._labels)) == newest


@pytest.mark.parametrize("rows", [
    np.arange(0, 3000, 7), np.arange(3000), np.empty(0, np.int64),
    np.array([5, 6, 2999])], ids=["some", "all", "none", "three"])
def test_gathered_labels_equal_labels_of_the_selection(rows):
    # what _group_ids does with the index against what it does
    # without: the same labels, the same count, whatever emptied
    rng = np.random.default_rng(len(rows))
    tags = TagMatrix(np.array([1, 4, 6]),
                     rng.integers(-1, 12, size=(3000, 3)))
    index = PlanIndex(3000, tags)
    for gb in ([4], [1, 6], [6, 4, 1], [3]):
        got, n = QueryEngine._group_ids(index.select(rows), gb)
        want, m = QueryEngine._group_ids(tags.select(rows), gb)
        assert n == m and got.dtype == want.dtype == np.int32
        assert np.array_equal(got, want)
        # a second selection composes with the first
        keep = np.arange(len(rows)) % 3 == 0
        got, n = QueryEngine._group_ids(
            index.select(rows).select(keep), gb)
        want, m = QueryEngine._group_ids(tags.select(rows[keep]), gb)
        assert n == m and np.array_equal(got, want)


# --- PR 30: the assemble stage reads the index -------------------------

GROUP_BYS = {"one": ["dc"], "two": ["dc", "fleet"], "none": []}


@pytest.mark.parametrize("served", BACKENDS, indirect=True)
@pytest.mark.parametrize("keys", GROUP_BYS)
@pytest.mark.parametrize("type_, expr", FILTERS,
                         ids=[t for t, _ in FILTERS])
def test_index_matrix_and_walk_agree_on_the_groups_tags(served, type_,
                                                        expr, keys):
    served.index_matrix_and_walk({"filters": [
        flt(type_, "rack", expr),
        *(flt("wildcard", k, "*", True) for k in GROUP_BYS[keys])]})


def tag_rule(rows, key, by=None):
    """Where each group's answer put ``key``: ``tags``' value, "agg"
    or None (vanished); a list, or a dict by the groups' tag ``by``."""
    rule = [r["tags"].get(key) or
            ("agg" if key in r["aggregateTags"] else None)
            for r in rows]
    if by is None:
        return rule
    return dict(zip((r["tags"][by] for r in rows), rule, strict=True))


@pytest.mark.parametrize("served", BACKENDS, indirect=True)
def test_a_key_absent_on_members_the_filter_removes_or_keeps(served):
    # a sixth of the series has no rack, all of them in d0 and d1:
    # over those whole groups rack vanishes, and the cached
    # whole-group summary says so
    rows = served.index_matrix_and_walk(
        {"filters": [flt("wildcard", "dc", "*", True)]})
    assert dcs_of(rows) == DCS
    assert tag_rule(rows, "rack") == ["agg", None, "agg", None]
    # the filter removes them: rack is back, aggregated
    rows = served.index_matrix_and_walk(
        {"filters": [flt("wildcard", "dc", "*", True),
                     flt("wildcard", "rack", "*")]})
    assert tag_rule(rows, "rack") == ["agg"] * 4
    # ... or common, where one value is left
    rows = served.index_matrix_and_walk(
        {"filters": [flt("wildcard", "dc", "*", True),
                     flt("literal_or", "rack", "r2")]})
    assert tag_rule(rows, "rack") == ["r2"] * 4
    # a filter that keeps some of them: still vanished
    rows = served.index_matrix_and_walk(
        {"filters": [flt("wildcard", "dc", "*", True),
                     flt("literal_or", "fleet", "f1")]})
    assert dcs_of(rows) == ["d0", "d1"]
    assert tag_rule(rows, "rack") == [None, None]
    assert tag_rule(rows, "fleet") == ["f1", "f1"]


@pytest.mark.parametrize("served", BACKENDS, indirect=True)
def test_one_value_left_of_a_key_the_whole_group_has_many_of(served):
    rows = served.index_matrix_and_walk(
        {"filters": [flt("wildcard", "fleet", "*", True)]})
    assert tag_rule(rows, "host") == ["agg"] * 2
    rows = served.index_matrix_and_walk(
        {"filters": [flt("wildcard", "fleet", "*", True),
                     flt("regexp", "host", "h0[0-7]")]})
    assert tag_rule(rows, "host") == ["agg"] * 2
    # h07, of the odd fleet, has no dc
    assert tag_rule(rows, "dc", "fleet") == {"f0": "agg", "f1": None}
    rows = served.index_matrix_and_walk(
        {"filters": [flt("wildcard", "dc", "*", True),
                     flt("regexp", "host", "h0[0-7]")]})
    # d3: h00, h04; d0: h01, h05; d2: h02, h06; d1: h03 alone
    assert tag_rule(rows, "host") == ["agg", "agg", "agg", "h03"]


@pytest.mark.parametrize("served", BACKENDS, indirect=True)
def test_groups_the_selection_empties_leave_the_summary(served):
    hosts = "|".join(f"h{h:02d}" for h in (1, 3, 5, 9, 11, 13, 17))
    rows = served.index_matrix_and_walk(
        {"filters": [flt("wildcard", "dc", "*", True),
                     flt("literal_or", "host", hosts)]})
    assert dcs_of(rows) == ["d0", "d1"]
    assert tag_rule(rows, "fleet") == ["f1", "f1"]


@pytest.mark.parametrize("served", BACKENDS, indirect=True)
@pytest.mark.parametrize("sub", [
    {}, {"filters": [flt("wildcard", "host", "*", True)]},
    {"filters": [flt("wildcard", "fleet", "*", True),
                 flt("not_key", "nosuch", "")]}],
    ids=["no-filter", "group-by-host", "two-groups"])
def test_an_unfiltered_request_reads_the_whole_groups(served, sub,
                                                      monkeypatch):
    served.query(sub)
    # every row selected: no mask, no pass over the columns
    monkeypatch.setattr(GroupLayout, "selected", None)
    rows = served.index_matrix_and_walk(sub)
    by = next((f["tagk"] for f in sub.get("filters", ())
               if f["groupBy"]), None)
    if by == "host":    # a group each: its own rack, or none at all
        assert tag_rule(rows, "rack", by) == {
            f"h{h:02d}": f"r{h % 5}" if h % 6 != 5 else None
            for h in range(SERIES)}
    elif by == "fleet":     # every series without a rack is odd
        assert tag_rule(rows, "rack", by) == {"f0": "agg", "f1": None}
    else:
        assert tag_rule(rows, "rack") == [None]


@pytest.mark.parametrize("served", BACKENDS, indirect=True)
def test_agg_none_gives_every_series_its_own_tags(served):
    sub = {"aggregator": "none",
           "filters": [flt("wildcard", "dc", "*", True)]}
    served.query(sub)
    body, (plan,) = served.warm(sub, showTSUIDs=True)
    assert served.ways == ["matrix"]
    assert body == served.bypass(sub, showTSUIDs=True)[0]
    rows = json.loads(body)
    want = served.walked([build_filter(sub["filters"][0])],
                         tsuids=True)
    assert plan["groups"] == len(rows) == len(want)
    assert [(r["tags"], r["aggregateTags"], r["tsuids"])
            for r in rows] == [(tags, [], [tsuid])
                               for tags, tsuid in want]


@pytest.mark.parametrize("served", BACKENDS, indirect=True)
def test_tsuids_and_annotations_keep_the_member_order(served):
    from opentsdb_tpu.meta.annotation import Annotation
    sub = {"filters": [flt("wildcard", "dc", "*", True),
                       flt("not_literal_or", "rack", "r0")]}
    members = served.walked([build_filter(f) for f in sub["filters"]],
                            tsuids=True)
    for i, (_, tsuid) in enumerate(reversed(members)):
        served.tsdb.annotations.store(Annotation(
            start_time=BASE + 60, tsuid=tsuid, description=f"n{i}"))
    rows = served.index_matrix_and_walk(sub)
    for row in rows:
        noted = [a["tsuid"] for a in row["annotations"]]
        assert noted == row["tsuids"] and len(noted) > 1
    # annotations alone ask for the members too
    body, _ = served.warm(sub)
    assert served.ways == ["index"] and body == served.bypass(sub)[0]
    assert [[a["tsuid"] for a in r["annotations"]]
            for r in json.loads(body)] == [r["tsuids"] for r in rows]


@pytest.mark.parametrize("served", BACKENDS, indirect=True)
def test_explicit_tags_compose_a_second_selection(served):
    # host, fleet and dc filtered: the rack-less series alone, picked
    # out of the first selection; their matrix still knows its rows
    rows = served.index_matrix_and_walk(
        {"filters": [flt("wildcard", "host", "*"),
                     flt("wildcard", "fleet", "*"),
                     flt("wildcard", "dc", "*", True)]})
    assert tag_rule(rows, "rack", "dc") == {
        "d3": "agg", "d0": None, "d2": "agg", "d1": None}
    sub = {"filters": [flt("wildcard", "host", "*"),
                       flt("wildcard", "fleet", "*"),
                       flt("wildcard", "dc", "*", True)],
           "explicitTags": True}
    body, (plan,) = served.warm(sub, showTSUIDs=True)
    assert served.ways == ["index"] and plan["series"] == 6
    assert body == served.bypass(sub, showTSUIDs=True)[0]
    rows = json.loads(body)
    assert sum(len(r["tsuids"]) for r in rows) == 6
    assert all("rack" not in r["tags"] and
               "rack" not in r["aggregateTags"] for r in rows)


@pytest.mark.parametrize("served", BACKENDS, indirect=True)
def test_a_small_selection_never_reads_or_builds_the_layout(served):
    served.tsdb._tagmat_cache.clear()
    hosts = "|".join(f"h{h:02d}" for h in (1, 3, 5, 9, 11))
    sub = {"filters": [flt("wildcard", "dc", "*", True),
                       flt("literal_or", "host", hosts)]}
    for _ in range(2):
        body, (plan,) = served.query(sub, showTSUIDs=True)
        assert served.ways == ["small"] and plan["series"] == 5
    (index,) = served.tsdb._tagmat_cache.values()
    assert [s.layout for s in index._labels.values()] == [None]
    assert body == served.bypass(sub, showTSUIDs=True)[0]
    # one more host and it is no small part of 48 any more
    sub["filters"][1]["filter"] += "|h13"
    served.query(sub)
    assert served.ways == ["index"]


@pytest.mark.parametrize("served", BACKENDS, indirect=True)
def test_a_request_with_no_index_still_counts_matrix(served):
    """``small`` is a choice between two ways; ``matrix`` is the fall
    to the one way left (``path.fallbacks`` counts it): the same small
    selection, once of the index and once of a matrix without one."""
    hosts = "|".join(f"h{h:02d}" for h in (1, 3, 5, 9, 11))
    sub = {"filters": [flt("wildcard", "dc", "*", True),
                       flt("literal_or", "host", hosts)]}
    counted = served.tsdb.tracer.assembles
    before = dict(counted)
    served.query(sub)
    assert served.ways == ["small"]
    served.bypass(sub)
    assert served.ways == ["matrix"]
    assert {k: counted[k] - before[k] for k in counted} \
        == {"index": 0, "small": 1, "matrix": 1}
    exported = {r["tags"]["tags"]: r["value"] for r in json.loads(
        served.router.handle(HttpRequest(
            method="GET", path="/api/stats", params={}, headers={})
        ).body) if r["metric"] == "tsd.query.assemble"}
    assert exported == counted


@pytest.mark.parametrize("served", BACKENDS, indirect=True)
def test_the_hit_path_never_gathers_the_selected_tag_rows(
        served, monkeypatch):
    seen = []
    whole = QueryEngine._build_results

    def watched(self, tsq, sub, metric_name, sids, tags, *rest):
        seen.append(tags)
        return whole(self, tsq, sub, metric_name, sids, tags, *rest)

    monkeypatch.setattr(QueryEngine, "_build_results", watched)
    filtered = {"filters": [flt("wildcard", "dc", "*", True),
                            flt("not_literal_or", "rack", "r1")]}
    served.query(filtered, {}, showTSUIDs=True)
    body, plans = served.warm(filtered, {}, showTSUIDs=True)
    assert served.ways == ["index", "index"] and len(seen) == 4
    some, every = sorted(seen[2:], key=lambda t: t.origin[1] is None)
    (index,) = served.tsdb._tagmat_cache.values()
    assert some.origin[0] is index and some._vids is None
    assert some.num_series == plans[0]["series"] < SERIES
    # the unfiltered matrix is the index's own, not a copy
    assert every.origin == (index, None)
    assert every._vids is index.tags.vids
    # what the request counted came from the index as well
    done = json.loads(served.router.handle(HttpRequest(
        method="GET", path="/api/stats/query", params={}, headers={},
        body=b"")).body)["completed"][-1]["stats"]
    pairs = sum(len(t) for t in served.walked(
        [build_filter(f) for f in filtered["filters"]]))
    whole_pairs = sum(len(t) for t in served.walked([]))
    assert done["uidPairsResolved"] == pairs + whole_pairs
    assert some.num_pairs() == pairs == int((some.vids >= 0).sum())


@pytest.mark.parametrize("served", BACKENDS, indirect=True)
def test_a_new_series_builds_the_layout_again(served):
    sub = {"filters": [flt("wildcard", "fleet", "*", True),
                       flt("not_literal_or", "rack", "r4")]}
    rows = served.index_matrix_and_walk(sub)
    assert tag_rule(rows, "dc", "fleet") == {"f0": "agg", "f1": None}
    (old,) = served.tsdb._tagmat_cache.values()
    served.tsdb.add_point("sys.plan", BASE + 30, 7, {
        "host": "new", "dc": "d9", "fleet": "f2", "rack": "r9"})
    rows = served.index_matrix_and_walk(sub)
    assert {"host": "new", "dc": "d9", "fleet": "f2",
            "rack": "r9"} in [r["tags"] for r in rows]
    (new,) = served.tsdb._tagmat_cache.values()
    assert new is not old and new.version == old.version + 1
    (label_set,) = new._labels.values()
    assert len(label_set.layout.order) == SERIES + 1


@pytest.fixture
def built(monkeypatch):
    """The length of every GroupLayout made, in order."""
    made = []
    init = GroupLayout.__init__

    def counted(self, order, *args):
        made.append(len(order))
        init(self, order, *args)

    monkeypatch.setattr(GroupLayout, "__init__", counted)
    return made


@pytest.mark.parametrize("served", BACKENDS, indirect=True)
def test_two_cold_sub_queries_build_one_layout(served, built):
    assert served.tsdb.query_fanout_pool is not None
    subs = [{"filters": [flt("wildcard", "dc", "*", True),
                         flt("not_literal_or", "rack", "r1")],
             "aggregator": agg} for agg in ("sum", "max")]
    want, _ = served.bypass(*subs)
    for _ in range(5):
        del built[:]
        body, _ = served.cold(*subs)
        assert body == want and served.ways == ["index", "index"]
        # the metric's layout once, by whichever index won the race
        assert built.count(SERIES) in (1, 2)
        indexes = list(served.tsdb._tagmat_cache.values())
        assert len(indexes) == 1


def test_a_layout_is_built_once_under_threads(built):
    rng = np.random.default_rng(30)
    index = PlanIndex(20000, TagMatrix(
        np.array([2, 5, 9]), rng.integers(-1, 40, size=(20000, 3))))
    barrier = threading.Barrier(4)
    got = []

    def ask():
        barrier.wait()
        got.append(index.layout([5, 9]))

    threads = [threading.Thread(target=ask) for _ in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=60)
        assert not t.is_alive()
    assert len(got) == 4 and built == [20000]
    assert all(layout is got[0] for layout in got)
    assert got[0].cols[0].dtype == np.int32 == got[0].order.dtype


@pytest.mark.parametrize("served", BACKENDS, indirect=True)
def test_an_evicted_label_set_takes_its_layout_with_it(served):
    keys = ["host", "dc", "rack", "fleet"]
    sets = [c for n in range(1, 5)
            for c in itertools.combinations(keys, n)]
    served.tsdb._tagmat_cache.clear()
    uids = served.tsdb.uids.tag_names
    first = None
    for gb in sets:
        sub = {"filters": [flt("wildcard", k, "*", True) for k in gb]}
        body, _ = served.query(sub)
        assert served.ways == ["index"]
        (index,) = served.tsdb._tagmat_cache.values()
        key = tuple(sorted(uids.get_id(k) for k in gb))
        first = first or (key, index._labels[key].layout)
        assert 0 < len(index._labels) <= PlanIndex.LABEL_SETS
        assert all(s.layout is not None
                   for s in index._labels.values())
    key, layout = first
    assert key not in index._labels and layout is not None
    # asked for again, it is made again: nothing else kept it
    assert index.layout(key) is not layout
    assert np.array_equal(index.layout(key).order, layout.order)


def summary_of(tags, group_ids, num_groups, gb, k=0):
    way, minv, maxv, members, source = group_tag_summary(
        tags, group_ids, num_groups, gb)
    return way, minv.tolist(), maxv.tolist(), [
        [source.tags_of(m) for m in members(g)[:k]]
        for g in range(num_groups)], minv.dtype


@pytest.mark.parametrize("wide", [False, True], ids=["int32", "int64"])
@pytest.mark.parametrize("rows", [
    np.arange(0, 3000, 7), np.arange(3000), np.arange(1500, 3000),
    np.array([5, 6, 2999]), np.flatnonzero(np.arange(3000) % 11 > 2)],
    ids=["a-seventh", "all", "half", "three", "most"])
def test_a_summary_from_the_layout_equals_one_from_the_rows(
        rows, wide, monkeypatch):
    # blocks of a few groups, so that a selection cuts some blocks,
    # empties some and leaves some whole
    monkeypatch.setattr(GroupLayout, "BLOCK", 256)
    rng = np.random.default_rng(len(rows))
    vids = rng.integers(-1, 12, size=(3000, 3))
    vids[:, 2] = np.where(vids[:, 1] > 4, 7, vids[:, 2])
    if wide:
        vids[vids >= 0] += 1 << 40
    tags = TagMatrix(np.array([1, 4, 6]), vids)
    index = PlanIndex(3000, tags)
    for gb in ([4], [1, 6], [6, 4, 1], [3], []):
        selected = index.select(rows)
        gids, n = QueryEngine._group_ids(selected, gb)
        got = summary_of(selected, gids, n, gb, k=3)
        want = summary_of(tags.select(rows), gids, n, gb, k=3)
        small = len(rows) * plan_mod.SMALL_SELECTION < 3000
        assert got[0] == ("small" if small else "index")
        assert want[0] == "matrix"
        assert small or selected._vids is None
        assert got[1:] == want[1:] and got[4] == np.int64
        layout = index.layout(gb)
        assert layout.cols[0].dtype == (np.int64 if wide
                                        else np.int32)
        # a second selection composes with the first
        keep = np.arange(len(rows)) % 3 != 1
        twice = selected.select(keep)
        gids, n = QueryEngine._group_ids(twice, gb)
        got = summary_of(twice, gids, n, gb, k=3)
        want = summary_of(tags.select(rows[keep]), gids, n, gb, k=3)
        assert got[1:] == want[1:]
        # agg=none: the labels are not the request's groups
        own = np.arange(len(rows), dtype=np.int32)
        assert summary_of(selected, own, len(rows), None)[0] == \
            "matrix"
