"""What happens inside ``query.execute``, by name (PR 24): spans nest
by a thread-local stack, a parent's self time is what no child covers,
the device-occupancy clock puts every idle millisecond down to a
stage, the program counts its own compiles, collections and start-up
phases, and a loader outside any request still records its stages.
CPU only: "the device" here is JAX's default CPU device, and a
host-placed tail is one pinned by ``PipelineSpec.host``.
"""

import gc
import json
import threading

import numpy as np
import pytest

from opentsdb_tpu import TSDB, Config
from opentsdb_tpu.obs import trace as trace_mod
from opentsdb_tpu.obs.trace import (RUNTIME, DeviceClock, Tracer,
                                    build_tree)
from opentsdb_tpu.ops.pipeline import PipelineSpec, execute_grid
from opentsdb_tpu.stats.stats import StatsCollectorRegistry
from opentsdb_tpu.tsd.http_api import HttpRequest, HttpRpcRouter

pytestmark = pytest.mark.obs

BASE = 1356998400
STAGES = ("query.scan", "query.grid_build", "query.upload",
          "query.program", "query.download")


def mk_tracer(**over):
    stats = StatsCollectorRegistry()
    return Tracer(Config(**{"tsd.tpu.warmup": "false",
                            "tsd.trace.sample": "1", **over}),
                  stats=stats), stats


def mk_tsdb(**cfg):
    return TSDB(Config(**{
        "tsd.core.auto_create_metrics": "true",
        "tsd.tpu.warmup": "false", "tsd.trace.sample": "1",
        "tsd.query.cache.enable": "false", **cfg}))


def req(method, path, body=b"", **params):
    return HttpRequest(method=method, path=path,
                       params={k: str(v) for k, v in params.items()},
                       headers={}, body=body)


def import_text(series=16, points=60):
    return "".join(
        f"sys.stage {BASE + i * 10} {i + h} host=h{h} dc=d{h % 4}\n"
        for h in range(series) for i in range(points)).encode()


class Clock:
    """A scripted ``trace._now``: milliseconds set by the test."""

    def __init__(self, monkeypatch):
        self.ms = 0.0
        monkeypatch.setattr(trace_mod, "_now", lambda: self.ms / 1e3)


def by_name(ctx_or_data):
    spans = ctx_or_data.spans
    return {s.name: s for s in spans}


# ---------------------------------------------------------------------
# nesting
# ---------------------------------------------------------------------

class TestNesting:
    def test_innermost_open_span_on_the_thread_is_the_parent(self):
        tracer, _ = mk_tracer()
        ctx = tracer.start_request("query.http")
        with trace_mod.use(ctx):
            with trace_mod.trace_span("query.execute") as ex:
                with trace_mod.trace_span("query.plan") as plan:
                    pass
                with trace_mod.trace_span("query.assemble") as asm:
                    # an explicit parent= still wins (the cluster legs)
                    leg = ctx.begin("cluster.peer",
                                    parent=ctx.root_span_id)
                    leg.finish()
            after = ctx.begin("query.serialize")
            after.finish()
        assert ex.parent_id == ctx.root_span_id
        assert plan.parent_id == asm.parent_id == ex.span_id
        assert leg.parent_id == ctx.root_span_id
        # execute has closed: the next span hangs off the root again
        assert after.parent_id == ctx.root_span_id
        tracer.finish(ctx)
        tree = build_tree(list(tracer.get(ctx.trace_id).spans))
        (root,) = tree
        execute = next(c for c in root["children"]
                       if c["name"] == "query.execute")
        assert [c["name"] for c in execute["children"]] == \
            ["query.plan", "query.assemble"]

    def test_a_fanout_worker_nests_under_its_own_execute(self):
        tracer, _ = mk_tracer()
        ctx = tracer.start_request("query.http")
        both_open = threading.Barrier(2, timeout=10)
        out = {}

        def sub(index):
            with trace_mod.use(ctx):
                with trace_mod.trace_span("query.execute",
                                          sub=index) as ex:
                    both_open.wait()     # the sibling's is open too
                    with trace_mod.trace_span("query.plan") as plan:
                        pass
                    both_open.wait()
            out[index] = (ex, plan)

        worker = threading.Thread(target=sub, args=(1,))
        worker.start()
        sub(0)
        worker.join(10)
        assert not worker.is_alive()
        for index in (0, 1):
            ex, plan = out[index]
            assert ex.parent_id == ctx.root_span_id
            assert plan.parent_id == ex.span_id
        assert out[0][0].span_id != out[1][0].span_id
        tracer.finish(ctx)

    def test_an_abandoned_child_does_not_adopt_later_spans(self):
        tracer, _ = mk_tracer()
        ctx = tracer.start_request("query.http")
        with trace_mod.use(ctx):
            with pytest.raises(RuntimeError):
                with trace_mod.trace_span("query.execute"):
                    trace_mod.trace_begin("query.plan")  # never ended
                    raise RuntimeError("filters failed")
            nxt = trace_mod.trace_begin("query.serialize")
            nxt.finish()
        assert nxt.parent_id == ctx.root_span_id
        tracer.finish(ctx)
        # and a span of the next request on this thread starts clean
        ctx2 = tracer.start_request("query.http")
        h = ctx2.begin("query.execute")
        assert h.parent_id == ctx2.root_span_id
        h.finish()
        tracer.finish(ctx2)


# ---------------------------------------------------------------------
# self time and the occupancy clock, on a scripted clock
# ---------------------------------------------------------------------

class TestSelfTimeAndOccupancy:
    def test_self_time_is_duration_less_the_union_of_children(
            self, monkeypatch):
        clock = Clock(monkeypatch)
        tracer, stats = mk_tracer()
        ctx = tracer.start_request("query.http")
        with trace_mod.use(ctx):
            ex = ctx.begin("query.execute")
            # two children that overlap: [10, 50] and [30, 70]
            ctx.record("query.scan", 0.010, 0.050)
            ctx.record("query.grid_build", 0.030, 0.070)
            clock.ms = 100.0
            ex.finish()
        tracer.finish(ctx)
        h = stats.stage_self["query.execute"]
        assert h.count == 1 and h.sum == pytest.approx(40.0)
        # the root's one child covers all of it
        assert stats.stage_self["query.http"].sum == pytest.approx(0.0)
        # leaves have no self-time histogram: their duration is it
        assert "query.scan" not in stats.stage_self
        fams = {(f, l["stage"]) for f, l, _h in stats.histograms()
                if "stage" in l}
        assert ("tsd_stage_self_ms", "query.execute") in fams
        assert ("tsd_stage_latency_ms", "query.execute") in fams

    def test_two_programs_in_flight_occupy_once(self, monkeypatch):
        clock = Clock(monkeypatch)
        dev = DeviceClock()
        dev.enter()
        clock.ms = 10.0
        dev.enter()
        assert dev.occupied_ms() == pytest.approx(10.0)  # still open
        clock.ms = 30.0
        dev.exit()
        clock.ms = 50.0
        dev.exit()
        clock.ms = 80.0                  # idle since 50
        assert dev.occupied_ms() == pytest.approx(50.0)
        assert dev.dispatches == 2
        dev.enter()
        clock.ms = 85.0
        dev.exit()
        assert dev.occupied_ms() == pytest.approx(55.0)

    def test_idle_self_time_lands_on_the_stage_that_spent_it(
            self, monkeypatch):
        clock = Clock(monkeypatch)
        monkeypatch.setattr(RUNTIME, "clock", DeviceClock())
        tracer, _ = mk_tracer()
        ctx = tracer.start_request("query.http")
        with trace_mod.use(ctx):
            ex = ctx.begin("query.execute")
            clock.ms = 10.0
            plan = ctx.begin("query.plan")
            clock.ms = 30.0
            plan.finish()                          # 20 ms, all idle
            prog = ctx.begin("query.program", path="grid",
                             placement="device")
            RUNTIME.clock.enter()
            clock.ms = 70.0
            RUNTIME.clock.exit()
            prog.finish()                          # 40 ms, occupied
            clock.ms = 100.0
            ex.finish()                 # self: [0,10] + [70,100]
        tracer.finish(ctx)
        assert tracer.idle_stage_ms == pytest.approx({
            "query.http": 0.0, "query.execute": 40.0,
            "query.plan": 20.0, "query.program": 0.0})
        assert tracer.tails == {("grid", "device", "?"): 1}
        spans = by_name(tracer.get(ctx.trace_id))
        assert spans["query.program"].occupied_ms == pytest.approx(40.0)
        assert spans["query.execute"].occupied_ms == pytest.approx(40.0)
        assert spans["query.program"].to_json()["deviceOccupiedMs"] \
            == pytest.approx(40.0)
        rows = {(r[0], r[2].get("stage")): r[1]
                for r in _records(tracer)}
        assert rows[("tsd.device.idle_stage_ms", "query.execute")] \
            == pytest.approx(40.0)
        assert rows[("tsd.device.occupied_ms", None)] \
            == pytest.approx(40.0)
        assert rows[("tsd.device.dispatches", None)] == 1

    @pytest.mark.parametrize("walks, read", [
        ((), 0), ((1_000_000,), 1_000_000), ((100, 2000), 2100)])
    def test_resolving_a_filter_is_the_plans_child(self, monkeypatch,
                                                   walks, read):
        # PR 40: what the plan spent turning a filter into tagv ids
        # is a stage, so the plan's self time is the rest of it, and
        # the names the walks read add up in a counter of their own
        clock = Clock(monkeypatch)
        tracer, stats = mk_tracer()
        ctx = tracer.start_request("query.http")
        with trace_mod.use(ctx):
            ex = ctx.begin("query.execute")
            plan = ctx.begin("query.plan", index="hit")
            ids = ctx.begin("query.filter_resolve", way="ids",
                            names_read=0)
            clock.ms = 1.0
            ids.tag(matched=8)
            ids.finish()
            for n in walks:
                walk = ctx.begin("query.filter_resolve", way="walk")
                clock.ms += 10.0
                walk.tag(names_read=n, matched=1)
                walk.finish()
            clock.ms += 4.0
            plan.finish()
            ex.finish()
        tracer.finish(ctx)
        spans = tracer.get(ctx.trace_id).spans
        resolved = [s for s in spans
                    if s.name == "query.filter_resolve"]
        assert [s.tags["way"] for s in resolved] \
            == ["ids"] + ["walk"] * len(walks)
        assert {s.parent_id for s in resolved} == {plan.span_id}
        assert tracer.filter_names_read == read
        assert tracer.idle_stage_ms["query.plan"] == pytest.approx(4.0)
        assert tracer.idle_stage_ms["query.filter_resolve"] \
            == pytest.approx(1.0 + 10.0 * len(walks))
        rows = {r[0]: r[1] for r in _records(tracer)}
        assert rows["tsd.query.filter.names_read"] == read
        # the plan's self time is what no filter_resolve covers
        assert stats.stage_self["query.plan"].sum == pytest.approx(4.0)
        assert stats.stage_latency["query.filter_resolve"].count \
            == 1 + len(walks)

    @pytest.mark.parametrize("host, dispatches", [(False, 1),
                                                  (True, 0)])
    def test_only_a_device_placed_program_occupies(self, host,
                                                   dispatches):
        tracer, _ = mk_tracer()
        spec = PipelineSpec(num_series=3, num_buckets=5, num_groups=2,
                            ds_function="avg", agg_name="sum",
                            host=host)
        before = RUNTIME.clock.dispatches, RUNTIME.clock.occupied_ms()
        ctx = tracer.start_request("query.http")
        with trace_mod.use(ctx):
            result, _emit = execute_grid(
                np.ones((3, 5)), np.ones((3, 5), bool),
                np.arange(5) * 1000, np.array([0, 1, 1], np.int32),
                spec)
        tracer.finish(ctx)
        assert result.shape == (2, 5) and result[1, 0] == 2.0
        assert RUNTIME.clock.dispatches - before[0] == dispatches
        assert (RUNTIME.clock.occupied_ms() > before[1]) == (not host)
        prog = by_name(tracer.get(ctx.trace_id))["query.program"]
        assert prog.tags["path"] == "grid"
        assert prog.tags["placement"] == ("host" if host else "device")
        assert (prog.occupied_ms > 0) == (not host)
        assert prog.tags["class"] == "linear"
        assert tracer.tails == {
            ("grid", prog.tags["placement"], "linear"): 1}


    @pytest.mark.parametrize("states, want", [
        ((), {"hit": 0, "built": 0}),
        (("built",), {"hit": 0, "built": 1}),
        (("hit", "hit", "built", "mended"), {"hit": 2, "built": 1})])
    def test_a_name_tables_filters_count_by_what_it_cost(self, states,
                                                         want):
        # PR 41: way=table on the same stage; the tag ``table`` says
        # whether the filter found the key's names or read them, and
        # a value the tracer does not know counts nowhere
        tracer, _stats = mk_tracer()
        ctx = tracer.start_request("query.http")
        with trace_mod.use(ctx):
            plan = ctx.begin("query.plan", index="hit",
                             resolve_table=len(states), resolve_walk=1)
            for state in states:
                ctx.begin("query.filter_resolve", way="table",
                          table=state, matched=3,
                          names_read=7 if state == "built" else 0
                          ).finish()
            ctx.begin("query.filter_resolve", way="walk",
                      names_read=5, matched=0).finish()
            plan.finish()
        tracer.finish(ctx)
        assert tracer.filter_tables == want
        assert tracer.filters == {"ids": 0, "table": len(states),
                                  "walk": 1, "presence": 0}
        assert tracer.filter_names_read == 5 + 7 * want["built"]
        rows = {(r[0], r[2].get("state") or r[2].get("resolve")): r[1]
                for r in _records(tracer)
                if r[0].startswith("tsd.query.filter")}
        assert rows == {
            ("tsd.query.filter", "ids"): 0,
            ("tsd.query.filter", "table"): len(states),
            ("tsd.query.filter", "walk"): 1,
            ("tsd.query.filter", "presence"): 0,
            ("tsd.query.filter.names_read", None):
                5 + 7 * want["built"],
            ("tsd.query.filter.table", "hit"): want["hit"],
            ("tsd.query.filter.table", "built"): want["built"]}


def _records(tracer):
    from opentsdb_tpu.stats.stats import StatsCollector
    c = StatsCollector("tsd")
    tracer.collect_stats(c)
    return c.records


# ---------------------------------------------------------------------
# the served path
# ---------------------------------------------------------------------

@pytest.mark.parametrize("backend", ["native", "memory"])
@pytest.mark.parametrize("flags, placement", [
    ({}, "host"),        # a small grid: the tail on the host backend
    ({"tsd.query.host_tail_max_cells_linear": "-1"}, "device")])
def test_a_served_grid_query_names_every_stage(flags, placement,
                                               backend):
    tsdb = mk_tsdb(**{"tsd.storage.backend": backend, **flags})
    # the native store writes the padded grid in its own pass (PR 25):
    # what is left of the build (the allocation) comes BEFORE the scan;
    # any other store's f64 grids are filled and padded after it
    fused = backend == "native"
    stages = STAGES if not fused else \
        ("query.grid_build", "query.scan") + STAGES[2:]
    router = HttpRpcRouter(tsdb)
    try:
        written, errors = tsdb.import_buffer(import_text(),
                                             durable=False)
        assert written == 16 * 60 and not errors
        body = json.dumps({
            "start": BASE * 1000, "end": (BASE + 600) * 1000,
            "queries": [{
                "metric": "sys.stage", "aggregator": "sum",
                "downsample": "1m-avg", "filters": [{
                    "type": "wildcard", "tagk": "dc", "filter": "*",
                    "groupBy": True}]}]}).encode()
        resp = router.handle(req("POST", "/api/query", body))
        assert resp.status == 200
        doc = json.loads(router.handle(req(
            "GET", "/api/trace/" + resp.headers["X-TSD-Trace-Id"])).body)
        (root,) = doc["tree"]
        execute = next(c for c in root["children"]
                       if c["name"] == "query.execute")
        names = [c["name"] for c in execute["children"]]
        # children of execute, in time order, nothing left beside it
        assert names[0] == "query.plan" and names[-1] == "query.assemble"
        for stage in stages:
            assert stage in names, names
        last = {n: i for i, n in enumerate(names)}   # the cache
        # lookup is a grid_build before the scan; an upload may be two
        order = [last[s] for s in stages]
        assert order == sorted(order), names
        assert names.count("query.program") == 1
        assert names.count("query.scan") == 1
        assert not {c["name"] for c in root["children"]} & set(STAGES)
        prog = next(c for c in execute["children"]
                    if c["name"] == "query.program")
        # a device-placed tail over the whole metric runs over its
        # resident grid, which the native store's first request puts
        # together from the metric's per-bucket columns (PR 50)
        by_column = fused and placement == "device"
        assert prog["tags"]["path"] == ("columns" if by_column
                                        else "grid")
        assert prog["tags"]["placement"] == placement
        assert prog["tags"]["shape"] == "16x12x8"   # padded S x B x G
        if fused:   # the first of its shape (the memory case follows)
            assert prog["tags"]["compiled"] is True
        scan = next(c for c in execute["children"]
                    if c["name"] == "query.scan")
        assert scan["tags"] == {"points": 960, "series": 16}
        (build,) = [c for c in execute["children"]
                    if c["name"] == "query.grid_build"
                    and "fused" in c["tags"]]
        assert build["tags"] == {
            "stage": "alloc" if fused else "fill_pad", "fused": fused,
            # a cell of the compute dtype (f64: the tests run x64) and
            # a byte of mask
            # (of the columns, the window's 11 buckets and no pad)
            "cells": 16 * (11 if by_column else 12),
            "bytes": 16 * (11 if by_column else 12) * 9}
        # the span is the QueryStat's timer: one pair of clock reads
        done = json.loads(router.handle(req(
            "GET", "/api/stats/query")).body)["completed"]
        assert done[-1]["stats"]["queryScanTime"] == \
            pytest.approx(scan["durationMs"], abs=2e-3)
        # the counters an operator reads
        tails = [r for r in json.loads(router.handle(req(
            "GET", "/api/stats")).body)
            if r["metric"] == "tsd.query.tail"]
        assert [(r["tags"]["path"], r["tags"]["placement"], r["value"])
                for r in tails] == [(prog["tags"]["path"], placement, 1)]
        builds = {r["tags"]["mode"]: r["value"] for r in json.loads(
            router.handle(req("GET", "/api/stats")).body)
            if r["metric"] == "tsd.query.grid_build"}
        assert builds == {"fused": int(fused), "host": int(not fused)}
        raw = json.loads(router.handle(req(
            "GET", "/api/stats/raw")).body)
        selfs = {h["labels"]["stage"] for h in raw["histograms"]
                 if h["name"] == "tsd_stage_self_ms"}
        assert {"query.execute", "query.http"} <= selfs
        health = json.loads(router.handle(req(
            "GET", "/api/health")).body)
        assert isinstance(health["startup"], dict)
    finally:
        tsdb.shutdown()


@pytest.mark.parametrize("backend", ["native", "memory"])
def test_the_plan_span_says_what_the_plan_index_did(backend):
    # PR 28: the first grid query over a metric builds its plan index,
    # the next one plans from it, a tsuid query cannot use it
    tsdb = mk_tsdb(**{"tsd.storage.backend": backend})
    router = HttpRpcRouter(tsdb)

    def plan_tags(sub):
        body = json.dumps({
            "start": BASE * 1000, "end": (BASE + 600) * 1000,
            "queries": [{"aggregator": "sum", "downsample": "1m-avg",
                         **sub}]}).encode()
        resp = router.handle(req("POST", "/api/query", body))
        assert resp.status == 200, resp.body
        (root,) = json.loads(router.handle(req(
            "GET", "/api/trace/" + resp.headers["X-TSD-Trace-Id"])
        ).body)["tree"]
        execute = next(c for c in root["children"]
                       if c["name"] == "query.execute")
        (plan,) = [c for c in execute["children"]
                   if c["name"] == "query.plan"]
        return plan["tags"]

    try:
        tsdb.import_buffer(import_text(), durable=False)
        grid = {"metric": "sys.stage", "filters": [{
            "type": "wildcard", "tagk": "dc", "filter": "*",
            "groupBy": True}]}
        assert plan_tags(grid) == {
            "sub": 0, "source": "raw", "index": "built", "series": 16,
            "groups": 4, "names_read": 0, "resolve_presence": 1}
        assert plan_tags(grid)["index"] == "hit"
        rec = tsdb.store.series(int(tsdb.store.series_ids_for_metric(
            tsdb.uids.metrics.get_id("sys.stage"))[3]))
        tsuid = tsdb.uids.tsuid(rec.metric_id, rec.tags).hex()
        assert plan_tags({"tsuids": [tsuid]}) == {
            "sub": 0, "source": "raw", "index": "bypass", "series": 1,
            "groups": 1, "names_read": 0}
        plans = {r["tags"]["index"]: r["value"] for r in json.loads(
            router.handle(req("GET", "/api/stats")).body)
            if r["metric"] == "tsd.query.plan"}
        assert plans == {"built": 1, "hit": 1, "bypass": 1}
    finally:
        tsdb.shutdown()


@pytest.mark.parametrize("backend", ["native", "memory"])
def test_the_assemble_span_says_where_the_group_tags_were_read(backend):
    # PR 30: a planned request reads its groups' common and aggregated
    # tags from the plan index's layout; a tsuid query has no index
    # behind its matrix and sorts its own rows
    tsdb = mk_tsdb(**{"tsd.storage.backend": backend})
    router = HttpRpcRouter(tsdb)

    def assemble_tags(sub):
        body = json.dumps({
            "start": BASE * 1000, "end": (BASE + 600) * 1000,
            "queries": [{"aggregator": "sum", "downsample": "1m-avg",
                         **sub}]}).encode()
        resp = router.handle(req("POST", "/api/query", body))
        assert resp.status == 200, resp.body
        (root,) = json.loads(router.handle(req(
            "GET", "/api/trace/" + resp.headers["X-TSD-Trace-Id"])
        ).body)["tree"]
        execute = next(c for c in root["children"]
                       if c["name"] == "query.execute")
        (assemble,) = [c for c in execute["children"]
                       if c["name"] == "query.assemble"]
        return assemble["tags"]

    try:
        tsdb.import_buffer(import_text(), durable=False)
        grid = {"metric": "sys.stage", "filters": [{
            "type": "wildcard", "tagk": "dc", "filter": "*",
            "groupBy": True}]}
        for _ in range(2):      # the index built, then hit
            assert assemble_tags(grid) == {
                "sub": 0, "groups": 4, "pixels": 0, "tags": "index"}
        rec = tsdb.store.series(int(tsdb.store.series_ids_for_metric(
            tsdb.uids.metrics.get_id("sys.stage"))[3]))
        tsuid = tsdb.uids.tsuid(rec.metric_id, rec.tags).hex()
        assert assemble_tags({"tsuids": [tsuid]})["tags"] == "matrix"
        ways = {r["tags"]["tags"]: r["value"] for r in json.loads(
            router.handle(req("GET", "/api/stats")).body)
            if r["metric"] == "tsd.query.assemble"}
        assert ways == {"index": 2, "small": 0, "matrix": 1}
    finally:
        tsdb.shutdown()


def test_a_loader_outside_any_request_records_its_stages():
    tsdb = mk_tsdb()
    try:
        assert trace_mod.current() is None
        written, errors = tsdb.import_buffer(import_text(4, 10),
                                             durable=False)
        assert written == 40 and not errors
        stages = tsdb.stats.stage_latency
        for name in ("ingest.import", "ingest.decode",
                     "ingest.resolve", "store.scatter"):
            assert stages[name].count == 1, name
        (summary,) = [t for t in tsdb.tracer.recent()
                      if t["name"] == "ingest.import"]
        spans = by_name(tsdb.tracer.get(summary["traceId"]))
        assert spans["ingest.resolve"].tags == {"groups": 4}
        assert spans["ingest.decode"].tags == {"lines": 40}
        # inside a request nothing is rooted twice
        ctx = tsdb.tracer.start_request("ingest.put")
        with trace_mod.use(ctx):
            tsdb.import_buffer(import_text(4, 10), durable=False)
        tsdb.tracer.finish(ctx)
        assert stages["ingest.import"].count == 1
        assert stages["ingest.resolve"].count == 2
    finally:
        tsdb.shutdown()


# ---------------------------------------------------------------------
# the process's own counters
# ---------------------------------------------------------------------

@pytest.mark.parametrize("backend", ["native", "memory"])
def test_the_native_stores_pool_is_counted_at_api_stats(backend):
    # PR 39: the native store's parallel passes share one pool of parked
    # threads; a served panel is a pass on its caller alone, and the
    # threads the pool has made do not grow with requests
    tsdb = mk_tsdb(**{"tsd.storage.backend": backend})
    router = HttpRpcRouter(tsdb)

    def pool():
        rows = json.loads(router.handle(req("GET", "/api/stats")).body)
        passes = {r["tags"]["mode"]: r["value"] for r in rows
                  if r["metric"] == "tsd.storage.native.passes"}
        threads = [r["value"] for r in rows
                   if r["metric"] == "tsd.storage.native.pool_threads"]
        return passes, threads

    try:
        written, errors = tsdb.import_buffer(import_text(),
                                             durable=False)
        assert written == 16 * 60 and not errors
        if backend == "memory":     # the Python twin has no threads
            assert pool() == ({}, [])
            return
        (passes, (threads,)) = pool()
        assert set(passes) == {"inline", "pooled"}
        body = json.dumps({
            "start": BASE * 1000, "end": (BASE + 600) * 1000,
            "queries": [{"metric": "sys.stage", "aggregator": "max",
                         "downsample": "1m-max"}]}).encode()
        for _ in range(3):
            assert router.handle(
                req("POST", "/api/query", body)).status == 200
        after, (threads_after,) = pool()
        assert after == {"inline": passes["inline"] + 3,
                         "pooled": passes["pooled"]}
        assert threads_after == threads
    finally:
        tsdb.shutdown()


def test_the_compile_counter_moves_on_a_new_shape_only():
    import jax
    jax.clear_caches()
    tracer, _ = mk_tracer()
    spec = PipelineSpec(num_series=5, num_buckets=3, num_groups=2,
                        ds_function="avg", agg_name="max")
    args = (np.ones((5, 3)), np.ones((5, 3), bool),
            np.arange(3) * 1000, np.array([0, 1, 1, 0, 1], np.int32),
            spec)
    tagged = []
    counts = [RUNTIME.compiles]
    for _ in range(2):
        ctx = tracer.start_request("query.http")
        with trace_mod.use(ctx):
            execute_grid(*args)
        tracer.finish(ctx)
        counts.append(RUNTIME.compiles)
        prog = by_name(tracer.get(ctx.trace_id))["query.program"]
        tagged.append(prog.tags.get("compiled", False))
    assert counts[1] > counts[0] and counts[2] == counts[1]
    assert tagged == [True, False]
    assert RUNTIME.compile_ms > 0
    rows = {r[0]: r[1] for r in _records(tracer) if not r[2]}
    assert rows["tsd.device.compiles"] == counts[2]


def test_a_full_collection_is_counted_and_tags_the_root_it_hit():
    tracer, _ = mk_tracer()
    before = list(RUNTIME.gc_collections), list(RUNTIME.gc_pause_ms)
    ctx = tracer.start_request("query.http")
    gc.collect()                         # generation 2, inside the root
    tracer.finish(ctx)
    assert RUNTIME.gc_collections[2] == before[0][2] + 1
    assert RUNTIME.gc_pause_ms[2] > before[1][2]
    assert RUNTIME.gc_max_pause_ms > 0
    root = tracer.get(ctx.trace_id).root
    assert root.tags["gc_ms"] > 0
    quiet = tracer.start_request("query.http")
    tracer.finish(quiet)
    assert "gc_ms" not in tracer.get(quiet.trace_id).root.tags
    rows = {(r[0], r[2].get("gen")): r[1] for r in _records(tracer)}
    assert rows[("tsd.runtime.gc_collections", "2")] \
        == RUNTIME.gc_collections[2]
    assert rows[("tsd.runtime.gc_pause_ms", "2")] > 0


def test_start_up_is_timed_from_inside(monkeypatch):
    from opentsdb_tpu.tools.cli import make_tsdb
    monkeypatch.setattr(RUNTIME, "startup", {})
    tsdb = make_tsdb(Config(**{"tsd.tpu.warmup": "false"}))
    try:
        # a tool that only builds a TSDB never touches the backend
        assert list(RUNTIME.startup) == ["tsdb_init"]
        assert RUNTIME.startup["tsdb_init"] > 0
        with RUNTIME.phase("plugins"):
            pass
        rows = {r[2]["phase"]: r[1] for r in _records(tsdb.tracer)
                if r[0] == "tsd.startup.phase_s"}
        assert set(rows) == {"tsdb_init", "plugins"}
        health = json.loads(HttpRpcRouter(tsdb).handle(
            req("GET", "/api/health")).body)
        assert set(health["startup"]) == set(rows)
    finally:
        tsdb.shutdown()


@pytest.mark.parametrize("pass_id", ["trace-sites", "histogram-export"])
def test_the_registries_stay_closed(pass_id):
    """Every new span name is started somewhere, every started name is
    registered, and the self-time histograms are reachable from the
    one enumeration ``/metrics`` walks."""
    from opentsdb_tpu.tools.tsdlint import run_tsdlint
    rep = run_tsdlint(pass_ids=[pass_id], baseline_path=None)
    assert rep.unsuppressed == [], [str(f) for f in rep.unsuppressed]
