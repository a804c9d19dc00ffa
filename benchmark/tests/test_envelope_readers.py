"""The readers of the request's envelope and of the tracer's own
counters (PR 36): each gives a number on a CPU run of a shrunk cell,
None on a program without its span or counter, and ``path.fallbacks``
the sum of the five records' growth. They list the two cells whose
lists no test the benchmark had pins."""

import pytest
from conftest import TINY

import envreaders
import run

CELL = "fleet-1m.small-panels"
NEW = ("receive.ms", "admission.ms", "http.self_ms", "respond.ms",
       "worker.cpu_ms_per_query", "gc.gen0_pause_ms",
       "trace.finish_ms_per_query", "path.fallbacks")
# what the parent of PR 36 exported already
OLD_SOURCES = ("admission.ms", "http.self_ms", "gc.gen0_pause_ms",
               "path.fallbacks")


@pytest.fixture(scope="module")
def traced():
    """The result object of one traced run of the panels at a size a
    test can hold."""
    code, doc = run.run_cell(CELL, 2**31 + 36, 4.0, True, shrink=TINY)
    assert code == 3                 # this sandbox has no TPU
    assert doc["correct"] is True and doc["failed"] == 0
    return doc


def snap(at=0.0, histograms=(), records=()):
    """A hand-made ``Tsd.snapshot()``: ``histograms`` as (family,
    stage, count, sum), ``records`` as (metric, tags, value)."""
    return {"at": at, "stats": {
        "histograms": [{"name": f, "labels": {"stage": s}, "count": n,
                        "sum": total} for f, s, n, total in histograms],
        "records": [{"metric": m, "tags": t, "value": v}
                    for m, t, v in records]}}


def ctx_of(before, after):
    ctx = run.Context()
    ctx.before, ctx.after = before, after
    return ctx


@pytest.mark.parametrize("name", NEW)
def test_reader_gives_a_number_and_none_without_its_source(
        bench, traced, name):
    entry = next(m for m in bench["per_layer"] if m["name"] == name)
    # PR 36's two cells, then the cells gate (b) appended (PR 47): rank
    # wherever the wide cell is, the wildcard cell wherever the panels
    # are, the histograms and the moving window with the wide cell
    assert entry["workloads"] == [
        "fleet-1m.wide-groupby", CELL, "fleet-1m.rank-p95",
        "fleet-1m.wildcard-lookup", "hist-200k.percentiles",
        "fleet-1m.refresh"]
    assert entry["moves"] == "query_p50_ms" and entry["better"] == "lower"
    got = traced["metrics"][name]
    assert got["unit"] == entry["unit"]
    assert isinstance(got["value"], float) and got["value"] >= 0.0
    # the parent of PR 36: requests ran, and nothing of this PR's is
    # exported (query.admission and the tsd.query.* counters it had)
    roots = [("tsd_stage_latency_ms", "query.http", 10, 100.0)]
    old = ctx_of(snap(0.0), snap(10.0, roots))
    if name not in OLD_SOURCES:
        assert run.read_metric(name, old) is None
    # and no request at all in the window
    assert run.read_metric(name, ctx_of(snap(0.0), snap(10.0))) is None


def test_the_envelope_adds_up_on_a_cpu_run(traced):
    v = {k: m["value"] for k, m in traced["metrics"].items()}
    assert v["path.fallbacks"] == 0
    # a panel's threads run most of it: no chip to wait for here
    assert 0.0 < v["worker.cpu_ms_per_query"] < 1.5 * v["execute.ms"]
    assert 0.0 < v["gc.gen0_pause_ms"] < 50.0
    assert 0.0 < v["trace.finish_ms_per_query"] < 1.0
    assert 0.0 < v["respond.ms"] and 0.0 < v["admission.ms"]
    assert 0.0 < v["http.self_ms"] < v["execute.ms"]


def test_path_fallbacks_sums_the_five_records_growth():
    def recs(bypass, built, walk, host, matrix, hit=0, ids=0):
        return [("tsd.query.plan", {"index": "hit"}, hit),
                ("tsd.query.plan", {"index": "built"}, built),
                ("tsd.query.plan", {"index": "bypass"}, bypass),
                ("tsd.query.filter", {"resolve": "ids"}, ids),
                ("tsd.query.filter", {"resolve": "walk"}, walk),
                ("tsd.query.filter", {"resolve": "presence"}, 3),
                ("tsd.query.grid_build", {"mode": "fused"}, hit),
                ("tsd.query.grid_build", {"mode": "host"}, host),
                ("tsd.query.assemble", {"tags": "index"}, hit),
                ("tsd.query.assemble", {"tags": "matrix"}, matrix)]
    before = snap(0.0, records=recs(1, 1, 0, 2, 0, hit=5, ids=5))
    # the fast ways grow and do not count
    quiet = ctx_of(before, snap(10.0, records=recs(1, 1, 0, 2, 0,
                                                   hit=500, ids=900)))
    assert run.read_metric("path.fallbacks", quiet) == 0
    after = snap(10.0, records=recs(1 + 2, 1 + 3, 0 + 5, 2 + 7, 0 + 11,
                                    hit=500, ids=900))
    assert run.read_metric("path.fallbacks",
                           ctx_of(before, after)) == 2 + 3 + 5 + 7 + 11
    assert len(envreaders.FALLBACKS) == 5


def test_the_per_query_readers_divide_by_the_roots():
    roots = lambda n: [("tsd_stage_latency_ms", "query.http", n, n * 9.0),
                       ("tsd_stage_latency_ms", "query.respond", n,
                        n * 0.5),
                       ("tsd_stage_self_ms", "query.http", n, n * 1.5)]
    counters = lambda k: [
        ("tsd.trace.finish_ms", {}, 2.0 + 0.1 * k),
        ("tsd.runtime.thread_cpu_ms", {"thread": "tsd-query"},
         500.0 + 6.0 * k),
        ("tsd.runtime.thread_cpu_ms", {"thread": "tsd-subq"}, 1.0 * k),
        # the loop's and the writers' threads serve no query
        ("tsd.runtime.thread_cpu_ms", {"thread": "MainThread"}, 2.0 * k),
        ("tsd.runtime.thread_cpu_ms", {"thread": "asyncio"}, 90.0 * k),
        ("tsd.runtime.gc_pause_ms", {"gen": "0"}, 0.2 * k),
        ("tsd.runtime.gc_pause_ms", {"gen": "2"}, 30.0 * k),
        ("tsd.runtime.gc_collections", {"gen": "0"}, 2 * k),
        ("tsd.runtime.gc_collections", {"gen": "2"}, k // 8)]
    ctx = ctx_of(snap(0.0, roots(4), counters(4)),
                 snap(10.0, roots(24), counters(24)))
    assert run.read_metric("worker.cpu_ms_per_query", ctx) \
        == pytest.approx(7.0)
    assert run.read_metric("gc.gen0_pause_ms", ctx) \
        == pytest.approx(0.1)
    assert run.read_metric("trace.finish_ms_per_query", ctx) \
        == pytest.approx(0.1)
    assert run.read_metric("respond.ms", ctx) == pytest.approx(0.5)
    assert run.read_metric("http.self_ms", ctx) == pytest.approx(1.5)
