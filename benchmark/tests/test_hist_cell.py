"""Latency histograms at a deployment's size (``hist-200k.percentiles``,
PR 42): the configuration's data keys against the issue's, the cell
end to end at a size a test can hold on the CPU, three planted faults
the judge has to refuse (a merge in bfloat16, a dropped series, a
dropped bucket of time), the control, the roofline's bytes and the
readers the cell brings. The cell and its metrics are looked up by
NAME, never by position. (The served path against the judge at a
small size is tier-1: ``tests/test_histogram_served.py``.)"""

import types

import numpy as np
import pytest
from conftest import listed_with, load

import deploy
import run
import traffic

CELL = "hist-200k.percentiles"
CONFIG = "hist-200k"
NEW = {"hist.on_device_share", "hist.merge_ms_per_query",
       "hist_merge_roofline", "hist.upload_mb_per_query",
       "hist.resident_mb", "hist.plan_ms", "hist.load_points_per_s"}
# what only a device trace gives is left out on the CPU
TRACE_ONLY = {"hist.merge_ms_per_query", "hist_merge_roofline"}
# the per-layer metrics that list no cells: every cell reports them
EVERYWHERE = {"loadgen.late_ms", "loadgen.queries_per_s",
              "device.idle_share", "window.compiles",
              "startup.listen_s", "startup.compile_s"}
# the wide cell's stage metrics that a histogram request cannot report
NOT_A_HISTOGRAMS = {"scan.ms", "grid_build.ms", "grid_tail_roofline",
                    "startup.import_resolve_s"}
GENERIC = {
    "plan.ms", "execute.ms", "execute.self_ms", "upload.ms",
    "program.wait_ms", "download.ms", "assemble.ms", "serialize.ms",
    "tail.query_p90_ms", "devicecache.hit_share", "device.resident_mb",
    "program.busy_ms_per_query", "placement.on_device_share",
    "device.unoccupied_share", "idle.unnamed_share",
    "idle.no_request_share", "program.compiles", "gc.pause_share",
    "startup.backend_s", "receive.ms", "admission.ms", "http.self_ms",
    "respond.ms", "worker.cpu_ms_per_query", "gc.gen0_pause_ms",
    "trace.finish_ms_per_query", "path.fallbacks"}
# every tag rule and the gappy tenth; a rack of two hosts, 80 hosts a
# datacentre
SMALL = {"series": 8000, "chunk_series": 2000}
LOADER = "benchmark.hist_plugin.Loader"


def _config() -> dict:
    return load(f"benchmark/configs/{CONFIG}.json")


# -- the data files -----------------------------------------------------

def test_the_configuration_is_the_issues(bench):
    cfg = _config()
    d = cfg["data"]
    assert (d["metric"], d["series"], d["t0"], d["cadence_s"],
            d["points"], d["buckets"]) \
        == ("svc.latency_ms", 200_000, 1356998400, 60, 60, 64)
    assert (d["dcs"], d["racks"], d["fleets"]) == (100, 4000, 8)
    assert (d["drop_single"], d["drop_block"], d["count_max"]) \
        == (0.005, 0.005, 65_535)
    data = deploy.generator_of(cfg).Data(d)
    np.testing.assert_allclose(data.bounds, np.logspace(0, 4, 65))
    wide = load("benchmark/configs/fleet-1m.json")
    flags = dict(cfg["server"]["flags"])
    assert flags.pop("tsd.rpc.plugin") == LOADER
    assert flags.pop("tsd.query.device_cache_mb") == "8192"
    theirs = dict(wide["server"]["flags"])
    theirs.pop("tsd.rpc.plugin")
    assert flags == theirs and cfg["server"]["wal"] is False
    assert flags["tsd.query.degraded.host_fallback"] == "false"
    assert cfg["server"]["env"] == wide["server"]["env"]
    assert cfg["guarantees"]["durability"] \
        == wide["guarantees"]["durability"]
    for word in ("every loaded histogram point", "SUM", "no sketch",
                 "no sample", "no merged count rounded", "midpoint",
                 "cum < target"):
        assert word in cfg["guarantees"]["answers"], word
    assert cfg["reduced"] == ["series"] and "a fifth" in \
        cfg["reduced_why"]
    assert set(cfg["limits"]) == {"sum_rtol", "rank_atol", "tie_rtol",
                                  "tie_share"} == set(cfg["limits_why"])
    assert cfg["generator"] \
        == "benchmark/generators/histogram_points.py"
    assert cfg["reference"] == "benchmark/references/histograms.py"
    entry = next(c for c in bench["configs"] if c["name"] == CONFIG)
    assert entry["source"] == cfg["source"] and len(cfg["source"]) <= 200
    for word in ("config 4", "/api/histogram", "SimpleHistogramCodec",
                 "percentiles", "SimpleHistogram.percentile"):
        assert word in entry["source"], word
    assert entry["source"] not in {c["source"] for c in bench["configs"]
                                   if c["name"] != CONFIG}
    assert entry["reduced"] == ["series"]
    assert [(w["name"], w["traffic"], w["chips"])
            for w in bench["workloads"] if w["config"] == CONFIG] \
        == [(CELL, "percentiles", 1)]


def test_the_traffic_is_the_issues():
    spec = load("benchmark/traffic/percentiles.json")
    assert (spec["loop"], spec["clients"], spec["closed_list"],
            spec["warmup_per_template"], spec["timeout_s"]) \
        == ("closed", 1, 4000, 3, 30)
    assert "rate_per_s" not in spec and "trace_probe" not in spec
    (tpl,) = spec["requests"]
    assert (tpl["method"], tpl["path"]) == ("POST", "/api/query")
    assert tpl["draw"] == {"rack": {"tag": "rack", "range": [0, 4000],
                                    "pick": 1}}
    (sub,) = tpl["body"]["queries"]
    assert sub == {
        "metric": "$metric", "aggregator": "sum",
        "downsample": "5m-sum", "percentiles": [99.0, 99.9],
        "filters": [
            {"type": "wildcard", "tagk": "dc", "filter": "*",
             "groupBy": True},
            {"type": "not_literal_or", "tagk": "rack",
             "filter": "$rack", "groupBy": False}]}
    cfg = _config()
    data = deploy.generator_of(cfg).Data(cfg["data"])
    t = traffic.Traffic(spec, data, 2**31 + 42, 51)
    first = t.timed[0].doc["queries"][0]
    ref = deploy.judge_of(cfg).Reference(
        data, np.zeros((0, 0, 0), np.uint16), cfg["limits"])
    assert ref.selected(first) == 199_950


def test_new_metrics_list_the_cell_alone(bench):
    mine = {m["name"]: m for m in bench["per_layer"]
            if m["name"] in NEW}
    assert set(mine) == NEW
    for m in mine.values():
        assert m["workloads"] == [CELL]
    assert {n: m["moves"] for n, m in mine.items()
            if m["moves"] != "query_p50_ms"} \
        == {"hist.load_points_per_s": "setup_s"}
    assert mine["hist_merge_roofline"]["unit"] == "%"
    assert {m["layer"] for m in mine.values()} == {
        "plan + placement", "device programs", "upload + HBM cache",
        "start-up"}
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    reported = {m["name"] for m in run.metrics_of(bench, "per_layer",
                                                  cell)}
    # gate (b), PR 47: the generic stage metrics the wide cell reads,
    # but for the four a histogram request has nothing to show in (it
    # scans no store, builds no grid, runs no grid tail, and its loader
    # resolves no import text)
    assert reported == NEW | EVERYWHERE | GENERIC
    assert listed_with(bench, "fleet-1m.wide-groupby") - GENERIC \
        == NOT_A_HISTOGRAMS
    assert {m["name"] for m in run.metrics_of(bench, "end_to_end",
                                              cell)} \
        == {"query_p50_ms", "setup_s"}


def test_the_least_bytes_are_the_deployments_alone():
    import kernels_hist
    d = _config()["data"]
    least = kernels_hist.hist_merge_bytes(
        d["series"], d["points"], d["buckets"], 100, 12, 2)
    # every count once at two bytes, a label a series, the result
    assert least == 200_000 * 60 * 64 * 2 + 200_000 * 4 + 100 * 12 * 2 * 4
    # PR 42's resident float32 layout, padded: the share's ceiling
    resident = 229_376 * 64 * 64 * 4
    assert 0.40 < least / resident < 0.50


# -- the cell, end to end -------------------------------------------------

@pytest.mark.parametrize("trace", [0, 1])
def test_cell_runs_to_its_last_line_and_then_wants_a_tpu(
        bench, trace, capsys):
    code, doc = run.run_cell(CELL, 2**31 + 42, 3.0, bool(trace),
                             shrink=SMALL)
    assert code == 3                 # this sandbox has no TPU
    assert doc["correct"] is True and doc["failed"] == 0
    assert doc["attempted"] >= 5
    got = {k: m["value"] for k, m in doc["metrics"].items()}
    if trace:
        assert set(got) == (EVERYWHERE | NEW | GENERIC) - TRACE_ONLY
        assert got["path.fallbacks"] == 0
        assert got["devicecache.hit_share"] == 1.0
        assert got["hist.on_device_share"] == 100.0
        # a label a resident row (8,192 of them) and a few vectors
        assert 0.03 < got["hist.upload_mb_per_query"] < 0.04
        # 8,192 series x 64 slots x (64 buckets + presence) float32
        assert got["hist.resident_mb"] == pytest.approx(
            8192 * 64 * 65 * 4 / 1e6)
        assert 0 < got["hist.plan_ms"] < 50
        assert got["hist.load_points_per_s"] > 10_000
        assert got["window.compiles"] == 0
    else:
        assert set(got) == {"query_p50_ms", "setup_s"}
    c = doc["compared"]
    assert c["sum_rel_err"]["value"] == 0       # no summed cell
    assert 0 < c["rank_abs_err"]["value"] <= 5e-4 \
        < c["rank_abs_err"]["limit"] == 0.005
    assert c["shape_errors"]["value"] == 0
    assert "compared rank_abs_err" in capsys.readouterr().out


# four hosts a datacentre that differ by buckets: one of them gone
# moves a percentile's bucket (among 2,000 hosts that are alike, a
# percentile hides one host: PERF.md section 2)
FEW = {"series": 400, "chunk_series": 200, "mu_host": 0.5}


@pytest.mark.parametrize("fault, plugin, number, shrink", [
    ("a-merge-in-bfloat16", "Bfloat16Merge", "rank_abs_err", SMALL),
    ("a-dropped-series", "DroppedSeries", "rank_abs_err", FEW),
    ("a-dropped-bucket", "DroppedBucket", "shape_errors", SMALL)])
def test_a_fault_is_not_correct(fault, plugin, number, shrink, capsys):
    code, doc = run.run_cell(
        CELL, 2**31 + 43, 1.0, False, shrink=shrink, require_tpu=False,
        server_flags={"tsd.rpc.plugin": f"{LOADER},"
                      f"benchmark.tests.hist_faults.{plugin}"})
    assert code == 0 and doc["correct"] is False
    # at 80 hosts a datacentre a merged count is rounded by a few
    # units and one cell of an answer's 2,400 sits that near its
    # target; an answer whose excluded rack is in it may differ
    assert doc["failed"] > 0.8 * doc["attempted"] > 0
    c = doc["compared"][number]
    # a whole bucket's midpoint at the least (0.16), not a rounding
    assert c["value"] > (0.1 if number == "rank_abs_err" else 0)
    assert "failed: percentiles: " in capsys.readouterr().out


def test_without_a_fault_the_few_hosts_are_correct():
    code, doc = run.run_cell(CELL, 2**31 + 43, 1.0, False, shrink=FEW,
                             require_tpu=False)
    assert code == 0 and doc["correct"] is True and doc["failed"] == 0


def test_the_control_is_not_correct():
    """A merge that comes out in bfloat16, the step below the float32
    the configuration states, moves whole buckets; bfloat16 STORAGE
    (``control.py``'s lowering) changes nothing, a point's counts
    being under 256."""
    from hist_faults import to_bfloat16
    cfg = _config()
    cfg["data"].update(SMALL)
    generator = deploy.generator_of(cfg)
    data = generator.Data(cfg["data"])
    values, points = generator.generate(data, 11, None)
    assert points == int(values.any(axis=2).sum()) and values.max() < 256
    judge = deploy.judge_of(cfg)
    ref = judge.Reference(data, values, cfg["limits"])
    t = traffic.Traffic(load("benchmark/traffic/percentiles.json"),
                        data, 11, 5)
    limits = cfg["limits"]
    worst = 0.0
    for req in t.timed[:10]:
        sub = req.doc["queries"][0]
        _tagk, _names, _secs, cells = ref.answer(sub)
        low = judge.compare(ref.lowered(sub, to_bfloat16), 0, cells)
        assert not low.ok(limits["sum_rtol"], limits["rank_atol"])
        assert low.shape_errors >= 0 and low.rank_abs_err > 0.1
        worst = max(worst, low.rank_abs_err)
        # float32, what the configuration states, passes with no tie
        sound = judge.compare(
            ref.lowered(sub, lambda m: m.astype(np.float32))
            .astype(np.float32).astype(np.float64), 0, cells)
        assert sound.ok(limits["sum_rtol"], limits["rank_atol"])
        assert sound.shape_errors == 0 and sound.ties <= 2
    assert worst > 20 * limits["rank_atol"]
    same = judge.Reference(data, to_bfloat16(values).astype(np.float64),
                           limits)
    sub = t.timed[0].doc["queries"][0]
    np.testing.assert_array_equal(same.answer(sub)[3].want,
                                  ref.answer(sub)[3].want)


def test_a_tie_cell_takes_its_neighbour_and_no_other_cell_does():
    cfg = _config()
    cfg["data"].update(series=200, chunk_series=200, points=5,
                       buckets=4, dcs=1, racks=4, block_points=5)
    judge = deploy.judge_of(cfg)
    data = deploy.generator_of(cfg).Data(cfg["data"])
    values = np.zeros((200, 5, 4), dtype=np.uint16)
    # one group of 200 series, one bucket of time: 100,000 counts of
    # which bucket 0 holds exactly a half
    values[:, :, 0] = 50
    values[:, :, 1] = 30
    values[:, :, 3] = 20
    sub = {"metric": data.metric, "aggregator": "sum",
           "downsample": "5m-sum", "percentiles": [50.0, 60.0]}
    ref = judge.Reference(data, values, dict(cfg["limits"],
                                             tie_share=0.5))
    _tagk, names, _secs, cells = ref.answer(sub)
    assert names == ["|50", "|60"]
    mids = ref.mids
    # cum 50,000 is not < the target 50,000: bucket 0; within the
    # allowance it may read as below: bucket 1
    assert (cells.want[0, 0], cells.lo[0, 0], cells.hi[0, 0]) \
        == (mids[0], mids[0], mids[1])
    assert cells.want[1, 0] == cells.lo[1, 0] == cells.hi[1, 0] \
        == mids[1]
    for got, ok, ties in ((mids[[0, 1]], True, 0),
                          (mids[[1, 1]], True, 1),
                          (mids[[2, 1]], False, 0),
                          (mids[[0, 0]], False, 0)):
        v = judge.compare(got[:, None].astype(float), 0, cells)
        assert v.ok(0.0, 0.005) is ok, got
        assert not ok or v.ties == ties, got
    # the share an answer may have: one tie cell of two is too many
    # for a share of a tenth ... of 2 cells, which rounds up to one
    strict = judge.Reference(data, values, dict(cfg["limits"],
                                                tie_share=0.0))
    v = judge.compare(mids[[1, 1]][:, None].astype(float), 0,
                      strict.answer(sub)[3])
    assert v.shape_errors == 1 and not v.ok(0.0, 0.005)


# -- the readers ------------------------------------------------------------

def _snap(executes, tails, uploaded, resident):
    hists = [{"name": "tsd_stage_latency_ms", "count": executes,
              "sum": ms * executes, "labels": {"stage": stage}}
             for stage, ms in (("query.http", 11.0),
                               ("query.execute", 9.0),
                               ("query.plan", 2.0))]
    records = [{"metric": "tsd.query.tail", "value": n,
                "tags": {"class": cls, "placement": place,
                         "path": "hist"}}
               for (cls, place), n in tails.items()]
    if uploaded is not None:
        records += [
            {"metric": "tsd.query.histogram.upload_bytes",
             "value": uploaded, "tags": {}},
            {"metric": "tsd.query.histogram.resident_bytes",
             "value": resident, "tags": {}}]
    return {"stats": {"histograms": hists, "records": records}}


def test_the_readers():
    ctx = types.SimpleNamespace(
        before=_snap(3, {("histogram", "device"): 3}, 4_000_000_000,
                     3_800_000_000),
        after=_snap(13, {("histogram", "device"): 12,
                         ("histogram", "host"): 1,
                         ("linear", "host"): 5}, 4_009_000_000,
                    3_800_000_000),
        trace={"modules": [["jit_histogram_percentiles(123)", 8, 0.08],
                           ["jit__place_rows(7)", 2, 0.5]]},
        peaks={"hbm_bytes_per_s": 819e9}, first_shape=(199_950, 12, 200),
        config=_config(), workload={"name": "no-such-cell"},
        results=[types.SimpleNamespace(request=types.SimpleNamespace(
            doc={"queries": [{"percentiles": [99.0, 99.9]}]}))])
    read = {name: run.read_metric(name, ctx) for name in NEW}
    assert read["hist.on_device_share"] == pytest.approx(90.0)
    assert read["hist.merge_ms_per_query"] == pytest.approx(10.0)
    least_s = (200_000 * 60 * 64 * 2 + 800_000 + 9_600) / 819e9
    assert read["hist_merge_roofline"] == pytest.approx(
        100 * least_s / 0.010)
    assert 0 < read["hist_merge_roofline"] < 50
    assert read["hist.upload_mb_per_query"] == pytest.approx(0.9)
    assert read["hist.resident_mb"] == pytest.approx(3800.0)
    assert read["hist.plan_ms"] == pytest.approx(2.0)
    assert read["hist.load_points_per_s"] is None      # no such log
    # a program without the counters, the class or the module (the
    # parent of PR 42): nothing to read, and no reader raises
    bare = types.SimpleNamespace(
        before=_snap(3, {("linear", "device"): 3}, None, None),
        after=_snap(13, {("linear", "device"): 13}, None, None),
        trace={"modules": [["jit_run_pipeline_grid(5)", 10, 0.2]]},
        peaks=ctx.peaks, first_shape=ctx.first_shape, config=ctx.config,
        workload=ctx.workload, results=ctx.results)
    for name in NEW - {"hist.plan_ms"}:
        assert run.read_metric(name, bare) is None, name
    bare.trace = None
    assert run.read_metric("hist.merge_ms_per_query", bare) is None
