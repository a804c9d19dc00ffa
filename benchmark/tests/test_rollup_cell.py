"""The month a capacity dashboard reads from the 1h rollup tier
(``rollup-100k.month-avg``, PR 48): the configuration's data keys and
the traffic against the issue's, the fleet against ``live-100k``'s tag
for tag, the cell end to end at a size a test can hold on the CPU, an
altered answer and three planted faults the judge has to refuse, the
control, the judge against a cell-by-cell one written here, the
roofline's bytes, the readers the cell brings and the requests a
window can use of its list. The cell and its metrics are looked up by
NAME, never by position. (The served path against the judge at a
small size is tier-1: ``tests/test_rollup_served.py``.)"""

import types

import numpy as np
import pytest
from conftest import listed_with, load

import control
import deploy
import gen
import run
import traffic

CELL = "rollup-100k.month-avg"
CONFIG = "rollup-100k"
NEW = {"rollup.tier_share", "rollup.resident_mb",
       "rollup.upload_mb_per_query", "rollup.program_ms_per_query",
       "avg_div_roofline", "rollup.load_points_per_s"}
# what only a device trace gives is left out on the CPU
TRACE_ONLY = {"rollup.program_ms_per_query", "avg_div_roofline"}
# the per-layer metrics that list no cells: every cell reports them
EVERYWHERE = {"loadgen.late_ms", "loadgen.queries_per_s",
              "device.idle_share", "window.compiles",
              "startup.listen_s", "startup.compile_s"}
# every tag rule and the gappy tenth; two hosts a rack, 40 a
# datacentre; 4,096 x 768 padded cells: the tail is device-placed as
# at the cell's own size
SMALL = {"series": 4000, "chunk_series": 1000}
LOADER = "benchmark.rollup_plugin.Loader"


def _config() -> dict:
    return load(f"benchmark/configs/{CONFIG}.json")


# -- the data files -----------------------------------------------------

def test_the_configuration_is_the_issues(bench):
    cfg = _config()
    d = cfg["data"]
    assert (d["metric"], d["series"], d["t0"], d["cadence_s"],
            d["points"], d["raw_cadence_s"]) \
        == ("fleet.load", 100_000, 1356998400, 3600, 720, 10)
    data = deploy.generator_of(cfg).Data(d)
    assert (data.interval, data.raw_per_cell) == ("1h", 360)
    assert data.end - data.t0 + 1 == 30 * 86400 and data.t0 % 86400 == 0
    flags = dict(cfg["server"]["flags"])
    assert flags.pop("tsd.rpc.plugin") == LOADER
    assert flags.pop("tsd.query.device_cache_mb") == "2048"
    assert flags.pop("tsd.rollups.enable") == "true"
    wide = load("benchmark/configs/fleet-1m.json")
    theirs = dict(wide["server"]["flags"])
    theirs.pop("tsd.rpc.plugin")
    assert flags == theirs and cfg["server"]["wal"] is False
    assert cfg["server"]["env"] == wide["server"]["env"]
    for word in ("every cell written", "SUM cells", "COUNT cells",
                 "never a mean of hourly means",
                 "never SUM over the number of cells", "interpolates",
                 "live-100k's limits"):
        assert word in cfg["guarantees"]["answers"], word
    assert "fsync" in cfg["guarantees"]["durability"]
    assert cfg["reduced"] == ["series", "tiers", "tier_1m", "raw"] \
        == list(cfg["reduced_why"])
    assert cfg["limits"] == load(
        "benchmark/configs/live-100k.json")["limits"]
    assert "float32" in cfg["precision"]
    for key in ("source", "deployment", "assumed", "guarantees"):
        assert cfg[key], key
    assert cfg["generator"] == "benchmark/generators/rollup_tiers.py"
    assert cfg["reference"] == "benchmark/references/rollup_avg.py"
    entry = next(c for c in bench["configs"] if c["name"] == CONFIG)
    assert entry["source"] == cfg["source"] and len(cfg["source"]) <= 200
    for word in ("config 5", "rollups.html", "SUM/COUNT",
                 "api_http/rollup.html", "config 2"):
        assert word in entry["source"], word
    assert entry["source"] not in {c["source"] for c in bench["configs"]
                                   if c["name"] != CONFIG}
    assert entry["reduced"] == cfg["reduced"]
    assert [(w["name"], w["traffic"], w["chips"])
            for w in bench["workloads"] if w["config"] == CONFIG] \
        == [(CELL, "month-avg", 1)]
    assert len(bench["workloads"]) == 9
    assert not [w for w in bench["workloads"] if w["chips"] != 1]


def test_the_fleet_is_live_100ks_tag_for_tag():
    mine = deploy.generator_of(_config()).Data(_config()["data"])
    live = gen.Data(load("benchmark/configs/live-100k.json")["data"])
    assert (mine.series, mine.tags) == (live.series, live.tags)
    idx = np.arange(mine.series)
    for tagk in mine.tags:
        assert mine.tag_count(tagk) == live.tag_count(tagk)
        np.testing.assert_array_equal(mine.tag_ids(tagk, idx),
                                      live.tag_ids(tagk, idx))
        for i in (0, 1, mine.tag_count(tagk) - 1):
            assert mine.tag_name(tagk, i) == live.tag_name(tagk, i)
    np.testing.assert_array_equal(mine.is_gappy(idx),
                                  live.is_gappy(idx))
    assert (mine.cents_lo, mine.cents_hi) \
        == (live.cents_lo, live.cents_hi)
    assert mine.raw_cadence_s == live.cadence_s


def test_the_traffic_is_the_issues():
    spec = load("benchmark/traffic/month-avg.json")
    assert (spec["loop"], spec["clients"], spec["closed_list"],
            spec["warmup_per_template"], spec["timeout_s"]) \
        == ("closed", 1, 2000, 3, 30)
    for absent in ("rate_per_s", "trace_probe", "writes"):
        assert absent not in spec
    (tpl,) = spec["requests"]
    assert (tpl["method"], tpl["path"]) == ("POST", "/api/query")
    assert tpl["draw"] == {"rack": {"tag": "rack", "range": [0, 2000],
                                    "pick": 2}}
    assert tpl["body"]["start"] == "$start_ms" \
        and tpl["body"]["end"] == "$end_ms" and "window" not in tpl
    (sub,) = tpl["body"]["queries"]
    assert sub == {
        "metric": "$metric", "aggregator": "sum",
        "downsample": "1h-avg",
        "filters": [
            {"type": "wildcard", "tagk": "dc", "filter": "*",
             "groupBy": True},
            {"type": "not_literal_or", "tagk": "rack",
             "filter": "$rack", "groupBy": False}]}
    cfg = _config()
    data = deploy.generator_of(cfg).Data(cfg["data"])
    t = traffic.Traffic(spec, data, 2**31 + 42, 51)
    assert len(t.warmup) == 3 and len(t.timed) == 1997
    bodies = {r.body for r in t.warmup + t.timed}
    assert len(bodies) == 2000          # every request distinct
    first = t.timed[0].doc
    assert (first["start"], first["end"]) \
        == (data.t0 * 1000, (data.t0 + 30 * 86400) * 1000 - 1000)
    assert first["start"] % 3_600_000 == 0
    judge = deploy.judge_of(cfg)
    ref = judge.Reference(data, np.zeros((2, 0, 0)), cfg["limits"])
    assert ref.selected(first["queries"][0]) == 99_900
    deploy.refuse_unjudged(judge, data, t.timed[:1], "month-avg")


def test_requests_a_window_can_use_of_the_list(bench):
    """A closed loop's list has to outlast any window: at three times
    the predicted rate (150-260 ms a request: ~1,000 requests) the
    list of 1,997 still holds; it ends at 25.5 ms a request."""
    spec = load("benchmark/traffic/month-avg.json")
    timed = spec["closed_list"] - spec["warmup_per_template"]
    floor_ms = 1000.0 * bench["run_seconds"] / timed
    assert floor_ms == pytest.approx(25.54, abs=0.01)
    assert 3 * bench["run_seconds"] / 0.150 < timed


@pytest.mark.parametrize("template, why", [
    ({"downsample": "1h-sum"}, "divides the SUM tier"),
    ({"downsample": "90m-avg"}, "do not tile"),
    ({"downsample": "1h-avg", "rate": True}, "rate"),
    ({"downsample": "1h-avg", "metric": "other"}, "metric")])
def test_the_judge_refuses_what_it_cannot_answer(template, why):
    cfg = _config()
    judge = deploy.judge_of(cfg)
    data = deploy.generator_of(cfg).Data(cfg["data"])
    sub = {"metric": data.metric, "aggregator": "sum", **template}
    with pytest.raises(judge.Unsupported, match=why):
        judge.Reference.supports(sub, data)
    ok = {"metric": data.metric, "aggregator": "sum",
          "downsample": "1d-avg"}
    assert judge.Reference.supports(ok, data)[1] == 86400
    with pytest.raises(judge.Unsupported, match="window"):
        judge.Reference.supports(ok, data, (data.t0 * 1000,
                                            data.t0 * 1000 + 5))


def test_new_metrics_list_the_cell_alone(bench):
    mine = {m["name"]: m for m in bench["per_layer"]
            if m["name"] in NEW}
    assert set(mine) == NEW
    for m in mine.values():
        assert m["workloads"] == [CELL]
    assert {n: m["moves"] for n, m in mine.items()
            if m["moves"] != "query_p50_ms"} \
        == {"rollup.load_points_per_s": "setup_s"}
    assert mine["avg_div_roofline"]["unit"] == "%"
    assert {m["layer"] for m in mine.values()} == {
        "plan + placement", "device programs", "upload + HBM cache",
        "start-up"}
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    reported = {m["name"] for m in run.metrics_of(bench, "per_layer",
                                                  cell)}
    # the generic stage readers keep their lists until a benchmark PR
    # appends the cell (as after PR 34, 40 and 42)
    assert reported == NEW | EVERYWHERE
    assert listed_with(bench, CELL) == NEW
    assert {m["name"] for m in run.metrics_of(bench, "end_to_end",
                                              cell)} \
        == {"query_p50_ms", "setup_s"}


def test_the_least_bytes_are_the_deployments_alone():
    import kernels_rollup
    d = _config()["data"]
    least = kernels_rollup.avg_div_bytes(d["series"], d["points"], 100)
    # both grids once at four bytes, a label a series, the result
    assert least == 2 * 100_000 * 720 * 4 + 100_000 * 4 + 100 * 720 * 5
    # the resident pair, padded: what the program reads at the least
    resident = 2 * 114_688 * 768 * 4
    assert 0.80 < least / resident < 0.83


# -- the generator ----------------------------------------------------------

def test_the_generators_cells():
    cfg = _config()
    cfg["data"].update(SMALL)
    generator = deploy.generator_of(cfg)
    data = generator.Data(cfg["data"])
    frames = []
    values, points = generator.generate(data, 11, frames.append)
    again, _ = generator.generate(data, 11, None)
    np.testing.assert_array_equal(values, again)
    other, _ = generator.generate(data, 12, None)
    assert not np.array_equal(np.nan_to_num(values),
                              np.nan_to_num(other))
    sums, counts = values
    present = ~np.isnan(sums)
    np.testing.assert_array_equal(present, ~np.isnan(counts))
    assert points == 2 * int(present.sum())
    gappy = data.is_gappy(np.arange(data.series))
    assert present[~gappy].all() and (counts[~gappy] == 360).all()
    missing = 1 - present[gappy].mean()
    assert 0.005 < missing < 0.02           # about 1% of its hours
    kept = counts[gappy][present[gappy]]
    assert kept.min() >= 1 and kept.max() <= 360
    assert 0.01 < (kept < 300).mean() < 0.03    # the partial hours
    avg = sums[present] / counts[present]
    assert 1000.0 <= avg.min() and avg.max() < 10000.0
    cents = sums[present] * 100
    assert np.abs(cents - np.rint(cents)).max() < 1e-6      # whole cents
    assert len(frames) == data.chunks


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
        assert set(got) == (EVERYWHERE | NEW) - TRACE_ONLY
        assert got["rollup.tier_share"] == 100.0
        # a label a row of the metric (4,000 of them)
        assert got["rollup.upload_mb_per_query"] == pytest.approx(
            4000 * 4 / 1e6)
        # two float32 grids of 4,096 x 768
        assert got["rollup.resident_mb"] == pytest.approx(
            2 * 4096 * 768 * 4 / 1e6)
        assert got["rollup.load_points_per_s"] > 100_000
        assert got["window.compiles"] == 0
    else:
        assert set(got) == {"query_p50_ms", "setup_s"}
    c = doc["compared"]
    assert 0 <= c["sum_rel_err"]["value"] < 5e-6 \
        < c["sum_rel_err"]["limit"] == 4e-5
    assert c["shape_errors"]["value"] == 0
    assert "compared sum_rel_err" in capsys.readouterr().out


def test_every_generic_reader_reads_the_cell(bench, monkeypatch,
                                             tmp_path):
    """Through a scratch manifest that lists the cell wherever
    ``live-100k.groupby-quiet`` is listed, as the builder reads the
    cell's stage column on the chip: every one of those readers finds
    something but what only a device gives."""
    listed = listed_with(bench, "live-100k.groupby-quiet")
    scratch = load("BENCHMARK.json")
    for m in scratch["per_layer"]:
        if m["name"] in listed:
            m["workloads"] = m["workloads"] + [CELL]
    real = run.load_json
    monkeypatch.setattr(
        run, "load_json", lambda path: scratch
        if path.endswith("BENCHMARK.json") else real(path))
    code, doc = run.run_cell(CELL, 2**31 + 44, 3.0, True, shrink=SMALL)
    assert code == 3 and doc["correct"] is True
    got = {k: m["value"] for k, m in doc["metrics"].items()}
    # no device trace here; and this loader resolves no import text
    nothing_to_read = {"grid_tail_roofline", "startup.import_resolve_s"}
    assert set(got) == ((EVERYWHERE | NEW | listed) - TRACE_ONLY
                        - nothing_to_read)
    assert got["devicecache.hit_share"] == 1.0
    assert got["placement.on_device_share"] == 100.0
    assert got["program.compiles"] == 0 == got["window.compiles"]
    assert got["device.resident_mb"] == pytest.approx(
        got["rollup.resident_mb"])
    assert got["scan.ms"] < 1.0 and got["grid_build.ms"] < 1.0


@pytest.mark.parametrize("plugin", ["AlteredAnswer", "Undivided",
                                    "CellsNotCounts", "OtherRacks"])
def test_a_fault_is_not_correct(plugin, capsys):
    code, doc = run.run_cell(
        CELL, 2**31 + 43, 1.0, False, shrink=SMALL, require_tpu=False,
        server_flags={"tsd.rpc.plugin": f"{LOADER},"
                      f"benchmark.tests.rollup_faults.{plugin}"})
    assert code == 0 and doc["correct"] is False
    assert doc["failed"] == doc["attempted"] > 0
    c = doc["compared"]["sum_rel_err"]
    assert c["value"] > 10 * c["limit"]
    assert "failed: month-avg: " in capsys.readouterr().out


def test_without_a_fault_the_same_run_is_correct():
    code, doc = run.run_cell(CELL, 2**31 + 43, 1.0, False, shrink=SMALL,
                             require_tpu=False)
    assert code == 0 and doc["correct"] is True and doc["failed"] == 0


# -- the judge ----------------------------------------------------------------

def _cell_by_cell(data, values, sub: dict, group_tag: str,
                  excluded_racks: set) -> np.ndarray:
    """``sum:<n>h-avg`` by ``group_tag`` the slow way: a loop over the
    series, the buckets and the cells, ``[groups, buckets]`` with NaN
    where no member has a cell of its own."""
    k = int(sub["downsample"].split("h-")[0])
    nb = data.points // k
    out = np.zeros((data.tag_count(group_tag), nb))
    real = np.zeros(out.shape, dtype=bool)
    for i in range(data.series):
        one = np.array([i])
        if int(data.tag_ids("rack", one)[0]) in excluded_racks:
            continue
        gi = int(data.tag_ids(group_tag, one)[0])
        row = []
        for j in range(nb):
            total = count = 0.0
            for h in range(j * k, (j + 1) * k):
                if not np.isnan(values[0, i, h]):
                    total += values[0, i, h]
                    count += values[1, i, h]
            row.append(total / count if count > 0 else None)
        have = [j for j, v in enumerate(row) if v is not None]
        for j in range(nb):
            if row[j] is not None:
                out[gi, j] += row[j]
                real[gi, j] = True
            elif have and have[0] < j < have[-1]:
                a = max(x for x in have if x < j)
                b = min(x for x in have if x > j)
                out[gi, j] += row[a] + (row[b] - row[a]) \
                    * (j - a) / (b - a)
    return np.where(real, out, np.nan)


@pytest.mark.parametrize("downsample", ["1h-avg", "6h-avg", "24h-avg"])
def test_the_judge_is_the_cell_by_cell_one(downsample):
    cfg = _config()
    # few hosts, many of them gappy with long gaps: interpolation and
    # the weights both matter
    cfg["data"].update(series=400, chunk_series=200, points=48,
                       dcs=4, racks=40, drop_block=0.1,
                       outage_share=0.2)
    generator = deploy.generator_of(cfg)
    data = generator.Data(cfg["data"])
    values, _ = generator.generate(data, 5, None)
    judge = deploy.judge_of(cfg)
    ref = judge.Reference(data, values, cfg["limits"])
    for racks in ([], [3, 17]):
        filters = [{"type": "wildcard", "tagk": "dc", "filter": "*",
                    "groupBy": True}]
        if racks:
            filters.append({
                "type": "not_literal_or", "tagk": "rack",
                "filter": "|".join(data.tag_name("rack", r)
                                   for r in racks), "groupBy": False})
        sub = {"metric": data.metric, "aggregator": "sum",
               "downsample": downsample, "filters": filters}
        tagk, names, secs, cells = ref.answer(sub)
        assert (tagk, names, secs) == (
            "dc", [data.tag_name("dc", i) for i in range(4)],
            int(downsample.split("h-")[0]) * 3600)
        want = _cell_by_cell(data, values, sub, "dc", set(racks))
        got = np.where(cells.emitted, cells.want, np.nan)
        np.testing.assert_allclose(got, want, rtol=1e-12)
        assert np.isnan(want).sum() == 0 and (cells.scale > 0).all()
        # and the way reference.py goes, which the fast path replaces
        slow = judge.Reference(data, values, cfg["limits"])
        slow._partial = lambda *a: None
        np.testing.assert_allclose(
            slow.answer(sub)[3].want, cells.want, rtol=1e-12)
        np.testing.assert_allclose(
            slow.answer(sub)[3].atol, cells.atol, rtol=1e-12)
        np.testing.assert_array_equal(slow.answer(sub)[3].emitted,
                                      cells.emitted)


def test_the_control_is_not_correct():
    """SUM and COUNT cells held in bfloat16, the step below the
    float32 the configuration states: a summed cell is off by far more
    than ``sum_rtol``."""
    cfg = _config()
    cfg["data"].update(SMALL)
    generator = deploy.generator_of(cfg)
    data = generator.Data(cfg["data"])
    values, _ = generator.generate(data, 11, None)
    t = traffic.Traffic(load("benchmark/traffic/month-avg.json"), data,
                        11, 5)
    out = control.control_numbers(data, values, cfg["limits"],
                                  t.timed[:5], deploy.judge_of(cfg))
    assert out["correct"] is False and out["shape_errors"] == 0
    assert out["sum_rel_err"] > 5 * cfg["limits"]["sum_rtol"]


# -- the readers ------------------------------------------------------------

def _snap(executes, sources, uploaded, resident):
    hists = [{"name": "tsd_stage_latency_ms", "count": executes,
              "sum": ms * executes, "labels": {"stage": stage}}
             for stage, ms in (("query.http", 200.0),
                               ("query.execute", 150.0))]
    records = [{"metric": "tsd.query.rollup", "value": n,
                "tags": {"source": source}}
               for source, n in sources.items()]
    if uploaded is not None:
        records += [
            {"metric": "tsd.query.rollup.upload_bytes",
             "value": uploaded, "tags": {}},
            {"metric": "tsd.query.rollup.resident_bytes",
             "value": resident, "tags": {}}]
    return {"stats": {"histograms": hists, "records": records}}


def test_the_readers():
    ctx = types.SimpleNamespace(
        before=_snap(3, {"raw": 0, "tier": 3, "fallback": 0},
                     705_000_000, 704_643_072),
        after=_snap(13, {"raw": 1, "tier": 12, "fallback": 0},
                    709_000_000, 704_643_072),
        trace={"modules": [["jit_run_pipeline_avg_div(123)", 8, 0.8],
                           ["jit_run_pipeline_grid(7)", 2, 0.5]]},
        peaks={"hbm_bytes_per_s": 819e9}, first_shape=(99_900, 720, 100),
        config=_config(), workload={"name": "no-such-cell"})
    read = {name: run.read_metric(name, ctx) for name in NEW}
    assert read["rollup.tier_share"] == pytest.approx(90.0)
    assert read["rollup.resident_mb"] == pytest.approx(704.643072)
    assert read["rollup.upload_mb_per_query"] == pytest.approx(0.4)
    assert read["rollup.program_ms_per_query"] == pytest.approx(100.0)
    least_s = (2 * 100_000 * 720 * 4 + 400_000 + 360_000) / 819e9
    assert read["avg_div_roofline"] == pytest.approx(
        100 * least_s / 0.100)
    assert 0 < read["avg_div_roofline"] < 1
    assert read["rollup.load_points_per_s"] is None      # no such log
    # a program without the counters or the module (the parent of
    # PR 48): nothing to read, and no reader raises
    bare = types.SimpleNamespace(
        before=_snap(3, {}, None, None), after=_snap(13, {}, None, None),
        trace={"modules": [["jit_run_pipeline_grid(5)", 10, 0.2]]},
        peaks=ctx.peaks, first_shape=ctx.first_shape, config=ctx.config,
        workload=ctx.workload)
    for name in NEW:
        assert run.read_metric(name, bare) is None, name
    bare.trace = None
    assert run.read_metric("rollup.program_ms_per_query", bare) is None
