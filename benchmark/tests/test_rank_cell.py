"""The percentile over the fleet (``fleet-1m.rank-p95``, PR 34): its
judge against ``tests/oracle.py``, its data files against the wide
cell's, the cell end to end at a size a test can hold on the CPU, the
control, and the three readers it brings."""

import json
import types

import numpy as np
import pytest
from conftest import TINY, listed_with, load, tiny_config
from test_reference import load_oracle, oracle_answer

import control
import deploy
import gen
import reference
import run
import traffic

CELL = "fleet-1m.rank-p95"
CONFIG = "fleet-1m-rank"
# PR 34 brought three; the sort's device time went with the sort (PR 44
# left no ``sort...`` operation to read, and the trace carries no name
# scope to read the selection by: PR 47 took the metric out)
NEW = {"rank.on_device_share", "rank_tail_roofline"}
# the per-layer metrics that list no cells: every cell reports them
EVERYWHERE = {"loadgen.late_ms", "loadgen.queries_per_s",
              "device.idle_share", "window.compiles",
              "startup.listen_s", "startup.compile_s"}
# what only a device trace or a device-placed tail gives is left out
# on the CPU
DEVICE_ONLY = {"grid_tail_roofline", "devicecache.hit_share",
               "device.resident_mb"}


def _judge():
    return deploy.judge_of(load(f"benchmark/configs/{CONFIG}.json"))


def _sub(data, agg: str, rack: str | None, downsample="5m-avg") -> dict:
    filters = [{"type": "wildcard", "tagk": "dc", "filter": "*",
                "groupBy": True}]
    if rack:
        filters.append({"type": "not_literal_or", "tagk": "rack",
                        "filter": rack, "groupBy": False})
    return {"metric": data.metric, "aggregator": agg,
            "downsample": downsample, "filters": filters}


# -- the judge ----------------------------------------------------------

@pytest.mark.parametrize("agg, rack", [
    ("p50", "r0950"), ("p75", "r0007"), ("p90", "r1913"),
    ("p95", "r0123"), ("p99", "r0907"), ("p999", "r0950")])
def test_the_rank_judge_matches_the_oracle(agg, rack):
    oracle = load_oracle()
    cfg = tiny_config(CONFIG)
    cfg["data"].update(series=2000, chunk_series=1000, drop_single=0.05,
                       drop_block=0.05)
    data = gen.Data(cfg["data"])
    values = np.concatenate([gen.chunk_lines(data, 3, c)[1]
                             for c in range(data.chunks)])
    ref = _judge().Reference(data, values, cfg["limits"])
    sub = _sub(data, agg, rack)
    tagk, names, secs, cells = ref.answer(sub)
    assert (tagk, len(names), secs) == ("dc", data.dcs, 300)
    # an order statistic: held to rank_atol, like max and min, beyond
    # what float32 cannot resolve of the position h (at most h / 2**23
    # of the gap between the two neighbours)
    assert not cells.scale.any() and (cells.atol >= 0).all()
    n = 2000 // data.dcs
    assert cells.atol.max() <= (n + 1) * 2.0 ** -22 * 9000
    idx = np.arange(data.series)
    gone = data.tag_index("rack", rack)
    touched = gone % data.dcs
    for gi in (0, 57, touched):
        members = idx[(idx % data.dcs == gi) & (idx % data.racks != gone)]
        want = oracle_answer(oracle, data, values, members, sub)
        got = {data.t0 * 1000 + j * secs * 1000: cells.want[gi, j]
               for j in range(cells.want.shape[1]) if cells.emitted[gi, j]}
        assert sorted(got) == sorted(want) and len(want) == 12
        for t, v in want.items():
            assert got[t] == pytest.approx(v, rel=1e-12, abs=1e-9), (gi, t)
    # the excluded rack changed its own group and no other
    _t, _n, _s, whole = ref.answer(_sub(data, agg, None))
    same = np.isclose(whole.want, cells.want, equal_nan=True).all(axis=1)
    assert not same[touched] and same.sum() == data.dcs - 1


def test_the_ends_of_a_group_and_a_member_that_does_not_count(
        monkeypatch):
    """Groups of one and of two (h >= n: the maximum), a position
    below one (the minimum, through a percentile of the tests' own) and
    a member with no value, real or interpolated, in a bucket."""
    rank = _judge()
    cfg = tiny_config(CONFIG)
    data = gen.Data(dict(cfg["data"], series=6, chunk_series=6, dcs=3,
                         racks=6))
    values = np.tile(np.array([[40.0], [10.0], [7.0], [30.0], [20.0],
                               [9.0]]), (1, data.points))
    values[1, :10] = np.nan            # dc1: one member in two buckets
    values[4, 20:30] = np.nan          # dc1, inside: interpolated
    ref = rank.Reference(data, values, cfg["limits"])
    grids = {}
    for agg in ("p50", "p95", "max"):
        _t, names, _s, cells = ref.answer(_sub(data, agg, None))
        assert names == ["d00", "d01", "d02"] and cells.emitted.all()
        grids[agg] = cells.want
    # dc0 = {40, 30}: h = 1.5 between them, h = 2.85 >= 2 the maximum
    assert (grids["p50"][0] == 35.0).all()
    # the line's allowance: the spacing of h = 1.5 in float32 times the
    # gap of 10; a group's end is one value and has none
    _t, _n, _s, cells = ref.answer(_sub(data, "p50", None))
    assert (cells.atol[0] == 2.0 ** -23 * 10).all()
    assert not cells.atol[1, :2].any()
    _t, _n, _s, cells = ref.answer(_sub(data, "p95", None))
    assert not cells.atol.any()
    assert (grids["p95"][0] == 40.0).all()
    # dc1 = {10, 20} but series 1 has nothing in its first two buckets
    assert list(grids["p50"][1]) == [20.0, 20.0] + [15.0] * 10
    assert (grids["p95"] == grids["max"]).all()
    # one member left of dc0: every percentile is that member
    _t, _n, _s, cells = ref.answer(_sub(data, "p50", "r0003"))
    assert (cells.want[0] == 40.0).all()
    # no shipped percentile stands below one: a tenth does, h = 0.3
    import rank as rank_module       # loaded by deploy under its stem
    monkeypatch.setitem(rank_module.PERCENTILES, "p10", 0.1)
    monkeypatch.setattr(rank.Reference, "aggregators",
                        rank.Reference.aggregators + ("p10",))
    _t, _n, _s, cells = ref.answer(_sub(data, "p10", None))
    assert (cells.want[0] == 30.0).all()


def test_what_the_rank_judge_answers_and_what_it_does_not():
    cfg = load(f"benchmark/configs/{CONFIG}.json")
    data = gen.Data(cfg["data"])
    rank = _judge()
    assert issubclass(rank.Reference, reference.Reference)
    assert rank.Reference.aggregators == (
        "sum", "max", "min", "p50", "p75", "p90", "p95", "p99", "p999")
    assert rank.compare is not reference.compare
    assert rank.rows_to_grid is reference.rows_to_grid
    assert rank.Unsupported is reference.Unsupported
    for agg in rank.Reference.aggregators:
        rank.Reference.supports(_sub(data, agg, "r0001"), data)
    with pytest.raises(reference.Unsupported, match="a rate under"):
        rank.Reference.supports(
            dict(_sub(data, "p95", "r0001"), rate=True), data)
    with pytest.raises(reference.Unsupported, match="'median'"):
        rank.Reference.supports(_sub(data, "median", None), data)
    # the shipped judge still refuses the request, and asks-p95.json
    with pytest.raises(reference.Unsupported, match="aggregator 'p95'"):
        reference.Reference.supports(_sub(data, "p95", "r0001"), data)
    small = gen.Data(dict(cfg["data"], **TINY))
    t = traffic.Traffic(load("benchmark/tests/data/asks-p95.json"),
                        small, 1, 5)
    reqs = run.judged_requests(cfg, t)
    with pytest.raises(deploy.Failed, match="aggregator 'p95'"):
        deploy.refuse_unjudged(reference, small, reqs, "here")
    deploy.refuse_unjudged(rank, small, reqs, "here")


def test_a_ranked_cells_error_is_taken_beyond_its_allowance():
    rank = _judge()
    cells = reference.Cells(2, 3)
    cells.want[:] = [[7000.0, 7001.0, 7002.0], [10.0, 20.0, np.nan]]
    cells.emitted[:] = ~np.isnan(cells.want)
    cells.atol[0] = 3e-3
    got = cells.want.copy()
    got[0, 0] += 2e-3              # inside the allowance
    got[0, 1] -= 7e-3              # 4e-3 beyond it
    got[1, 0] += 1e-3              # a cell with no allowance
    v = rank.compare(got, 0, cells)
    assert v.shape_errors == 0 and v.sum_rel_err == 0
    assert v.rank_abs_err == pytest.approx(4e-3, rel=1e-6)
    assert reference.compare(got, 0, cells).rank_abs_err \
        == pytest.approx(7e-3, rel=1e-6)
    # a summed cell keeps reference.compare's rule, a cell emitted on
    # one side only is still a shape error
    cells.scale[1, 1] = 20.0
    got[1, 1] = 20.2
    got[1, 2] = 5.0
    got[0, 2] = np.nan
    v = rank.compare(got, 1, cells)
    assert v.shape_errors == 3
    assert v.sum_rel_err == pytest.approx(0.01)
    assert v.rank_abs_err == pytest.approx(4e-3, rel=1e-6)


# -- the data files -----------------------------------------------------

def test_the_store_is_fleet_1ms_key_for_key(bench):
    cfg = load(f"benchmark/configs/{CONFIG}.json")
    wide = load("benchmark/configs/fleet-1m.json")
    for key in ("data", "server", "precision", "limits"):
        assert cfg[key] == wide[key], key
    assert cfg["reference"] == "benchmark/references/rank.py"
    assert "generator" not in cfg and cfg["reduced"] == []
    assert cfg["assumed"][:-1] == wide["assumed"]
    assert "p95 for the source's p99 / p999" in cfg["assumed"][-1]
    assert cfg["guarantees"]["durability"] \
        == wide["guarantees"]["durability"]
    for word in ("exact order statistic", "no sketch", "no bins",
                 "no sample"):
        assert word in cfg["guarantees"]["answers"]
    entry = next(c for c in bench["configs"] if c["name"] == CONFIG)
    assert entry["source"] != next(
        c for c in bench["configs"] if c["name"] == "fleet-1m")["source"]
    assert [(w["name"], w["traffic"], w["chips"])
            for w in bench["workloads"] if w["config"] == CONFIG] \
        == [(CELL, "rank-p95", 1)]


def test_the_traffic_is_the_wide_cells_with_the_aggregator_changed():
    spec = load("benchmark/traffic/rank-p95.json")
    wide = load("benchmark/traffic/wide-groupby.json")
    # test_manifest.py test_traffic_files' rules
    assert spec["loop"] == "closed" and "rate_per_s" not in spec
    assert spec["warmup_per_template"] >= 3 and spec["timeout_s"] == 30
    assert spec["closed_list"] == 4000 and "trace_probe" not in spec
    for key in ("loop", "clients", "timeout_s", "warmup_per_template",
                "closed_list"):
        assert spec[key] == wide[key], key
    (mine,), (theirs,) = spec["requests"], wide["requests"]
    assert mine["draw"] == theirs["draw"]
    (sub,), (wsub,) = mine["body"]["queries"], theirs["body"]["queries"]
    assert sub["aggregator"] == "p95" and wsub["aggregator"] == "sum"
    assert "rate" not in sub and "rateOptions" not in sub
    for key in ("metric", "downsample", "filters"):
        assert sub[key] == wsub[key], key
    # every request distinct and in one shape class
    cfg = load(f"benchmark/configs/{CONFIG}.json")
    data = gen.Data(cfg["data"])
    t = traffic.Traffic(spec, data, 2**31 + 5, 51)
    assert len(t.warmup) == 3 and len(t.timed) == 3997
    assert len({r.body for r in t.warmup + t.timed}) == 4000
    assert not t.probes and not t.writes


def test_new_metrics_list_the_cell_alone(bench):
    mine = {m["name"]: m for m in bench["per_layer"]
            if m.get("workloads") == [CELL]}
    assert set(mine) == NEW
    assert {m["moves"] for m in mine.values()} == {"query_p50_ms"}
    assert mine["rank.on_device_share"]["layer"] == "plan + placement"
    assert mine["rank_tail_roofline"]["layer"] == "device programs"
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    # gate (b), PR 47: beside its own three the cell is listed wherever
    # the wide cell is, and nowhere else
    wide = listed_with(bench, "fleet-1m.wide-groupby")
    assert len(wide) > 25 and "grid_tail_roofline" in wide
    assert listed_with(bench, CELL) == NEW | wide
    assert {m["name"] for m in run.metrics_of(bench, "per_layer", cell)} \
        == NEW | EVERYWHERE | wide


# -- the cell, end to end -----------------------------------------------

@pytest.mark.parametrize("trace", [0, 1])
def test_cell_runs_to_its_last_line_and_then_wants_a_tpu(
        bench, trace, capsys):
    code, doc = run.run_cell(CELL, 2**31 + 34, 2.0, bool(trace),
                             shrink=TINY)
    assert code == 3                 # this sandbox has no TPU
    assert doc["correct"] is True and doc["failed"] == 0
    assert doc["attempted"] > 10
    got = {k: m["value"] for k, m in doc["metrics"].items()}
    if trace:
        # what only a device trace or a device-placed tail gives is
        # left out on the CPU
        assert set(got) == EVERYWHERE | {"rank.on_device_share"} | (
            listed_with(bench, "fleet-1m.wide-groupby") - DEVICE_ONLY)
        # 4,096 x 12 padded cells: under the rank class's host budget
        # (1 << 20), as the cell's 12.6M are not
        assert got["rank.on_device_share"] == 0.0
        assert got["window.compiles"] == 0
    else:
        assert set(got) == {"query_p50_ms", "setup_s"}
    c = doc["compared"]
    assert c["sum_rel_err"]["value"] == 0      # no summed cell
    assert 0 < c["rank_abs_err"]["value"] <= c["rank_abs_err"]["limit"] \
        == 0.005
    assert "compared rank_abs_err" in capsys.readouterr().out


def test_one_altered_answer_is_not_correct(capsys):
    code, doc = run.run_cell(
        CELL, 2**31 + 35, 1.0, False, shrink=TINY, require_tpu=False,
        server_flags={
            "tsd.rpc.plugin": "benchmark.tsd_plugin.Loader,"
            "benchmark.tests.broken_plugin.AlteredAnswer"})
    assert code == 0 and doc["correct"] is False
    assert doc["failed"] == doc["attempted"] > 0
    assert doc["compared"]["rank_abs_err"]["value"] > 0.005
    assert "failed: rank: " in capsys.readouterr().out


def test_the_control_is_not_correct(capsys):
    """bfloat16 storage, the step below the float32 the configuration
    states, moves an order statistic by whole units."""
    cfg = tiny_config(CONFIG)
    data = gen.Data(cfg["data"])
    values, _ = gen.generate(data, 11)
    t = traffic.Traffic(load("benchmark/traffic/rank-p95.json"), data,
                        11, 5)
    out = control.control_numbers(data, values, cfg["limits"],
                                  t.timed[:3], _judge())
    assert out["correct"] is False and out["shape_errors"] == 0
    assert out["rank_abs_err"] > 100 * cfg["limits"]["rank_atol"]
    assert out["sum_rel_err"] == 0
    # float32, what the configuration states, passes
    sound = _judge().Reference(data, values, cfg["limits"])
    _t, _n, _s, cells = sound.answer(t.timed[0].doc["queries"][0])
    f32 = np.where(cells.emitted, cells.want, np.nan) \
        .astype(np.float32).astype(np.float64)
    assert reference.compare(f32, 0, cells).ok(
        cfg["limits"]["sum_rtol"], cfg["limits"]["rank_atol"])


# -- the readers ----------------------------------------------------------

def _snap(rows):
    return {"stats": {"records": [
        {"metric": "tsd.query.tail", "value": v, "tags": tags}
        for tags, v in rows], "histograms": []}}


def test_the_placement_readers_by_class():
    ctx = types.SimpleNamespace()
    # a program that labels its tails by class: 7 rank programs on the
    # device and 2 on the host beside 4 linear ones, one of them on
    # the host
    ctx.before = _snap([
        ({"path": "grid", "placement": "device", "class": "rank"}, 10),
        ({"path": "grid", "placement": "device", "class": "linear"}, 5)])
    ctx.after = _snap([
        ({"path": "grid", "placement": "device", "class": "rank"}, 17),
        ({"path": "grid", "placement": "host", "class": "rank"}, 2),
        ({"path": "grid", "placement": "device", "class": "linear"}, 8),
        ({"path": "dense", "placement": "host", "class": "linear"}, 1)])
    assert run.read_metric("rank.on_device_share", ctx) \
        == pytest.approx(100.0 * 7 / 9)
    # the accepted reader sums over the label it does not name
    assert run.read_metric("placement.on_device_share", ctx) \
        == pytest.approx(100.0 * 10 / 13)
    # the parent of PR 34 has no such label: nothing to read, no error
    for snap in (ctx.before, ctx.after):
        for r in snap["stats"]["records"]:
            del r["tags"]["class"]
    assert run.read_metric("rank.on_device_share", ctx) is None
    assert run.read_metric("placement.on_device_share", ctx) \
        == pytest.approx(100.0 * 10 / 13)
    # a window without a rank program
    ctx.after = ctx.before = _snap([
        ({"path": "grid", "placement": "device", "class": "linear"}, 5)])
    assert run.read_metric("rank.on_device_share", ctx) is None


def test_the_trace_readers():
    ctx = types.SimpleNamespace(trace=None, trace_queries=0, peaks=None,
                                first_shape=None)
    assert "rank.sort_ms_per_query" not in {
        m["name"] for m in load("BENCHMARK.json")["per_layer"]}
    with pytest.raises(run.Failed, match="no reader"):
        run.read_metric("rank.sort_ms_per_query", ctx)
    assert run.read_metric("rank_tail_roofline", ctx) is None
    ctx.trace = {"busy_s": 2.0, "modules": [["jit_run_pipeline_grid", 10,
                                             1.9]],
                 "ops": [["%sort.7 = (s32[1048576,12]{0,1}, f32[10485"
                          "76,12]{0,1}) sort(...)", 1.5],
                         ["%fusion.3 = f32[128,16] fusion(...)", 0.3],
                         ["sort.9", 0.25],
                         ["%resort_fusion = f32[8] fusion(...)", 0.1]]}
    ctx.trace_queries = 10
    ctx.peaks = {"hbm_bytes_per_s": 819e9}
    ctx.first_shape = (999_500, 12, 100)
    # 1,048,576 x 12 padded cells of 5 bytes, the ids, a 112 x 12 result
    least = (1048576 * 12 * 5 + 1048576 * 4 + 112 * 12 * 5) / 819e9
    assert run.read_metric("rank_tail_roofline", ctx) \
        == pytest.approx(100.0 * least / 0.19)
    json.dumps(ctx.trace)
