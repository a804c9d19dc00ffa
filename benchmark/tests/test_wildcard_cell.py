"""The dashboard that names its hosts by a pattern
(``fleet-1m.wildcard-lookup``, PR 40): its data files against the
panels' and ``fleet-1m``'s, its judge against the shipped one, the cell
end to end at a size a test can hold on the CPU, two faults the judge
has to find, the control, and the two readers it brings. (The judge's
matcher, the generator's patterns and the served path at a small size
are tier-1: ``tests/test_pattern_filters_served.py``.)"""

import types

import numpy as np
import pytest
from conftest import listed_with, load

import control
import deploy
import reference
import run
import traffic

CELL = "fleet-1m.wildcard-lookup"
CONFIG = "fleet-1m-wildcard"
NEW = {"filter.resolve_ms", "filter.names_read_per_query"}
# the per-layer metrics that list no cells: every cell reports them
EVERYWHERE = {"loadgen.late_ms", "loadgen.queries_per_s",
              "device.idle_share", "window.compiles",
              "startup.listen_s", "startup.compile_s"}
# the smallest deployment whose patterns select more than one host
# (pattern_draws.py): 100,000 hosts, 20,000 patterns of ten
SMALL = {"series": 100_000, "chunk_series": 25_000}


def _config() -> dict:
    return load(f"benchmark/configs/{CONFIG}.json")


def _small():
    cfg = _config()
    cfg["data"].update(SMALL)
    return cfg, deploy.generator_of(cfg).Data(cfg["data"])


# -- the data files -----------------------------------------------------

def test_the_store_is_fleet_1ms_key_for_key(bench):
    cfg, wide = _config(), load("benchmark/configs/fleet-1m.json")
    for key in ("data", "server", "precision", "limits"):
        assert cfg[key] == wide[key], key
    assert cfg["generator"] == "benchmark/generators/pattern_draws.py"
    assert cfg["reference"] == "benchmark/references/patterns.py"
    assert cfg["reduced"] == []
    assert cfg["guarantees"]["durability"] \
        == wide["guarantees"]["durability"]
    for word in ("every host whose name the pattern matches",
                 "no other", "exactly"):
        assert word in cfg["guarantees"]["answers"]
    entry = next(c for c in bench["configs"] if c["name"] == CONFIG)
    assert entry["source"] == cfg["source"] and "filters.html" in \
        entry["source"] and "TagVWildcardFilter.java" in entry["source"]
    assert entry["source"] not in {c["source"] for c in bench["configs"]
                                   if c["name"] != CONFIG}
    assert [(w["name"], w["traffic"], w["chips"])
            for w in bench["workloads"] if w["config"] == CONFIG] \
        == [(CELL, "wildcard-lookup", 1)]
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert "device bypassed" in cell["why"] and "PR 41" in cell["why"]
    assert "name table" in cell["why"]


def test_the_traffic_is_one_pattern_a_request_and_the_panels_probe():
    spec = load("benchmark/traffic/wildcard-lookup.json")
    panels = load("benchmark/traffic/small-panels.json")
    # test_manifest.py test_traffic_files' rules
    assert spec["loop"] == "closed" and "rate_per_s" not in spec
    assert spec["warmup_per_template"] == 3 and spec["timeout_s"] == 30
    assert spec["clients"] == 1
    assert spec["closed_list"] == panels["closed_list"] == 16000
    assert spec["trace_probe"] == panels["trace_probe"]
    assert "PR 40" in spec["about"]
    (tpl,) = spec["requests"]
    assert tpl["draw"] == {"pattern": {"tag": "host~pattern",
                                       "range": "all", "pick": 1}}
    (sub,) = tpl["body"]["queries"]
    assert sub == {"metric": "$metric", "aggregator": "sum",
                   "downsample": "1m-avg", "filters": [{
                       "type": "wildcard", "tagk": "host",
                       "filter": "$pattern", "groupBy": False}]}
    # the full size: every request distinct, each pattern 100 hosts
    cfg = _config()
    data = deploy.generator_of(cfg).Data(cfg["data"])
    assert data.tag_count("host~pattern") == 20_000
    assert data.hosts_per_pattern == 100
    t = traffic.Traffic(spec, data, 2**31 + 5, 51)
    assert len(t.warmup) == 3 and len(t.timed) == 15_997
    assert len({r.body for r in t.warmup + t.timed}) == 16_000
    assert len(t.probes) == 3 and not t.writes


def test_new_metrics_list_the_cell_alone(bench):
    mine = {m["name"]: m for m in bench["per_layer"]
            if m.get("workloads") == [CELL]}
    assert set(mine) == NEW
    panels = listed_with(bench, "fleet-1m.small-panels")
    assert {m["moves"] for m in mine.values()} == {"query_p50_ms"}
    assert {m["layer"] for m in mine.values()} == {"plan + placement"}
    assert mine["filter.resolve_ms"]["source"] == "program_span"
    assert mine["filter.names_read_per_query"]["source"] \
        == "program_counter"
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    # gate (b), PR 47: beside its own two the cell is listed wherever
    # the panels are (the generic stages, PR 36's eight), nowhere else
    assert len(panels) > 20 and "plan.ms" in panels
    assert listed_with(bench, CELL) == NEW | panels
    assert {m["name"] for m in run.metrics_of(bench, "per_layer", cell)} \
        == NEW | EVERYWHERE | panels
    assert {m["name"] for m in run.metrics_of(bench, "end_to_end", cell)} \
        == {"query_p50_ms", "setup_s"}


# -- the judge ------------------------------------------------------------

def test_what_the_pattern_judge_answers_and_the_shipped_one_does_not():
    cfg, data = _small()
    judge = deploy.judge_of(cfg)
    assert issubclass(judge.Reference, reference.Reference)
    assert judge.compare is reference.compare
    assert judge.rows_to_grid is reference.rows_to_grid
    assert judge.Unsupported is reference.Unsupported
    t = traffic.Traffic(load("benchmark/traffic/wildcard-lookup.json"),
                        data, 1, 5)
    reqs = run.judged_requests(cfg, t)
    assert sorted(r.template for r in reqs) == ["device-probe",
                                                "pattern-panel"]
    deploy.refuse_unjudged(judge, data, reqs, "here")
    # fleet-1m's own judge refuses the cell's template
    with pytest.raises(deploy.Failed, match="'pattern-panel'.*wildcard"):
        deploy.refuse_unjudged(reference, data, reqs, "here")

    def sub(kind, expr, tagk="host", group_by=False):
        return {"metric": data.metric, "aggregator": "sum",
                "downsample": "1m-avg", "filters": [{
                    "type": kind, "tagk": tagk, "filter": expr,
                    "groupBy": group_by}]}

    for kind, expr in (("wildcard", "h00*7"), ("iwildcard", "*A*"),
                       ("regexp", "h0+1"), ("iliteral_or", "H0000001"),
                       ("not_iliteral_or", "h0000001|H0000002"),
                       ("literal_or", "h0000001"), ("wildcard", "*")):
        judge.Reference.supports(sub(kind, expr), data)
        if kind != "literal_or" and expr != "*":
            with pytest.raises(reference.Unsupported):
                reference.Reference.supports(sub(kind, expr), data)
    for kind, expr, tagk in (("wildcard", "h0000001", "host"),
                             ("regexp", "h(", "host"),
                             ("iwildcard", "", "host"),
                             ("wildcard", "a*", "zone"),
                             ("not_key", "", "host")):
        with pytest.raises(reference.Unsupported):
            judge.Reference.supports(sub(kind, expr, tagk), data)
    # a group-by on the key a pattern names, and two that disagree
    parsed = judge.Reference.supports(sub("wildcard", "h0001*",
                                          group_by=True), data)
    assert parsed[-1] == "host" and parsed[-3] == [
        ("host", {"type": "wildcard", "filter": "h0001*"})]
    two = sub("wildcard", "h0001*", group_by=True)
    two["filters"].append({"type": "wildcard", "tagk": "dc",
                           "filter": "*", "groupBy": True})
    with pytest.raises(reference.Unsupported, match="two group-by"):
        judge.Reference.supports(two, data)


def test_a_million_names_take_milliseconds():
    import time

    import patterns                  # loaded by deploy under its stem
    cfg = _config()
    data = deploy.generator_of(cfg).Data(cfg["data"])
    names = patterns.Names([data.tag_name("host", i)
                            for i in range(data.series)])
    took = []
    for expr, first in (("h01234*", 123_400), ("*4321", 4_321)):
        t0 = time.perf_counter()
        hit = names.glob(expr)
        took.append(time.perf_counter() - t0)
        assert int(hit.sum()) == 100 and int(np.argmax(hit)) == first
    # sixteen thousand answers after a window: a second each (a
    # Python loop over the names) would be four hours
    assert max(took) < 0.25, took


# -- the cell, end to end -------------------------------------------------

@pytest.mark.parametrize("trace", [0, 1])
def test_cell_runs_to_its_last_line_and_then_wants_a_tpu(
        bench, trace, capsys):
    code, doc = run.run_cell(CELL, 2**31 + 40, 2.0, bool(trace),
                             shrink=SMALL)
    assert code == 3                 # this sandbox has no TPU
    assert doc["correct"] is True and doc["failed"] == 0
    assert doc["attempted"] >= 5
    got = {k: m["value"] for k, m in doc["metrics"].items()}
    if trace:
        # every reader finds something to read, on the CPU too
        assert set(got) == EVERYWHERE | NEW | listed_with(
            bench, "fleet-1m.small-panels")
        # the name table is built by the warm-up: a timed request
        # reads no name (PR 41; PR 40's walk read every host's)
        assert got["filter.names_read_per_query"] == 0
        assert got["path.fallbacks"] == 0
        assert 0 < got["filter.resolve_ms"] < got["loadgen.late_ms"] \
            + 1000 / got["loadgen.queries_per_s"]
        assert got["window.compiles"] == 0
    else:
        assert set(got) == {"query_p50_ms", "setup_s"}
    c = doc["compared"]
    assert c["rank_abs_err"]["value"] == 0      # no ranked cell
    assert 0 < c["sum_rel_err"]["value"] <= c["sum_rel_err"]["limit"] \
        == 4e-05
    assert c["shape_errors"]["value"] == 0
    assert "compared sum_rel_err" in capsys.readouterr().out


@pytest.mark.parametrize("fault, plugin", [
    ("one-altered-answer", "benchmark.tests.broken_plugin.AlteredAnswer"),
    ("one-host-dropped", "benchmark.tests.pattern_faults.DroppedHost")])
def test_a_fault_is_not_correct(fault, plugin, capsys):
    code, doc = run.run_cell(
        CELL, 2**31 + 41, 1.0, False, shrink=SMALL, require_tpu=False,
        server_flags={"tsd.rpc.plugin":
                      "benchmark.tsd_plugin.Loader," + plugin})
    assert code == 0 and doc["correct"] is False
    assert doc["failed"] == doc["attempted"] > 0
    # a thousandth of a cell; a tenth of a selection of ten hosts
    assert doc["compared"]["sum_rel_err"]["value"] \
        > (1e-4 if fault == "one-altered-answer" else 1e-2)
    assert doc["compared"]["shape_errors"]["value"] == 0
    assert "failed: pattern-panel: " in capsys.readouterr().out


def test_the_control_is_not_correct():
    """bfloat16 storage, the step below the float32 the configuration
    states, moves a sum of ten hosts by a part in a few thousand."""
    cfg, data = _small()
    generator = deploy.generator_of(cfg)
    values, _ = generator.generate(data, 11, None)
    t = traffic.Traffic(load("benchmark/traffic/wildcard-lookup.json"),
                        data, 11, 5)
    judge = deploy.judge_of(cfg)
    out = control.control_numbers(data, values, cfg["limits"],
                                  t.timed[:20], judge)
    assert out["correct"] is False and out["shape_errors"] == 0
    assert out["sum_rel_err"] > 5 * cfg["limits"]["sum_rtol"]
    assert out["rank_abs_err"] == 0
    # float32, what the configuration states, passes
    sound = judge.Reference(data, values, cfg["limits"])
    _t, _n, _s, cells = sound.answer(t.timed[0].doc["queries"][0])
    f32 = np.where(cells.emitted, cells.want, np.nan) \
        .astype(np.float32).astype(np.float64)
    assert reference.compare(f32, 0, cells).ok(
        cfg["limits"]["sum_rtol"], cfg["limits"]["rank_atol"])


# -- the readers ------------------------------------------------------------

def _snap(executes, resolve, names_read):
    hists = [{"name": "tsd_stage_latency_ms", "count": executes,
              "sum": 700.0 * executes,
              "labels": {"stage": "query.execute"}}]
    if resolve is not None:
        hists.append({"name": "tsd_stage_latency_ms",
                      "count": resolve[0], "sum": resolve[1],
                      "labels": {"stage": "query.filter_resolve"}})
    records = [] if names_read is None else [
        {"metric": "tsd.query.filter.names_read", "value": names_read,
         "tags": {}}]
    return {"stats": {"records": records, "histograms": hists}}


def test_the_two_readers():
    ctx = types.SimpleNamespace()
    # 75 sub-queries of the window, one walked filter each
    ctx.before = _snap(5, (5, 3300.0), 5_000_000)
    ctx.after = _snap(80, (80, 52800.0), 80_000_000)
    assert run.read_metric("filter.resolve_ms", ctx) \
        == pytest.approx(660.0)
    assert run.read_metric("filter.names_read_per_query", ctx) \
        == 1_000_000
    # two filters resolved in a sub-query are one sub-query's time
    ctx.after = _snap(80, (155, 52800.0), 80_000_000)
    assert run.read_metric("filter.resolve_ms", ctx) \
        == pytest.approx(660.0)
    # a window whose filters were all the key's presence
    ctx.after = _snap(80, (5, 3300.0), 5_000_000)
    assert run.read_metric("filter.resolve_ms", ctx) is None
    assert run.read_metric("filter.names_read_per_query", ctx) == 0
    # the parent of PR 40 has neither: nothing to read, no error
    ctx.before, ctx.after = _snap(5, None, None), _snap(80, None, None)
    assert run.read_metric("filter.resolve_ms", ctx) is None
    assert run.read_metric("filter.names_read_per_query", ctx) is None
    # no sub-query in the window
    ctx.after = ctx.before = _snap(5, (5, 3300.0), 5_000_000)
    assert run.read_metric("filter.names_read_per_query", ctx) is None
