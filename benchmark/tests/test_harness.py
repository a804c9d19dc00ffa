"""The harness end to end at a size a test can hold, on the CPU: every
cell down to the keys of the last line, then "no TPU"; a broken served
path, a wrong answer and a non-200 each come out as failed."""

import json
import os

import numpy as np
import pytest
from conftest import HERE, TINY, tiny_config

import gen
import loadgen
import reference
import run
import traffic

CELLS = ["fleet-1m.wide-groupby", "live-100k.groupby-quiet",
         "fleet-1m.small-panels"]
KEYS = {"correct", "attempted", "failed", "metrics", "device"}


@pytest.mark.parametrize("trace", [0, 1])
@pytest.mark.parametrize("cell", CELLS)
def test_cell_runs_to_its_last_line_and_then_wants_a_tpu(
        bench, cell, trace, capsys):
    code, doc = run.run_cell(cell, 2**31 + 11, 2.0, bool(trace),
                             shrink=TINY)
    assert code == 3                 # this sandbox has no TPU
    assert KEYS <= set(doc) and doc["correct"] is True
    assert doc["failed"] == 0 and doc["attempted"] > 10
    assert doc["device"]["platform"] == "cpu"
    w = next(x for x in bench["workloads"] if x["name"] == cell)
    kind = "per_layer" if trace else "end_to_end"
    want = {m["name"] for m in run.metrics_of(bench, kind, w)}
    got = set(doc["metrics"])
    if trace:
        # what only a device trace or a device-placed tail gives is
        # left out on the CPU; everything else has to be there
        device_only = {"grid_tail_roofline", "devicecache.hit_share",
                       "device.resident_mb", "program.busy_ms_per_query"}
        assert want - device_only <= got <= want
        assert {"busy_s", "window_s"} <= set(doc["device"])
        assert doc["metrics"]["window.compiles"]["value"] == 0
    else:
        assert got == want and "setup_s" in got
    for m in doc["metrics"].values():
        assert set(m) == {"value", "unit"}
    assert "compared sum_rel_err" in capsys.readouterr().out


def test_no_result_line_without_a_tpu(monkeypatch, capsys):
    monkeypatch.setattr(run, "run_cell",
                        lambda *a, **k: (3, {"correct": True}))
    assert run.main(["--workload", "x", "--seed", "1", "--seconds",
                     "1"]) == 3
    assert capsys.readouterr().out == ""


def test_a_broken_served_path_is_not_correct():
    code, doc = run.run_cell(
        "fleet-1m.wide-groupby", 5, 1.0, False, shrink=TINY,
        require_tpu=False, server_flags={
            "tsd.rpc.plugin": "benchmark.tsd_plugin.Loader,"
            "benchmark.tests.broken_plugin.AlteredAnswer"})
    assert code == 0 and doc["correct"] is False
    assert doc["failed"] == doc["attempted"] > 0


def _answers(cell="live-100k.groupby-quiet", n=3):
    """Requests of a cell with the answers a sound server would give."""
    cfg = tiny_config("live-100k")
    data = gen.Data(cfg["data"])
    values, _ = gen.generate(data, 9)
    ref = reference.Reference(data, values, cfg["limits"])
    spec = run.load_json(os.path.join(
        run.HERE, "traffic", "groupby-quiet.json"))
    t = traffic.Traffic(spec, data, 9, 5)
    results = []
    for req in t.timed[:n]:
        rows = []
        for sub in req.doc["queries"]:
            tagk, names, secs, cells = ref.answer(sub)
            for gi, name in enumerate(names):
                rows.append({"metric": data.metric, "tags": {tagk: name},
                             "dps": {str(data.t0 + j * secs):
                                     float(cells.want[gi, j])
                                     for j in range(cells.want.shape[1])
                                     if cells.emitted[gi, j]}})
        res = loadgen.Result(req)
        res.status, res.body = 200, json.dumps(rows).encode()
        results.append(res)
    return ref, data, cfg["limits"], results


def test_a_wrong_answer_and_a_non_200_each_count_as_failed():
    ref, data, limits, results = _answers()
    assert run.check_answers(ref, data, results, limits)["failed"] == 0
    rows = json.loads(results[0].body)
    ts = next(iter(rows[7]["dps"]))
    rows[7]["dps"][ts] *= 1.0001          # one series in 10,000
    results[0].body = json.dumps(rows).encode()
    results[1].status, results[1].body = 503, b'{"error":{}}'
    out = run.check_answers(ref, data, results, limits)
    assert out["failed"] == 2
    numbers = {n: v for n, v, _limit in out["numbers"]}
    assert numbers["http_failures"] == 1
    assert numbers["sum_rel_err"] > limits["sum_rtol"]
    results[2].error = "TimeoutError: "
    results[2].status = 0
    assert run.check_answers(ref, data, results, limits)["failed"] == 3
    rows = json.loads(results[0].body)
    results[0].body = json.dumps(rows[:-1]).encode()   # a group lost
    out = run.check_answers(ref, data, results[:1], limits)
    assert out["failed"] == 1 and out["numbers"][1][1] > 0


def test_a_lost_write_and_an_unacknowledged_one_count_as_failed():
    cfg = tiny_config("live-100k")
    data = gen.Data(cfg["data"])
    # the mix of the cell that PR 23 left out (PERF.md, Open questions)
    spec = run.load_json(os.path.join(HERE, "data",
                                      "groupby-ingest.json"))
    t = traffic.Traffic(spec, data, 9, 3)
    assert len(t.writes) == 30 and t.written.shape == (4000, 8)
    assert [r.due_s for r in t.writes] == pytest.approx(
        [k / 10 for k in range(30)])
    # one new point for every series, block after block
    assert (~np.isnan(t.written)).sum() == 30 * 1000
    ctx = run.Context()
    ctx.config, ctx.traffic = cfg, t
    d = run.written_data(cfg, t)
    ref = reference.Reference(d, t.written, cfg["limits"])
    sub = run.readback_request(cfg, t).doc["queries"][0]
    tagk, names, secs, cells = ref.answer(sub)
    rows = [{"metric": d.metric, "tags": {tagk: name},
             "dps": {str(d.t0 + j * secs): float(cells.want[gi, j])
                     for j in range(cells.want.shape[1])
                     if cells.emitted[gi, j]}}
            for gi, name in enumerate(names)]
    ctx.readback = loadgen.Result(run.readback_request(cfg, t))
    ctx.readback.status = 200
    ctx.readback.body = json.dumps(rows).encode()
    for req in t.writes:
        res = loadgen.Result(req)
        res.status = 204
        ctx.write_results.append(res)
    assert run.check_writes(ctx, cfg["limits"])["failed"] == 0
    ctx.write_results[3].status = 400
    assert run.check_writes(ctx, cfg["limits"])["failed"] == 1
    ctx.write_results[3].status = 204
    # an acknowledged point that is not in the answer: one of forty
    rows[5]["dps"][str(d.t0)] -= t.written[5, 0]
    ctx.readback.body = json.dumps(rows).encode()
    out = run.check_writes(ctx, cfg["limits"])
    assert out["failed"] == 1
    assert dict((n, v) for n, v, _ in out["numbers"])[
        "readback_sum_rel_err"] > cfg["limits"]["sum_rtol"]
