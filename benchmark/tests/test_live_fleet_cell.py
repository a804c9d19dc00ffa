"""The north-star fleet while its collectors write
(``fleet-1m-live.wide-ingest``, PR 51), at a size a test can hold, on
the CPU: its data files against the quiet cell's and the live cell's,
the sizes the issue reckons, the read-back against its judge, the cell
end to end with and without a trace, and a planted fault: a server
that keeps a stale column, caught by ``check_answers``. The cell is
looked up by NAME."""

import asyncio
import json
import os
import types

import numpy as np
import pytest
from conftest import ROOT, TINY, load, tiny_config

import control
import deploy
import gen
import loadgen
import reference
import run
import traffic
import tsdproc

CELL = "fleet-1m-live.wide-ingest"
CONFIG = "fleet-1m-live"
# the per-layer metrics that list no cells: every cell reports them
EVERYWHERE = {"loadgen.late_ms", "loadgen.queries_per_s",
              "device.idle_share", "window.compiles",
              "startup.listen_s", "startup.compile_s"}
NEW = {"residency.kept_share", "residency.dropped_mb_per_body"}
# the device's branch for a tail the program would place on the host
# at a test's size, as at the cell's own
ON_DEVICE = {"tsd.query.host_tail_max_cells_linear": "-1"}


def _spec():
    return load("benchmark/traffic/wide-ingest.json")


def test_the_store_is_fleet_1ms_and_the_writers_live_100ks(bench):
    cfg = load(f"benchmark/configs/{CONFIG}.json")
    quiet = load("benchmark/configs/fleet-1m.json")
    live = load("benchmark/configs/live-100k-ingest.json")
    # the store is fleet-1m's, key for key and value for value
    assert json.dumps(cfg["data"]) == json.dumps(quiet["data"])
    for key in ("precision", "limits"):
        assert cfg[key] == quiet[key], key
    assert cfg["server"] == dict(quiet["server"], wal=True)
    assert cfg["assumed"][:len(quiet["assumed"])] == quiet["assumed"]
    # durability is the program's default, not set looser
    assert not any("wal" in k for k in cfg["server"]["flags"])
    # the guarantees: fleet-1m's answers extended, the live cell's
    # durability and visibility word for word
    g = cfg["guarantees"]
    assert g["answers"] == quiet["guarantees"]["answers"].replace(
        "every point loaded is in every answer",
        "every point loaded or acknowledged is in every later answer")
    assert g["answers"] != quiet["guarantees"]["answers"]
    assert g["durability"] == live["guarantees"]["durability"]
    assert g["visibility"] == live["guarantees"]["visibility"]
    # the writers
    w, spec = cfg["writers"], _spec()["writes"]
    d = cfg["data"]
    assert w["own_points_per_s"] == d["series"] // d["cadence_s"] \
        == 16666
    assert spec["rate_per_s"] * spec["series_per_body"] \
        == w["write_points_per_s"] == 2000 < w["own_points_per_s"]
    assert spec["series_per_body"] == w["series_per_body"] == 50
    assert d["series"] % w["series_per_body"] == 0
    assert spec["clients"] == w["connections"] == 4
    assert spec["path"] == w["path"] == live["writers"]["path"] \
        == "/api/put"
    assert set(w) == set(live["writers"])
    assert cfg["reduced"] == ["write_points_per_s"] \
        == list(cfg["reduced_why"])
    entry = next(c for c in bench["configs"] if c["name"] == CONFIG)
    assert entry["source"] == cfg["source"] and len(cfg["source"]) <= 200
    assert entry["reduced"] == cfg["reduced"]
    assert entry["file"] == f"benchmark/configs/{CONFIG}.json"
    assert entry["source"] not in {c["source"] for c in bench["configs"]
                                   if c["name"] != CONFIG}
    cells = [w for w in bench["workloads"] if w["config"] == CONFIG]
    assert [(c["name"], c["traffic"], c["chips"]) for c in cells] \
        == [(CELL, "wide-ingest", 1)]


def test_the_traffic_is_wide_groupbys_beside_the_writes():
    spec, wide = _spec(), load("benchmark/traffic/wide-groupby.json")
    # the one template and its loop, byte for byte
    for key in ("closed_list", "loop", "clients", "timeout_s",
                "warmup_per_template", "requests"):
        assert json.dumps(spec[key]) == json.dumps(wide[key]), key
    assert set(spec) == set(wide) | {"writes"}
    assert "trace_probe" not in spec
    assert spec["loop"] == "closed" and spec["closed_list"] == 4000
    assert spec["writes"] == {
        "name": "put", "path": "/api/put", "rate_per_s": 40,
        "series_per_body": 50, "clients": 4, "warmup": 4}


def test_the_sizes_the_issue_reckons():
    """2,040 bodies, one step, 102,000 acknowledged points a 51 s
    window, a body every 25 ms; the points a second after the end of
    the window the requests ask."""
    cfg = load(f"benchmark/configs/{CONFIG}.json")
    # the real series count, no values drawn but the bodies'
    data = gen.Data(cfg["data"])
    t = traffic.Traffic(_spec(), data, 2**31 + 51, 51)
    assert len(t.writes) == 2040 and len(t.write_warmup) == 4
    assert t.write_clients == 4
    assert t.written.shape == (1_000_000, 1)
    assert int((~np.isnan(t.written)).sum()) == 102_000
    assert {len(r.doc) for r in t.writes} == {50}
    assert [r.due_s for r in t.writes[:3]] == [0.0, 0.025, 0.05]
    assert {p["timestamp"] for r in t.writes for p in r.doc} \
        == {data.end + 1}
    ends = {r.doc["end"] for r in t.timed[:50]}
    assert ends == {data.end * 1000}
    assert (data.end + 1) * 1000 > max(ends)
    # block after block through the series: no series twice
    hosts = [p["tags"]["host"] for r in t.writes for p in r.doc]
    assert len(set(hosts)) == len(hosts) == 102_000
    assert hosts[0] == "h0000000" and hosts[-1] == "h0101999"
    # the warm-up's bodies are another metric's
    assert {p["metric"] for r in t.write_warmup for p in r.doc} \
        == {data.metric + ".warm"}
    # the list outlasts the window at three times the quiet rate
    assert len(t.timed) + len(t.warmup) == 4000


@pytest.mark.parametrize("seconds", [3, 51])
def test_counts_and_sizes_do_not_depend_on_the_seed(seconds):
    cfg = tiny_config(CONFIG)
    data = gen.Data(cfg["data"])
    shapes = []
    for seed in (1, 2**31 + 5):
        t = traffic.Traffic(_spec(), data, seed, seconds)
        shapes.append((
            len(t.writes), len(t.write_warmup), t.write_clients,
            t.written.shape, int((~np.isnan(t.written)).sum()),
            [len(r.doc) for r in t.writes],
            [r.due_s for r in t.writes],
            [(r.doc[0]["timestamp"], r.doc[0]["tags"]["host"],
              r.doc[-1]["tags"]["host"]) for r in t.writes],
            len(t.timed), len(t.warmup), len(t.probes)))
    assert shapes[0] == shapes[1]
    n, warm, clients, shape, points, sizes, due, *_ = shapes[0]
    assert n == 40 * seconds and warm == 4 and clients == 4
    blocks = TINY["series"] // 50
    assert shape == (TINY["series"], -(-n // blocks))
    assert set(sizes) == {50} and points == min(n, blocks * shape[1]) \
        * 50
    assert due == pytest.approx([k / 40 for k in range(n)])
    a = traffic.Traffic(_spec(), data, 1, seconds)
    b = traffic.Traffic(_spec(), data, 2, seconds)
    assert not np.array_equal(a.written, b.written, equal_nan=True)


def test_the_read_back_and_its_judge_agree():
    """The written span asked again is a request the judge answers
    (``sum:60s-avg`` by dc over every series, one bucket a step), and
    a judge over what was sent holds a served answer made from the
    same numbers; one acknowledged body left out of the answer is
    not correct."""
    cfg = tiny_config(CONFIG)
    data = gen.Data(cfg["data"])
    t = traffic.Traffic(_spec(), data, 2**31 + 52, 3)
    d = run.written_data(cfg, t)
    assert (d.t0, d.points, d.cadence_s) == (data.end + 1,
                                             t.written.shape[1], 60)
    back = run.readback_request(cfg, t)
    (sub,) = back.doc["queries"]
    assert (sub["aggregator"], sub["downsample"]) == ("sum", "60s-avg")
    assert back.doc["start"] == d.t0 * 1000
    deploy.refuse_unjudged(reference, d, [back], "the read-back")
    ref = reference.Reference(d, t.written, cfg["limits"])
    tagk, names, secs, cells = ref.answer(sub)
    assert (tagk, secs, len(names)) == ("dc", 60, data.dcs)

    def served(written):
        rows = []
        for dc in range(data.dcs):
            mine = written[dc::data.dcs]
            dps = {str(d.t0 + k * 60): float(np.nansum(mine[:, k]))
                   for k in range(mine.shape[1])
                   if not np.isnan(mine[:, k]).all()}
            rows.append({"metric": d.metric,
                         "tags": {"dc": data.tag_name("dc", dc)},
                         "aggregateTags": ["host", "rack", "fleet"],
                         "dps": dps})
        return _result(back, rows)

    sound = run.check_answers(ref, d, served(t.written), cfg["limits"])
    assert sound["failed"] == 0, sound["notes"]
    lost = t.written.copy()
    body = t.writes[len(t.writes) // 2]
    hosts = [data.tag_index("host", p["tags"]["host"]) for p in body.doc]
    lost[hosts, 0] = np.nan
    caught = run.check_answers(ref, d, served(lost), cfg["limits"])
    assert caught["failed"] == 1
    numbers = {n: v for n, v, _ in caught["numbers"]}
    assert numbers["sum_rel_err"] > 10 * cfg["limits"]["sum_rtol"]


def _result(request, rows):
    return [types.SimpleNamespace(
        error=None, status=200, body=json.dumps(rows).encode(),
        request=request)]


def test_the_control_is_not_correct():
    cfg = tiny_config(CONFIG)
    data = gen.Data(cfg["data"])
    values, _ = gen.generate(data, 2**31 + 7)
    t = traffic.Traffic(_spec(), data, 2**31 + 7, 5)
    out = control.control_numbers(data, values, cfg["limits"],
                                  t.timed[:3])
    assert out["correct"] is False
    assert out["sum_rel_err"] > cfg["limits"]["sum_rtol"]
    # and the read-back: the written span held in bfloat16
    d = run.written_data(cfg, t)
    back = control.control_numbers(
        d, t.written, cfg["limits"], [run.readback_request(cfg, t)])
    assert back["correct"] is False


def test_new_metrics_list_the_cell_alone(bench):
    mine = {m["name"]: m for m in bench["per_layer"]
            if m.get("workloads") == [CELL]}
    assert set(mine) == NEW
    assert {m["moves"] for m in mine.values()} == {"query_p50_ms"}
    assert {m["layer"] for m in mine.values()} \
        == {"upload + HBM cache"}
    assert {m["source"] for m in mine.values()} == {"program_counter"}
    # a model_config PR extends no accepted metric's list
    assert {m["name"] for m in bench["per_layer"]
            if CELL in m.get("workloads", ())} == NEW
    for name in NEW:
        assert os.path.isfile(os.path.join(
            ROOT, "benchmark", "metrics", name + ".py"))


@pytest.mark.parametrize("trace", [0, 1])
def test_cell_runs_to_its_last_line_and_then_wants_a_tpu(
        bench, trace, capsys):
    seconds = 4.0
    code, doc = run.run_cell(CELL, 2**31 + 51, seconds, bool(trace),
                             shrink=TINY, server_flags=ON_DEVICE)
    assert code == 3                 # this sandbox has no TPU
    assert doc["correct"] is True and doc["failed"] == 0
    bodies = round(40 * seconds)
    assert doc["attempted"] > bodies
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    kind = "per_layer" if trace else "end_to_end"
    want = {m["name"] for m in run.metrics_of(bench, kind, cell)}
    got = {k: m["value"] for k, m in doc["metrics"].items()}
    if trace:
        assert want == EVERYWHERE | NEW
        assert set(got) == want
        assert got["window.compiles"] == 0
        # every look-up behind a body met a newer version and kept
        # the window's grid: nothing resident was dropped
        assert got["residency.kept_share"] == 1.0
        assert got["residency.dropped_mb_per_body"] == 0.0
    else:
        assert set(got) == want == {"query_p50_ms", "setup_s"}
    for m in doc["metrics"].values():
        assert set(m) == {"value", "unit"}
    out = capsys.readouterr().out
    for name in ("writes_not_acked", "readback_shape_errors",
                 "readback_sum_rel_err", "readback_rank_abs_err",
                 "sum_rel_err", "window_compiles"):
        assert f"compared {name} = " in out
    assert "compared writes_not_acked = 0 " in out


@pytest.mark.parametrize("fault", [None, "KeepsWhatAWriteTouched"])
def test_a_server_that_keeps_a_stale_column_is_caught(fault, tmp_path):
    """A request, a put INSIDE a resident whole bucket, the next
    request: the judge over what was loaded and acknowledged holds the
    second answer to the point. A sound server drops the column and
    the window's grid and is correct; one that keeps them is not."""
    cfg = tiny_config(CONFIG)
    data = gen.Data(cfg["data"])
    seed = 2**31 + 53
    t = traffic.Traffic(_spec(), data, seed, 3)
    plugins = "benchmark.tsd_plugin.Loader"
    if fault:
        plugins += ",benchmark.tests.live_faults." + fault
    tsd = tsdproc.Tsd(ROOT, str(tmp_path), cfg)
    try:
        tsd.start(dict(ON_DEVICE, **{"tsd.rpc.plugin": plugins}))
        values, _points = tsd.load(data, seed)
        tsd.wait_listening()
        host, point = 1234, 27                 # minute 27: bucket 5
        value = 8765.5
        put = traffic.Request("put", "POST", "/api/put", [{
            "metric": data.metric,
            "timestamp": data.t0 + point * data.cadence_s,
            "value": value, "tags": {
                k: data.tag_name(k, int(data.tag_ids(
                    k, np.array([host]))[0])) for k in data.tags}}])
        first, ack, second = [asyncio.run(loadgen.send_all(
            tsd.port, [r], 120.0))[0]
            for r in (t.timed[0], put, t.timed[1])]
    finally:
        tsd.kill()
    assert (first.status, ack.status, second.status) == (200, 204, 200)
    limits = cfg["limits"]
    before = run.check_answers(
        reference.Reference(data, values, limits), data, [first],
        limits)
    assert before["failed"] == 0, before["notes"]
    values[host, point] = value
    after = run.check_answers(
        reference.Reference(data, values, limits), data, [second],
        limits)
    assert after["failed"] == (1 if fault else 0), after["notes"]
    if fault:
        numbers = {n: v for n, v, _ in after["numbers"]}
        assert numbers["sum_rel_err"] > 10 * limits["sum_rtol"]
