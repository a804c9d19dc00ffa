"""The cell whose store is written while it is read
(``live-100k.groupby-ingest``), at a size a test can hold, on the CPU:
down to the keys of its last line with and without a trace, its data
files against each other, and the control."""

import json

import numpy as np
import pytest
from conftest import TINY, load, tiny_config

import control
import gen
import run
import traffic

CELL = "live-100k.groupby-ingest"
CONFIG = "live-100k-ingest"
# what only a device trace or a device-placed tail gives is left out
# on the CPU
DEVICE_ONLY = {"device.idle_share", "grid_tail_roofline",
               "devicecache.hit_share", "device.resident_mb",
               "program.busy_ms_per_query"}
# the query stages and the device programs the cell shares with its
# quiet pair: PR 31 could not touch their lists, PR 33 did
SHARED = {"plan.ms", "scan.ms", "execute.ms", "execute.self_ms",
          "grid_build.ms", "upload.ms", "program.wait_ms", "download.ms",
          "assemble.ms", "serialize.ms", "tail.query_p90_ms",
          "placement.on_device_share", "device.unoccupied_share",
          "idle.unnamed_share", "idle.no_request_share",
          "program.compiles", "gc.pause_share",
          "program.busy_ms_per_query", "grid_tail_roofline",
          "devicecache.hit_share", "device.resident_mb"}
NEW = {"put.ack_p50_ms", "put.ack_p95_ms", "put.late_ms",
       "ingest.decode_ms", "ingest.scatter_ms", "wal.commit_wait_ms",
       "ingest.points_per_s", "wal.bodies_per_fsync",
       "ingest.host_share", "ingest.new_series"}


def _spec():
    return load("benchmark/traffic/groupby-ingest.json")


@pytest.mark.parametrize("trace", [0, 1])
def test_cell_runs_to_its_last_line_and_then_wants_a_tpu(
        bench, trace, capsys):
    seconds = 6.0
    code, doc = run.run_cell(CELL, 2**31 + 31, seconds, bool(trace),
                             shrink=TINY)
    assert code == 3                 # this sandbox has no TPU
    assert doc["correct"] is True and doc["failed"] == 0
    rate = _spec()["writes"]["rate_per_s"]
    bodies = round(rate * seconds)
    # the queries and every scheduled body were attempted
    assert doc["attempted"] > bodies >= 6
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    kind = "per_layer" if trace else "end_to_end"
    want = {m["name"] for m in run.metrics_of(bench, kind, cell)}
    got = set(doc["metrics"])
    if trace:
        assert NEW | SHARED <= want
        assert want - DEVICE_ONLY <= got <= want
        v = {k: m["value"] for k, m in doc["metrics"].items()}
        assert v["window.compiles"] == 0
        assert v["ingest.new_series"] == 0
        # the store took what was offered, one fsync a body or fewer
        assert v["ingest.points_per_s"] == pytest.approx(
            rate * _spec()["writes"]["series_per_body"], rel=0.25)
        assert v["wal.bodies_per_fsync"] >= 1.0
        assert v["wal.commit_wait_ms"] > 0
        assert 0 < v["ingest.host_share"]
        assert v["put.ack_p50_ms"] <= v["put.ack_p95_ms"]
    else:
        assert got == want == {"query_p50_ms", "setup_s"}
    for m in doc["metrics"].values():
        assert set(m) == {"value", "unit"}
    out = capsys.readouterr().out
    for name in ("writes_not_acked", "readback_shape_errors",
                 "readback_sum_rel_err", "readback_rank_abs_err",
                 "sum_rel_err", "window_compiles"):
        assert f"compared {name} = " in out
    assert "compared writes_not_acked = 0 " in out


def test_traffic_file_keeps_the_rules_of_the_others():
    spec = _spec()
    # test_manifest.py test_traffic_files' rules
    assert spec["loop"] == "closed" and "rate_per_s" not in spec
    assert spec["warmup_per_template"] >= 3
    assert spec["timeout_s"] == 30
    # the quiet cell's requests and probe, byte for byte
    quiet = load("benchmark/traffic/groupby-quiet.json")
    for key in ("loop", "clients", "timeout_s", "warmup_per_template",
                "requests", "trace_probe"):
        assert json.dumps(spec[key]) == json.dumps(quiet[key]), key
    # the copy the harness's own test reads differs by the rate alone
    kept = load("benchmark/tests/data/groupby-ingest.json")
    assert dict(spec["writes"], rate_per_s=0) \
        == dict(kept["writes"], rate_per_s=0)


def test_the_writers_are_the_configurations(bench):
    cfg = load(f"benchmark/configs/{CONFIG}.json")
    quiet = load("benchmark/configs/live-100k.json")
    # the store is live-100k's, key for key: nothing of it is cut
    for key in ("data", "server", "precision", "limits"):
        assert cfg[key] == quiet[key], key
    w, spec = cfg["writers"], _spec()["writes"]
    assert spec["rate_per_s"] * spec["series_per_body"] \
        == w["write_points_per_s"]
    assert spec["series_per_body"] == w["series_per_body"]
    assert spec["clients"] == w["connections"]
    assert spec["path"] == w["path"] == "/api/put"
    d = cfg["data"]
    assert w["own_points_per_s"] == d["series"] // d["cadence_s"]
    assert w["write_points_per_s"] < w["own_points_per_s"]
    assert cfg["reduced"] == ["write_points_per_s"] \
        == list(cfg["reduced_why"])
    # durability is the program's default, not set looser
    assert cfg["server"]["wal"] is True
    assert not any("wal" in k for k in cfg["server"]["flags"])
    assert {"answers", "durability", "visibility"} \
        <= set(cfg["guarantees"])
    entry = next(c for c in bench["configs"] if c["name"] == CONFIG)
    assert entry["source"] != next(
        c for c in bench["configs"] if c["name"] == "live-100k")["source"]
    assert [w["name"] for w in bench["workloads"]
            if w["config"] == CONFIG] == [CELL]


def test_new_metrics_list_the_cell_alone(bench):
    mine = {m["name"]: m for m in bench["per_layer"]
            if m.get("workloads") == [CELL]}
    assert set(mine) == NEW
    assert {m["moves"] for m in mine.values()} == {"query_p50_ms"}
    assert {m["layer"] for m in mine.values()} == {
        "ingest front end", "store write", "WAL", "load generator"}
    # beside them the cell is listed where its quiet pair is (but for
    # two start-up readers ISSUE 33 did not name), and nowhere else
    quiet = {m["name"] for m in bench["per_layer"]
             if "live-100k.groupby-quiet" in m.get("workloads", ())}
    assert quiet - {"startup.backend_s", "startup.import_resolve_s"} \
        == SHARED
    assert {m["name"] for m in bench["per_layer"]
            if CELL in m.get("workloads", ())} == NEW | SHARED


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
    n, warm, clients, _shape, points, sizes, due, _heads, *_ = shapes[0]
    rate = _spec()["writes"]["rate_per_s"]
    assert n == round(rate * seconds) and warm == 4 and clients == 4
    assert set(sizes) == {1000} and points == n * 1000
    assert due == pytest.approx([k / rate for k in range(n)])
    # the values are the seed's
    a = traffic.Traffic(_spec(), data, 1, seconds)
    b = traffic.Traffic(_spec(), data, 2, seconds)
    assert not np.array_equal(a.written, b.written, equal_nan=True)


def test_the_control_is_not_correct():
    cfg = tiny_config(CONFIG)
    data = gen.Data(cfg["data"])
    values, _ = gen.generate(data, 2**31 + 7)
    t = traffic.Traffic(_spec(), data, 2**31 + 7, 5)
    out = control.control_numbers(data, values, cfg["limits"],
                                  t.timed[:3])
    assert out["correct"] is False
    assert out["sum_rel_err"] > cfg["limits"]["sum_rtol"] \
        or out["rank_abs_err"] > cfg["limits"]["rank_atol"]
    # and the read-back: the written span held in bfloat16
    d = run.written_data(cfg, t)
    back = control.control_numbers(
        d, t.written, cfg["limits"], [run.readback_request(cfg, t)])
    assert back["correct"] is False
