"""``BENCHMARK.json`` against the rules a manifest is refused by before
any run, and against the files the harness will look for."""

import os
import re

import pytest
from conftest import BENCH, ROOT, load

import deploy

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter",
           "host_clock"}


def line(s):
    return isinstance(s, str) and 1 <= len(s) <= 200 \
        and "\n" not in s and "\t" not in s


def test_top_level(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert bench["paths"] == ["benchmark"]
    assert bench["command"] == ["python3", "benchmark/run.py"]
    assert isinstance(bench["run_seconds"], int) \
        and 1 <= bench["run_seconds"] <= 51
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) \
        < 64 * 1024


def test_configs(bench):
    names = [c["name"] for c in bench["configs"]]
    assert len(set(names)) == len(names) and 1 <= len(names) <= 24
    used = {w["config"] for w in bench["workloads"]}
    files = set()
    for c in bench["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and c["name"] in used
        assert line(c["source"]) and line(c["why"])
        assert c["file"].startswith("benchmark/") \
            and c["file"] not in files
        files.add(c["file"])
        doc = load(c["file"])
        assert doc["name"] == c["name"]
        assert doc["source"] == c["source"]
        assert doc["reduced"] == c["reduced"] and len(c["reduced"]) <= 16
        assert all(NAME.match(k) for k in c["reduced"])
        # the environment and the flags of the child, listed in full
        assert doc["server"]["env"]["PYTHONHASHSEED"] == "0"
        assert doc["guarantees"] and doc["assumed"]
        # the generator and the reference a deployment brings
        # (deploy.py): plain files under benchmark/
        assert all(os.path.isfile(os.path.join(ROOT, doc[key]))
                   for key in ("generator", "reference") if key in doc)
        deploy.generator_of(doc)
        deploy.judge_of(doc)


def test_workloads(bench):
    names = [w["name"] for w in bench["workloads"]]
    assert len(set(names)) == len(names) and 1 <= len(names) <= 24
    pairs = {(w["config"], w["traffic"]) for w in bench["workloads"]}
    assert len(pairs) == len(names)
    configs = {c["name"] for c in bench["configs"]}
    for w in bench["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["config"] in configs and w["chips"] in (1, 4)
        assert line(w["why"])
        assert os.path.isfile(os.path.join(
            BENCH, "traffic", w["traffic"] + ".json"))
    assert sum(w["chips"] == 4 for w in bench["workloads"]) \
        <= max(1, len(names) // 2)


def test_metrics(bench):
    cells = {w["name"] for w in bench["workloads"]}
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    assert "setup_s" in e2e and 1 <= len(e2e) <= 16
    assert len(e2e) == len(bench["end_to_end"])
    for m in bench["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better",
                                          "bound", "source"}
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
        assert set(m.get("workloads", cells)) <= cells
    assert "workloads" not in e2e["setup_s"]
    names = set(e2e)
    layers = set()
    assert 1 <= len(bench["per_layer"]) <= 128
    for m in bench["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better",
                                          "source", "layer", "moves"}
        assert NAME.match(m["name"]) and m["name"] not in names
        names.add(m["name"])
        assert UNIT.match(m["unit"]) and m["source"] in SOURCES
        assert m["better"] in ("lower", "higher") and line(m["layer"])
        layers.add(m["layer"])
        moved = e2e[m["moves"]]
        reported_in = set(moved.get("workloads", cells))
        assert set(m.get("workloads", reported_in)) <= reported_in
        if m["name"].endswith("_roofline"):
            assert m["unit"] == "%"
        assert os.path.isfile(os.path.join(BENCH, "metrics",
                                           m["name"] + ".py"))
    perf = open(os.path.join(ROOT, "PERF.md"), encoding="utf-8").read()
    for layer in layers:
        assert layer in perf, f"PERF.md's layers lack {layer!r}"
    # every cell reports set-up, another end-to-end metric and a layer
    for c in cells:
        mine = [m for m in bench["end_to_end"]
                if c in m.get("workloads", cells)]
        assert len(mine) >= 2
        assert any(c in m.get("workloads", cells)
                   for m in bench["per_layer"])


def test_files_under_paths_have_plain_names():
    ok = re.compile(r"^[A-Za-z0-9_.\-/]+$")
    for base, dirs, files in os.walk(BENCH):
        dirs[:] = [d for d in dirs if d != "__pycache__"]
        for f in files:
            rel = os.path.relpath(os.path.join(base, f), ROOT)
            assert ok.match(rel) and len(rel) <= 200, rel


LISTS = {"wide-groupby": 4000, "groupby-quiet": 8000,
         "small-panels": 16000, "groupby-ingest": 8000, "rank-p95": 4000,
         "wildcard-lookup": 16000, "percentiles": 4000, "refresh": 2400}


@pytest.mark.parametrize("traffic", sorted(LISTS))
def test_traffic_files(traffic, bench):
    spec = load(f"benchmark/traffic/{traffic}.json")
    assert set(LISTS) == {w["traffic"] for w in bench["workloads"]}
    # a closed list that a window of 51 s cannot use up at three times
    # the rate of PR 47's day (test_refresh_cell.py states each cell's
    # floor; the panels and the wildcard cell: 3.2 ms a request)
    assert spec["closed_list"] == LISTS[traffic]
    assert spec["loop"] in ("closed", "open")
    assert spec["warmup_per_template"] >= 3
    assert spec["timeout_s"] == 30
    if spec["loop"] == "open":
        assert spec["clients"] >= 32 and spec["rate_per_s"] > 0
    else:
        assert "rate_per_s" not in spec
