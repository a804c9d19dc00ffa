"""A configuration that brings its own generator and judge
(``deploy.py``): run, loaded and judged by them through every entry
point; one that names neither makes byte for byte what the parent of
PR 33 made; a file the loader must not take, and traffic the judge
cannot answer, fail before any server starts."""

import hashlib
import os
import re
import sys
import types

import numpy as np
import pytest
from conftest import BENCH, HERE, TINY, load

import control
import deploy
import gen
import reference
import run
import traffic
import tsdproc

TAGGED = {"name": "tagged", "source": "benchmark/tests",
          "file": "benchmark/tests/data/tagged.json", "reduced": [],
          "why": "a deployment that names its generator and its judge"}


@pytest.fixture
def manifest(monkeypatch, bench):
    """``run_cell`` with cells of the tests' own beside the shipped
    ones: their configuration and traffic files are under
    ``benchmark/tests/data/``."""
    cells = {"tagged.zone-avg": ("tagged", "zone-avg"),
             "fleet-1m.asks-p95": ("fleet-1m", "asks-p95")}
    doc = dict(bench, configs=bench["configs"] + [TAGGED],
               workloads=bench["workloads"] + [
                   {"name": n, "config": c, "traffic": t, "chips": 1,
                    "why": "a test's"} for n, (c, t) in cells.items()])
    real = run.load_json

    def fake(path):
        if path == os.path.join(run.ROOT, "BENCHMARK.json"):
            return doc
        mine = os.path.join(HERE, "data", os.path.basename(path))
        if os.path.dirname(path) == os.path.join(BENCH, "traffic") \
                and not os.path.isfile(path):
            return real(mine)
        return real(path)

    monkeypatch.setattr(run, "load_json", fake)
    return doc


@pytest.fixture
def no_server(monkeypatch):
    def start(self, extra_flags=None):
        raise AssertionError("the server was started")
    monkeypatch.setattr(tsdproc.Tsd, "start", start)


def test_a_configuration_that_names_neither_gets_the_shipped_two():
    assert deploy.generator_of({}) is gen
    assert deploy.judge_of({}) is reference
    for name in ("fleet-1m", "live-100k", "live-100k-ingest"):
        cfg = load(f"benchmark/configs/{name}.json")
        assert "generator" not in cfg and "reference" not in cfg


@pytest.mark.filterwarnings("ignore:Mean of empty slice")
def test_the_named_judge_builds_on_the_shipped_one():
    cfg = load(TAGGED["file"])
    judge = deploy.judge_of(cfg)
    assert judge.Reference is not reference.Reference
    assert issubclass(judge.Reference, reference.Reference)
    # what the named file does not define is reference.py's
    assert judge.compare is reference.compare
    assert judge.rows_to_grid is reference.rows_to_grid
    assert judge.Unsupported is reference.Unsupported
    data = deploy.generator_of(cfg).Data(cfg["data"])
    assert data.tags[-1] == "zone" and data.tag_count("zone") == 4
    sub = {"metric": data.metric, "aggregator": "avg",
           "downsample": "1m-avg", "filters": [
               {"type": "wildcard", "tagk": "zone", "filter": "*",
                "groupBy": True}]}
    judge.Reference.supports(sub, data)
    with pytest.raises(reference.Unsupported, match="aggregator 'avg'"):
        reference.Reference.supports(sub, data)
    with pytest.raises(reference.Unsupported, match="no tag 'zone'"):
        reference.Reference.supports(
            dict(sub, aggregator="sum"), gen.Data(cfg["data"]))
    # an average is the sum over the members that have a value
    values = np.arange(8.0 * data.points).reshape(8, data.points)
    values[3, 7:30] = np.nan
    small = deploy.generator_of(cfg).Data(
        dict(cfg["data"], series=8, chunk_series=8))
    _tagk, names, _secs, cells = judge.Reference(
        small, values, cfg["limits"]).answer(sub)
    assert names == ["z0", "z1", "z2", "z3"]
    k = 60 // small.cadence_s
    want = np.nanmean(values.reshape(8, -1, k), axis=2)
    assert cells.want[3] == pytest.approx(
        np.nanmean(reference.lerp_fill(want)[[3, 7]], axis=0))


def test_the_named_generator_writes_its_tag_on_every_line():
    cfg = load(TAGGED["file"])
    generator = deploy.generator_of(cfg)
    data = generator.Data(cfg["data"])
    chunks = []
    # through gen.generate's worker processes, which import the named
    # module by its name
    values, points = generator.generate(data, 5, chunks.append)
    again, _ = generator.generate(data, 5, None)
    assert np.array_equal(values, again, equal_nan=True)
    lines = b"".join(chunks).decode().splitlines()
    assert len(lines) == points == int((~np.isnan(values)).sum())
    for ln in lines[::997]:
        tags = dict(x.split("=") for x in ln.split()[3:])
        assert list(tags) == list(data.tags)
        i = data.tag_index("host", tags["host"])
        assert tags["zone"] == data.tag_name("zone", i % 4)
        assert data.tag_index("zone", tags["zone"]) == i % 4
    plain, _values, _points = gen.chunk_lines(gen.Data(cfg["data"]), 5, 0)
    assert chunks[0].replace(b" zone=z", b"\n").splitlines()[::2] \
        == plain.splitlines()


def test_a_cell_is_run_loaded_and_judged_by_the_files_it_names(
        manifest, capsys):
    # avg by zone: the shipped generator has no such tag, the shipped
    # judge no such aggregator
    code, doc = run.run_cell("tagged.zone-avg", 2**31 + 33, 2.0, False,
                             require_tpu=False)
    assert code == 0 and doc["correct"] is True and doc["failed"] == 0
    assert doc["attempted"] > 10
    assert list(doc)[-1] == "compared"
    assert doc["compared"]["sum_rel_err"]["limit"] == 4e-05
    assert 0 < doc["compared"]["sum_rel_err"]["value"] < 4e-05
    capsys.readouterr()
    # one group's answer altered where it is produced: the named judge
    # says so
    code, doc = run.run_cell(
        "tagged.zone-avg", 2**31 + 34, 1.0, False, require_tpu=False,
        server_flags={
            "tsd.rpc.plugin": "benchmark.tsd_plugin.Loader,"
            "benchmark.tests.broken_plugin.AlteredAnswer"})
    assert code == 0 and doc["correct"] is False
    assert doc["failed"] == doc["attempted"] > 0
    assert doc["compared"]["sum_rel_err"]["value"] > 4e-05
    assert "failed: avg-by-zone: cell (" in capsys.readouterr().out


def test_the_control_of_a_named_judge_is_that_judge_in_bfloat16(
        manifest, capsys):
    cfg = load(TAGGED["file"])
    generator, judge = deploy.generator_of(cfg), deploy.judge_of(cfg)
    data = generator.Data(cfg["data"])
    values, _ = generator.generate(data, 3, None)
    t = traffic.Traffic(load("benchmark/tests/data/zone-avg.json"),
                        data, 3, 5)
    out = control.control_numbers(data, values, cfg["limits"],
                                  t.timed[:3], judge)
    assert out["correct"] is False
    assert out["sum_rel_err"] > cfg["limits"]["sum_rtol"]
    with pytest.raises(reference.Unsupported):
        control.control_numbers(data, values, cfg["limits"], t.timed[:1])
    # and from the command line, through the loader
    assert control.main(["--workload", "tagged.zone-avg", "--seed", "3",
                         "--requests", "2"]) == 0
    assert '"correct": false' in capsys.readouterr().out


@pytest.mark.parametrize("key", ["generator", "reference"])
@pytest.mark.parametrize("path, says", [
    ("tests/oracle.py", "not a plain path under benchmark/"),
    ("benchmark/../tests/oracle.py", "not a plain path under benchmark/"),
    ("/root/repo/benchmark/reference.py", "not a plain path"),
    ("benchmark/references/no_such_file.py", "is not a file"),
    ("benchmark/tests/data/imports_program.py",
     "imports 'opentsdb_tpu.query'"),
    ("benchmark/tests/data/no_contract.py", "lacks"),
    ("benchmark/tests/data/reference.py", "is not a file"),
])
def test_a_file_the_loader_must_not_take_fails_before_any_server(
        monkeypatch, manifest, no_server, key, path, says):
    real = run.load_json

    def named(p):
        doc = real(p)
        if p.endswith("tagged.json"):
            doc[key] = path
        return doc

    monkeypatch.setattr(run, "load_json", named)
    with pytest.raises(deploy.Failed, match=re.escape(says)) as e:
        run.run_cell("tagged.zone-avg", 1, 1.0, False, require_tpu=False)
    assert path in str(e.value) and key in str(e.value)


def test_a_module_already_loaded_is_not_hidden(monkeypatch):
    # a named file is loaded under its stem, so that a generator's
    # worker processes find it: another module of that name stays
    monkeypatch.setitem(sys.modules, "ref_avg",
                        types.ModuleType("ref_avg"))
    with pytest.raises(deploy.Failed, match="would hide the module"):
        deploy.judge_of(load(TAGGED["file"]))


def test_a_template_the_judge_cannot_answer_fails_before_any_server(
        manifest, no_server):
    with pytest.raises(deploy.Failed) as e:
        run.run_cell("fleet-1m.asks-p95", 1, 1.0, False,
                     require_tpu=False, shrink=TINY)
    msg = str(e.value)
    assert "'rank-p95'" in msg and "aggregator 'p95'" in msg
    assert "benchmark/traffic/asks-p95.json" in msg
    assert "benchmark/reference.py" in msg
    # the shipped cells' templates, probes and read-back all pass
    for w in manifest["workloads"][:4]:
        cfg = load(next(c["file"] for c in manifest["configs"]
                        if c["name"] == w["config"]))
        data = gen.Data(cfg["data"])
        t = traffic.Traffic(
            load(f"benchmark/traffic/{w['traffic']}.json"), data, 1, 51)
        reqs = run.judged_requests(cfg, t)
        assert len(reqs) == 1 + bool(t.probes) + bool(t.writes)
        deploy.refuse_unjudged(reference, data, reqs, "here")


# what the parent of PR 33 (a8367c9) made of the files PR 33 leaves as
# they are, for the seeds 0, 7 and 2**31 + 11: sha256 over every
# request of a run of 51 s at the configuration's own size (warm-up,
# timed list, probes, put bodies with their schedule, warm-up bodies:
# template, method, path, due time and body, then the written array),
# and over the import text, the values and the count of every chunk at
# the tests' size
SEEDS = (0, 7, 2**31 + 11)
# the three lists are no longer the parent of PR 33's: PR 47 lengthened
# them (4,000 and 8,000 requests, two racks excluded a request), so
# these are the digests of PR 47's own lists, pinned for the PRs after
# it (test_refresh_cell.py pins every cell's list, and the three files
# PR 47 left alone against ITS parent's traffic.py)
PARENT_TRAFFIC = {
    ("wide-groupby", "fleet-1m"): (
        "a30bfeebf248d018cdd161bfa571a14db034d7ea4e5d4b90fa98b04798c62f2d",
        "dd92849d2d0c955ee67dc1df307c603b6aabd0116b1df65f668967785155781c",
        "81dab4a152646be0d66a61ef20b3ff59bc777d034617833fc55a15da7d07117f"),
    ("groupby-quiet", "live-100k"): (
        "55a385461dd44bfa5db275ae656de5b938998389f8205d84610c308d09f00ba5",
        "be19c4d0bd542d0c29c20f4f61db0aea9125b1ddf867e303af17682ee1ee5325",
        "664964d2adc4db866f349b93cdd8a0e4c502819165c81e508ffd5c5762cd82f3"),
    ("groupby-ingest", "live-100k-ingest"): (
        "e6418b2b81cad0ffa8f73a39d90c0cc7506726c428f8641363a70a7a4a369378",
        "2f6257bcd07e600e79ce16c61b0d4ecfa1fb9333f1488ed54b98e2a227637224",
        "1ba3a3319049537ad91b5d532713a5db292342b7b6b0869301a0cca4d2b6352b"),
}
LIST_LENGTH = {"wide-groupby": 4000, "groupby-quiet": 8000,
               "groupby-ingest": 8000}
PARENT_TEXT = {
    "fleet-1m": (
        "ac34e8cfae8c04fc03068abdc3a3aaf2394d3507e8c6473984aa0134e57f8fe7",
        "b97f0db9d96f7606a60051e2332002caed547b7c42b19d346c21b588bdc1a436",
        "5f81e8a7165a84d0d9a8a30ae1ff5d5623303db03fba4393cd6ca353a99cc626"),
    "live-100k": (
        "b75627f6b54693a630c38aa69ec01dce750ee0311f603da24fa6354cd1e17fa0",
        "ce6c558a77ea181c2a319100f0b7074e0cb3c16e783c7f6c6befac1efba91acf",
        "0b0d8a4a99ea3abf4f3b6bbbcbb06fcc2c7e92784adc74ee82a43efb2ffcb0d0"),
}
PARENT_TEXT["live-100k-ingest"] = PARENT_TEXT["live-100k"]


@pytest.mark.parametrize("mix, config", sorted(PARENT_TRAFFIC))
def test_unedited_traffic_files_give_the_parents_lists(mix, config):
    cfg = load(f"benchmark/configs/{config}.json")
    data = deploy.generator_of(cfg).Data(cfg["data"])
    spec = load(f"benchmark/traffic/{mix}.json")
    assert spec["closed_list"] == LIST_LENGTH[mix]
    for seed, want in zip(SEEDS, PARENT_TRAFFIC[mix, config]):
        t = traffic.Traffic(spec, data, seed, 51)
        assert len(t.warmup) + len(t.timed) == LIST_LENGTH[mix]
        h = hashlib.sha256()
        for r in t.warmup + t.timed + t.probes + t.writes \
                + t.write_warmup:
            h.update(repr((r.template, r.method, r.path,
                           r.due_s)).encode())
            h.update(r.body)
        if t.written is not None:
            h.update(np.ascontiguousarray(t.written).tobytes())
        assert h.hexdigest() == want, seed


@pytest.mark.parametrize("config", sorted(PARENT_TEXT))
def test_the_shipped_generator_gives_the_parents_text(config):
    cfg = load(f"benchmark/configs/{config}.json")
    generator = deploy.generator_of(cfg)
    data = generator.Data(dict(cfg["data"], **TINY))
    for seed, want in zip(SEEDS, PARENT_TEXT[config]):
        h = hashlib.sha256()
        for c in range(data.chunks):
            text, values, points = generator.chunk_lines(data, seed, c)
            h.update(text)
            h.update(values.tobytes())
            h.update(str(points).encode())
        assert h.hexdigest() == want, seed


def test_generators_and_references_import_nothing_of_the_program():
    """The judge takes nothing of the program, and the harness's
    process never imports JAX: every file a configuration can name,
    the loader and what they build on."""
    bad = re.compile(r"^\s*(import|from)\s+(jax|jaxlib|opentsdb_tpu)\b",
                     re.M)
    files = [os.path.join(BENCH, f) for f in (
        "gen.py", "reference.py", "deploy.py", "traffic.py",
        "control.py", "run.py", "tsdproc.py", "sweep.py")]
    for sub in ("generators", "references", "tests/data"):
        d = os.path.join(BENCH, sub)
        if os.path.isdir(d):
            files += [os.path.join(d, f) for f in sorted(os.listdir(d))
                      if f.endswith(".py")]
    assert len(files) >= 12
    for path in files:
        with open(path, encoding="utf-8") as fh:
            hit = bad.search(fh.read())
        if path.endswith("imports_program.py"):
            assert hit      # the one that shows the loader refusing it
        else:
            assert not hit, f"{path}: {hit.group(0)!r}"
