"""The float64 reference against ``tests/oracle.py``, the repo's
per-datapoint reimplementation of the reference semantics, which shares
no code with the kernels or with ``benchmark/reference.py``."""

import importlib.util
import os

import numpy as np
import pytest
from conftest import ROOT, tiny_config

import control
import gen
import reference


def load_oracle():
    spec = importlib.util.spec_from_file_location(
        "bench_oracle", os.path.join(ROOT, "tests", "oracle.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def make(config: str, seed: int = 3):
    cfg = tiny_config(config)
    cfg["data"].update(series=2000, chunk_series=1000, drop_single=0.05,
                       drop_block=0.05)
    data = gen.Data(cfg["data"])
    values = np.concatenate([gen.chunk_lines(data, seed, c)[1]
                             for c in range(data.chunks)])
    return data, values, reference.Reference(data, values, cfg["limits"])


def oracle_answer(oracle, data, values, members, sub):
    secs, fn = reference.parse_downsample(sub["downsample"])
    ts_ms = (data.t0 + data.cadence_s * np.arange(data.points)) * 1000
    series = []
    for i in members:
        ok = ~np.isnan(values[i])
        series.append((ts_ms[ok], values[i][ok]))
    opts = sub.get("rateOptions") or {}
    kw = {"counter": True, "counter_max": float(opts["counterMax"])} \
        if opts.get("counter") else {}
    return oracle.run_oracle(
        series, sub["aggregator"], secs * 1000, fn, data.t0 * 1000,
        data.end * 1000, rate=bool(sub.get("rate")), rate_kwargs=kw)


CASES = [
    ("fleet-1m", {"aggregator": "sum", "downsample": "5m-avg",
                  "rate": True,
                  "rateOptions": {"counter": True, "counterMax": 10000}},
     "r0950"),
    ("fleet-1m", {"aggregator": "max", "downsample": "1m-max"}, "r0123"),
    ("live-100k", {"aggregator": "sum", "downsample": "1m-avg"},
     "r1913"),
    ("live-100k", {"aggregator": "max", "downsample": "1m-avg"},
     "r0007"),
    ("live-100k", {"aggregator": "min", "downsample": "1m-sum"},
     "r0907"),
]


@pytest.mark.parametrize("config,shape,rack", CASES)
def test_grouped_answers_match_the_oracle(config, shape, rack):
    oracle = load_oracle()
    data, values, ref = make(config)
    sub = dict(shape, metric=data.metric, filters=[
        {"type": "wildcard", "tagk": "dc", "filter": "*",
         "groupBy": True},
        {"type": "not_literal_or", "tagk": "rack", "filter": rack,
         "groupBy": False}])
    tagk, names, secs, cells = ref.answer(sub)
    assert tagk == "dc" and len(names) == data.dcs
    idx = np.arange(data.series)
    gone = data.tag_index("rack", rack)
    touched = gone % data.dcs        # the group the rack belongs to
    # the gappy tenth sits in the blocks (i // 100) % 10 == 9
    for gi in (0, 57, touched):
        members = idx[(idx % data.dcs == gi) & (idx % data.racks != gone)]
        want = oracle_answer(oracle, data, values, members, sub)
        got = {data.t0 * 1000 + j * secs * 1000: cells.want[gi, j]
               for j in range(cells.want.shape[1]) if cells.emitted[gi, j]}
        assert sorted(got) == sorted(want)
        for t, v in want.items():
            assert got[t] == pytest.approx(v, rel=1e-9, abs=1e-9), (gi, t)
    # the excluded rack changed its own group and no other
    _t, _n, _s, whole = ref.answer(dict(sub, filters=sub["filters"][:1]))
    same = np.isclose(whole.want, cells.want, equal_nan=True).all(axis=1)
    assert not same[touched] and same.sum() == data.dcs - 1


def test_host_list_matches_the_oracle():
    oracle = load_oracle()
    data, values, ref = make("fleet-1m")
    hosts = [900, 901, 17, 1999, 950, 3, 1000, 1234]   # three gappy
    sub = {"metric": data.metric, "aggregator": "max",
           "downsample": "1m-max", "filters": [
               {"type": "literal_or", "tagk": "host", "groupBy": False,
                "filter": "|".join(data.tag_name("host", h)
                                   for h in hosts)}]}
    tagk, names, secs, cells = ref.answer(sub)
    assert (tagk, names, secs) == ("", [""], 60)
    assert ref.selected(sub) == 8
    want = oracle_answer(oracle, data, values, hosts, sub)
    got = {data.t0 * 1000 + j * 60000: cells.want[0, j]
           for j in range(data.points) if cells.emitted[0, j]}
    assert got == pytest.approx(want)


def test_what_the_reference_does_not_answer_raises():
    data, _values, ref = make("fleet-1m")
    for bad in ({"aggregator": "p95", "downsample": "5m-avg"},
                {"aggregator": "sum", "downsample": "7m-avg"},
                {"aggregator": "sum", "downsample": "5m-avg",
                 "filters": [{"type": "regexp", "tagk": "dc",
                              "filter": ".*"}]}):
        with pytest.raises(reference.Unsupported):
            ref.answer(dict(bad, metric=data.metric))


def test_compare_sees_a_missing_cell_and_a_wrong_value():
    data, _values, ref = make("live-100k")
    sub = {"metric": data.metric, "aggregator": "sum",
           "downsample": "1m-avg", "filters": [
               {"type": "wildcard", "tagk": "dc", "filter": "*",
                "groupBy": True}]}
    _t, _n, _s, cells = ref.answer(sub)
    lim = (ref.sum_rtol, ref.rank_atol)
    good = np.where(cells.emitted, cells.want, np.nan)
    assert reference.compare(good, 0, cells).ok(*lim)
    hole = good.copy()
    hole[3, 4] = np.nan
    assert reference.compare(hole, 0, cells).shape_errors == 1
    off = good.copy()
    off[5, 6] *= 1 + 1e-4          # one series in 10,000 dropped
    v = reference.compare(off, 0, cells)
    assert not v.ok(*lim) and v.sum_rel_err > ref.sum_rtol
    assert not reference.compare(good, 1, cells).ok(*lim)


@pytest.mark.parametrize("cell,request_shape", [
    ("fleet-1m", CASES[0][1]), ("fleet-1m", CASES[1][1]),
    ("live-100k", CASES[2][1]), ("live-100k", CASES[3][1])])
def test_the_control_is_not_correct(cell, request_shape):
    """bfloat16 storage, the step below the float32 the configurations
    state, has to fail a number of every kind of request the cells
    send, at a size a test can hold."""
    cfg = tiny_config(cell)
    data = gen.Data(cfg["data"])
    values = np.concatenate([gen.chunk_lines(data, 11, c)[1]
                             for c in range(data.chunks)])

    class Req:
        doc = {"queries": [dict(request_shape, metric=data.metric,
                                filters=[{"type": "wildcard",
                                          "tagk": "dc", "filter": "*",
                                          "groupBy": True}])]}
    out = control.control_numbers(data, values, cfg["limits"], [Req])
    assert out["correct"] is False
    assert out["shape_errors"] == 0
    sound = reference.Reference(data, values, cfg["limits"])
    _t, _n, _s, cells = sound.answer(Req.doc["queries"][0])
    f32 = np.where(cells.emitted, cells.want, np.nan) \
        .astype(np.float32).astype(np.float64)
    assert reference.compare(f32, 0, cells).ok(
        cfg["limits"]["sum_rtol"], cfg["limits"]["rank_atol"])


def test_bfloat16_rounding():
    x = np.array([1.0, 1.00390625, 9999.99, np.nan, 1 + 2**-8])
    got = control.to_bfloat16(x)
    assert got[0] == 1.0 and got[1] == 1.0 and np.isnan(got[3])
    assert got[2] == 9984.0 and got[4] == 1.0
