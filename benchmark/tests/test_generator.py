"""The generator: the same seed gives the same bytes, and the seed
changes values and order, never a count."""

import collections

import numpy as np
import pytest
from conftest import load, tiny_config

import gen
import traffic

SEEDS = (0, 7, 2**31 + 11)


@pytest.mark.parametrize("config", ["fleet-1m", "live-100k"])
def test_same_seed_same_bytes(config):
    data = gen.Data(tiny_config(config)["data"])
    a = gen.chunk_lines(data, 2**31 + 11, 1)
    b = gen.chunk_lines(data, 2**31 + 11, 1)
    assert a[0] == b[0] and a[2] == b[2]
    assert np.array_equal(a[1], b[1], equal_nan=True)
    assert gen.chunk_lines(data, 5, 1)[0] != a[0]


@pytest.mark.parametrize("config", ["fleet-1m", "live-100k"])
def test_lines_say_what_the_reference_holds(config):
    data = gen.Data(tiny_config(config)["data"])
    text, values, points = gen.chunk_lines(data, 3, 3)
    lines = text.decode().splitlines()
    assert len(lines) == points == int((~np.isnan(values)).sum())
    seen = collections.defaultdict(dict)
    for ln in lines:
        metric, ts, val, *tags = ln.split()
        assert metric == data.metric
        seen[tuple(tags)][int(ts)] = float(val)
    lo = 3 * data.chunk_series
    assert len(seen) == values.shape[0]
    for tags, pts in seen.items():
        t = dict(x.split("=") for x in tags)
        i = data.tag_index("host", t["host"])
        assert t["dc"] == data.tag_name("dc", i % data.dcs)
        assert t["rack"] == data.tag_name("rack", i % data.racks)
        assert t["fleet"] == data.tag_name(
            "fleet", (i // 100) % data.fleets)
        row = values[i - lo]
        want = {data.t0 + data.cadence_s * j: row[j]
                for j in range(data.points) if not np.isnan(row[j])}
        assert pts == pytest.approx(want)


@pytest.mark.parametrize("config", ["fleet-1m", "live-100k"])
def test_counts_do_not_depend_on_the_seed(config):
    data = gen.Data(tiny_config(config)["data"])
    shapes = set()
    for seed in SEEDS:
        gappy = total = 0
        sizes = collections.Counter()
        for c in range(data.chunks):
            idx, cents, drop = gen.chunk_values(data, seed, c)
            assert cents.shape == drop.shape == (len(idx), data.points)
            # points drop in the gappy tenth and nowhere else
            assert not drop[~data.is_gappy(idx)].any()
            gappy += int(data.is_gappy(idx).sum())
            total += len(idx)
            sizes.update(data.tag_ids("dc", idx).tolist())
        shapes.add((gappy, total, tuple(sorted(sizes.items()))))
    assert len(shapes) == 1
    (gappy, total, sizes), = shapes
    assert total == data.series and gappy == data.series // 10
    assert {n for _dc, n in sizes} == {data.series // data.dcs}


@pytest.mark.parametrize("cell", ["fleet-1m.wide-groupby",
                                  "live-100k.groupby-quiet",
                                  "fleet-1m.small-panels"])
def test_requests_per_template_do_not_depend_on_the_seed(bench, cell):
    w = next(x for x in bench["workloads"] if x["name"] == cell)
    data = gen.Data(tiny_config(w["config"])["data"])
    spec = load(f"benchmark/traffic/{w['traffic']}.json")
    seen = set()
    bodies = []
    for seed in SEEDS:
        t = traffic.Traffic(spec, data, seed, bench["run_seconds"])
        count = collections.Counter(r.template for r in t.timed)
        sizes = collections.Counter(len(r.body) for r in t.timed)
        seen.add((tuple(sorted(count.items())),
                  tuple(sorted(sizes.items())), len(t.warmup),
                  len(t.writes), tuple(len(r.body) > 0
                                       for r in t.writes)))
        bodies.append([r.body for r in t.timed])
        # one cost class: every request of the cell has the same shape
        assert len(count) == 1 and len(sizes) == 1
        # and no request repeats, so no cache can answer it
        assert len(set(bodies[-1])) == len(bodies[-1])
        if t.loop == "open":
            due = [r.due_s for r in t.timed]
            assert due == sorted(due) and 0 <= due[0] \
                and due[-1] < bench["run_seconds"]
    assert len(seen) == 1
    assert bodies[0] != bodies[1]
    again = traffic.Traffic(spec, data, SEEDS[0], bench["run_seconds"])
    assert [r.body for r in again.timed] == bodies[0]


def _mix(name="wide-groupby", **top):
    return dict(load(f"benchmark/traffic/{name}.json"), **top)


def test_the_closed_list_is_a_key_of_the_traffic_file():
    data = gen.Data(tiny_config("fleet-1m")["data"])
    # the default stays 2,000, warm-up included; the wide cell ships
    # 4,000 since PR 47
    assert _mix()["closed_list"] == 4000
    plain = {k: v for k, v in _mix().items() if k != "closed_list"}
    t = traffic.Traffic(plain, data, 1, 51)
    assert len(t.warmup) == 3 and len(t.timed) == 2000 - 3
    t = traffic.Traffic(_mix(closed_list=300), data, 1, 51)
    assert len(t.warmup) == 3 and len(t.timed) == 300 - 3
    # the panels ship 16,000: 3.2 ms a request over 51 s
    spec = _mix("small-panels")
    assert spec["closed_list"] == 16000
    t = traffic.Traffic(spec, data, 1, 51)
    assert len(t.warmup) + len(t.timed) == 16000
    assert 51 / len(t.timed) < 0.0032
    # an open loop's count is its rate's, whatever the key says
    t = traffic.Traffic(_mix(loop="open", clients=32, rate_per_s=10,
                             closed_list=300), data, 1, 51)
    assert len(t.timed) == 510


def test_a_draw_repeats_only_where_its_file_says_so():
    data = gen.Data(tiny_config("fleet-1m")["data"])
    # 2,000 racks, one a request, and a list of 5,000: refused, a
    # repeated request would be answered from the result cache (the
    # file draws two a request since PR 47: 1,999,000 pairs)
    spec = _mix(closed_list=5000)
    assert spec["requests"][0]["draw"]["rack"]["pick"] == 2
    traffic.Traffic(spec, data, 1, 51)
    spec["requests"][0]["draw"]["rack"]["pick"] = 1
    with pytest.raises(ValueError, match="a repeated request"):
        traffic.Traffic(spec, data, 1, 51)
    spec = _mix(closed_list=5000)
    spec["requests"][0]["draw"]["rack"].update(range=[0, 20],
                                               repeat=True, pick=1)
    seen = set()
    for seed in (1, 2):
        t = traffic.Traffic(spec, data, seed, 51)
        assert len(t.timed) == 5000 - 3
        bodies = [r.body for r in t.timed]
        # 20 panels asked again and again, each about as often
        count = collections.Counter(bodies)
        assert len(count) == 20 and min(count.values()) > 150
        seen.add(tuple(bodies[:50]))
    assert len(seen) == 2
    # said of a range that is wide enough, it still draws with
    # replacement: some rack comes twice in 1,500 of 2,000
    spec = _mix(closed_list=1500)
    spec["requests"][0]["draw"]["rack"].update(repeat=True, pick=1)
    t = traffic.Traffic(spec, data, 1, 51)
    assert len(set(r.body for r in t.timed)) < len(t.timed)
