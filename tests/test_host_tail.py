"""Host-tail fast path: small [S, B] grids run the fill/rate/aggregate
tail on the host CPU backend instead of the accelerator —
engine.host_tail_device. On the CPU test matrix the
default backend IS cpu, so these tests pin the decision logic and the
committed-device plumbing (cache placement + execute), and the
equivalence of results with the path forced off."""

import numpy as np
import pytest

from opentsdb_tpu.query.engine import (HOST_TAIL_DEFAULT_CELLS,
                                       HOST_TAIL_DEFAULT_CELLS_LINEAR,
                                       host_tail_device)
from opentsdb_tpu.query.model import TSQuery


def _cfg(**over):
    from opentsdb_tpu import Config
    return Config(**{k: str(v) for k, v in over.items()})


LINEAR, RANK = True, False


@pytest.mark.parametrize("over, cells, linear, placed", [
    # the rank class: under its default budget a committed cpu device,
    # above it the accelerator (None)
    ({}, 64 * 1024, RANK, "host"),
    ({}, HOST_TAIL_DEFAULT_CELLS + 1, RANK, "device"),
    # the linear class: the crossover measured on the chip (PR 32).
    # The panels' 8 x 64 and the sweep's 1,024 x 64 on the host, the
    # sweep's 8,192 x 64, config 2's 114,688 x 64 and the north star's
    # 1,048,576 x 64 on the accelerator
    ({}, 8 * 64, LINEAR, "host"),
    ({}, 1024 * 64, LINEAR, "host"),
    ({}, HOST_TAIL_DEFAULT_CELLS_LINEAR + 1, LINEAR, "device"),
    ({}, 8192 * 64, LINEAR, "device"),
    ({}, 114688 * 64, LINEAR, "device"),
    ({}, 1048576 * 64, LINEAR, "device"),
    # custom thresholds
    ({"tsd.query.host_tail_max_cells": 1000}, 999, RANK, "host"),
    ({"tsd.query.host_tail_max_cells": 1000}, 1001, RANK, "device"),
    ({"tsd.query.host_tail_max_cells_linear": 1000}, 999, LINEAR,
     "host"),
    ({"tsd.query.host_tail_max_cells_linear": 1000}, 1001, LINEAR,
     "device"),
    # -1 disables the path entirely, a class at a time
    ({"tsd.query.host_tail_max_cells": -1}, 1, RANK, "device"),
    ({"tsd.query.host_tail_max_cells": -1}, 1, LINEAR, "host"),
    ({"tsd.query.host_tail_max_cells_linear": -1}, 1, LINEAR,
     "device"),
    ({"tsd.query.host_tail_max_cells_linear": -1}, 1, RANK, "host"),
])
def test_host_tail_decision_thresholds(over, cells, linear, placed):
    dev = host_tail_device(_cfg(**over), cells, linear_agg=linear)
    if placed == "host":
        assert dev is not None and dev.platform == "cpu"
    else:
        assert dev is None


def _query(tsdb, m):
    q = TSQuery.from_json({
        "start": 1356998400000, "end": 1356998400000 + 300 * 10_000,
        "queries": [{"aggregator": "sum", "metric": "sys.cpu.user",
                     "downsample": m,
                     "filters": [{"type": "wildcard", "tagk": "host",
                                  "filter": "*", "groupBy": True}]}],
    })
    return tsdb.new_query().run(q.validate())


@pytest.mark.parametrize("ds", ["1m-avg", "30s-sum", "1m-max"])
def test_small_query_host_tail_matches_device_path(seeded_tsdb, ds):
    """The same small query answered with the host-tail path on vs
    forced off must produce identical series (both run on CPU in the
    test matrix; this pins the committed-device plumbing end to end).
    Host-tail queries bypass the device grid cache (host RAM must not
    evict HBM-resident grids), so the warm repeat re-scans natively —
    results must still be identical."""
    on = _query(seeded_tsdb, ds)
    # warm repeat: exercises the cache-hit path with committed arrays
    on_warm = _query(seeded_tsdb, ds)
    seeded_tsdb.config.override_config("tsd.query.host_tail_max_cells", "-1")
    seeded_tsdb.drop_caches()
    off = _query(seeded_tsdb, ds)
    seeded_tsdb.config.override_config("tsd.query.host_tail_max_cells", "0")
    assert len(on) == len(off) == len(on_warm) == 2
    for a, w, b in zip(on, on_warm, off):
        assert a.tags == b.tags
        assert [t for t, _ in a.dps] == [t for t, _ in w.dps] \
            == [t for t, _ in b.dps]
        np.testing.assert_allclose([v for _, v in a.dps],
                                   [v for _, v in b.dps], rtol=1e-12)
        np.testing.assert_allclose([v for _, v in a.dps],
                                   [v for _, v in w.dps], rtol=1e-12)


def test_rollup_avg_host_tail(tsdb):
    """The avg-rollup division tail also takes the host device for
    small grids: write raw, roll up, delete raw, query 1m-avg."""
    base_ms = 1356998400000
    for i in range(120):
        tsdb.add_point("r.m", 1356998400 + i * 10, float(i % 7),
                       {"host": "a"})
    from opentsdb_tpu.rollup.job import run_rollup_job
    run_rollup_job(tsdb, base_ms, base_ms + 1200_000)
    q = TSQuery.from_json({
        "start": base_ms, "end": base_ms + 1200_000,
        "queries": [{"aggregator": "sum", "metric": "r.m",
                     "downsample": "1m-avg"}]})
    want = tsdb.new_query().run(q.validate())
    tsdb.config.override_config("tsd.query.host_tail_max_cells", "-1")
    tsdb.drop_caches()
    off = tsdb.new_query().run(q.validate())
    assert len(want) == len(off) == 1
    assert [t for t, _ in want[0].dps] == [t for t, _ in off[0].dps]
    np.testing.assert_allclose([v for _, v in want[0].dps],
                               [v for _, v in off[0].dps], rtol=1e-12)
