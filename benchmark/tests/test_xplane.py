"""The reduction from a profiler trace to numbers."""

import os

import pytest
from conftest import HERE

import kernels
import xplane


def test_union_of_intervals():
    assert xplane.union_ns([]) == 0
    assert xplane.union_ns([(0, 10), (5, 15), (20, 30), (22, 25)]) == 25
    assert xplane.union_ns([(5, 6), (0, 1)]) == 2


def test_reduce_planes_counts_device_ops_once():
    planes = [
        ("/host:CPU", [("thread", [("x", 0, 10**9)])]),
        ("/device:TPU:0", [
            ("Steps", [("0", 0, 1000)]),
            ("XLA Modules", [("jit_f(1)", 100, 400),
                             ("jit_f(1)", 600, 300)]),
            ("XLA Ops", [("fusion.1", 100, 200), ("copy.2", 250, 250),
                         ("fusion.1", 600, 300)]),
        ]),
        ("/device:TPU:1", [("XLA Ops", [("fusion.1", 0, 100)])]),
    ]
    out = xplane.reduce_planes(planes)
    assert out["devices"] == 2
    # chip 0: [100, 500) and [600, 900) = 700 ns; chip 1: 100 ns
    assert out["busy_s"] == pytest.approx((700 + 100) / 2 / 1e9)
    assert out["ops"][0] == ["fusion.1", pytest.approx(600e-9)]
    assert out["modules"] == [["jit_f(1)", 2, pytest.approx(700e-9)]]
    assert xplane.reduce_planes(planes[:1])["busy_s"] == 0.0


def test_recorded_trace():
    """A few seconds of ``fleet-1m.wide-groupby`` recorded on a TPU v5e
    by ``run.py --trace 1`` (PR 23)."""
    path = xplane.find_xplane(os.path.join(HERE, "data"))
    if path is None:
        pytest.skip("no recorded trace beside the tests")
    out = xplane.reduce_file(path)
    assert out["devices"] == 1 and out["busy_s"] > 0
    assert any("run_pipeline_grid" in m[0] for m in out["modules"])
    assert out["busy_s"] <= sum(t for _n, t in out["ops"]) * 1.0001 \
        or len(out["ops"]) == 40


def test_grid_tail_bytes_and_buckets():
    assert kernels.shape_bucket(999_500) == 1_048_576
    assert kernels.shape_bucket(99_950) == 114_688
    assert kernels.shape_bucket(12) == 12 and kernels.shape_bucket(60) == 64
    assert kernels.shape_bucket(101) == 112
    assert kernels.grid_tail_bytes(999_500, 12, 100) == \
        1_048_576 * 12 * 5 + 1_048_576 * 4 + 112 * 12 * 5
