"""Tests of the benchmark itself (not tier-1): run with
``JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q`` from the
root of the checkout."""

import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
for p in (BENCH, ROOT):
    if p not in sys.path:
        sys.path.insert(0, p)

# a deployment a test can hold: every tag rule and the gappy tenth are
# there, the widths of a line are the real ones
TINY = {"series": 4000, "chunk_series": 1000}


def load(rel: str):
    with open(os.path.join(ROOT, rel), encoding="utf-8") as fh:
        return json.load(fh)


@pytest.fixture(scope="session")
def bench():
    return load("BENCHMARK.json")


def listed_with(bench: dict, cell: str) -> set:
    """The per-layer metrics whose list of cells holds ``cell``."""
    return {m["name"] for m in bench["per_layer"]
            if cell in m.get("workloads", ())}


def tiny_config(name: str) -> dict:
    cfg = load(f"benchmark/configs/{name}.json")
    cfg["data"].update(TINY)
    return cfg
