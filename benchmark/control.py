#!/usr/bin/env python3
"""The control of ``correct``: the reference put in the program's place
and computed in the nearest precision below the one the configurations
state. They state float32, so the control holds every stored value in
bfloat16 (8 bits of mantissa: what a later PR that halves the grid's
bytes would do) and is otherwise the reference itself, in float64: the
kindest form of that step, so a program that also accumulates in
bfloat16 is farther off still. The control has to come out as not
correct, on the cell's own data and requests at the cell's own size.

    python3 benchmark/control.py --workload fleet-1m.wide-groupby \\
        --seed 7 --requests 40

prints one JSON object: each number compared, the limit it is held to,
and ``correct``. It needs no chip and starts no server.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, HERE)

import deploy  # noqa: E402
import reference  # noqa: E402
import run  # noqa: E402
import traffic as traffic_mod  # noqa: E402


def to_bfloat16(x: np.ndarray) -> np.ndarray:
    """float64 -> the nearest bfloat16 (round to nearest even on the
    float32 bit pattern), back as float64. NaN stays NaN."""
    f = np.ascontiguousarray(x, dtype=np.float32)
    bits = f.view(np.uint32)
    rounded = (bits + np.uint32(0x7FFF) + ((bits >> np.uint32(16))
                                          & np.uint32(1))) \
        & np.uint32(0xFFFF0000)
    out = rounded.view(np.float32).astype(np.float64)
    return np.where(np.isnan(x), np.nan, out)


def control_numbers(data, values: np.ndarray, limits: dict,
                    requests, judge=reference) -> dict:
    """The numbers a run would compare, had the program answered as
    the control does: the configuration's judge (``deploy.judge_of``)
    over bfloat16 values against itself over the values as made."""
    true = judge.Reference(data, values, limits)
    low = judge.Reference(data, to_bfloat16(values), limits)
    worst = {"shape_errors": 0, "sum_rel_err": 0.0, "rank_abs_err": 0.0}
    for req in requests:
        for sub in req.doc["queries"]:
            want = run.answered(true, data, req.doc, sub)[3]
            got = run.answered(low, data, req.doc, sub)[3]
            v = judge.compare(
                np.where(got.emitted, got.want, np.nan), 0, want)
            worst["shape_errors"] += v.shape_errors
            worst["sum_rel_err"] = max(worst["sum_rel_err"],
                                       v.sum_rel_err)
            worst["rank_abs_err"] = max(worst["rank_abs_err"],
                                        v.rank_abs_err)
    worst["limits"] = {"shape_errors": 0,
                       "sum_rel_err": limits["sum_rtol"],
                       "rank_abs_err": limits["rank_atol"]}
    worst["correct"] = all(worst[k] <= worst["limits"][k]
                           for k in worst["limits"])
    return worst


def main(argv: list[str]) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--requests", type=int, default=40)
    args = ap.parse_args(argv)
    bench = run.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    cell, conf = run.find_cell(bench, args.workload)
    config = run.load_json(os.path.join(ROOT, conf["file"]))
    spec = run.load_json(os.path.join(HERE, "traffic",
                                      cell["traffic"] + ".json"))
    generator = deploy.generator_of(config)
    data = generator.Data(config["data"])
    values, _points = generator.generate(data, args.seed, None)
    traffic = traffic_mod.Traffic(spec, data, args.seed,
                                  bench["run_seconds"])
    out = control_numbers(data, values, config["limits"],
                          traffic.timed[:args.requests],
                          deploy.judge_of(config))
    out.update(workload=args.workload, seed=args.seed,
               requests=min(args.requests, len(traffic.timed)))
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
