#!/usr/bin/env python3
"""The rate sweep of an open-loop cell: one set-up, then a window at
each offered rate, to find the highest rate the server sustains without
a growing backlog. The cell's traffic file then carries four fifths of
it as a number; the benchmark itself never searches.

    python3 benchmark/sweep.py --workload fleet-1m.small-panels \\
        --seed 3 --seconds 20 --rates 20,40,60,80,100,120

prints one JSON line per rate: offered and answered rate, the median
and 95th percentile of latency in the window's first and second half
(a backlog that grows shows as a second half far above the first), how
late the generator sent, and the most requests in flight.
"""

from __future__ import annotations

import argparse
import asyncio
import copy
import json
import os
import shutil
import statistics
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, HERE)

import deploy  # noqa: E402
import loadgen  # noqa: E402
import readers  # noqa: E402
import run  # noqa: E402
import traffic as traffic_mod  # noqa: E402
import tsdproc  # noqa: E402


def summarize(rate: float, seconds: float, results) -> dict:
    t0 = min(r.due for r in results)
    half = t0 + seconds / 2
    first = [r.latency_ms for r in results if r.due < half]
    second = [r.latency_ms for r in results if r.due >= half]
    events = sorted([(r.sent, 1) for r in results]
                    + [(r.done, -1) for r in results])
    flying = peak = 0
    for _t, d in events:
        flying += d
        peak = max(peak, flying)
    span = max(r.done for r in results) - t0
    return {"rate_per_s": rate, "answered_per_s": len(results) / span,
            "failed": sum(1 for r in results
                          if r.error or r.status != 200),
            "p50_first_half_ms": statistics.median(first),
            "p50_second_half_ms": statistics.median(second),
            "p95_first_half_ms": readers.percentile(first, 95),
            "p95_second_half_ms": readers.percentile(second, 95),
            "late_mean_ms": statistics.fmean(r.late_ms for r in results),
            "late_max_ms": max(r.late_ms for r in results),
            "max_in_flight": peak}


def main(argv: list[str]) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args(argv)
    bench = run.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    cell, conf = run.find_cell(bench, args.workload)
    config = run.load_json(os.path.join(ROOT, conf["file"]))
    spec = run.load_json(os.path.join(HERE, "traffic",
                                      cell["traffic"] + ".json"))
    if spec["loop"] != "open":
        print("sweep.py: the cell's loop is closed: it has no rate",
              file=sys.stderr)
        return 2
    data = deploy.generator_of(config).Data(config["data"])
    work = os.path.join(ROOT, ".bench", "work", args.workload)
    shutil.rmtree(work, ignore_errors=True)
    os.makedirs(work)
    tsd = tsdproc.Tsd(ROOT, work, config)
    try:
        tsd.start()
        tsd.load(data, args.seed)
        tsd.wait_listening()
        for i, rate in enumerate(float(x) for x in
                                 args.rates.split(",")):
            one = copy.deepcopy(spec)
            one["rate_per_s"] = rate
            # another draw of hosts at each rate: nothing is answered
            # from a cache filled by the rate before
            t = traffic_mod.Traffic(one, data, args.seed + i,
                                    args.seconds)
            if i == 0:
                asyncio.run(loadgen.send_all(tsd.port, t.warmup, 600.0,
                                             t.clients))
            results, _side, _t0 = asyncio.run(
                loadgen.drive(tsd.port, t, args.seconds))
            print(json.dumps(summarize(rate, args.seconds, results)),
                  flush=True)
    finally:
        tsd.kill()
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
