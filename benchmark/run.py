#!/usr/bin/env python3
"""One run of one cell of the benchmark.

    python3 benchmark/run.py --workload fleet-1m.wide-groupby \\
        --seed 7 --seconds 51 --trace 0

reads ``BENCHMARK.json`` at the root of the checkout for the cell, the
cell's configuration under ``benchmark/configs/`` (with the generator
and the reference it names, ``deploy.py``) and its traffic mix under
``benchmark/traffic/``, makes the deployment's data from the seed,
loads it into a TSD started as a child process, warms the cell's own
requests by count, drives the window over real sockets, and only then
compares every answer of the window with the float64 reference. The
last line of standard output is the result object; every line before it
is commentary. Each number compared stands beside its limit under the
object's last key, ``compared``, and in the last lines of standard
error. With ``--trace 1`` the run also has ``jax.profiler``
record the end of the window inside the server and reports the cell's
per-layer metrics in place of the end-to-end ones.

This process never imports JAX or ``opentsdb_tpu``. Without a TPU the
run does all its work and then exits 3 with no result line.
"""

from __future__ import annotations

import argparse
import asyncio
import gc
import importlib.util
import json
import os
import re
import shutil
import statistics
import subprocess
import sys
import time

T_START = time.monotonic()

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, HERE)

import deploy  # noqa: E402
import loadgen  # noqa: E402
import readers  # noqa: E402
import reference  # noqa: E402
import traffic as traffic_mod  # noqa: E402
import tsdproc  # noqa: E402
from tsdproc import Failed  # noqa: E402

TRACE_TAIL_S = 10.0        # the traced stretch: the window's last 10 s


def say(msg: str) -> None:
    print(f"[bench +{time.monotonic() - T_START:7.1f}s] {msg}",
          flush=True)


def load_json(path: str):
    with open(path, "r", encoding="utf-8") as fh:
        return json.load(fh)


class Context:
    """What a run knows when its window has closed; the per-layer
    readers under ``benchmark/metrics/`` take their numbers from it."""

    def __init__(self):
        self.workload = self.config = self.traffic = None
        self.seconds = 0.0
        self.results: list = []       # timed requests, in send order
        self.write_results: list = []  # scheduled /api/put bodies
        self.readback = None          # the written span, asked again
        self.before = self.after = None   # Tsd.snapshot()
        self.query_stats: list = []   # /api/stats/query, in the window
        self.trace = None             # xplane.reduce_file(), or None
        self.trace_window_s = 0.0
        self.trace_queries = 0        # timed requests ended in it
        self.profile = None           # /api/profile, format=json
        self.setup: dict = {}         # listen_s, warm_s, setup_s
        self.first_shape = None       # (series, buckets, groups)
        self.window_t0 = 0.0          # perf_counter at the window's start
        self.window_compiles = 0      # compilations asked for in it
        self.peaks = None             # this device's row of peaks.json

    # helpers the readers share
    def stage_mean_ms(self, stage: str) -> float | None:
        """Mean of one stage histogram over the window: the growth of
        its sum over the growth of its count."""
        def pick(snap):
            for h in snap["stats"]["histograms"]:
                if h["name"] == "tsd_stage_latency_ms" \
                        and h["labels"].get("stage") == stage:
                    return h["count"], h["sum"]
            return 0, 0.0
        n0, s0 = pick(self.before)
        n1, s1 = pick(self.after)
        return (s1 - s0) / (n1 - n0) if n1 > n0 else None

    def counter_delta(self, metric: str) -> float:
        def pick(snap):
            return sum(r["value"] for r in snap["stats"]["records"]
                       if r["metric"] == metric)
        return pick(self.after) - pick(self.before)

    def latencies_ms(self) -> list[float]:
        return [r.latency_ms for r in self.results]


def read_metric(name: str, ctx: Context):
    path = os.path.join(HERE, "metrics", name + ".py")
    if not os.path.isfile(path):
        raise Failed(f"no reader for the per-layer metric {name!r} "
                     f"(expected {path})")
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_").replace("-", "_"),
        path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(ctx)


def find_cell(bench: dict, name: str):
    cell = next((w for w in bench["workloads"] if w["name"] == name),
                None)
    if cell is None:
        raise Failed(f"BENCHMARK.json has no workload {name!r}")
    conf = next(c for c in bench["configs"]
                if c["name"] == cell["config"])
    return cell, conf


def metrics_of(bench: dict, kind: str, cell: dict) -> list[dict]:
    """The cell's metrics of one kind: those that list it, and those
    that list no cells and move (or are) a metric the cell reports."""
    e2e = [m for m in bench["end_to_end"]
           if "workloads" not in m or cell["name"] in m["workloads"]]
    if kind == "end_to_end":
        return e2e
    names = {m["name"] for m in e2e}
    return [m for m in bench["per_layer"]
            if (cell["name"] in m["workloads"] if "workloads" in m
                else m["moves"] in names)]


# ---------------------------------------------------------------------
# the comparison that decides ``correct``
# ---------------------------------------------------------------------

def answered(ref, data, doc: dict, sub: dict):
    """What the judge ``ref`` says of one sub-query of the request
    ``doc``, for the window THAT request asked (``deploy.window_of``:
    its own ``start`` and ``end``, or the deployment's span), and where
    its rows lie: (group-by tag, names, bucket seconds, Cells, first
    bucket's timestamp in seconds, number of buckets)."""
    window = deploy.window_of(doc, data)
    if window is None:
        tagk, names, secs, cells = ref.answer(sub)
        return (tagk, names, secs, cells, data.t0,
                data.points * data.cadence_s // secs)
    tagk, names, secs, cells = ref.answer(sub, window=window)
    return (tagk, names, secs, cells,
            *reference.window_buckets(*window, secs))


def check_answers(ref, data, results, limits: dict,
                  judge=reference) -> dict:
    """Every answer of the window against the reference ``ref``, a
    ``Reference`` of ``judge`` (``deploy.judge_of``), each for the
    window its request asked. Returns the numbers compared, each with
    its limit, and the count that failed."""
    worst = {"http_failures": 0, "shape_errors": 0,
             "sum_rel_err": 0.0, "rank_abs_err": 0.0}
    failed, notes = 0, []
    for res in results:
        bad = ""
        if res.error or res.status != 200:
            worst["http_failures"] += 1
            bad = res.error or f"HTTP {res.status}: {res.body[:200]!r}"
        else:
            try:
                rows = json.loads(res.body)
            except ValueError:
                rows = None
            if not isinstance(rows, list):
                worst["shape_errors"] += 1
                bad = "the answer is not a JSON list"
            else:
                at = 0
                for sub in res.request.doc["queries"]:
                    tagk, names, secs, cells, first_s, nb = answered(
                        ref, data, res.request.doc, sub)
                    mine = rows[at:at + len(names)]
                    at += len(names)
                    got, stray = judge.rows_to_grid(
                        mine, tagk, names, first_s, nb, secs,
                        data.metric)
                    v = judge.compare(got, stray, cells)
                    worst["shape_errors"] += v.shape_errors
                    worst["sum_rel_err"] = max(worst["sum_rel_err"],
                                               v.sum_rel_err)
                    worst["rank_abs_err"] = max(worst["rank_abs_err"],
                                                v.rank_abs_err)
                    if not v.ok(limits["sum_rtol"],
                                limits["rank_atol"]):
                        bad = bad or v.note or "outside the tolerance"
                if at != len(rows):
                    worst["shape_errors"] += abs(len(rows) - at)
                    bad = bad or (f"{len(rows)} rows, the reference "
                                  f"has {at}")
        if bad:
            failed += 1
            if len(notes) < 5:
                notes.append(f"{res.request.template}: {bad}")
    return {"failed": failed, "notes": notes, "numbers": [
        ("http_failures", worst["http_failures"], 0),
        ("shape_errors", worst["shape_errors"], 0),
        ("sum_rel_err", worst["sum_rel_err"], limits["sum_rtol"]),
        ("rank_abs_err", worst["rank_abs_err"], limits["rank_atol"]),
    ]}


def written_data(config: dict, traffic):
    """The span the window's writes fill, as a deployment of its own:
    the same series, one point a step from the end of the history."""
    make = deploy.generator_of(config).Data
    spec = dict(config["data"], block_points=1,
                points=traffic.written.shape[1])
    spec["t0"] = make(config["data"]).end + 1
    return make(spec)


def readback_request(config: dict, traffic):
    d = written_data(config, traffic)
    return traffic_mod.Request("readback", "POST", "/api/query", {
        "start": d.t0 * 1000, "end": d.end * 1000, "queries": [{
            "metric": d.metric, "aggregator": "sum",
            "downsample": f"{d.cadence_s}s-avg", "filters": [
                {"type": "wildcard", "tagk": "dc", "filter": "*",
                 "groupBy": True}]}]})


def judged_requests(config: dict, traffic) -> list:
    """One request of every template a run will have judged: the timed
    templates, the probe and, with writers, the read-back."""
    one = {r.template: r for r in traffic.timed + traffic.probes}
    if traffic.writes:
        one["readback"] = readback_request(config, traffic)
    return list(one.values())


def check_writes(ctx: Context, limits: dict) -> dict:
    """Every body acknowledged, and every acknowledged point read
    back: the written span asked again after the window, against the
    reference over what was sent."""
    lost = sum(1 for r in ctx.write_results
               if r.error or r.status != 204)
    d = written_data(ctx.config, ctx.traffic)
    judge = deploy.judge_of(ctx.config)
    ref = judge.Reference(d, ctx.traffic.written, limits)
    back = check_answers(ref, d, [ctx.readback], limits, judge)
    return {"failed": lost + back["failed"], "notes": back["notes"],
            "numbers": [("writes_not_acked", lost, 0)]
            + [("readback_" + n, v, lim)
               for n, v, lim in back["numbers"][1:]]}


# ---------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------

def run_cell(workload: str, seed: int, seconds: float, trace: bool,
             out_dir: str = "", require_tpu: bool = True,
             server_flags: dict | None = None,
             stages_every_s: float = 0.0, shrink: dict | None = None):
    """Returns (exit code, result object or None). ``require_tpu``,
    ``server_flags`` and ``shrink`` (overrides of the configuration's
    ``data`` section) exist for the tests beside this file, which run a
    cell at a tiny size on the CPU; the command line sets none."""
    bench = load_json(os.path.join(ROOT, "BENCHMARK.json"))
    cell, conf = find_cell(bench, workload)
    config = load_json(os.path.join(ROOT, conf["file"]))
    if shrink:
        config["data"].update(shrink)
    spec = load_json(os.path.join(HERE, "traffic",
                                  cell["traffic"] + ".json"))
    generator = deploy.generator_of(config)
    judge = deploy.judge_of(config)
    data = generator.Data(config["data"])
    peaks_table = load_json(os.path.join(HERE, "peaks.json"))
    tag = f"{workload}.seed{seed}.trace{int(trace)}"
    work = os.path.join(ROOT, ".bench", "work", workload)
    out = out_dir or os.path.join(ROOT, ".bench", "out", tag)
    shutil.rmtree(work, ignore_errors=True)
    shutil.rmtree(out, ignore_errors=True)
    os.makedirs(work)
    os.makedirs(out)
    traffic = traffic_mod.Traffic(spec, data, seed, seconds)
    ctx = Context()
    ctx.workload, ctx.config, ctx.traffic = cell, config, traffic
    ctx.seconds = seconds
    # traffic the judge cannot answer is refused here, not after the
    # window
    deploy.refuse_unjudged(judge, data, judged_requests(config, traffic),
                           f"benchmark/traffic/{cell['traffic']}.json")
    say(f"{workload} seed {seed}: {data.series} series x {data.points} "
        f"points, {traffic.loop} loop, {seconds:g} s, trace "
        f"{int(trace)}")
    tsd = tsdproc.Tsd(ROOT, work, config)
    try:
        tsd.start(server_flags)
        values, points = tsd.load(data, seed)
        tsd.wait_listening()
        ctx.setup["listen_s"] = time.monotonic() - T_START
        if tsd.loaded_points() != points:
            raise Failed(f"the server imported {tsd.loaded_points()} "
                         f"points of {points}")
        device = tsd.must("/plugin/bench?op=state")
        say(f"listening after {ctx.setup['listen_s']:.1f}s with "
            f"{points} points on {device['count']} x "
            f"{device['platform']} ({device['kind']})")
        if device["platform"] == "tpu":
            if device["count"] < cell["chips"]:
                raise Failed(f"the cell needs {cell['chips']} chips, "
                             f"the server sees {device['count']}")
            ctx.peaks = peaks_table.get(device["kind"])
            if ctx.peaks is None:
                raise Failed(f"device kind {device['kind']!r} is not "
                             f"in benchmark/peaks.json")
        asyncio.run(_window(tsd, ctx, traffic, seconds, trace, out,
                            stages_every_s))
        state = tsd.must("/plugin/bench?op=state")
    finally:
        tsd.kill()
    if trace:
        ctx.trace = _reduce_trace(os.path.join(out, "trace"))
    # the reference, after the window and after the server has gone
    t_ref = time.monotonic()
    ref = judge.Reference(data, values, config["limits"])
    verdict = check_answers(ref, data, ctx.results, config["limits"],
                            judge)
    first = ctx.results[0].request.doc
    _tagk, names, _secs, _cells, _first_s, nb = answered(
        ref, data, first, first["queries"][0])
    ctx.first_shape = (ref.selected(first["queries"][0]), nb,
                       len(names))
    attempted = len(ctx.results)
    if traffic.writes:
        w = check_writes(ctx, config["limits"])
        verdict["failed"] += w["failed"]
        verdict["notes"] += w["notes"]
        verdict["numbers"] += w["numbers"]
        attempted += len(ctx.write_results)
    say(f"reference and comparison of {len(ctx.results)} answers: "
        f"{time.monotonic() - t_ref:.1f}s")
    verdict["numbers"].append(
        ("window_compiles", ctx.window_compiles, 0))
    correct = verdict["failed"] == 0 and ctx.window_compiles == 0 \
        and len(ctx.results) > 0
    for name, value, limit in verdict["numbers"]:
        say(f"compared {name} = {value:.6g} (limit {limit:g})")
    for note in verdict["notes"]:
        say(f"failed: {note}")
    _write_latencies(ctx, out)
    kind = "per_layer" if trace else "end_to_end"
    metrics = {}
    for m in metrics_of(bench, kind, cell):
        if kind == "end_to_end":
            value = _end_to_end(m["name"], ctx)
        else:
            value = read_metric(m["name"], ctx)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    peak = [d["peak_bytes_in_use"] for d in state["memory"]
            if d["peak_bytes_in_use"] is not None]
    dev = {"platform": device["platform"], "kind": device["kind"],
           "count": device["count"],
           "memory_peak_bytes": max(peak) if peak else None}
    doc = {"correct": bool(correct), "attempted": attempted,
           "failed": verdict["failed"], "metrics": metrics,
           "device": dev}
    if trace and ctx.trace is not None:
        dev["busy_s"] = ctx.trace["busy_s"]
        dev["window_s"] = ctx.trace_window_s
        doc["breakdown"] = _breakdown(ctx)
    # each number compared beside its limit, last in the line
    doc["compared"] = {name: {"value": value, "limit": limit}
                       for name, value, limit in verdict["numbers"]}
    if device["platform"] != "tpu" and require_tpu:
        # the work was done, but nothing ran on an accelerator: no
        # result line
        print(f"benchmark/run.py: no TPU: the server reports platform "
              f"{device['platform']!r}", file=sys.stderr)
        return 3, doc
    if trace and (ctx.trace is None or ctx.trace["busy_s"] <= 0) \
            and require_tpu:
        raise Failed("the traced stretch holds no device operation")
    return 0, doc


async def _window(tsd, ctx: Context, traffic, seconds: float,
                  trace: bool, out: str, stages_every_s: float):
    port = tsd.port
    # warm-up by count: every template a fixed number of times, and in
    # a traced run the probe as well
    t_warm = time.monotonic()
    warm = list(traffic.warmup) + list(traffic.write_warmup)
    if trace:
        warm += traffic.probes[:2]
    clients = traffic.clients if traffic.loop == "open" else 1
    done = await loadgen.send_all(port, warm, 1200.0, clients)
    bad = [r for r in done if r.error or r.status not in (200, 204)]
    if bad:
        raise Failed(f"warm-up request failed: {bad[0].error or bad[0].status}"
                     f" {bad[0].body[:300]!r}")
    ctx.setup["warm_s"] = time.monotonic() - t_warm
    say(f"warm-up: {len(warm)} requests in {ctx.setup['warm_s']:.1f}s "
        f"(first {done[0].latency_ms:.0f} ms, last "
        f"{done[-1].latency_ms:.0f} ms)")
    ctx.before = await asyncio.to_thread(tsd.snapshot)
    gc.collect()
    gc.freeze()
    trace_dir = os.path.join(out, "trace")
    marks = {}
    seen_stats: dict = {}

    async def side(t0: float):
        loop_tasks = []
        if stages_every_s > 0:
            async def poll():
                while True:
                    await asyncio.sleep(stages_every_s)
                    doc = await asyncio.to_thread(
                        tsd.must, "/api/stats/query")
                    for q in doc["completed"]:
                        seen_stats[q["queryId"]] = q
            loop_tasks.append(asyncio.ensure_future(poll()))
        if trace:
            await asyncio.sleep(max(0.0, seconds - TRACE_TAIL_S
                                    if seconds > TRACE_TAIL_S
                                    else seconds * 0.25))
            await asyncio.to_thread(
                tsd.must, "/plugin/bench?op=trace_start&dir="
                + trace_dir, 300.0)
            marks["start"] = time.perf_counter()
        return loop_tasks

    ctx.setup["setup_s"] = time.monotonic() - T_START
    results, loop_tasks, t0 = await loadgen.drive(port, traffic,
                                                  seconds, side)
    t_end = time.perf_counter()
    ctx.results = results
    ctx.write_results = traffic.write_results
    ctx.window_t0 = t0
    for t in loop_tasks or []:
        t.cancel()
    ctx.after = await asyncio.to_thread(tsd.snapshot)
    ctx.window_compiles = \
        ctx.after["bench"]["compile"]["compile_requests_use_cache"] \
        - ctx.before["bench"]["compile"]["compile_requests_use_cache"]
    if traffic.writes:
        # every acknowledged point has to be in a later answer
        ctx.readback = (await loadgen.send_all(
            port, [readback_request(ctx.config, traffic)],
            traffic.timeout_s))[0]
    doc = await asyncio.to_thread(tsd.must, "/api/stats/query")
    for q in doc["completed"]:
        seen_stats[q["queryId"]] = q
    # the queries of the window are the last len(results) started
    ids = sorted(seen_stats)
    ids = [i for i in ids if seen_stats[i]["queryStartTimestamp"]
           <= ctx.after["stats"]["ts"] * 1000 + 999]
    ctx.query_stats = [seen_stats[i] for i in ids[-len(results):]] \
        if results else []
    if trace:
        if "start" not in marks:
            raise Failed("the window closed before the trace started")
        ctx.trace_queries = sum(
            1 for r in results if r.done >= marks["start"])
        if traffic.probes:
            # traffic whose tail the program places on the host (the
            # panels; the live cells' tails run on the chip since
            # PR 32) runs no device program: one request that does,
            # after the last timed one and inside the trace, so that
            # the trace holds the device path
            probe = await loadgen.send_all(port, traffic.probes[2:],
                                           traffic.timeout_s)
            if probe[0].error or probe[0].status != 200:
                raise Failed(f"the probe failed: {probe[0].error or probe[0].status}")
        marks["stop"] = time.perf_counter()
        # what the host did, before stopping the trace fills the ring
        ctx.profile = await asyncio.to_thread(
            tsd.must, f"/api/profile?seconds="
            f"{int(min(60, marks['stop'] - t0 + 1))}&format=json")
        stopped = await asyncio.to_thread(
            tsd.must, "/plugin/bench?op=trace_stop", 600.0)
        ctx.trace_window_s = marks["stop"] - marks["start"]
        say(f"trace: {ctx.trace_window_s:.1f}s traced, "
            f"{ctx.trace_queries} timed requests ended in it, "
            f"stopping took {stopped['stop_s']:.1f}s")
    gc.unfreeze()
    say(f"window: {len(results)} requests in {t_end - t0:.1f}s")


def _reduce_trace(trace_dir: str):
    """``xplane.py`` in a process of its own, held to the CPU."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(HERE, "xplane.py"), trace_dir],
        env=env, capture_output=True, text=True, timeout=600)
    if p.returncode != 0:
        say(f"trace reduction failed: {p.stderr[-500:]}")
        return None
    return json.loads(p.stdout.strip().splitlines()[-1])


def _op_name(hlo: str) -> str:
    """``%rev.3 = f32[1048576,12]{0,1:T(8,128)} reverse(...`` ->
    ``rev.3 f32[1048576,12]``."""
    m = re.match(r"%?([\w.\-]+) = \(?(\w+\[[\d,]*\])", hlo)
    return f"{m.group(1)} {m.group(2)}" if m else hlo[:64]


def _breakdown(ctx: Context) -> dict:
    """Top device operations by name, and the longest idle gaps by
    what ``/api/profile`` saw the host doing (its 4 Hz sampler: a
    sample is a quarter of a second of one thread)."""
    ops = [[_op_name(name), secs]
           for name, secs in ctx.trace["ops"][:10]]
    gaps: dict = {}
    prof = ctx.profile or {}
    hz = float(prof.get("hz") or 4.0)
    for role, stacks in (prof.get("roles") or {}).items():
        for stack, count in stacks.items():
            frames = stack.split(";")
            leaf = frames[-1]
            if leaf.split(":")[0] in ("threading.py", "selectors.py",
                                      "queue.py") \
                    or leaf == "thread.py:_worker" \
                    or "tsd_plugin.py" in stack:
                continue       # a thread that waits does no work
            name = f"{role}:{leaf}<{frames[-2] if len(frames) > 1 else ''}"
            name = "".join(c if c.isalnum() or c in "._:<-" else "_"
                           for c in name)[:64]
            gaps[name] = gaps.get(name, 0.0) + count / hz
    top = sorted(gaps.items(), key=lambda kv: -kv[1])[:10]
    return {"device_ops": ops, "idle_gaps": [[k, v] for k, v in top]}


def _end_to_end(name: str, ctx: Context):
    lat = ctx.latencies_ms()
    if name == "setup_s":
        return ctx.setup["setup_s"]
    if name == "put_ack_p95_ms":
        acks = [r.latency_ms for r in ctx.write_results]
        return readers.percentile(acks, 95) if acks else None
    if not lat:
        return None
    if name == "query_p50_ms":
        return statistics.median(lat)
    if name == "query_p95_ms":
        return readers.percentile(lat, 95)
    raise Failed(f"no code measures the end-to-end metric {name!r}")


def _write_latencies(ctx: Context, out: str) -> None:
    with open(os.path.join(out, "latencies.csv"), "w") as fh:
        fh.write("n,template,due_s,late_ms,latency_ms,status,bytes\n")
        for i, r in enumerate(ctx.results):
            fh.write(f"{i},{r.request.template},"
                     f"{r.due - ctx.window_t0:.6f},{r.late_ms:.4f},"
                     f"{r.latency_ms:.4f},{r.status},{len(r.body)}\n")
    if ctx.write_results:
        with open(os.path.join(out, "writes.csv"), "w") as fh:
            fh.write("n,due_s,late_ms,latency_ms,status\n")
            for i, r in enumerate(ctx.write_results):
                fh.write(f"{i},{r.due - ctx.window_t0:.6f},"
                         f"{r.late_ms:.4f},{r.latency_ms:.4f},"
                         f"{r.status}\n")
    if ctx.query_stats:
        keys = ("queryScanTime", "stringToUidTime", "computeTime",
                "serializationTime", "totalTime")
        with open(os.path.join(out, "stages.csv"), "w") as fh:
            fh.write("queryId,start_ms," + ",".join(keys) + "\n")
            for q in ctx.query_stats:
                fh.write(f"{q['queryId']},{q['queryStartTimestamp']},"
                         + ",".join(f"{q['stats'].get(k, 0):.4f}"
                                    for k in keys) + "\n")


def main(argv: list[str]) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--out", default="",
                    help="where latencies.csv and the trace go "
                         "(default .bench/out/<cell>.<seed>.<trace>)")
    ap.add_argument("--stages-every", type=float, default=0.0,
                    help="noise study: poll /api/stats/query this often"
                         " for every request's stage times")
    args = ap.parse_args(argv)
    if not os.path.isdir(os.path.join(ROOT, "opentsdb_tpu")):
        print("benchmark/run.py: no opentsdb_tpu package beside "
              "benchmark/: there is no system to measure",
              file=sys.stderr)
        return 2
    try:
        code, doc = run_cell(args.workload, args.seed, args.seconds,
                             bool(args.trace), args.out,
                             stages_every_s=args.stages_every)
    except Failed as e:
        print(f"benchmark/run.py: {e}", file=sys.stderr)
        return 1
    if code == 0:
        print(json.dumps(doc), flush=True)
        for name, c in doc["compared"].items():
            print(f"compared {name} = {c['value']:.6g} (limit "
                  f"{c['limit']:g})", file=sys.stderr)
    return code


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
