#!/usr/bin/env python3
"""chip_smoke.py: the quickest proof that the TSD still starts on the chip.

Drives the served query path end to end on the accelerator JAX finds,
through the entry points a user has, at the width of the repo's
north-star deployment (BASELINE config 3: one million series of one
metric, one hour at a 60 s cadence), and checks every answer against a
plain NumPy float64 computation of the same semantics over the same
seeded arrays.

    python3 chip_smoke.py            # everything, on the chip
    JAX_PLATFORMS=cpu python3 chip_smoke.py --series 2000 ...
                                     # plumbing check: does the work,
                                     # then exits non-zero with "no TPU"

This process never imports JAX or ``opentsdb_tpu``: one process holds
the chip at a time, so every phase is a child process (``tsdb import``,
``tsdb tsd``, ``tsdb rollup``) and each child that needs the chip has
exited before the next one starts.

Phases
  load  seeded data -> text lines -> ``tsdb import`` into one data dir
  A     default server (rollups on, warm-up on): histograms and the
        dashboard metric go in over HTTP, a point over telnet; the
        grouped rate at 1M series, the gappy series, a rank-class
        aggregator, histogram percentiles, the dashboard query
  B     a second server on the same data dir (snapshot load + WAL
        replay) with the storage-side grid reduction off and nothing
        kept resident, so regular-cadence queries take the point path
        and run its dense program on the device: 100 groups, 2000
        groups, max downsample, counter rate
  C     (4+ devices) a third server with tsd.query.mesh=series:4
  R     a router and one shard as two processes: the router must not
        take the chip
  Z     the batch rollup job (``tsdb rollup``), a default server on
        the result, avg answered from the 1m tier as sum over count

Last line of stdout on success: one JSON object
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``
with the device as the SERVER reported it. Exit code 0 only then.
"""

from __future__ import annotations

import argparse
import base64
import collections
import importlib.util
import json
import multiprocessing
import os
import shutil
import signal
import socket
import struct
import subprocess
import sys
import tempfile
import time
import urllib.error
import urllib.request
from concurrent.futures import ProcessPoolExecutor

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))

# ---------------------------------------------------------------------
# the deployment (BASELINE.json configs 3, 4 and 1)
# ---------------------------------------------------------------------
T0 = 1356998400            # 2013-01-01 00:00:00 UTC, seconds
CADENCE_S = 60
POINTS = 60                # one hour
K = 5                      # points per 5m bucket
BUCKETS = POINTS // K
INTERVAL_S = CADENCE_S * K
END = T0 + POINTS * CADENCE_S - 1
DCS = 100                  # group-by dc -> 100 groups
RACKS = 2000               # group-by rack -> 2000 groups
CHUNK = 20_000             # series per generated chunk
HIST_BUCKETS = 64
HIST_BOUNDS = np.logspace(0, 4, HIST_BUCKETS + 1)
DASH_SERIES = 1000         # config 1: 1k series, 1h @ 10 s
DASH_POINTS = 360
COUNTER_MAX = 10000.0

# Tolerances, and why.
#
# The server computes in float32 on the chip (x64 is off there); the
# reference below is float64. A group sum over 10,000 series loses up
# to ~1e-5 of the sum of magnitudes to f32 accumulation (measured on
# the v5e: 1e-5 for the XLA segment sum),
# while ONE dropped series moves it by 1e-4 and a wrong bucket or a
# truncated timestamp by orders of magnitude more. So a cell passes
# when |got - want| <= SUM_RTOL * (sum over the group's series of
# |term|), between the two.
SUM_RTOL = 4e-5
# ... plus, for each member, what f32 cannot resolve of ONE value of
# magnitude <= 1e4 (half an ulp = 5e-4; a rate's delta has two):
# it matters for groups of a few series, whose sums can cancel to
# nothing while each term still carries that rounding.
VALUE_ATOL = 5e-4
# An order statistic carries only the f32 rounding of one value of
# magnitude <= 1e4 (half an ulp = 5e-4); one dropped series moves a
# p95 over 10,000 series by ~1.
RANK_ATOL = 5e-3
# Over a mesh, percentiles are estimated from a 512-bin histogram of
# each cell's value range (parallel/sharded_pipeline.PERCENTILE_BINS):
# the documented error is range / 512, doubled for both bin edges.
MESH_RANK_BINS = 512
# Histogram percentiles are bucket midpoints: exact up to f32.
HIST_RTOL = 1e-5
# A counter rate is discontinuous at delta == 0: where two bucket
# averages differ by less than f32 can resolve (0.01 at magnitude
# 1e4), float32 and float64 may disagree on the sign and one of them
# adds counter_max / dt. Such (series, bucket) pairs widen their cell
# by that amount instead of failing it.
COUNTER_TIE = 0.01

# tsd.tpu.warmup.budget_s for every server: at 1M series ~13 s pass
# before the first program starts (TPU client start included) and one
# cold program then takes 33-59 s, so this ends warm-up after exactly
# one program, cold or cached. Plumbing runs at a small --series
# compile a program in well under a second and get 1 s.
WARMUP_BUDGET_S = 20
FULL_WIDTH = 700_000       # series from which placement is the chip's


def say(msg: str) -> None:
    print(f"[smoke +{time.monotonic() - _T_START:7.1f}s] {msg}",
          flush=True)


_T_START = time.monotonic()


# ---------------------------------------------------------------------
# seeded data (used by the generator workers AND by the reference)
# ---------------------------------------------------------------------

def chunk_values(seed: int, chunk: int, n_total: int):
    """Series ``[chunk*CHUNK, ...)`` of ``smoke.cpu``: global index,
    values in cents [n, POINTS] and the dropped-point mask. A tenth of
    the series (those with ``(i // 100) % 10 == 9``, spread over every
    dc) lose ~1% of their points: half as single points, half as
    whole 5m buckets, so the [series, bucket] grid has real holes."""
    lo = chunk * CHUNK
    hi = min(lo + CHUNK, n_total)
    rng = np.random.default_rng([seed, chunk])
    cents = rng.integers(100_000, 1_000_000, size=(hi - lo, POINTS),
                         dtype=np.int64)
    idx = np.arange(lo, hi)
    drop = np.zeros((hi - lo, POINTS), dtype=bool)
    gappy = np.nonzero(is_gappy(idx))[0]
    if len(gappy):
        single = rng.random((len(gappy), POINTS)) < 0.005
        whole = np.repeat(rng.random((len(gappy), BUCKETS)) < 0.005,
                          K, axis=1)
        drop[gappy] = single | whole
    return idx, cents, drop


def is_gappy(idx: np.ndarray) -> np.ndarray:
    return (idx // 100) % 10 == 9


_POW10 = 10 ** np.arange(9, -1, -1, dtype=np.int64)


def _digits(x: np.ndarray, width: int) -> np.ndarray:
    """[n] non-negative ints -> [n, width] zero-padded ASCII digits."""
    return ((x[:, None] // _POW10[10 - width:]) % 10 + 48) \
        .astype(np.uint8)


_LINE = (b"smoke.cpu 0000000000 0000.00 host=h0000000 dc=d00 "
         b"rack=r0000 fleet=a\n")


def chunk_lines(seed: int, chunk: int, n_total: int):
    """One chunk as ``tsdb import`` text (series-major, the order of a
    ``tsdb scan --import`` dump) plus what the reference needs of it:
    the per-series 5m average and 5m maximum grids in float64."""
    idx, cents, drop = chunk_values(seed, chunk, n_total)
    n = len(idx)
    buf = np.empty((n, POINTS, len(_LINE)), dtype=np.uint8)
    buf[:] = np.frombuffer(_LINE, dtype=np.uint8)
    ts = T0 + CADENCE_S * np.arange(POINTS, dtype=np.int64)
    buf[:, :, 10:20] = _digits(ts, 10)[None]
    d = _digits(cents.reshape(-1), 6).reshape(n, POINTS, 6)
    buf[:, :, 21:25] = d[:, :, :4]
    buf[:, :, 26:28] = d[:, :, 4:]
    buf[:, :, 35:42] = _digits(idx, 7)[:, None, :]
    buf[:, :, 47:49] = _digits(idx % DCS, 2)[:, None, :]
    buf[:, :, 56:60] = _digits(idx % RACKS, 4)[:, None, :]
    buf[is_gappy(idx), :, 67] = ord("b")
    vals = np.where(drop, np.nan, cents / 100.0).reshape(
        n, BUCKETS, K)
    cnt = (~np.isnan(vals)).sum(axis=2)
    with np.errstate(invalid="ignore", divide="ignore"):
        avg = np.nansum(vals, axis=2) / cnt
    mx = np.where(cnt > 0, np.nanmax(
        np.where(np.isnan(vals), -np.inf, vals), axis=2), np.nan)
    return buf[~drop].tobytes(), avg, mx, int((~drop).sum())


# ---------------------------------------------------------------------
# the plain reference: NumPy float64, independent of the code under
# test (cross-checked against tests/oracle.py on sampled groups)
# ---------------------------------------------------------------------

def ref_rate(grid: np.ndarray, counter_max: float | None = None):
    """Per-series rate over the bucket grid (ref: RateSpan): each
    present bucket against the PREVIOUS PRESENT one, dv / dt seconds;
    a series' first present bucket emits nothing. Returns (rate grid
    with NaN where nothing is emitted, count of near-tie deltas per
    cell for the counter tolerance)."""
    s, b = grid.shape
    present = ~np.isnan(grid)
    cols = np.arange(b)
    last = np.maximum.accumulate(
        np.where(present, cols[None, :], -1), axis=1)
    prev = np.concatenate([np.full((s, 1), -1), last[:, :-1]], axis=1)
    ok = present & (prev >= 0)
    pv = np.take_along_axis(grid, np.maximum(prev, 0), axis=1)
    delta = grid - pv
    ties = np.zeros((s, b), dtype=bool)
    if counter_max is not None:
        ties = ok & (np.abs(delta) <= COUNTER_TIE)
        delta = np.where(delta < 0, counter_max - pv + grid, delta)
    dt = (cols[None, :] - prev) * float(INTERVAL_S)
    rate = np.where(ok, delta / np.where(ok, dt, 1.0), np.nan)
    return rate, ties


def lerp_fill(grid: np.ndarray) -> np.ndarray:
    """Merge-time interpolation of the lerp aggregators (sum, p95):
    a missing cell BETWEEN two present ones takes the straight line
    between them; before the first or after the last present cell a
    series contributes nothing (ref: AggregationIterator)."""
    s, b = grid.shape
    present = ~np.isnan(grid)
    cols = np.arange(b)
    prev = np.maximum.accumulate(
        np.where(present, cols[None, :], -1), axis=1)
    nxt = np.minimum.accumulate(
        np.where(present, cols[None, :], b)[:, ::-1], axis=1)[:, ::-1]
    inner = ~present & (prev >= 0) & (nxt < b)
    p = np.clip(prev, 0, b - 1)
    q = np.clip(nxt, 0, b - 1)
    v0 = np.take_along_axis(grid, p, axis=1)
    v1 = np.take_along_axis(grid, q, axis=1)
    w = (cols[None, :] - p) / np.maximum(q - p, 1)
    return np.where(inner, v0 + (v1 - v0) * w, grid)


def ref_group_sum(grid: np.ndarray, gids: np.ndarray, g: int,
                  term_atol: float):
    """Sum over each group's series of the lerp-filled grid. Returns
    (sums [g, b], tolerance [g, b], emitted [g, b]: a bucket exists
    for a group when some member has a REAL value there).
    ``term_atol`` is the absolute f32 error of one member's term."""
    filled = lerp_fill(grid)
    b = grid.shape[1]
    sums = np.zeros((g, b))
    tol = np.zeros((g, b))
    emitted = np.zeros((g, b), dtype=bool)
    for j in range(b):
        col = filled[:, j]
        ok = ~np.isnan(col)
        sums[:, j] = np.bincount(gids[ok], weights=col[ok], minlength=g)
        tol[:, j] = SUM_RTOL * np.bincount(
            gids[ok], weights=np.abs(col[ok]), minlength=g) \
            + term_atol * np.bincount(gids[ok], minlength=g)
        emitted[:, j] = np.bincount(
            gids, weights=~np.isnan(grid[:, j]), minlength=g) > 0
    return sums, tol, emitted


def ref_group_p95(grid: np.ndarray, gids: np.ndarray, g: int):
    """p95 over each group's series, commons-math3 LEGACY estimation
    (ref: Aggregators.PercentileAgg): h = 0.95 (n + 1), 1-based,
    clamped to [1, n], linear between the two order statistics.
    Returns (p95 [g, b], value range [g, b] for the mesh tolerance)."""
    filled = lerp_fill(grid)
    b = grid.shape[1]
    out = np.full((g, b), np.nan)
    spread = np.zeros((g, b))
    order = np.argsort(gids, kind="stable")
    bounds = np.searchsorted(gids[order], np.arange(g + 1))
    for gi in range(g):
        rows = filled[order[bounds[gi]:bounds[gi + 1]]]
        for j in range(b):
            x = np.sort(rows[:, j][~np.isnan(rows[:, j])])
            n = len(x)
            if n == 0:
                continue
            h = min(max(0.95 * (n + 1), 1.0), float(n))
            lo = int(np.floor(h)) - 1
            hi = min(lo + 1, n - 1)
            out[gi, j] = x[lo] + (h - np.floor(h)) * (x[hi] - x[lo])
            spread[gi, j] = x[-1] - x[0]
    return out, spread


def hist_counts(seed: int, n: int) -> np.ndarray:
    return np.random.default_rng([seed, 1 << 20]).integers(
        0, 50, size=(n, HIST_BUCKETS), dtype=np.int64)


def ref_hist_percentiles(counts: np.ndarray, gids: np.ndarray, g: int,
                         qs: list[float]) -> np.ndarray:
    """Bucket-wise SUM merge per group, then the midpoint of the
    bucket whose cumulative count crosses the rank (ref:
    SimpleHistogram.percentile). Returns [len(qs), g]."""
    merged = np.zeros((g, HIST_BUCKETS))
    np.add.at(merged, gids, counts)
    mids = (HIST_BOUNDS[:-1] + HIST_BOUNDS[1:]) / 2.0
    cum = np.cumsum(merged, axis=1)
    out = np.empty((len(qs), g))
    for qi, q in enumerate(qs):
        target = cum[:, -1] * (q / 100.0)
        idx = np.sum(cum < target[:, None], axis=1)
        out[qi] = mids[np.clip(idx, 0, HIST_BUCKETS - 1)]
    return out


def dash_values(seed: int) -> np.ndarray:
    return np.random.default_rng([seed, 2 << 20]).integers(
        0, 10_000, size=(DASH_SERIES, DASH_POINTS)).astype(np.float64)


# ---------------------------------------------------------------------
# talking to a TSD from outside
# ---------------------------------------------------------------------

class Failed(Exception):
    """A check did not hold; the message says which."""


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def http(port: int, path: str, body: bytes | None = None,
         timeout: float = 600.0):
    """(status, parsed JSON or raw bytes)."""
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}{path}", data=body,
        headers={"Content-Type": "application/json"} if body else {})
    try:
        with urllib.request.urlopen(req, timeout=timeout) as resp:
            status, raw = resp.status, resp.read()
    except urllib.error.HTTPError as e:
        status, raw = e.code, e.read()
    try:
        return status, json.loads(raw) if raw else None
    except ValueError:
        return status, raw


def telnet(port: int, lines: list[str]) -> str:
    """Send telnet lines, then ``version`` as a barrier: the answer to
    it arrives after every earlier line on the connection was handled
    (a successful ``put`` itself is silent)."""
    with socket.create_connection(("127.0.0.1", port), timeout=60) as s:
        s.sendall(("".join(ln + "\n" for ln in lines)
                   + "version\n").encode())
        s.settimeout(60)
        got = b""
        while b"opentsdb" not in got.lower():
            chunk = s.recv(65536)
            if not chunk:
                break
            got += chunk
        s.sendall(b"exit\n")
    return got.decode(errors="replace")


class Smoke:
    def __init__(self, args):
        self.args = args
        self.seed = args.seed
        self.n = args.series
        self.procs: list[subprocess.Popen] = []
        self.failures: list[str] = []
        self.report: dict = {"cuts": [], "queries": [], "phases": {}}
        self.device: dict | None = None
        self.work = args.workdir or tempfile.mkdtemp(prefix="chip_smoke-")
        os.makedirs(self.work, exist_ok=True)
        self.data_dir = os.path.join(self.work, "data")
        self.env = dict(os.environ, PYTHONPATH=ROOT + os.pathsep
                        + os.environ.get("PYTHONPATH", ""),
                        PYTHONUNBUFFERED="1")
        # the reference's inputs, filled by load()
        self.avg = self.max = None
        self.warmup_budget = WARMUP_BUDGET_S \
            if self.n >= FULL_WIDTH else 1
        self.cache_dir = os.path.abspath(
            os.environ.get("JAX_COMPILATION_CACHE_DIR")
            or os.path.join(ROOT, ".jax_cache"))

    # -- bookkeeping ---------------------------------------------------

    def cut(self, msg: str) -> None:
        self.report["cuts"].append(msg)
        say(f"CUT: {msg}")

    def fail(self, msg: str) -> None:
        self.failures.append(msg)
        say(f"FAIL: {msg}")

    def check(self, cond: bool, msg: str) -> None:
        if not cond:
            self.fail(msg)

    # -- child processes -----------------------------------------------

    def cli(self, *argv: str, **popen_kw) -> subprocess.Popen:
        p = subprocess.Popen(
            [sys.executable, "-m", "opentsdb_tpu.tools.cli", *argv],
            cwd=ROOT, env=self.env, **popen_kw)
        self.procs.append(p)
        return p

    def run_cli(self, name: str, *argv: str, timeout: float = 900):
        """A one-shot tool: run to its end, keep its output."""
        log = os.path.join(self.work, f"{name}.log")
        t0 = time.monotonic()
        with open(log, "wb") as fh:
            p = self.cli(*argv, stdout=fh, stderr=subprocess.STDOUT)
            rc = p.wait(timeout=timeout)
        with open(log, "r", errors="replace") as fh:
            out = fh.read()
        say(f"{name}: exit {rc} in {time.monotonic() - t0:.1f}s")
        if rc != 0:
            say(out[-3000:])
            raise Failed(f"child {name!r} exited {rc}")
        return out

    def cache_entries(self) -> int:
        """Entries in the compile cache: each compilation that missed
        writes one, so the growth over a stretch of work counts its
        compilations."""
        return len(os.listdir(self.cache_dir)) \
            if os.path.isdir(self.cache_dir) else 0

    def stop_all(self) -> None:
        for p in self.procs:
            if p.poll() is None:
                p.kill()
        for p in self.procs:
            try:
                p.wait(timeout=30)
            except subprocess.TimeoutExpired:
                pass

    # -- phase: load ---------------------------------------------------

    def load(self, data_dir: str, n: int, name: str = "load"):
        """Seeded series -> text -> ``tsdb import``. Generator workers
        run beside the importer; the text never touches the disk (the
        importer reads /dev/stdin). Returns (avg grid, max grid,
        points)."""
        t0 = time.monotonic()
        n_chunks = -(-n // CHUNK)
        avg = np.empty((n, BUCKETS))
        mx = np.empty((n, BUCKETS))
        points = 0
        log = os.path.join(self.work, f"{name}.log")
        workers = max(1, min(8, (os.cpu_count() or 2) - 2))
        with open(log, "wb") as fh, ProcessPoolExecutor(
                workers,
                mp_context=multiprocessing.get_context("spawn")) as ex:
            # --no-wal: the reference's batch import writes no WAL
            # either (setDurable(false)); the snapshot flush at the
            # end of the import is what makes the load durable
            imp = self.cli("import", "--no-wal", "/dev/stdin",
                           "--datadir", data_dir, "--auto-metric",
                           stdin=subprocess.PIPE, stdout=fh,
                           stderr=subprocess.STDOUT)
            pending: collections.deque = collections.deque()
            nxt = 0
            try:
                while nxt < n_chunks or pending:
                    while nxt < n_chunks and len(pending) < 2 * workers:
                        pending.append((nxt, ex.submit(
                            chunk_lines, self.seed, nxt, n)))
                        nxt += 1
                    c, fut = pending.popleft()
                    text, a, m, pts = fut.result()
                    avg[c * CHUNK:c * CHUNK + len(a)] = a
                    mx[c * CHUNK:c * CHUNK + len(m)] = m
                    points += pts
                    imp.stdin.write(text)
                imp.stdin.close()
            except BrokenPipeError:
                pass
            fed = time.monotonic() - t0
            rc = imp.wait(timeout=1200)
        with open(log, "r", errors="replace") as fh:
            out = fh.read()
        if rc != 0:
            say(out[-3000:])
            raise Failed(f"tsdb import exited {rc}")
        total = time.monotonic() - t0
        say(f"{name}: {n} series, {points} points through tsdb import "
            f"in {total:.1f}s (lines fed in {fed:.1f}s, the rest is "
            f"the snapshot flush): {out.strip().splitlines()[-1]}")
        self.check(f"imported {points} data points" in out,
                   f"{name}: tsdb import did not report {points} points")
        self.report["phases"][name] = {
            "series": n, "points": points, "seconds": round(total, 1)}
        return avg, mx, points


class Server:
    """One ``tsdb tsd`` child."""

    def __init__(self, smoke: Smoke, name: str, data_dir: str | None,
                 flags):
        self.smoke = smoke
        self.name = name
        self.port = free_port()
        self.log = os.path.join(smoke.work, f"server-{name}.log")
        argv = ["tsd", "--port", str(self.port),
                "--tsd.network.bind=127.0.0.1",
                # a device failure must be a 5xx, not a quiet CPU answer
                "--tsd.query.degraded.host_fallback=false",
                f"--tsd.tpu.warmup.budget_s={smoke.warmup_budget}"]
        if data_dir:
            # new metrics arrive over HTTP and telnet, as from a fleet
            argv += ["--datadir", data_dir, "--auto-metric"]
        self.argv = argv + list(flags)
        self.proc: subprocess.Popen | None = None
        self.t_start = 0.0

    def start(self) -> None:
        self.t_start = time.monotonic()
        self._fh = open(self.log, "wb")
        self.proc = self.smoke.cli(*self.argv, stdout=self._fh,
                                   stderr=subprocess.STDOUT)

    def tail(self, n: int = 3000) -> str:
        with open(self.log, "r", errors="replace") as fh:
            return fh.read()[-n:]

    def wait_listening(self, timeout: float = 600) -> float:
        """Until the socket answers (snapshot load and WAL replay
        happen before it is bound). Returns seconds since start."""
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            if self.proc.poll() is not None:
                say(self.tail())
                raise Failed(f"server {self.name} exited "
                             f"{self.proc.returncode} before listening")
            try:
                socket.create_connection(
                    ("127.0.0.1", self.port), timeout=1).close()
                return time.monotonic() - self.t_start
            except OSError:
                time.sleep(0.25)
        raise Failed(f"server {self.name} not listening after "
                     f"{timeout:.0f}s")

    def health(self) -> dict:
        status, doc = http(self.port, "/api/health")
        if status != 200 or not isinstance(doc, dict):
            raise Failed(f"{self.name}: /api/health -> {status}")
        return doc

    def wait_warm(self, timeout: float = 900) -> dict:
        """Until the server reports warm-up finished."""
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            if self.proc.poll() is not None:
                say(self.tail())
                raise Failed(f"server {self.name} died during warm-up")
            dev = self.health()["device"]
            if dev["warmup"]["state"] != "running":
                return dev
            time.sleep(1.0)
        raise Failed(f"{self.name}: warm-up not finished after "
                     f"{timeout:.0f}s")

    def stats(self) -> list:
        status, rows = http(self.port, "/api/stats")
        if status != 200:
            raise Failed(f"{self.name}: /api/stats -> {status}")
        return rows

    def device_lookups(self) -> float:
        """Device-grid-cache lookups so far: only a device-placed tail
        consults that cache (host-placed ones skip it), so a query
        during which this grows was placed on the device."""
        return sum(r["value"] for r in self.stats() if r["metric"] in (
            "tsd.query.devicecache.hits",
            "tsd.query.devicecache.misses"))

    def tails(self) -> collections.Counter:
        """Programs dispatched so far, by (path, placement): the
        ``tsd.query.tail`` counter."""
        return collections.Counter({
            (r["tags"]["path"], r["tags"]["placement"]): r["value"]
            for r in self.stats() if r["metric"] == "tsd.query.tail"})

    def query(self, sub: dict, start: int = T0, end: int = END):
        body = json.dumps({"start": start * 1000, "end": end * 1000,
                           "queries": [sub]}).encode()
        t0 = time.monotonic()
        status, doc = http(self.port, "/api/query", body)
        return status, doc, time.monotonic() - t0

    def stop(self, how: str) -> None:
        """``term``: SIGTERM, a clean shutdown (snapshot flush), exit
        code 0 expected. ``kill``: SIGKILL, a crash — what the next
        server on the data dir recovers from by WAL replay."""
        t0 = time.monotonic()
        if how == "term":
            self.proc.send_signal(signal.SIGTERM)
            try:
                rc = self.proc.wait(timeout=600)
            except subprocess.TimeoutExpired:
                self.proc.kill()
                raise Failed(f"{self.name}: no exit 600s after SIGTERM")
            if rc != 0:
                say(self.tail())
                raise Failed(f"{self.name}: clean shutdown exited {rc}")
        else:
            self.proc.kill()
            self.proc.wait(timeout=60)
        self._fh.close()
        say(f"server {self.name}: stopped ({how}) in "
            f"{time.monotonic() - t0:.1f}s")


# ---------------------------------------------------------------------
# checking answers
# ---------------------------------------------------------------------

def rows_to_grid(rows, tagk: str, names: list[str], n_buckets: int,
                 step: int = INTERVAL_S):
    """/api/query rows -> [groups, buckets] (NaN where no dp)."""
    out = np.full((len(names), n_buckets), np.nan)
    pos = {name: i for i, name in enumerate(names)}
    for row in rows:
        gi = pos.get(row["tags"].get(tagk)) if tagk else 0
        if gi is None:
            raise Failed(f"unexpected group {row['tags']}")
        for ts, v in row["dps"].items():
            j, rem = divmod(int(ts) - T0, step)
            if rem or not 0 <= j < n_buckets:
                raise Failed(f"datapoint at unexpected time {ts}")
            out[gi, j] = np.nan if v is None else float(v)
    return out


def compare(got: np.ndarray, want: np.ndarray, tol: np.ndarray,
            emitted: np.ndarray) -> str:
    """'' when every cell holds, else what is wrong with the worst."""
    want = np.where(emitted, want, np.nan)
    if (np.isnan(got) != np.isnan(want)).any():
        g, j = np.argwhere(np.isnan(got) != np.isnan(want))[0]
        return (f"cell ({g}, {j}) emitted={not np.isnan(got[g, j])}, "
                f"reference emitted={not np.isnan(want[g, j])}")
    ok = ~np.isnan(want)
    if not ok.any():
        return "nothing to compare"
    excess = np.where(ok, np.abs(got - want) - tol, -np.inf)
    if excess.max() > 0:
        g, j = np.unravel_index(np.argmax(excess), excess.shape)
        return (f"cell ({g}, {j}): got {got[g, j]!r} want "
                f"{want[g, j]!r} tol {tol[g, j]:.3g}")
    return ""


def load_oracle():
    spec = importlib.util.spec_from_file_location(
        "smoke_oracle", os.path.join(ROOT, "tests", "oracle.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# ---------------------------------------------------------------------
# the phases
# ---------------------------------------------------------------------

def dc_names():
    return [f"d{i:02d}" for i in range(DCS)]


def group_by(tagk: str, **literal):
    f = [{"type": "wildcard", "tagk": tagk, "filter": "*",
          "groupBy": True}]
    for k, v in literal.items():
        f.append({"type": "literal_or", "tagk": k, "filter": v,
                  "groupBy": False})
    return f


class Phases:
    def __init__(self, smoke: Smoke):
        self.s = smoke
        self.idx = np.arange(smoke.n)
        self.dc = (self.idx % DCS).astype(np.int64)
        self.rack = (self.idx % RACKS).astype(np.int64)
        self.gappy = is_gappy(self.idx)
        self.q1_answer = None   # phase A's, for phase C to compare
        self.dash_want = None
        self.p95_ref = None

    # one query, checked and recorded
    def run(self, srv: Server, label: str, sub: dict, names, tagk,
            want, tol, emitted, n_buckets=BUCKETS, step=INTERVAL_S,
            placement_from="cache", repeat=False, end=END,
            expect: str | None = None, to_grid=None
            ) -> np.ndarray | None:
        """``to_grid(rows)`` turns the response into the array to
        compare; by default one row per group, one column per bucket."""
        s = self.s
        before = srv.device_lookups() if placement_from == "cache" \
            else None
        status, rows, secs = srv.query(sub, T0, end)
        rec = {"phase": srv.name, "query": label, "status": status,
               "seconds_first": round(secs, 2)}
        s.report["queries"].append(rec)
        if status != 200:
            rec["match"] = False
            s.fail(f"{srv.name}/{label}: HTTP {status}: "
                   f"{str(rows)[:400]}")
            return None
        if placement_from == "cache":
            rec["placement"] = "device" \
                if srv.device_lookups() > before else "host"
        else:
            rec["placement"] = placement_from
        if expect is not None:
            s.check(rec["placement"] == expect,
                    f"{srv.name}/{label}: placed on the "
                    f"{rec['placement']}, expected the {expect} by "
                    f"today's thresholds")
        got = to_grid(rows) if to_grid else rows_to_grid(
            rows, tagk, names, n_buckets, step)
        why = compare(got, want, tol, emitted)
        rec["match"] = not why
        if repeat:
            # one second less of the last bucket's tail: the same
            # buckets, data and compiled program, but a new key for
            # the result cache and the resident grids, so this times
            # scan + upload + execution without the compile
            _, _, secs2 = srv.query(sub, T0, end - 1)
            rec["seconds_repeat"] = round(secs2, 2)
        say(f"{srv.name}/{label}: {status} placement="
            f"{rec['placement']} groups={len(rows)} "
            f"first={secs:.2f}s"
            + (f" repeat={rec['seconds_repeat']:.2f}s" if repeat else "")
            + f" match={'yes' if not why else 'NO: ' + why}")
        if why:
            s.fail(f"{srv.name}/{label}: {why}")
        return got

    # -- references ----------------------------------------------------

    def want_rate(self, rows_mask, gids, g, avg=None,
                  counter_max=None):
        avg = self.s.avg if avg is None else avg
        rate, ties = ref_rate(avg[rows_mask], counter_max)
        sums, tol, emitted = ref_group_sum(
            rate, gids[rows_mask], g, 2 * VALUE_ATOL / INTERVAL_S)
        if counter_max is not None:
            n_ties = np.zeros((g, rate.shape[1]))
            for j in range(rate.shape[1]):
                n_ties[:, j] = np.bincount(
                    gids[rows_mask], weights=ties[:, j], minlength=g)
            tol = tol + n_ties * (counter_max / INTERVAL_S)
        return sums, tol, emitted

    def oracle_check(self, label: str, rows_mask, gids, got,
                     groups=(0, 57)):
        """tests/oracle.py (per-datapoint, shares nothing with the
        kernels or with the NumPy reference above) on a sample of
        groups of the gappy query: the lerp and rate-over-holes
        semantics have no closed form."""
        oracle = load_oracle()
        s = self.s
        ts_ms = (T0 + INTERVAL_S * np.arange(BUCKETS)) * 1000
        members_all = np.nonzero(rows_mask)[0]
        for g in groups:
            members = members_all[gids[members_all] == g]
            series = []
            for i in members:
                ok = ~np.isnan(s.avg[i])
                series.append((ts_ms[ok], s.avg[i][ok]))
            # the bucket averages ARE the downsampled points: feed
            # them at the bucket starts with a 5m 'avg' of one point
            out = oracle.run_oracle(
                series, "sum", INTERVAL_S * 1000, "avg", T0 * 1000,
                END * 1000, rate=True)
            want = np.full(BUCKETS, np.nan)
            for t, v in out.items():
                want[(t // 1000 - T0) // INTERVAL_S] = v
            l1 = np.nansum(np.abs(ref_rate(s.avg[members])[0]), axis=0)
            bad = np.abs(got[g] - want) > SUM_RTOL * l1 + len(
                members) * 2 * VALUE_ATOL / INTERVAL_S
            bad |= np.isnan(got[g]) != np.isnan(want)
            s.check(not bad.any(),
                    f"{label}: group {g} disagrees with tests/oracle.py"
                    f" at buckets {np.nonzero(bad)[0].tolist()}")
        say(f"{label}: tests/oracle.py agrees on groups {list(groups)}")

    # -- phase A -------------------------------------------------------

    def ingest_http(self, srv: Server):
        """Histograms (config 4) over /api/histogram, the dashboard
        metric (config 1) over /api/put."""
        s = self.s
        t0 = time.monotonic()
        nh = s.args.hist_series
        counts = hist_counts(s.seed, nh)
        head = b"\x01" + struct.pack(">H", HIST_BUCKETS + 1) \
            + HIST_BOUNDS.astype(">f8").tobytes()
        rows = counts.astype(">u8")
        tail = struct.pack(">QQ", 0, 0)
        for lo in range(0, nh, 5000):
            body = "[" + ",".join(
                '{"metric":"smoke.lat","timestamp":%d,"value":"%s",'
                '"tags":{"host":"h%07d","dc":"d%02d"}}' % (
                    T0, base64.b64encode(
                        head + rows[i].tobytes() + tail).decode(),
                    i, i % DCS)
                for i in range(lo, min(lo + 5000, nh))) + "]"
            status, doc = http(srv.port, "/api/histogram?summary",
                               body.encode())
            if status != 200 or doc.get("failed"):
                raise Failed(f"/api/histogram -> {status} {doc}")
        t1 = time.monotonic()
        dash = dash_values(s.seed)
        for lo in range(0, DASH_SERIES, 100):
            body = "[" + ",".join(
                '{"metric":"smoke.dash","timestamp":%d,"value":%d,'
                '"tags":{"host":"w%04d"}}' % (T0 + 10 * j, dash[i, j], i)
                for i in range(lo, lo + 100)
                for j in range(DASH_POINTS)) + "]"
            status, doc = http(srv.port, "/api/put?summary",
                               body.encode())
            if status != 200 or doc.get("failed"):
                raise Failed(f"/api/put -> {status} {doc}")
        say(f"{srv.name}: {nh} histogram points over /api/histogram "
            f"in {t1 - t0:.1f}s, {DASH_SERIES * DASH_POINTS} points "
            f"over /api/put in {time.monotonic() - t1:.1f}s")
        return counts, dash

    def server_facts(self, srv: Server, dev: dict, listening_s: float,
                     entries_before: int) -> dict:
        facts = {
            "platform": dev["platform"], "device_kind":
            dev["device_kind"], "count": dev["count"],
            "x64": dev["x64"], "storage_backend":
            dev["storage_backend"], "compile_cache_dir":
            dev["compile_cache_dir"], "mesh": dev["mesh"],
            "load_seconds": round(listening_s, 1),
            "warmup": dev["warmup"],
            "cache_entries_new_in_warmup":
            self.s.cache_entries() - entries_before}
        self.s.report["phases"][srv.name] = facts
        w = dev["warmup"]
        say(f"server {srv.name}: {dev['count']} x {dev['platform']} "
            f"({dev['device_kind']}), x64={dev['x64']}, backend="
            f"{dev['storage_backend']}, listening after "
            f"{listening_s:.1f}s; warm-up {w['state']}: "
            f"{w['compiled']} compiled, {w['failed']} failed in "
            f"{w['seconds']}s; compile cache {dev['compile_cache_dir']}"
            f" (+{facts['cache_entries_new_in_warmup']} entries)")
        s = self.s
        s.check(dev["compile_cache_dir"] == s.cache_dir,
                f"{srv.name}: the server caches compiled programs in "
                f"{dev['compile_cache_dir']!r}, not {s.cache_dir!r}")
        s.check(dev["storage_backend"] == "native",
                f"{srv.name}: storage backend is "
                f"{dev['storage_backend']!r}, not 'native'")
        s.check(w["failed"] == 0 and w["state"] != "failed",
                f"{srv.name}: warm-up reports a failed compile: "
                f"{w['last_error']}")
        return facts

    def end_of_server(self, srv: Server) -> dict:
        """Breaker counters at the end of a phase."""
        doc = srv.health()
        br = doc["breakers"].get("device.pipeline", {})
        dev = doc["device"]
        rec = self.s.report["phases"][srv.name]
        rec.update(breaker_failures=br.get("total_failures"),
                   breaker_fallbacks=br.get("fallbacks"),
                   resident=dev["resident"],
                   cache_entries_end=self.s.cache_entries())
        say(f"server {srv.name}: breaker failures="
            f"{br.get('total_failures')} fallbacks="
            f"{br.get('fallbacks')}")
        self.s.check(not br.get("total_failures")
                     and not br.get("fallbacks"),
                     f"{srv.name}: device.pipeline breaker counted "
                     f"failures={br.get('total_failures')} fallbacks="
                     f"{br.get('fallbacks')}")
        return dev

    def boot(self, name: str, data_dir, *flags,
             while_warming=None) -> tuple[Server, dict]:
        """Start a server and wait until it reports warm-up finished;
        ``while_warming(srv)`` runs once it listens."""
        s = self.s
        entries = s.cache_entries()
        srv = Server(s, name, data_dir, flags)
        srv.start()
        listening = srv.wait_listening()
        extra = while_warming(srv) if while_warming else None
        dev = srv.wait_warm()
        self.server_facts(srv, dev, listening, entries)
        if s.device is None and dev["platform"]:
            s.device = {"platform": dev["platform"],
                        "kind": dev["device_kind"],
                        "count": dev["count"]}
        return srv, dev, extra

    def phase_a(self):
        s = self.s
        # histograms and the dashboard metric go in while warm-up
        # compiles (XLA releases the GIL)
        srv, dev, (counts, dash) = self.boot(
            "A", s.data_dir, "--tsd.rollups.enable=true",
            while_warming=self.ingest_http)
        everyone = np.ones(s.n, dtype=bool)
        names = dc_names()
        # 1,048,576 x 12 padded cells are past the 1 << 16 host budget
        on_device = "device" if s.n >= FULL_WIDTH else None

        # 1. the north-star shape: sum:5m-avg:rate by dc, all series
        q1 = {"metric": "smoke.cpu", "aggregator": "sum",
              "downsample": "5m-avg", "rate": True,
              "filters": group_by("dc")}
        want1, tol1, emit1 = self.want_rate(everyone, self.dc, DCS)
        got1 = self.run(srv, f"sum:5m-avg:rate{{dc=*}} {s.n} series",
                        q1, names, "dc", want1, tol1, emit1,
                        repeat=True, expect=on_device)
        if got1 is not None:
            self.oracle_check("sum:5m-avg:rate{dc=*}", everyone,
                              self.dc, got1)

        # 2. the same over the gappy tenth alone
        q2 = dict(q1, filters=group_by("dc", fleet="b"))
        want2, tol2, emit2 = self.want_rate(self.gappy, self.dc, DCS)
        self.run(
            srv, "sum:5m-avg:rate{dc=*,fleet=b} gappy tenth", q2,
            names, "dc", want2, tol2, emit2)

        # 3. a rank-class aggregator over all series
        q3 = {"metric": "smoke.cpu", "aggregator": "p95",
              "downsample": "5m-avg", "filters": group_by("dc")}
        want3, spread3 = ref_group_p95(s.avg, self.dc, DCS)
        self.p95_ref = (want3, spread3)
        self.run(
            srv, f"p95:5m-avg{{dc=*}} {s.n} series", q3, names, "dc", want3,
            np.full_like(want3, RANK_ATOL), ~np.isnan(want3),
            repeat=True, expect=on_device)

        # 4. histogram percentiles (ops/histogram_kernels.py)
        nh = s.args.hist_series
        hq = {"metric": "smoke.lat", "aggregator": "sum",
              "percentiles": [99.0, 99.9], "filters": group_by("dc")}
        hwant = ref_hist_percentiles(
            counts, np.arange(nh) % DCS, DCS, [99.0, 99.9]).T

        def hist_grid(rows):
            """[dc, (p99, p99.9)] from the `_pct_<q>` rows."""
            col = {"smoke.lat_pct_99": 0, "smoke.lat_pct_99.9": 1}
            got = np.full((DCS, 2), np.nan)
            for row in rows:
                (v,) = row["dps"].values()
                got[names.index(row["tags"]["dc"]),
                    col[row["metric"]]] = v
            return got

        self.run(srv, f"percentiles[99,99.9]{{dc=*}} {nh} histogram "
                 "series", hq, names, "dc", hwant, HIST_RTOL * hwant,
                 np.ones_like(hwant, bool), end=T0 + 60,
                 to_grid=hist_grid)

        # 5. the 1k-series dashboard: host-placed by today's
        # thresholds, reported as such and not counted as device work
        dq = {"metric": "smoke.dash", "aggregator": "avg",
              "downsample": "1m-avg"}
        dgrid = dash.reshape(DASH_SERIES, DASH_POINTS // 6, 6).mean(2)
        dwant = dgrid.mean(axis=0)[None, :]
        self.dash_want = dwant
        self.run(
            srv, "avg:1m-avg dashboard, 1k series", dq, [""], "",
            dwant, np.abs(dwant) * 1e-5, np.ones_like(dwant, bool),
            n_buckets=60, step=60, expect="host")

        # 6. an acknowledged telnet put must change the first answer
        new_cents = 123456
        telnet(srv.port, [
            f"put smoke.cpu {T0 + 30 * CADENCE_S} {new_cents / 100} "
            f"host=h0000000 dc=d00 rack=r0000 fleet=a"])
        cents0 = chunk_values(s.seed, 0, s.n)[1][0].copy()
        cents0[30] = new_cents
        s.avg[0] = (cents0 / 100.0).reshape(BUCKETS, K).mean(axis=1)
        s.max[0] = (cents0 / 100.0).reshape(BUCKETS, K).max(axis=1)
        want1b, tol1b, emit1b = self.want_rate(everyone, self.dc, DCS)
        got1b = self.run(srv, "the same after a telnet put", q1, names,
                         "dc", want1b, tol1b, emit1b)
        if got1 is not None and got1b is not None:
            s.check(not np.array_equal(got1[0], got1b[0],
                                       equal_nan=True),
                    "the telnet put did not change the answer")
        self.q1_answer = got1b
        self.end_of_server(srv)
        # a crash, not a shutdown: the next server on this data dir
        # must recover every acknowledged write from the WAL
        srv.stop("kill")

    # -- phase B -------------------------------------------------------

    def phase_b(self):
        s = self.s
        flags = ["--tsd.rollups.enable=true",
                 "--tsd.query.device_cache_mb=0",
                 "--tsd.query.grid_reduce=false"]
        if s.n < FULL_WIDTH:
            # a plumbing run: too few series for device placement
            flags.append("--tsd.query.host_tail_max_cells_linear=-1")
            say("B: small run, host-tail placement switched off so "
                "that the point path's program is placed on the device")
        srv, dev, _ = self.boot("B", s.data_dir, *flags)
        a = s.report["phases"].get("A")
        if a is not None:
            new_a = a["cache_entries_new_in_warmup"]
            new_b = s.report["phases"]["B"][
                "cache_entries_new_in_warmup"]
            ran_a, ran_b = a["warmup"]["compiled"], \
                s.report["phases"]["B"]["warmup"]["compiled"]
            say(f"compile cache: the first start (A) ran {ran_a} "
                f"warm-up programs and compiled {new_a} (new cache "
                f"entries); the second (B) ran {ran_b} and compiled "
                f"{new_b}: {ran_b - new_b} came from the cache")
            if s.n >= FULL_WIDTH:
                # the same programs in the same order: B may only
                # compile programs A never reached
                s.check(new_b <= max(0, ran_b - ran_a),
                        f"second start compiled again what the first "
                        f"had cached ({new_b} new entries for "
                        f"{ran_b} programs, first start ran {ran_a})")
            else:
                say("(not asserted at this size: the small-run "
                    "placement flag gives B other programs)")
        full = ~self.gappy
        names = dc_names()

        def point_query(label, sub, names, tagk, want, tol, emitted):
            # regular cadence, no storage-side grid: the point path's
            # dense program, placed on the device
            before = srv.tails()
            got = self.run(srv, label, sub, names, tagk, want, tol,
                           emitted, placement_from="device")
            ran = srv.tails() - before
            s.report["queries"][-1]["tail"] = {
                f"{path}/{placement}": n
                for (path, placement), n in ran.items()}
            s.check(ran == {("dense", "device"): 1},
                    f"B/{label}: expected one dense program on the "
                    f"device, tsd.query.tail counted {dict(ran)}")
            return got

        # the WAL replay: the point acknowledged over telnet in phase
        # A is in the reference (s.avg[0]) and must be in the answer
        b1 = {"metric": "smoke.cpu", "aggregator": "sum",
              "downsample": "5m-avg", "rate": True,
              "filters": group_by("dc", fleet="a")}
        want, tol, emit = self.want_rate(full, self.dc, DCS)
        point_query("point path: sum:5m-avg:rate{dc=*,fleet=a}", b1,
                    names, "dc", want, tol, emit)
        rack_names = [f"r{i:04d}" for i in range(RACKS)]
        b2 = dict(b1, filters=group_by("rack", fleet="a"))
        want, tol, emit = self.want_rate(full, self.rack, RACKS)
        point_query("point path, 2000 groups: "
                    "sum:5m-avg:rate{rack=*,fleet=a}",
                    b2, rack_names, "rack", want, tol, emit)
        b3 = {"metric": "smoke.cpu", "aggregator": "sum",
              "downsample": "5m-max",
              "filters": group_by("dc", fleet="a")}
        want, tol, emit = ref_group_sum(s.max[full], self.dc[full],
                                        DCS, VALUE_ATOL)
        point_query("point path, max downsample: "
                    "sum:5m-max{dc=*,fleet=a}",
                    b3, names, "dc", want, tol, emit)
        b4 = dict(b1, rateOptions={"counter": True,
                                   "counterMax": COUNTER_MAX})
        want, tol, emit = self.want_rate(full, self.dc, DCS,
                                         counter_max=COUNTER_MAX)
        point_query("point path, counter rate: sum:5m-avg:"
                    "rate{counter,10000}{dc=*,fleet=a}", b4, names,
                    "dc", want, tol, emit)
        # what the WAL brought back besides: the dashboard metric
        if self.dash_want is not None:
            self.run(srv, "avg:1m-avg dashboard, back from the WAL",
                     {"metric": "smoke.dash", "aggregator": "avg",
                      "downsample": "1m-avg"}, [""], "",
                     self.dash_want, np.abs(self.dash_want) * 1e-5,
                     np.ones_like(self.dash_want, bool), n_buckets=60,
                     step=60, placement_from="not asked")
        self.end_of_server(srv)
        srv.stop("kill")

    # -- phase C -------------------------------------------------------

    def phase_c(self):
        s = self.s
        srv, dev, _ = self.boot("C", s.data_dir,
                                "--tsd.rollups.enable=true",
                                "--tsd.query.mesh=series:4")
        if dev["count"] < 4:
            say(f"phase C not run: {dev['count']} device")
            srv.stop("kill")
            return
        s.check(dev["mesh"]["shape"] == {"series": 4, "time": 1}
                and dev["mesh"]["devices"] == 4,
                f"C: server built mesh {dev['mesh']}, not series:4")
        names = dc_names()
        everyone = np.ones(s.n, dtype=bool)
        q1 = {"metric": "smoke.cpu", "aggregator": "sum",
              "downsample": "5m-avg", "rate": True,
              "filters": group_by("dc")}
        want, tol, emit = self.want_rate(everyone, self.dc, DCS)
        got = self.run(srv, f"mesh sum:5m-avg:rate{{dc=*}} {s.n} series", q1,
                       names, "dc", want, tol, emit,
                       placement_from="mesh", repeat=True)
        if got is not None and self.q1_answer is not None:
            s.check(not compare(got, self.q1_answer, 2 * tol, emit),
                    "C: mesh answer differs from phase A's beyond "
                    "the f32 tolerance")
        q2 = dict(q1, filters=group_by("dc", fleet="b"))
        want, tol, emit = self.want_rate(self.gappy, self.dc, DCS)
        self.run(srv, "mesh sum:5m-avg:rate{dc=*,fleet=b} gappy", q2,
                 names, "dc", want, tol, emit, placement_from="mesh")
        q3 = {"metric": "smoke.cpu", "aggregator": "p95",
              "downsample": "5m-avg", "filters": group_by("dc")}
        want3, spread3 = self.p95_ref \
            or ref_group_p95(s.avg, self.dc, DCS)
        self.run(srv, f"mesh p95:5m-avg{{dc=*}} {s.n} series", q3, names,
                 "dc", want3,
                 2 * spread3 / MESH_RANK_BINS + 1e-2,
                 ~np.isnan(want3), placement_from="mesh")
        end = self.end_of_server(srv)
        by_dev = end["resident"]["bytes_by_device"]
        say(f"C: mesh {end['mesh']}, resident grids by device: "
            f"{by_dev}")
        s.check(len(by_dev) == 4 and min(by_dev.values()) > 0
                and max(by_dev.values()) < 2 * min(by_dev.values()),
                f"C: resident grids are not spread over four "
                f"devices: {by_dev}")
        srv.stop("kill")

    # -- phase R -------------------------------------------------------

    def phase_r(self):
        """One process for each chip: a router beside a shard."""
        s = self.s
        shard_port = free_port()
        router = Server(s, "R-router", None, [
            "--tsd.cluster.role=router",
            f"--tsd.cluster.peers=s0=127.0.0.1:{shard_port}",
            "--tsd.cluster.spool.dir="
            + os.path.join(s.work, "spool")])
        router.start()
        router.wait_listening()
        rdev = router.health()["device"]
        say(f"router: device section {rdev['platform']!r}, warm-up "
            f"{rdev['warmup']['state']}")
        s.check(rdev["platform"] is None
                and rdev["warmup"]["state"] == "off",
                f"the router initialised a backend or warmed up: "
                f"{rdev['platform']}, {rdev['warmup']}")
        # the shard starts SECOND: had the router taken the chip, the
        # shard could not
        shard = Server(s, "R-shard", os.path.join(s.work, "shard"), [
            "--tsd.cluster.role=shard"])
        shard.port = shard_port
        shard.argv[shard.argv.index("--port") + 1] = str(shard_port)
        shard.start()
        shard.wait_listening()
        sdev = shard.wait_warm()
        say(f"shard beside the router: {sdev['count']} x "
            f"{sdev['platform']} ({sdev['device_kind']})")
        if s.device is not None:
            s.check(sdev["platform"] == s.device["platform"],
                    f"the shard runs on {sdev['platform']!r} beside "
                    f"the router, the default server on "
                    f"{s.device['platform']!r}")
        else:
            s.device = {"platform": sdev["platform"], "kind":
                        sdev["device_kind"], "count": sdev["count"]}
        vals = np.random.default_rng([s.seed, 3 << 20]).integers(
            0, 1000, size=(20, 100))
        body = json.dumps([
            {"metric": "smoke.ring", "timestamp": T0 + 10 * j,
             "value": int(vals[i, j]), "tags": {"host": f"n{i:02d}"}}
            for i in range(20) for j in range(100)]).encode()
        status, doc = http(router.port, "/api/put?summary", body)
        s.check(status == 200 and doc.get("success") == 2000,
                f"router /api/put -> {status} {doc}")
        status, rows, secs = router.query(
            {"metric": "smoke.ring", "aggregator": "sum",
             "downsample": "1m-sum"}, T0, T0 + 999)
        ok = False
        if status == 200 and rows:
            got = rows_to_grid(rows, "", [""], 17, 60)
            pad = np.zeros((20, 102))
            pad[:, :100] = vals
            want = pad.reshape(20, 17, 6).sum(axis=(0, 2))[None, :]
            ok = np.allclose(got, want, rtol=1e-6)
        s.report["queries"].append({
            "phase": "R", "query": "sum:1m-sum through the router",
            "status": status, "match": bool(ok), "placement": "host",
            "seconds_first": round(secs, 2)})
        say(f"R/sum:1m-sum through the router: {status} "
            f"match={'yes' if ok else 'NO'}")
        s.check(ok, f"router query -> {status} {str(rows)[:300]}")
        s.check(router.health()["device"]["platform"] is None,
                "the router initialised a backend while serving")
        router.stop("term")
        shard.stop("term")

    # -- phase Z -------------------------------------------------------

    def phase_z(self):
        """The batch rollup job as a user runs it, then avg from the
        1m tier as sum over count."""
        s = self.s
        n = min(s.args.rollup_series, s.n)
        if n < s.n:
            s.cut(f"rollup phase runs on its own data dir of {n} "
                  f"series, not {s.n}: the 1m tier adds four tier "
                  f"series per raw series, and snapshot load and save "
                  f"cost ~60-100 us per series per process start")
        d = os.path.join(s.work, "rollup-data")
        avg, _mx, points = s.load(d, n, name="load-rollup")
        out = s.run_cli("rollup", "rollup", str(T0), str(END), "1m",
                        "--datadir", d)
        say("tsdb rollup: " + out.strip().splitlines()[-1])
        s.check(f"1m: {4 * points} rollup points written" in out,
                f"tsdb rollup did not write {4 * points} 1m points")
        srv, dev, _ = self.boot("Z", d, "--tsd.rollups.enable=true")
        idx = np.arange(n)
        dc = (idx % DCS).astype(np.int64)
        names = dc_names()
        zq = {"metric": "smoke.cpu", "aggregator": "sum",
              "downsample": "5m-avg", "filters": group_by("dc")}
        want, tol, emit = ref_group_sum(avg, dc, DCS, VALUE_ATOL)
        self.run(srv, f"sum:5m-avg{{dc=*}} from the 1m tier, {n} "
                 "series", zq, names, "dc", want, tol, emit)
        zp = dict(zq, aggregator="p95")
        want, _ = ref_group_p95(avg, dc, DCS)
        self.run(srv, f"p95:5m-avg{{dc=*}} from the 1m tier, {n} "
                 "series", zp, names, "dc", want,
                 np.full_like(want, RANK_ATOL), ~np.isnan(want))
        self.end_of_server(srv)
        srv.stop("term")


# ---------------------------------------------------------------------

def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--series", type=int, default=1_000_000)
    ap.add_argument("--hist-series", type=int, default=200_000)
    ap.add_argument("--rollup-series", type=int, default=100_000)
    ap.add_argument("--phases", default="A,B,C,R,Z")
    ap.add_argument("--workdir", default="")
    ap.add_argument("--keep", action="store_true",
                    help="keep the work directory")
    ap.add_argument("--report", default="",
                    help="also write the full report as JSON here")
    args = ap.parse_args()
    if not os.path.isdir(os.path.join(ROOT, "opentsdb_tpu")):
        print("chip_smoke.py: no opentsdb_tpu package beside this "
              "script; run it from a checkout", file=sys.stderr)
        return 2
    if args.series % 1000:
        print("--series must be a multiple of 1000", file=sys.stderr)
        return 2
    phases = [p.strip().upper() for p in args.phases.split(",")]
    smoke = Smoke(args)
    say(f"seed {args.seed}, {args.series} series x {POINTS} points, "
        f"{args.hist_series} histogram series, work dir {smoke.work}, "
        f"compile cache {smoke.cache_dir} "
        f"({'JAX_COMPILATION_CACHE_DIR' if os.environ.get('JAX_COMPILATION_CACHE_DIR') else 'fixed path in the checkout'})")
    smoke.cut(f"warm-up budget {smoke.warmup_budget} s per server "
              f"(tsd.tpu.warmup.budget_s, default 600), which ends it "
              f"after its first program: at 1M series a cold program "
              f"takes ~45 s on the v5e, the full set is 42, and the "
              f"default budget stops after 16")
    smoke.cut("servers on the 1M-series data dir are stopped by "
              "SIGKILL: a clean shutdown rewrites the whole snapshot "
              "(~100 s), and the kill makes the next server prove "
              "WAL replay; clean shutdowns are checked in phases R "
              "and Z")
    try:
        smoke.avg, smoke.max, _ = smoke.load(smoke.data_dir,
                                             args.series)
        ph = Phases(smoke)
        for name, fn in (("A", ph.phase_a), ("B", ph.phase_b),
                         ("C", ph.phase_c), ("R", ph.phase_r),
                         ("Z", ph.phase_z)):
            if name not in phases:
                say(f"phase {name} not run: not selected")
                continue
            if name == "C" and smoke.device is not None \
                    and smoke.device["count"] < 4:
                # (when no server has run yet, phase C finds out)
                say(f"phase C not run: {smoke.device['count']} device")
                continue
            say(f"--- phase {name} ---")
            t0 = time.monotonic()
            n_fail = len(smoke.failures)
            try:
                fn()
            except Failed as e:
                smoke.fail(f"phase {name}: {e}")
            finally:
                smoke.stop_all()
            say(f"--- phase {name} "
                f"{'passed' if len(smoke.failures) == n_fail else 'FAILED'}"
                f" in {time.monotonic() - t0:.1f}s ---")
    except Failed as e:
        smoke.fail(str(e))
    finally:
        smoke.stop_all()
        if not args.keep and not args.workdir:
            shutil.rmtree(smoke.work, ignore_errors=True)
    smoke.report.update(failures=smoke.failures, device=smoke.device,
                        seconds=round(time.monotonic() - _T_START, 1))
    if args.report:
        os.makedirs(os.path.dirname(os.path.abspath(args.report)),
                    exist_ok=True)
        with open(args.report, "w") as fh:
            json.dump(smoke.report, fh, indent=1, default=str)
    say(f"total {time.monotonic() - _T_START:.1f}s; cuts: "
        f"{len(smoke.report['cuts'])}; failures: {len(smoke.failures)}")
    for f in smoke.failures:
        say(f"  failed: {f}")
    if smoke.failures:
        print(json.dumps({"ok": False, "device": smoke.device,
                          "failed": smoke.failures}))
        return 1
    if (smoke.device or {}).get("platform") != "tpu":
        # the work was done and every answer matched, but nothing ran
        # on an accelerator: no result line
        print(f"chip_smoke.py: no TPU: the server reports platform "
              f"{(smoke.device or {}).get('platform')!r}",
              file=sys.stderr)
        return 3
    print(json.dumps({"ok": True, "device": smoke.device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
