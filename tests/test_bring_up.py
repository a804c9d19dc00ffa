"""What bringing the served path up on the chip changed (PR 21): no
fallback hides the device, the server says what it runs on, a router
stays off JAX's backends, the native library is keyed on what it was
built from, and ``chip_smoke.py`` refuses a run without a TPU."""

from __future__ import annotations

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from opentsdb_tpu import TSDB, Config
from opentsdb_tpu.query.model import TSQuery
from opentsdb_tpu.tsd.http_api import HttpRequest, HttpRpcRouter

BASE = 1356998400
REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _point_tsdb(**extra):
    """A TSDB whose regular-cadence queries take the point path and
    run its program on the device: storage-side grid reduction and
    host-tail placement off (the phase-B settings of chip_smoke.py)."""
    t = TSDB(Config(**{
        "tsd.core.auto_create_metrics": "true",
        "tsd.tpu.warmup": "false",
        "tsd.query.grid_reduce": "false",
        "tsd.query.host_tail_max_cells_linear": "-1",
        "tsd.query.cache.enable": "false", **extra}))
    for i in range(8):
        for j in range(12):
            t.add_point("k.cpu", BASE + 60 * j, float(i * j),
                        {"host": f"h{i}", "dc": f"d{i % 2}"})
    return t


_POINT_QUERY = {
    "start": BASE * 1000, "end": (BASE + 719) * 1000,
    "queries": [{"metric": "k.cpu", "aggregator": "sum",
                 "downsample": "3m-avg", "rate": True,
                 "filters": [{"type": "wildcard", "tagk": "dc",
                              "filter": "*", "groupBy": True}]}]}


def _point_query():
    return TSQuery.from_json(_POINT_QUERY).validate()


def _device_program_fails(monkeypatch):
    """The point path's program raises when it is placed on the
    device; its host-placed twin (the designed degradation) runs."""
    from opentsdb_tpu.query import engine as engine_mod
    real = engine_mod.run_prepared

    def boom(prep, bucket_ts, group_ids, spec, *a, **kw):
        if not spec.host:
            raise RuntimeError("the compiler said no")
        return real(prep, bucket_ts, group_ids, spec, *a, **kw)

    monkeypatch.setattr(engine_mod, "run_prepared", boom)


class TestNoFallbackHidesTheDevice:
    def test_kernel_failure_reaches_the_breaker(self, monkeypatch):
        _device_program_fails(monkeypatch)
        t = _point_tsdb(**{
            "tsd.query.degraded.host_fallback": "false"})
        with pytest.raises(RuntimeError, match="the compiler said no"):
            t.execute_query(_point_query())
        assert t.device_breaker.health_info()["total_failures"] == 1
        assert t.device_breaker.fallbacks == 0

    def test_designed_degradation_still_answers(self, monkeypatch):
        want = _point_tsdb().execute_query(_point_query())
        _device_program_fails(monkeypatch)
        t = _point_tsdb()  # tsd.query.degraded.host_fallback = true
        got = t.execute_query(_point_query())
        assert t.device_breaker.health_info()["total_failures"] == 1
        assert t.device_breaker.fallbacks == 1
        assert len(got) == len(want) == 2
        for g, w in zip(got, want):
            np.testing.assert_allclose([v for _, v in g.dps],
                                       [v for _, v in w.dps])

    def test_device_cache_size_selects_no_executor(self):
        # PR 29: tsd.query.device_cache_mb=0 keeps nothing resident and
        # runs the same program as the default
        seen = []
        for extra in ({}, {"tsd.query.device_cache_mb": "0"}):
            t = _point_tsdb(**{"tsd.trace.sample": "1", **extra})
            assert (t.device_grid_cache is None) == bool(extra)
            router = HttpRpcRouter(t)
            resp = router.handle(HttpRequest(
                method="POST", path="/api/query", params={},
                body=json.dumps(_POINT_QUERY).encode()))
            assert resp.status == 200, resp.body
            tails = [(r["tags"]["path"], r["tags"]["placement"],
                      r["value"])
                     for r in json.loads(router.handle(HttpRequest(
                         method="GET", path="/api/stats", params={},
                         body=b"")).body)
                     if r["metric"] == "tsd.query.tail"]
            seen.append((resp.body, tails))
            t.shutdown()
        assert seen[0] == seen[1]
        assert len(json.loads(seen[0][0])) == 2
        assert seen[0][1] == [("dense", "device", 1)]


class TestServerSaysWhatItRunsOn:
    def test_health_device_section(self, tsdb):
        router = HttpRpcRouter(tsdb)
        resp = router.handle(HttpRequest(
            method="GET", path="/api/health", params={}, body=b""))
        dev = json.loads(resp.body)["device"]
        assert dev["platform"] == "cpu" and dev["count"] == 8
        assert dev["device_kind"] == "cpu"
        assert dev["x64"] is True
        assert dev["storage_backend"] in ("native", "memory")
        assert dev["compile_cache_dir"]
        assert dev["mesh"] == {"requested": "", "shape": None,
                               "devices": 1, "error": ""}
        assert dev["warmup"]["state"] == "off"
        assert "pallas" not in dev      # PR 29: the kernel is gone
        assert dev["resident"]["entries"] == 0

    def test_stats_twin(self, tsdb):
        router = HttpRpcRouter(tsdb)
        rows = json.loads(router.handle(HttpRequest(
            method="GET", path="/api/stats", params={},
            body=b"")).body)
        by_name = {r["metric"]: r for r in rows}
        assert by_name["tsd.device.count"]["value"] == 8
        assert by_name["tsd.device.count"]["tags"]["platform"] == "cpu"
        assert not [n for n in by_name
                    if n.startswith("tsd.device.pallas.")]
        assert "tsd.device.warmup.failed" in by_name

    def test_mesh_really_built_is_reported(self):
        t = TSDB(Config(**{"tsd.query.mesh": "series:4",
                           "tsd.tpu.warmup": "false"}))
        assert t.query_mesh is not None
        mesh = t.device_info()["mesh"]
        assert mesh["shape"] == {"series": 4, "time": 1}
        assert mesh["devices"] == 4

    def test_unbuildable_mesh_is_reported_not_claimed(self):
        t = TSDB(Config(**{"tsd.query.mesh": "series:64",
                           "tsd.tpu.warmup": "false"}))
        assert t.query_mesh is None
        mesh = t.device_info()["mesh"]
        assert mesh["requested"] == "series:64"
        assert mesh["shape"] is None and mesh["devices"] == 1
        assert "64 devices" in mesh["error"]

    def test_failed_warmup_compile_is_counted(self, monkeypatch):
        from opentsdb_tpu.ops import pipeline
        from opentsdb_tpu.tsd import warmup

        def boom(*a, **kw):
            raise RuntimeError("no such program")

        t = TSDB(Config(**{"tsd.core.auto_create_metrics": "true",
                           "tsd.tpu.warmup.percentiles": "false"}))
        t.add_point("w.m", BASE, 1.0, {"host": "a"})
        monkeypatch.setattr(pipeline, "run_pipeline_grid", boom)
        assert warmup.run_warmup(t) == 0
        report = t.device_info()["warmup"]
        assert report["state"] == "done"
        assert report["failed"] > 0 and report["compiled"] == 0
        assert "no such program" in report["last_error"]

    def test_platform_list_without_cpu_fails_at_boot(self):
        import jax
        prev = jax.config.jax_platforms
        # the string only: initialised backends do not follow it
        jax.config.update("jax_platforms", "tpu")
        try:
            with pytest.raises(ValueError, match="JAX_PLATFORMS='tpu'.*"
                               "JAX_PLATFORMS=tpu,cpu"):
                TSDB(Config())
            # a router runs no device program and needs no CPU backend
            TSDB(Config(**{"tsd.cluster.role": "router",
                           "tsd.cluster.peers": "s0=127.0.0.1:1"}))
        finally:
            jax.config.update("jax_platforms", prev)


class TestOneProcessForEachChip:
    def test_router_starts_no_warmup_thread(self):
        import asyncio

        from opentsdb_tpu.tsd.server import TSDServer

        async def boot(role):
            t = TSDB(Config(**{
                "tsd.cluster.role": role,
                "tsd.cluster.peers": "s0=127.0.0.1:1",
                "tsd.network.bind": "127.0.0.1",
                "tsd.network.port": "0"}))
            server = TSDServer(t, host="127.0.0.1", port=0)
            await server.start()
            try:
                return server._warmup_thread, \
                    t.device_info()["warmup"]["state"]
            finally:
                await server.stop()

        thread, state = asyncio.run(boot("router"))
        assert thread is None and state == "off"
        thread, _ = asyncio.run(boot("shard"))
        assert thread is not None

    def test_router_device_section_names_no_platform(self):
        t = TSDB(Config(**{"tsd.cluster.role": "router",
                           "tsd.cluster.peers": "s0=127.0.0.1:1"}))
        dev = t.device_info()
        assert dev["platform"] is None and dev["count"] == 0

    @pytest.mark.slow
    def test_router_process_initialises_no_backend(self):
        """In a process of its own: build a router TSDB, ask for its
        health and stats, and look at JAX's backend table."""
        code = (
            "from opentsdb_tpu import TSDB, Config\n"
            "from opentsdb_tpu.tsd.http_api import HttpRequest, "
            "HttpRpcRouter\n"
            "t = TSDB(Config(**{'tsd.cluster.role': 'router', "
            "'tsd.cluster.peers': 's0=127.0.0.1:1'}))\n"
            "r = HttpRpcRouter(t)\n"
            "for p in ('/api/health', '/api/stats'):\n"
            "    assert r.handle(HttpRequest(method='GET', path=p, "
            "params={}, body=b'')).status == 200\n"
            "from jax._src import xla_bridge\n"
            "print('INITIALISED', xla_bridge.backends_are_initialized())\n")
        out = subprocess.run(
            [sys.executable, "-c", code], cwd=REPO, text=True,
            capture_output=True, timeout=120,
            env=dict(os.environ, JAX_PLATFORMS="cpu"))
        assert "INITIALISED False" in out.stdout, out.stderr[-2000:]


class TestNativeLibraryKeyedOnContent:
    def test_foreign_library_is_never_loaded(self, tmp_path,
                                             monkeypatch):
        """A libtsdbstore carried over from another machine (or made
        from another source or flags) has another key in its name: it
        is left alone and the right one is built."""
        import shutil

        from opentsdb_tpu.native import store_backend as sb
        shutil.copy(sb._SRC, tmp_path / "tsdbstore.cc")
        foreign = tmp_path / "libtsdbstore.so"
        foreign.write_bytes(b"built with -march=native elsewhere")
        stale = tmp_path / "libtsdbstore.0123456789abcdef.so"
        stale.write_bytes(b"another source, another key")
        monkeypatch.setattr(sb, "_LIB_DIR", str(tmp_path))
        monkeypatch.setattr(sb, "_SRC", str(tmp_path / "tsdbstore.cc"))
        key = sb._build_key()
        path = sb.build_library()
        assert os.path.basename(path) == f"libtsdbstore.{key}.so"
        with open(path, "rb") as fh:
            assert fh.read(4) == b"\x7fELF"
        # libraries of other keys are tidied away, never loaded
        assert not foreign.exists() and not stale.exists()
        # the key follows the source and the flags
        with open(tmp_path / "tsdbstore.cc", "a") as fh:
            fh.write("\n// changed\n")
        assert sb._build_key() != key
        monkeypatch.setattr(sb, "_CXXFLAGS", sb._CXXFLAGS + ("-g",))
        assert sb._build_key() != key


def _run_smoke(*argv):
    return subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py"), *argv],
        cwd=REPO, text=True, capture_output=True, timeout=900,
        env=dict(os.environ, JAX_PLATFORMS="cpu", XLA_FLAGS="",
                 JAX_ENABLE_X64="0"))


class TestChipSmoke:
    def test_bare_directory_prints_no_result(self, tmp_path):
        import shutil
        shutil.copy(os.path.join(REPO, "chip_smoke.py"), tmp_path)
        out = subprocess.run(
            [sys.executable, "chip_smoke.py"], cwd=tmp_path, text=True,
            capture_output=True, timeout=60)
        assert out.returncode != 0
        assert '"ok"' not in out.stdout

    @pytest.mark.slow  # ~50 s of child processes: tier-1 has no room
    def test_every_phase_on_cpu_then_no_tpu(self):
        out = _run_smoke("--series", "2000", "--hist-series", "200",
                         "--rollup-series", "2000")
        assert out.returncode == 3, out.stdout[-3000:] + out.stderr[-2000:]
        for phase in "ABRZ":
            assert f"phase {phase} passed" in out.stdout
        assert "phase C not run: 1 device" in out.stdout
        assert "tests/oracle.py agrees" in out.stdout
        assert "no TPU" in out.stderr
        assert '"ok"' not in out.stdout
