"""The system under test as a child process: ``tsdb tsd`` started from
the checkout with the flags and the environment its configuration file
lists, fed its history on standard input, asked over its socket. This
process never imports JAX or ``opentsdb_tpu``: one process holds the
chip at a time.
"""

from __future__ import annotations

import json
import os
import signal
import socket
import subprocess
import sys
import time
import urllib.error
import urllib.request

import numpy as np

import deploy
from deploy import Failed


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def child_env(root: str, listed: dict) -> dict:
    """The child's environment: what the machine needs to find its
    chip and its tools, and nothing that tunes the program except what
    the configuration file lists (``PYTHON*`` and the driver's own
    ``BENCH_RUN`` are dropped)."""
    env = {k: v for k, v in os.environ.items()
           if not k.startswith("PYTHON") and k != "BENCH_RUN"}
    env["PYTHONPATH"] = root
    env.update(listed)
    return env


class Tsd:
    def __init__(self, root: str, work: str, config: dict):
        self.root = root
        self.work = work
        self.config = config
        self.port = free_port()
        self.log = os.path.join(work, "tsd.log")
        self.proc: subprocess.Popen | None = None
        self._fh = None

    # -- life ------------------------------------------------------------

    def start(self, extra_flags: dict | None = None) -> None:
        server = self.config["server"]
        flags = dict(server["flags"])
        flags.update(extra_flags or {})
        argv = [sys.executable, "-m", "opentsdb_tpu.tools.cli", "tsd",
                "--port", str(self.port)]
        if server["wal"]:
            argv += ["--datadir", os.path.join(self.work, "data")]
        argv += [f"--{k}={v}" for k, v in sorted(flags.items())]
        self._fh = open(self.log, "wb")
        self.proc = subprocess.Popen(
            argv, cwd=self.root, env=child_env(self.root, server["env"]),
            stdin=subprocess.PIPE, stdout=self._fh,
            stderr=subprocess.STDOUT)

    def tail(self, n: int = 3000) -> str:
        with open(self.log, "r", errors="replace") as fh:
            return fh.read()[-n:]

    def load(self, data, seed: int) -> tuple[np.ndarray, int]:
        """Seeded series -> ``tsdb import`` text -> the server's
        standard input, by the configuration's generator. Generator
        workers run beside the server; the text never touches the
        disk. Returns the values the reference wants ([series, points],
        NaN where dropped) and the number of points written."""
        generator = deploy.generator_of(self.config)
        try:
            values, points = generator.generate(data, seed,
                                                self.proc.stdin.write)
            self.proc.stdin.close()
        except BrokenPipeError:
            raise Failed("the server closed its standard input "
                         "during the load:\n" + self.tail()) from None
        return values, points

    def wait_listening(self, timeout: float = 900) -> None:
        """Until the socket answers: the loader plugin has read all of
        standard input before the server binds it."""
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            if self.proc.poll() is not None:
                raise Failed(f"the server exited {self.proc.returncode} "
                             f"before listening:\n{self.tail()}")
            try:
                socket.create_connection(
                    ("127.0.0.1", self.port), timeout=1).close()
                return
            except OSError:
                time.sleep(0.1)
        raise Failed(f"the server is not listening after {timeout:.0f}s")

    def loaded_points(self) -> int:
        """What the loader plugin says it imported."""
        with open(self.log, "r", errors="replace") as fh:
            for line in fh:
                if line.startswith("benchmark-loader: imported "):
                    return int(line.split()[2])
        raise Failed("the loader plugin did not report:\n" + self.tail())

    def kill(self) -> None:
        """SIGKILL and wait: nothing of a run is kept, so there is no
        state worth a clean shutdown's flush."""
        if self.proc is not None and self.proc.poll() is None:
            self.proc.send_signal(signal.SIGKILL)
        if self.proc is not None:
            try:
                self.proc.wait(timeout=60)
            except subprocess.TimeoutExpired:
                pass
        if self._fh is not None:
            self._fh.close()
            self._fh = None

    # -- asking ----------------------------------------------------------

    def get(self, path: str, timeout: float = 120.0):
        """(status, parsed JSON or raw bytes)."""
        req = urllib.request.Request(
            f"http://127.0.0.1:{self.port}{path}")
        try:
            with urllib.request.urlopen(req, timeout=timeout) as resp:
                status, raw = resp.status, resp.read()
        except urllib.error.HTTPError as e:
            status, raw = e.code, e.read()
        try:
            return status, json.loads(raw) if raw else None
        except ValueError:
            return status, raw

    def must(self, path: str, timeout: float = 120.0):
        status, doc = self.get(path, timeout)
        if status != 200:
            raise Failed(f"GET {path} -> {status}: {str(doc)[:300]}")
        return doc

    def snapshot(self) -> dict:
        """The program's counters and the plugin's, at one instant."""
        return {"at": time.perf_counter(),
                "stats": self.must("/api/stats/raw"),
                "bench": self.must("/plugin/bench?op=state"),
                "health": self.must("/api/health")}
