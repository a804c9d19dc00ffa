"""Telnet line protocol (ref: ``src/tsd/TelnetRpc.java`` +
RpcManager's telnet command table: put, rollup, histogram, stats,
version, dropcaches, help, exit, diediedie, auth).

Commands return response text (possibly empty — successful ``put`` is
silent, matching PutDataPointRpc.java:129's error-only write-back).
"""

from __future__ import annotations

import base64
from typing import Callable

from opentsdb_tpu.core import tags as tags_mod
from opentsdb_tpu.tsd.http_api import version_info


class TelnetServerShutdown(Exception):
    """Raised by ``diediedie`` to stop the whole TSD."""


class TelnetCloseConnection(Exception):
    """Raised by ``exit`` to close this connection."""


class TelnetRouter:
    def __init__(self, tsdb, server=None):
        self.tsdb = tsdb
        self.server = server
        self.commands: dict[str, Callable[[list[str]], str]] = {}
        mode = tsdb.mode
        if mode in ("rw", "wo"):
            self.commands["put"] = self._cmd_put
            self.commands["rollup"] = self._cmd_rollup
            self.commands["histogram"] = self._cmd_histogram
        self.commands.update({
            "stats": self._cmd_stats,
            "version": self._cmd_version,
            "dropcaches": self._cmd_dropcaches,
            "help": self._cmd_help,
            "exit": self._cmd_exit,
            "diediedie": self._cmd_die,
        })

    def execute(self, line: str, auth=None) -> str:
        words = line.split()
        if not words:
            return ""
        cmd = self.commands.get(words[0])
        if cmd is None:
            return f"error: unknown command: {words[0]}"
        if auth is not None and words[0] in ("put", "rollup",
                                             "histogram"):
            # telnet writes are gated per role
            # (ref: Permissions.TELNET_PUT, Permissions.java:26)
            from opentsdb_tpu.auth.simple import Permissions
            if not auth.has_permission(Permissions.TELNET_PUT):
                return (f"{words[0]}: permission denied "
                        "(TELNET_PUT not granted)")
        return cmd(words)

    def execute_lines(self, lines: list[str], auth=None
                      ) -> tuple[list[str], Exception | None]:
        """Process a burst of complete telnet lines: consecutive
        ``put`` commands decode as ONE columnar batch (one WAL write,
        one group-committed fsync — see :meth:`put_lines`), everything
        else executes in input order. Returns ``(responses,
        deferred_exc)`` where ``deferred_exc`` is a close/shutdown
        raised by a line in the burst — the caller must write the
        responses for the EARLIER lines before honoring it."""
        responses: list[str] = []
        run: list[str] = []

        def flush_run() -> None:
            if run:
                responses.extend(self.put_lines(run, auth=auth))
                run.clear()

        batch_put = "put" in self.commands
        for line in lines:
            words = line.split()
            if batch_put and words and words[0] == "put":
                run.append(line)
                continue
            flush_run()
            try:
                r = self.execute(line, auth=auth)
            except (TelnetCloseConnection, TelnetServerShutdown) as e:
                return responses, e
            if r:
                responses.append(r)
        flush_run()
        return responses, None

    def put_lines(self, lines: list[str], auth=None) -> list[str]:
        """Columnar decode of a run of ``put`` lines: the payloads
        (identical to the import line format once the command word is
        stripped) parse in one :func:`parse_import_buffer` pass and
        land via the grouped bulk path — one WAL write + one fsync for
        the whole burst instead of one per line. Lines the columnar
        parser rejects replay through the scalar ``put`` path, so
        every error message, special value (nan/inf), and acceptance
        quirk stays EXACTLY what a line-at-a-time client sees.
        Returns the error responses (successes are silent)."""
        if auth is not None:
            from opentsdb_tpu.auth.simple import Permissions
            if not auth.has_permission(Permissions.TELNET_PUT):
                return ["put: permission denied "
                        "(TELNET_PUT not granted)"] * len(lines)
        if len(lines) == 1:
            r = self._cmd_put(lines[0].split())
            return [r] if r else []
        # one ingest.telnet trace roots the whole burst (per-line
        # roots would tax the hot loop); stages recorded inside
        # import_buffer (decode / store.scatter / wal.commit_wait /
        # stream.tap)
        from opentsdb_tpu.obs import trace as trace_mod
        tracer = getattr(self.tsdb, "tracer", None)
        tctx = tracer.start_request("ingest.telnet") \
            if tracer is not None and tracer.enabled else None
        if tctx is not None:
            tctx.tag(lines=len(lines))
            try:
                with trace_mod.use(tctx):
                    return self._put_lines_run(lines)
            except Exception as exc:
                tctx.set_error(exc)
                raise
            finally:
                tracer.finish(tctx)
        return self._put_lines_run(lines)

    def _put_lines_run(self, lines: list[str]) -> list[str]:
        if self.tsdb.cluster is not None:
            return self._put_lines_cluster(lines)
        failed: set[int] = set()
        bodies = []
        for i, ln in enumerate(lines):
            parts = ln.split(None, 1)
            body = parts[1] if len(parts) > 1 else ""
            if not body.strip() or body.lstrip().startswith("#"):
                # the import parser treats an empty/'#' body as a
                # skippable blank/comment line and reports NO error —
                # but 'put' with no args (or a '#' metric) must error
                # like the scalar path. Blank the body (keeps line
                # numbering aligned, writes nothing) and pre-mark the
                # line for scalar replay.
                failed.add(i)
                body = ""
            bodies.append(body)
        buf = ("\n".join(bodies) + "\n").encode("utf-8", "replace")

        def on_error(lineno: int, exc: Exception) -> None:
            failed.add(lineno - 1)

        try:
            self.tsdb.import_buffer(buf, on_error=on_error)
        except Exception as e:  # noqa: BLE001 - decode must not 500
            # unexpected bulk-path failure: report once, loudly — per-
            # line replay here could double-write lines that landed
            import logging
            logging.getLogger("tsd.telnet").exception(
                "columnar put decode failed")
            return [f"put: {type(e).__name__}: {e}"]
        out: list[str] = []
        for i in sorted(failed):
            # scalar replay: the failing line wrote nothing, so this
            # cannot double-write; its response text (and any telnet-
            # only acceptance, e.g. nan/inf values) matches the
            # line-at-a-time path byte for byte
            r = self._cmd_put(lines[i].split())
            if r:
                out.append(r)
        return out

    def _put_lines_cluster(self, lines: list[str]) -> list[str]:
        """Router role: one parse pass builds the burst's datapoint
        batch, which forwards through the consistent-hash partition
        (one series-grouped body per shard — the peer's ``/api/put``
        commits it as ONE WAL write + fsync) and spools durably for
        unreachable replicas exactly like HTTP puts. Rejected lines
        answer through the same scalar parse, so their error text is
        byte-identical to a standalone TSD's."""
        out: list[str] = []
        dps: list[dict] = []
        for line in lines:
            words = line.split()
            if len(words) < 5:
                out.append("put: illegal argument: not enough "
                           f"arguments (need least 4, got "
                           f"{len(words) - 1})")
                continue
            try:
                metric, ts, value, tags = self._parse_put_words(words)
            except Exception as e:  # noqa: BLE001 - per-line report
                out.append(f"put: {type(e).__name__}: {e}")
                continue
            dps.append({"metric": metric, "timestamp": ts,
                        "value": value, "tags": tags})
        if dps:
            _ok, bad, errs = self.tsdb.cluster.forward_writes(dps)
            if bad:
                out.extend(
                    f"put: {e.get('error', 'forward failed')}"
                    for e in errs)
        return out

    # ------------------------------------------------------------------

    def _parse_value(self, raw: str) -> int | float:
        # strict parse: int()/float() leniency (underscores,
        # whitespace, unicode digits) would silently store a DIFFERENT
        # number than the client sent (e.g. "1_0" -> 10)
        return tags_mod.parse_put_value(raw, allow_special=True)

    def _parse_put_words(self, words: list[str]
                         ) -> tuple[str, int, int | float, dict]:
        """Shared scalar parse + validation of one ``put`` line: the
        SAME calls (and so the same exception text) whether the point
        lands locally or forwards through a cluster router."""
        metric = words[1]
        ts = int(words[2])
        value = self._parse_value(words[3])
        tags = dict(tags_mod.parse(w) for w in words[4:])
        cluster = self.tsdb.cluster
        if cluster is not None:
            # router role: mirror add_point's local validation BEFORE
            # forwarding, so a rejected line's error text is
            # byte-identical to what a standalone/shard TSD answers
            self.tsdb._check_timestamp(ts)
            tags_mod.check_metric_and_tags(metric, tags)
        return metric, ts, value, tags

    def _cmd_put(self, words: list[str]) -> str:
        """``put <metric> <timestamp> <value> <tagk=tagv> [...]``
        (ref: PutDataPointRpc.execute :129). On a cluster router the
        point forwards to its replica owners (spooling like HTTP
        puts); rejected lines answer the same error text either
        way."""
        if len(words) < 5:
            return ("put: illegal argument: not enough arguments "
                    f"(need least 4, got {len(words) - 1})")
        try:
            metric, ts, value, tags = self._parse_put_words(words)
            cluster = self.tsdb.cluster
            if cluster is not None:
                _ok, bad, errs = cluster.forward_writes(
                    [{"metric": metric, "timestamp": ts,
                      "value": value, "tags": tags}])
                if bad:
                    detail = errs[0].get("error", "forward failed") \
                        if errs else "forward failed"
                    return f"put: {detail}"
                return ""
            self.tsdb.add_point(metric, ts, value, tags)
            return ""  # silent on success
        except Exception as e:  # noqa: BLE001
            return f"put: {type(e).__name__}: {e}"

    def _cmd_rollup(self, words: list[str]) -> str:
        """``rollup <interval>:<agg>[:<groupby_agg>] <metric> <ts> <value>
        <tagk=tagv> [...]`` (ref: RollupDataPointRpc telnet format)"""
        if len(words) < 6:
            return "rollup: illegal argument: not enough arguments"
        try:
            spec = words[1].split(":")
            interval: str | None
            if len(spec) == 1:
                # pure group-by pre-agg: "sum" alone
                interval, agg, gb_agg = None, None, spec[0]
                is_gb = True
            elif len(spec) == 2:
                interval, agg, gb_agg = spec[0], spec[1], None
                is_gb = False
            else:
                interval, agg, gb_agg = spec[0], spec[1], spec[2]
                is_gb = True
            metric = words[2]
            ts = int(words[3])
            value = self._parse_value(words[4])
            tags = dict(tags_mod.parse(w) for w in words[5:])
            self.tsdb.add_aggregate_point(metric, ts, value, tags, is_gb,
                                          interval, agg, gb_agg)
            return ""
        except Exception as e:  # noqa: BLE001
            return f"rollup: {type(e).__name__}: {e}"

    def _cmd_histogram(self, words: list[str]) -> str:
        """``histogram <metric> <timestamp> <base64-blob> <tagk=tagv>...``
        (ref: HistogramDataPointRpc)"""
        if len(words) < 5:
            return "histogram: illegal argument: not enough arguments"
        try:
            metric = words[1]
            ts = int(words[2])
            blob = base64.b64decode(words[3])
            tags = dict(tags_mod.parse(w) for w in words[4:])
            # the entry /api/histogram uses; its first error, raised
            failed: list[Exception] = []
            self.tsdb.add_histogram_batch(
                [(metric, ts, blob, tags)],
                on_error=lambda _i, e: failed.append(e))
            if failed:
                raise failed[0]
            return ""
        except Exception as e:  # noqa: BLE001
            return f"histogram: {type(e).__name__}: {e}"

    def _cmd_stats(self, words: list[str]) -> str:
        collector = self.tsdb.stats.collect()
        self.tsdb.collect_stats(collector)
        return "\n".join(collector.lines())

    def _cmd_version(self, words: list[str]) -> str:
        info = version_info()
        return (f"opentsdb_tpu version [{info['version']}] built from "
                f"revision {info['short_revision']}")

    def _cmd_dropcaches(self, words: list[str]) -> str:
        self.tsdb.drop_caches()
        return "Caches dropped."

    def _cmd_help(self, words: list[str]) -> str:
        return "available commands: " + " ".join(sorted(self.commands))

    def _cmd_exit(self, words: list[str]) -> str:
        raise TelnetCloseConnection()

    def _cmd_die(self, words: list[str]) -> str:
        """(ref: RpcManager DieDieDie)"""
        raise TelnetServerShutdown()
