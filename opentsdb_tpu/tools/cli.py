"""The ``tsdb`` command-line dispatcher (ref: ``tsdb.in:65-117``,
``src/tools/``).

Subcommands mirror the reference shell wrapper:

- ``tsd``      start the daemon (TSDMain.java:48)
- ``query``    ad-hoc queries, CliQuery output format (CliQuery.java:34)
- ``import``   bulk load text files (TextImporter.java:40)
- ``scan``     dump series, optionally in import format (DumpSeries.java:42)
- ``mkmetric`` assign metric UIDs (shortcut for ``uid assign metrics``)
- ``uid``      grep/assign/rename/delete/fsck the UID tables
  (UidManager.java:50)
- ``fsck``     storage integrity check/repair (Fsck.java:83)
- ``search``   time-series lookup (Search.java)
- ``treesync`` batch-rebuild trees (TreeSync.java)
- ``rollup``   run the in-framework rollup job (no reference
  equivalent: the reference relies on external jobs, SURVEY.md §2.3)
- ``version``

Config handling mirrors CliOptions/ConfigArgP: ``--config=PATH`` loads
a properties file; any ``--tsd.key=value`` flag overrides a config key.
"""

from __future__ import annotations

import gzip
import os
import sys
import time

from opentsdb_tpu.utils.config import Config
from opentsdb_tpu.utils import datetime_util

USAGE = """usage: tsdb <command> [args]
Valid commands: fsck, import, mkmetric, query, tsd, scan, search,
                treesync, rollup, uid, version, drain, check,
                cleancache
"""


def parse_common_args(argv: list[str]) -> tuple[Config, list[str]]:
    """(ref: CliOptions.parse + ConfigArgP overrides)"""
    config_file = None
    overrides: dict[str, str] = {}
    rest: list[str] = []
    i = 0
    while i < len(argv):
        arg = argv[i]
        if arg.startswith("--config"):
            config_file = (arg.split("=", 1)[1] if "=" in arg
                           else argv[(i := i + 1)])
        elif arg.startswith("--tsd."):
            if "=" in arg:
                key, val = arg[2:].split("=", 1)
            else:
                key, val = arg[2:], argv[(i := i + 1)]
            overrides[key] = val
        elif arg == "--auto-metric":
            overrides["tsd.core.auto_create_metrics"] = "true"
        elif arg.startswith("--datadir"):
            overrides["tsd.storage.data_dir"] = (
                arg.split("=", 1)[1] if "=" in arg else argv[(i := i + 1)])
        elif arg.startswith("--port"):
            overrides["tsd.network.port"] = (
                arg.split("=", 1)[1] if "=" in arg else argv[(i := i + 1)])
        else:
            rest.append(arg)
        i += 1
    config = Config(config_file=config_file, auto_load=config_file is None)
    for k, v in overrides.items():
        config.override_config(k, v)
    return config, rest


def make_tsdb(config: Config):
    """Build the TSDB, timed from inside as the start-up phase
    ``tsdb_init`` (the constructor; it contains ``snapshot_load`` and
    ``wal_replay``), served as ``/api/health`` ``startup``."""
    from opentsdb_tpu.core.tsdb import TSDB
    from opentsdb_tpu.obs.trace import RUNTIME
    with RUNTIME.phase("tsdb_init"):
        return TSDB(config)


# ---------------------------------------------------------------------------
# subcommands
# ---------------------------------------------------------------------------

def cmd_tsd(config: Config, args: list[str]) -> int:
    """(ref: TSDMain.java:71)"""
    import asyncio
    import signal

    from opentsdb_tpu.tsd.server import TSDServer
    from opentsdb_tpu.utils.plugin import load_plugin_instances

    # StartupPlugin.initialize runs before the TSDB exists
    # (ref: TSDMain.java:251)
    from opentsdb_tpu.obs.trace import RUNTIME
    startup = load_plugin_instances(config, "tsd.startup", single=True)
    tsdb = make_tsdb(config)
    if config.get_string("tsd.cluster.role", "") != "router":
        # JAX's client and the device, now and not inside the first
        # request (a router runs no device program: the chip belongs
        # to the shard process beside it)
        with RUNTIME.phase("backend"):
            import jax
            jax.devices()
    with RUNTIME.phase("plugins"):
        tsdb.initialize_plugins()
        server = TSDServer(tsdb)
        # protocol plugins sharing the process (ref: RpcPlugin.java:36,
        # RpcManager tsd.rpc.plugins)
        rpc_plugins = load_plugin_instances(config, "tsd.rpc",
                                            init_arg=tsdb) or []

    async def main():
        loop = asyncio.get_event_loop()
        for sig in (signal.SIGINT, signal.SIGTERM):
            try:
                loop.add_signal_handler(sig, server.request_shutdown)
            except NotImplementedError:
                pass
        with RUNTIME.phase("bind"):
            await server.start()
        if startup is not None:
            # server socket is bound (ref: StartupPlugin.setReady)
            startup.set_ready(tsdb)
        await server.serve_forever()

    asyncio.run(main())
    for plugin in rpc_plugins:
        plugin.shutdown()
    if startup is not None:
        startup.shutdown()
    return 0


def cmd_query(config: Config, args: list[str]) -> int:
    """``tsdb query [--graph PATH] START [END]
    <aggregator:[ds:][rate:]metric tagk=v...>`` (ref: CliQuery.java:34,
    incl. its --graph basepath chart output — matplotlib PNG here
    instead of gnuplot files). Output: ``metric timestamp value tags``.
    """
    from opentsdb_tpu.query.model import TSQuery, parse_uri_subquery
    graph_path = None
    if "--graph" in args:
        i = args.index("--graph")
        if i + 1 >= len(args):
            print("--graph needs a PATH", file=sys.stderr)
            return 2
        graph_path = args[i + 1]
        del args[i:i + 2]
    if len(args) < 2:
        print("usage: tsdb query [--graph PATH] START-DATE [END-DATE] "
              "[queries...]", file=sys.stderr)
        return 2
    start = args[0]
    pos = 1
    end = None
    # END is optional: detect by absence of ':' (queries contain agg:)
    if pos < len(args) and ":" not in args[pos]:
        end = args[pos]
        pos += 1
    subs = []
    while pos < len(args):
        spec = args[pos]
        pos += 1
        tag_parts = []
        while pos < len(args) and "=" in args[pos] \
                and ":" not in args[pos]:
            tag_parts.append(args[pos])
            pos += 1
        if tag_parts:
            spec += "{" + ",".join(tag_parts) + "}"
        subs.append(parse_uri_subquery(spec, len(subs)))
    tsq = TSQuery(start=start, end=end, queries=subs)
    tsq.validate()
    if graph_path:
        # fail fast BEFORE running the query: scanning a large range
        # only to discard the results on a missing optional dep is
        # wasted work
        try:
            import matplotlib
            matplotlib.use("Agg")
            import matplotlib.pyplot as plt
        except ImportError:
            print("--graph requires matplotlib", file=sys.stderr)
            return 2
    tsdb = make_tsdb(config)
    results = tsdb.new_query().run(tsq)
    if graph_path:
        from opentsdb_tpu.tsd.graph import plot_results_basic
        fig, ax = plt.subplots(figsize=(10, 6), dpi=100)
        plot_results_basic(ax, results)
        if results:
            ax.legend(fontsize=8)
        fig.autofmt_xdate()
        fig.savefig(graph_path)
        plt.close(fig)
        print(f"wrote {graph_path}")
        return 0
    for r in results:
        tag_str = " ".join(f"{k}={v}" for k, v in sorted(r.tags.items()))
        for ts, v in r.dps:
            val = int(v) if float(v).is_integer() else v
            print(f"{r.metric} {ts // 1000} {val} {tag_str}".rstrip())
    return 0


def cmd_import(config: Config, args: list[str]) -> int:
    """(ref: TextImporter.java:40) Lines: ``metric ts value tagk=tagv...``
    Gzip files auto-detected by extension.

    Files stream through the columnar import
    (``TSDB.import_buffer``): one pass parses each chunk (native C++
    when the toolchain exists, the strict pure-Python twin otherwise),
    UID resolution runs once per distinct series, points land via bulk
    appends, and each chunk commits as one WAL write + one fsync.

    ``--no-wal`` skips write-ahead logging for the bulk load (parity
    with the reference batch import's ``setDurable(false)``,
    IncomingDataPoints.java:355-360) — run ``flush``/let the daemon
    snapshot afterwards."""
    durable = "--no-wal" not in args
    args = [a for a in args if a != "--no-wal"]
    if not args:
        print("usage: tsdb import [--no-wal] path [more paths]",
              file=sys.stderr)
        return 2
    tsdb = make_tsdb(config)
    total = 0
    errors = 0
    start = time.monotonic()
    CHUNK_BYTES = 64 << 20

    class _TooManyErrors(Exception):
        pass

    # the columnar import path no longer needs the native library —
    # parse_import_buffer carries a strict pure-Python twin, so every
    # host gets the one-pass decode + batched WAL commit per chunk
    for path in args:
        opener = gzip.open if path.endswith(".gz") else open
        base_line = 0

        def on_error(i: int, e: Exception) -> None:
            # stop printing (and abort) promptly at the cap — a
            # binary/wrong-format chunk can hold millions of bad
            # lines
            nonlocal errors
            errors += 1
            if errors <= 100:
                print(f"error: {path}:{base_line + i}: {e}",
                      file=sys.stderr)
            else:
                raise _TooManyErrors

        with opener(path, "rb") as fh:
            tail = b""
            while True:
                block = fh.read(CHUNK_BYTES)
                if not block:
                    buf, tail = tail, b""
                    if not buf:
                        break
                else:
                    block = tail + block
                    cut = block.rfind(b"\n")
                    if cut < 0:
                        tail = block
                        continue
                    buf, tail = block[:cut + 1], block[cut + 1:]
                try:
                    written, _ = tsdb.import_buffer(
                        buf, on_error=on_error, durable=durable)
                except _TooManyErrors:
                    print("too many errors, aborting",
                          file=sys.stderr)
                    return 1
                total += written
                base_line += buf.count(b"\n")
                if not block:
                    break
    tsdb.flush()
    dt = time.monotonic() - start
    rate = total / dt if dt > 0 else 0
    print(f"Total: imported {total} data points in {dt:.3f}s "
          f"({rate:,.1f} points/s)")
    return 0 if errors == 0 else 1


def cmd_scan(config: Config, args: list[str]) -> int:
    """(ref: DumpSeries.java:42) ``tsdb scan [--import] START [END]
    query...``"""
    import_format = False
    if args and args[0] == "--import":
        import_format = True
        args = args[1:]
    rc_config = config
    code = _scan_impl(rc_config, args, import_format)
    return code


def _scan_impl(config: Config, args: list[str],
               import_format: bool) -> int:
    from opentsdb_tpu.query.model import TSQuery, parse_uri_subquery
    if len(args) < 2:
        print("usage: tsdb scan [--import] START [END] queries...",
              file=sys.stderr)
        return 2
    start = args[0]
    pos = 1
    end = None
    if pos < len(args) and ":" not in args[pos]:
        end = args[pos]
        pos += 1
    subs = []
    while pos < len(args):
        spec = args[pos]
        pos += 1
        tag_parts = []
        while pos < len(args) and "=" in args[pos] \
                and ":" not in args[pos]:
            tag_parts.append(args[pos])
            pos += 1
        if tag_parts:
            spec += "{" + ",".join(tag_parts) + "}"
        if ":" not in spec:
            spec = "none:" + spec
        subs.append(parse_uri_subquery(spec, len(subs)))
    for sub in subs:
        if sub.aggregator != "none":
            sub.aggregator = "none"
    tsq = TSQuery(start=start, end=end, queries=subs)
    tsq.validate()
    tsdb = make_tsdb(config)
    results = tsdb.new_query().run(tsq)
    for r in results:
        tag_str = " ".join(f"{k}={v}" for k, v in sorted(r.tags.items()))
        for ts, v in r.dps:
            val = int(v) if float(v).is_integer() else v
            if import_format:
                print(f"{r.metric} {ts // 1000} {val} {tag_str}".rstrip())
            else:
                print(f"{r.metric} {ts} {val} {{{tag_str}}}")
    return 0


def cmd_mkmetric(config: Config, args: list[str]) -> int:
    """(ref: tsdb.in mkmetric = uid assign metrics)"""
    return cmd_uid(config, ["assign", "metrics"] + args)


def cmd_uid(config: Config, args: list[str]) -> int:
    """(ref: UidManager.java:50)"""
    if not args:
        print("usage: tsdb uid <subcommand> args\n"
              "  grep [kind] <RE>\n"
              "  assign <kind> <name>...\n"
              "  rename <kind> <name> <newname>\n"
              "  delete <kind> <name>\n"
              "  fsck\n  metasync\n  metapurge", file=sys.stderr)
        return 2
    tsdb = make_tsdb(config)
    sub = args[0]
    kinds = ("metrics", "tagk", "tagv")
    if sub == "assign":
        if len(args) < 3:
            print("usage: tsdb uid assign <kind> <name>...",
                  file=sys.stderr)
            return 2
        registry = tsdb.uids.by_kind(args[1])
        for name in args[2:]:
            try:
                uid = tsdb.assign_uid(
                    args[1].rstrip("s") if args[1] == "metrics"
                    else args[1], name)
                print(f"{name} {args[1]}: "
                      f"[{', '.join(str(b) for b in registry.int_to_uid(uid))}]")
            except Exception as e:  # noqa: BLE001
                print(f"{name} {args[1]}: {e}", file=sys.stderr)
        tsdb.flush()
        return 0
    if sub == "grep":
        kind_filter = None
        pattern_args = args[1:]
        if pattern_args and pattern_args[0] in kinds:
            kind_filter = pattern_args[0]
            pattern_args = pattern_args[1:]
        if not pattern_args:
            print("usage: tsdb uid grep [kind] <RE>", file=sys.stderr)
            return 2
        pattern = pattern_args[0]
        for kind in (kind_filter,) if kind_filter else kinds:
            registry = tsdb.uids.by_kind(kind)
            for name in registry.grep(pattern):
                uid = registry.int_to_uid(registry.get_id(name))
                print(f"{kind} {name}: {uid.hex()}")
        return 0
    if sub == "rename":
        if len(args) != 4:
            print("usage: tsdb uid rename <kind> <name> <newname>",
                  file=sys.stderr)
            return 2
        tsdb.uids.by_kind(args[1]).rename(args[2], args[3])
        tsdb.flush()
        return 0
    if sub == "delete":
        if len(args) != 3:
            print("usage: tsdb uid delete <kind> <name>", file=sys.stderr)
            return 2
        tsdb.uids.by_kind(args[1]).delete(args[2])
        tsdb.flush()
        return 0
    if sub == "fsck":
        errors = _uid_fsck(tsdb)
        print(f"{errors} errors found")
        return 0 if errors == 0 else 1
    if sub == "metasync":
        count = 0
        for mid in tsdb.store.metric_ids():
            for sid in tsdb.store.series_ids_for_metric(mid):
                rec = tsdb.store.series(int(sid))
                tsdb.meta.on_datapoint(rec.metric_id, rec.tags,
                                       rec.series_id)
                count += 1
        print(f"synced meta for {count} timeseries")
        tsdb.flush()
        return 0
    if sub == "metapurge":
        # (ref: UidManager.java:208 -> MetaPurge threads)
        n_ts, n_uid = tsdb.meta.purge()
        print(f"purged {n_ts} TSMeta and {n_uid} UIDMeta entries")
        tsdb.flush()
        return 0
    print(f"unknown uid subcommand: {sub}", file=sys.stderr)
    return 2


def _uid_fsck(tsdb) -> int:
    """(ref: UidManager fsck — forward/reverse map consistency)"""
    errors = 0
    for kind in ("metric", "tagk", "tagv"):
        registry = tsdb.uids.by_kind(kind)
        with registry._lock:
            fwd = dict(registry._name_to_id)
            rev = dict(registry._id_to_name)
        for name, uid in fwd.items():
            if rev.get(uid) != name:
                print(f"ERROR: {kind} forward map {name}->{uid} has no "
                      f"matching reverse entry")
                errors += 1
        for uid, name in rev.items():
            if fwd.get(name) != uid:
                print(f"ERROR: {kind} reverse map {uid}->{name} has no "
                      f"matching forward entry")
                errors += 1
    return errors


def cmd_fsck(config: Config, args: list[str]) -> int:
    from opentsdb_tpu.tools.fsck import run_fsck
    fix = "--fix" in args or "--fix-all" in args
    tsdb = make_tsdb(config)
    report = run_fsck(tsdb, fix=fix)
    for line in report.lines:
        print(line)
    print(f"Total errors: {report.errors}  "
          f"(fixed: {report.fixed})" if fix
          else f"Total errors: {report.errors}")
    if fix and report.fixed:
        tsdb.flush()
    return 0 if report.errors == report.fixed else 1


def cmd_search(config: Config, args: list[str]) -> int:
    """(ref: Search.java) ``tsdb search lookup [--use_meta] metric
    tagk=tagv...``"""
    if not args or args[0] != "lookup":
        print("usage: tsdb search lookup [--use_meta] <query>",
              file=sys.stderr)
        return 2
    args = args[1:]
    use_meta = False
    if args and args[0] == "--use_meta":
        use_meta = True
        args = args[1:]
    metric = args[0] if args and "=" not in args[0] else "*"
    tag_args = [a for a in args if "=" in a]
    tags = [tuple(a.split("=", 1)) for a in tag_args]
    tsdb = make_tsdb(config)
    from opentsdb_tpu.search.lookup import time_series_lookup
    out = time_series_lookup(tsdb, metric, tags, limit=2**31,
                             use_meta=use_meta)
    for r in out["results"]:
        tag_str = " ".join(f"{k}={v}" for k, v in sorted(r["tags"].items()))
        print(f"{r['metric']} {tag_str}  tsuid={r['tsuid']}")
    print(f"{out['totalResults']} results")
    return 0


def cmd_treesync(config: Config, args: list[str]) -> int:
    """(ref: TreeSync.java)"""
    tsdb = make_tsdb(config)
    from opentsdb_tpu.tree.tree import tree_manager
    count = tree_manager(tsdb).sync_all()
    print(f"Processed {count} timeseries through trees")
    return 0


def cmd_rollup(config: Config, args: list[str]) -> int:
    """Run the batch rollup job over a time range."""
    from opentsdb_tpu.rollup.job import run_rollup_job
    if len(args) < 2:
        print("usage: tsdb rollup START END [interval...]",
              file=sys.stderr)
        return 2
    config.override_config("tsd.rollups.enable", "true")
    tsdb = make_tsdb(config)
    start_ms = datetime_util.parse_datetime_ms(args[0])
    end_ms = datetime_util.parse_datetime_ms(args[1])
    intervals = args[2:] or None
    written = run_rollup_job(tsdb, start_ms, end_ms, intervals)
    for interval, count in written.items():
        print(f"{interval}: {count} rollup points written")
    tsdb.flush()
    return 0


def cmd_version(config: Config, args: list[str]) -> int:
    from opentsdb_tpu.tsd.http_api import version_info
    info = version_info()
    print(f"opentsdb_tpu version [{info['version']}] "
          f"built from revision {info['short_revision']}")
    return 0


def cmd_drain(config: Config, args: list[str]) -> int:
    """(ref: tools/tsddrain.py — outage spooler)"""
    from opentsdb_tpu.tools.drain import main as drain_main
    return drain_main(args)


def cmd_check(config: Config, args: list[str]) -> int:
    """(ref: tools/check_tsd — Nagios threshold check)"""
    from opentsdb_tpu.tools.check_tsd import main as check_main
    return check_main(args)


def cmd_cleancache(config: Config, args: list[str]) -> int:
    """Purge the /q graph cache (ref: tools/clean_cache.sh)."""
    import shutil
    cache_dir = config.get_string("tsd.http.cachedir",
                                  "/tmp/opentsdb_tpu")
    if os.path.isdir(cache_dir):
        n = len(os.listdir(cache_dir))
        shutil.rmtree(cache_dir, ignore_errors=True)
        print(f"removed {n} cached entries from {cache_dir}")
    else:
        print(f"no cache at {cache_dir}")
    return 0


COMMANDS = {
    "tsd": cmd_tsd,
    "query": cmd_query,
    "import": cmd_import,
    "scan": cmd_scan,
    "mkmetric": cmd_mkmetric,
    "uid": cmd_uid,
    "fsck": cmd_fsck,
    "search": cmd_search,
    "treesync": cmd_treesync,
    "rollup": cmd_rollup,
    "version": cmd_version,
    "drain": cmd_drain,
    "check": cmd_check,
    "cleancache": cmd_cleancache,
}


def main(argv: list[str] | None = None) -> int:
    argv = list(sys.argv[1:] if argv is None else argv)
    if not argv or argv[0] in ("-h", "--help", "help"):
        print(USAGE, file=sys.stderr)
        return 2
    command = argv[0]
    handler = COMMANDS.get(command)
    if handler is None:
        print(f"unknown command: {command}\n{USAGE}", file=sys.stderr)
        return 2
    config, rest = parse_common_args(argv[1:])
    return handler(config, rest)


if __name__ == "__main__":
    sys.exit(main())
