"""Storage durability: snapshot/restore of the host column store.

The reference delegates durability to HBase's WAL and keeps the TSD
stateless (SURVEY.md §5.4); this build's analogue is a persistent host
store directory (``tsd.storage.data_dir``): UID tables as JSON, series
index + point columns as ``.npy`` blobs, written atomically
(tmp + rename) on ``flush``/``shutdown`` and loaded on startup. CLI
tools (import/scan/fsck/uid) operate on the same directory the daemon
serves from — the moral equivalent of tools talking to the same HBase
tables.

Snapshots are also the checkpoint/resume story: restart rebuilds
device arrays lazily from the host store, exactly like the reference
rebuilds UID caches lazily after a restart.
"""

from __future__ import annotations

import base64
import json
import os
import tempfile

import numpy as np

_FORMAT_VERSION = 1


def save_store(tsdb, data_dir: str) -> int:
    """Write a full snapshot. Returns the WAL sequence the snapshot
    covers (captured BEFORE content capture, so a concurrent write can
    only be double-covered — replay duplicates are dedupe-tolerant —
    never lost)."""
    faults = getattr(tsdb, "faults", None)
    if faults is not None:
        # fault-injection point for the snapshot flush path
        # (tsd.faults.store.flush_*); TSDB.flush retries around this
        faults.check("store.flush")
    wal = getattr(tsdb, "wal", None)
    wal_seq = wal.last_seq() if wal is not None else 0
    os.makedirs(data_dir, exist_ok=True)
    _save_uids(tsdb.uids, data_dir)
    _save_timeseries(tsdb.store, os.path.join(data_dir, "data"))
    if tsdb.rollup_store is not None:
        for (interval, agg), store in tsdb.rollup_store._tiers.items():
            _save_timeseries(store, os.path.join(
                data_dir, f"rollup-{interval}-{agg}"))
        _save_timeseries(tsdb.rollup_store.preagg_store(),
                         os.path.join(data_dir, "rollup-preagg"))
    _save_annotations(tsdb.annotations, data_dir)
    _save_histograms(tsdb, data_dir)
    _save_meta(tsdb, data_dir)
    _save_trees(tsdb, data_dir)
    meta = {"format": _FORMAT_VERSION,
            "points_written": tsdb.store.points_written,
            "wal_applied_seq": wal_seq}
    _atomic_write(os.path.join(data_dir, "META.json"),
                  json.dumps(meta).encode())
    return wal_seq


def load_store(tsdb, data_dir: str) -> bool:
    """Load a snapshot into a fresh TSDB. Returns False when absent."""
    if not os.path.isfile(os.path.join(data_dir, "META.json")):
        return False
    with open(os.path.join(data_dir, "META.json"), encoding="utf-8") as fh:
        meta = json.load(fh)
    if meta.get("format") != _FORMAT_VERSION:
        raise ValueError(f"unsupported snapshot format {meta.get('format')}")
    tsdb._wal_applied_seq = int(meta.get("wal_applied_seq", 0))
    _load_uids(tsdb.uids, data_dir)
    _load_timeseries(tsdb.store, os.path.join(data_dir, "data"))
    if tsdb.rollup_store is not None:
        prefix = "rollup-"
        for name in os.listdir(data_dir):
            full = os.path.join(data_dir, name)
            if not (name.startswith(prefix) and os.path.isdir(full)):
                continue
            rest = name[len(prefix):]
            if rest == "preagg":
                _load_timeseries(tsdb.rollup_store.preagg_store(), full)
            else:
                interval, _, agg = rest.rpartition("-")
                try:
                    _load_timeseries(tsdb.rollup_store.tier(interval, agg),
                                     full)
                except ValueError:
                    pass  # tier no longer configured
    _load_annotations(tsdb.annotations, data_dir)
    _load_histograms(tsdb, data_dir)
    _load_meta(tsdb, data_dir)
    _load_trees(tsdb, data_dir)
    return True


def _save_trees(tsdb, data_dir: str) -> None:
    """Tree DEFINITIONS (name + rules; ref: tsdb-tree table rows).
    Branches are materialized views — rebuilt by realtime processing or
    `tsdb treesync`, like the reference's TreeSync."""
    mgr = getattr(tsdb, "_tree_manager", None)
    if mgr is None:
        return
    _atomic_write(os.path.join(data_dir, "trees.json"),
                  json.dumps([t.to_json()
                              for t in mgr.all_trees()]).encode())


def _load_trees(tsdb, data_dir: str) -> None:
    path = os.path.join(data_dir, "trees.json")
    if not os.path.isfile(path):
        return
    from opentsdb_tpu.tree.tree import tree_manager
    mgr = tree_manager(tsdb)
    from opentsdb_tpu.tree.tree import Tree, TreeRule
    with open(path, encoding="utf-8") as fh:
        doc = json.load(fh)
    with mgr._lock:
        for obj in doc:
            tree = Tree(int(obj["treeId"]))
            tree.update(obj, overwrite=True)
            tree.created = int(obj.get("created", 0))
            for robj in obj.get("rules", []):
                tree.set_rule(TreeRule.from_json(robj))
            mgr.trees[tree.tree_id] = tree
            mgr._next_id = max(mgr._next_id, tree.tree_id)


def _save_meta(tsdb, data_dir: str) -> None:
    """TSMeta/UIDMeta documents + counters (ref: tsdb-meta/tsdb-uid
    meta rows — user edits like displayName must survive restarts)."""
    import dataclasses
    m = tsdb.meta
    if m is None:
        return
    with m._lock:
        doc = {
            "ts_counters": dict(m.ts_counters),
            "uid_meta": [dataclasses.asdict(v) | {"_key": list(k)}
                         for k, v in m.uid_meta.items()],
            "ts_meta": [dataclasses.asdict(v)
                        for v in m.ts_meta.values()],
        }
    _atomic_write(os.path.join(data_dir, "meta.json"),
                  json.dumps(doc).encode())


def _load_meta(tsdb, data_dir: str) -> None:
    path = os.path.join(data_dir, "meta.json")
    m = tsdb.meta
    if m is None or not os.path.isfile(path):
        return
    from opentsdb_tpu.meta.meta_store import TSMeta, UIDMeta
    with open(path, encoding="utf-8") as fh:
        doc = json.load(fh)
    with m._lock:
        for e in doc.get("uid_meta", []):
            key = tuple(e.pop("_key"))
            m.uid_meta[key] = UIDMeta(**e)
        for e in doc.get("ts_meta", []):
            metric = e.pop("metric", None)
            tags = e.pop("tags", None) or []
            t = TSMeta(**e)
            t.metric = UIDMeta(**metric) if metric else None
            t.tags = [UIDMeta(**x) for x in tags]
            m.ts_meta[t.tsuid] = t
        m.ts_counters.update(doc.get("ts_counters", {}))


def _save_histograms(tsdb, data_dir: str) -> None:
    """Distribution-valued series: identity + columnar arena arrays
    (v2 format — base64 of the raw ts/sid/rows buffers; the v1 format
    re-encoded one blob per point, which walked every stored point).
    (ref: histogram cells beside scalar cells in the data table)."""
    with tsdb._histogram_lock:
        # under the lock: only capture stable snapshot views (see
        # _Sub.snapshot) — the O(total bytes) base64 work runs outside
        # so ingestion never stalls on a flush
        raw = [(mid, sub.bounds, *sub.snapshot(),
                sub.under[:sub.n], sub.over[:sub.n])
               for mid, arena in tsdb._histogram_arenas.items()
               for sub in arena.groups.values()]
    arenas = []
    seen_sids: set[int] = set()
    for mid, bounds, ts, sid, rows, under, over in raw:
        arenas.append({
            "metric": mid,
            "bounds": list(bounds),
            "n": int(len(ts)),
            "ts": base64.b64encode(
                np.ascontiguousarray(ts).tobytes()).decode(),
            "sid": base64.b64encode(
                np.ascontiguousarray(sid).tobytes()).decode(),
            "rows": base64.b64encode(
                np.ascontiguousarray(rows).tobytes()).decode(),
            "under": base64.b64encode(
                np.ascontiguousarray(under).tobytes()).decode(),
            "over": base64.b64encode(
                np.ascontiguousarray(over).tobytes()).decode(),
        })
        seen_sids.update(int(s) for s in np.unique(sid))
    series = {}
    for s in sorted(seen_sids):
        rec = tsdb.histogram_store.series(s)
        series[str(s)] = {"metric": rec.metric_id,
                          "tags": [list(p) for p in rec.tags]}
    doc = {"v": 2, "series": series, "arenas": arenas}
    _atomic_write(os.path.join(data_dir, "histograms.json"),
                  json.dumps(doc).encode())


def _load_histograms(tsdb, data_dir: str) -> None:
    from opentsdb_tpu.core.histogram import HistogramArena
    path = os.path.join(data_dir, "histograms.json")
    if not os.path.isfile(path):
        return
    with open(path, encoding="utf-8") as fh:
        doc = json.load(fh)
    if isinstance(doc, list):
        # v1 legacy: per-series blob lists
        for entry in doc:
            for ts, blob in entry["points"]:
                hist = tsdb.histogram_manager.decode(
                    base64.b64decode(blob))
                sid = tsdb.histogram_store.get_or_create_series(
                    entry["metric"], [tuple(p) for p in entry["tags"]])
                arena = tsdb._histogram_arenas.setdefault(
                    entry["metric"], HistogramArena())
                arena.append(int(ts), sid, hist)
        return
    # v2: rebuild series ids first (old sid -> new sid remap), then
    # bulk-append the columnar arrays
    sid_map: dict[int, int] = {}
    for old_sid, ident in doc.get("series", {}).items():
        sid_map[int(old_sid)] = tsdb.histogram_store \
            .get_or_create_series(ident["metric"],
                                  [tuple(p) for p in ident["tags"]])
    # dense LUT remap, built once (vectorized; a per-element dict call
    # would re-add the per-point Python walk this layout removed)
    if sid_map:
        old_ids = np.fromiter(sid_map, dtype=np.int64,
                              count=len(sid_map))
        lut = np.zeros(int(old_ids.max()) + 1, dtype=np.int64)
        lut[old_ids] = np.fromiter(sid_map.values(), dtype=np.int64,
                                   count=len(sid_map))
    for entry in doc.get("arenas", []):
        n = int(entry["n"])
        nb = max(1, len(entry["bounds"]) - 1)
        ts = np.frombuffer(base64.b64decode(entry["ts"]),
                           dtype=np.int64)[:n]
        sid = np.frombuffer(base64.b64decode(entry["sid"]),
                            dtype=np.int64)[:n]
        rows = np.frombuffer(base64.b64decode(entry["rows"]),
                             dtype=np.float64).reshape(-1, nb)[:n]
        under = np.frombuffer(base64.b64decode(entry.get("under", "")),
                              dtype=np.int64)[:n] \
            if entry.get("under") else None
        over = np.frombuffer(base64.b64decode(entry.get("over", "")),
                             dtype=np.int64)[:n] \
            if entry.get("over") else None
        arena = tsdb._histogram_arenas.setdefault(
            entry["metric"], HistogramArena())
        key = tuple(entry["bounds"])
        sub = arena.groups.get(key)
        if sub is None:
            sub = arena.groups[key] = HistogramArena._Sub(key, nb)
        remap = lut[sid] if len(sid) else sid
        sub.append_many(ts, remap, rows, under, over)
        arena.total_points += n


# ---------------------------------------------------------------------------

def _atomic_write(path: str, data: bytes) -> None:
    fd, tmp = tempfile.mkstemp(dir=os.path.dirname(path))
    try:
        with os.fdopen(fd, "wb") as fh:
            fh.write(data)
        os.replace(tmp, path)
    except BaseException:
        if os.path.exists(tmp):
            os.unlink(tmp)
        raise


def _save_uids(uids, data_dir: str) -> None:
    doc = {}
    for kind in ("metric", "tagk", "tagv"):
        registry = uids.by_kind(kind)
        doc[kind] = {"width": registry.width,
                     "max_id": registry.max_id(),
                     "names": dict(registry.items())}
    _atomic_write(os.path.join(data_dir, "uids.json"),
                  json.dumps(doc).encode())


def _load_uids(uids, data_dir: str) -> None:
    path = os.path.join(data_dir, "uids.json")
    if not os.path.isfile(path):
        return
    with open(path, encoding="utf-8") as fh:
        doc = json.load(fh)
    for kind in ("metric", "tagk", "tagv"):
        registry = uids.by_kind(kind)
        entry = doc.get(kind, {})
        registry.load(entry.get("names", {}), entry.get("max_id", 0))


def _save_timeseries(store, directory: str) -> None:
    os.makedirs(directory, exist_ok=True)
    index = []
    ts_parts, val_parts, int_parts = [], [], []
    offset = 0
    for sid in range(store.num_series()):
        rec = store.series(sid)
        ts, vals, ints = rec.buffer.view_full()
        index.append({"metric": rec.metric_id,
                      "tags": [list(p) for p in rec.tags],
                      "offset": offset, "count": len(ts)})
        ts_parts.append(ts.copy())
        val_parts.append(vals.copy())
        int_parts.append(ints.copy())
        offset += len(ts)
    _atomic_write(os.path.join(directory, "series.json"),
                  json.dumps(index).encode())
    all_ts = (np.concatenate(ts_parts) if ts_parts
              else np.empty(0, np.int64))
    all_vals = (np.concatenate(val_parts) if val_parts
                else np.empty(0, np.float64))
    all_ints = (np.concatenate(int_parts) if int_parts
                else np.empty(0, bool))
    with open(os.path.join(directory, "points.npz"), "wb") as fh:
        np.savez_compressed(fh, ts=all_ts, vals=all_vals, ints=all_ints)


def _load_timeseries(store, directory: str) -> None:
    index_path = os.path.join(directory, "series.json")
    if not os.path.isfile(index_path):
        return
    with open(index_path, encoding="utf-8") as fh:
        index = json.load(fh)
    npz = np.load(os.path.join(directory, "points.npz"))
    all_ts, all_vals, all_ints = npz["ts"], npz["vals"], npz["ints"]
    for entry in index:
        sid = store.get_or_create_series(
            entry["metric"], [tuple(p) for p in entry["tags"]])
        lo, n = entry["offset"], entry["count"]
        if n:
            store.append_many(sid, all_ts[lo:lo + n],
                              all_vals[lo:lo + n],
                              is_int=all_ints[lo:lo + n])


def _save_annotations(annotations, data_dir: str) -> None:
    doc = []
    with annotations._lock:
        for tsuid, by_time in annotations._by_tsuid.items():
            for note in by_time.values():
                doc.append(note.to_json() | {"tsuid": tsuid})
    _atomic_write(os.path.join(data_dir, "annotations.json"),
                  json.dumps(doc).encode())


def _load_annotations(annotations, data_dir: str) -> None:
    path = os.path.join(data_dir, "annotations.json")
    if not os.path.isfile(path):
        return
    from opentsdb_tpu.meta.annotation import Annotation
    with open(path, encoding="utf-8") as fh:
        for obj in json.load(fh):
            annotations.store(Annotation.from_json(obj))
