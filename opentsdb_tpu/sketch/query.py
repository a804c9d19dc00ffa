"""Sketch-backed percentile queries over regular (scalar) metrics.

Before the fifth stat column existed, ``percentiles`` on a scalar
metric answered [] (no histogram arenas) and demoted/cold history had
no percentile story at all — the stat columns keep sum/count/min/max
only. This path serves ``sub.percentiles`` from quantile sketches
merged across the stitched three-way read
(:func:`opentsdb_tpu.lifecycle.stitch.sketch_zone_read`):

- cold segment sketch blobs for ``[start, spill_b)``,
- the in-RAM sketch tier for ``[spill_b, demote_b)``,
- a vectorized fold of the raw tail for ``[demote_b, end]``.

Semantics match the histogram percentile path: per (group, time
bucket), the POPULATION percentile of every point the bucket covers,
emitted as ``{metric}_pct_{q:g}`` rows. Accuracy: raw-tail buckets are
sketch-exact over the points (within the DDSketch alpha bound of the
exact order statistic); demoted/cold buckets answer from cells folded
at demotion time — same bound, over the same points the tier cells
aggregate.

``partials=True`` (the cluster scatter) skips quantile extraction and
returns one row per group carrying the serialized per-bucket sketches;
the router merges shard partials exactly (canonical DDSketch state is
merge-order independent, so the merged result is bit-equal to a
single node folding all shards' points) and extracts quantiles once.

Histogram metrics take the arena engine for live windows; their
spilled history (arena rows converted to sketches on spill) comes
back through the cold zone here and the engine splices the two row
sets — see :func:`merge_pct_rows`.
"""

from __future__ import annotations

import numpy as np

from opentsdb_tpu.query.model import BadRequestError, TSQuery, TSSubQuery
from opentsdb_tpu.sketch.ddsketch import DDSketch, SketchError


def _config_sketch(tsdb) -> tuple[bool, float, int]:
    cfg = tsdb.config
    return (cfg.get_bool("tsd.sketch.enable", True),
            cfg.get_float("tsd.sketch.alpha", 0.01),
            cfg.get_int("tsd.sketch.max_buckets", 4096))


def documented_alpha(tsdb) -> float:
    """The sketch's documented relative-error bound (config alpha)."""
    return _config_sketch(tsdb)[1]


def _bucket_of(ts: np.ndarray, tsq: TSQuery, sub: TSSubQuery
               ) -> tuple[np.ndarray, np.ndarray]:
    """(slot_ts[N], in_range[N]): output bucket timestamp per input
    timestamp — downsample buckets when the sub has a ds spec (the
    histogram engine's time-axis rule), else the timestamp itself."""
    ts = np.asarray(ts, dtype=np.int64)
    if sub.ds_spec is None or not len(ts):
        return ts, np.ones(len(ts), dtype=bool)
    from opentsdb_tpu.ops import downsample as ds_mod
    bidx, bts = ds_mod.assign_buckets(ts, sub.ds_spec, tsq.start_ms,
                                      tsq.end_ms)
    bidx = np.asarray(bidx)
    bts = np.asarray(bts, dtype=np.int64)
    ok = (bidx >= 0) & (bidx < len(bts))
    return bts[np.clip(bidx, 0, max(len(bts) - 1, 0))], ok


def _names_of_sids(store, uids, sids) -> dict[tuple, int]:
    """tag-NAMES tuple -> position in ``sids`` (the identity cold
    segments and sketch cells key by). Unresolvable series are
    skipped — their cells can't be attributed anyway."""
    out: dict[tuple, int] = {}
    for i, sid in enumerate(np.asarray(sids).tolist()):
        rec = store.series(int(sid))
        try:
            names = tuple(sorted((uids.tag_names.get_name(k),
                                  uids.tag_values.get_name(v))
                                 for k, v in rec.tags))
        except LookupError:
            continue
        out[names] = i
    return out


def run_sketch_percentiles(tsdb, tsq: TSQuery, sub: TSSubQuery,
                           partials: bool = False) -> list | None:
    """Serve one percentile sub-query from sketches. Returns None when
    the sketch path is disabled (``tsd.sketch.enable = false``) — the
    caller keeps the pre-sketch behavior — else a (possibly empty)
    list of QueryResults."""
    enabled, alpha, max_buckets = _config_sketch(tsdb)
    if not enabled:
        return None
    uids = tsdb.uids
    try:
        mid = uids.metrics.get_id(sub.metric)
    except LookupError:
        raise BadRequestError(
            f"No such name for 'metrics': '{sub.metric}'") from None
    hsids = tsdb.histogram_store.series_ids_for_metric(mid)
    if len(hsids):
        return _run_over_store(tsdb, tsq, sub, tsdb.histogram_store,
                               mid, alpha, max_buckets, partials,
                               hist=True)
    return _run_over_store(tsdb, tsq, sub, tsdb.store, mid, alpha,
                           max_buckets, partials, hist=False)


def _run_over_store(tsdb, tsq, sub, store, mid, alpha, max_buckets,
                    partials, hist):
    from opentsdb_tpu.query.engine import QueryEngine
    from opentsdb_tpu.query.filters import FilterEvaluator
    from opentsdb_tpu.query.plan import TagMatrix
    uids = tsdb.uids
    sids = store.series_ids_for_metric(mid)
    if len(sids) == 0:
        return []
    hist_zones = None
    if hist:
        # a histogram metric has sketches only where its arenas were
        # spilled (or a shard is asked for partials): without any,
        # nothing below can emit a row, and the plan over every series
        # of the metric (a tag matrix of 200,000 rows a request) is
        # left to the arena engine, which has an index for it
        hist_zones = _hist_zones(tsdb, tsq, sub, mid, alpha,
                                 max_buckets, partials)
        if not hist_zones[0]:
            return []
    idx = store.metric_index(mid)
    _, triples = idx.arrays()
    tag_mat = TagMatrix.from_triples(sids, triples)
    if sub.filters:
        mask = FilterEvaluator(uids).apply(sub.filters, tag_mat)
        sids = sids[mask]
        tag_mat = tag_mat.select(mask)
        if len(sids) == 0:
            return []
    gb_kids = sorted({uids.tag_names.get_id(f.tagk)
                      for f in sub.filters if f.group_by
                      and uids.tag_names.has_name(f.tagk)})
    group_ids, num_groups = QueryEngine._group_ids(tag_mat, gb_kids)
    gvec = np.asarray(group_ids, dtype=np.int64)

    # ---- gather the three zones as (sid_pos, cell_ts, sketch) ------
    if hist:
        items, raw_rng, cold_ok = hist_zones
    else:
        from opentsdb_tpu.lifecycle.stitch import sketch_zone_read
        items, raw_rng, cold_ok = sketch_zone_read(
            tsdb, sub.metric, mid, tsq.start_ms, tsq.end_ms)

    # (group, output bucket) accumulators
    acc: dict[tuple[int, int], DDSketch] = {}

    def _fold_in(gid: int, slot: int, sk: DDSketch) -> None:
        cur = acc.get((gid, slot))
        if cur is None:
            acc[(gid, slot)] = sk
        else:
            try:
                cur.merge(sk)
            except SketchError:
                pass  # alpha changed under old cells: skip, serve rest

    if items:
        pos_of = _names_of_sids(store, uids, sids)
        cell_ts = np.asarray([c[1] for c in items], dtype=np.int64)
        slots, ok = _bucket_of(cell_ts, tsq, sub)
        for j, (tags, _cts, sk) in enumerate(items):
            i = pos_of.get(tuple(tags))
            if i is None or not ok[j]:
                continue  # filtered out, or out of the bucket grid
            _fold_in(int(gvec[i]), int(slots[j]), sk)

    if raw_rng is not None and not hist:
        from opentsdb_tpu.ops import sketch_fold
        batch = tsdb.store.materialize(sids, raw_rng[0], raw_rng[1])
        if batch.num_points:
            slots, ok = _bucket_of(batch.ts_ms, tsq, sub)
            sidx = np.asarray(batch.series_idx, dtype=np.int64)
            vals = np.asarray(batch.values, dtype=np.float64)
            if not ok.all():
                sidx, slots, vals = sidx[ok], slots[ok], vals[ok]
            folded = sketch_fold.fold_series_cells(
                gvec[sidx], slots, vals, 1, alpha, max_buckets)
            for (gid, slot), sk in folded.items():
                _fold_in(int(gid), int(slot), sk)

    if not acc:
        return []
    return _emit(tsdb, tsq, sub, tag_mat, group_ids, num_groups, acc,
                 partials, cold_ok)


def _hist_zones(tsdb, tsq, sub, mid, alpha, max_buckets, partials):
    """Zones for a histogram metric: cold sketch rows (the arena
    spill's output) plus — in partials mode only — the live arena
    rows converted through bucket midpoints (the same convention
    ``percentiles_from_counts`` extracts with), so a shard can hand
    the router mergeable partials. Batch (non-partials) queries serve
    live arenas through the exact arena engine instead."""
    from opentsdb_tpu.lifecycle.stitch import guarded_sketch_rows
    lc = tsdb.lifecycle
    cold = getattr(lc, "coldstore", None) if lc is not None else None
    spill_b = cold.spill_boundary(
        tsdb.uids.metrics.get_name(mid)) if cold is not None else 0
    items: list = []
    cold_ok = True
    if cold is not None and spill_b and tsq.start_ms < spill_b:
        rows, cold_ok = guarded_sketch_rows(
            cold, sub.metric, tsq.start_ms,
            min(tsq.end_ms, spill_b - 1))
        for tags, cts, blob in rows:
            try:
                items.append((tags, cts, DDSketch.from_bytes(blob)))
            except (SketchError, ValueError):
                cold_ok = False
    if partials:
        items.extend(arena_sketch_items(
            tsdb, mid, max(tsq.start_ms, spill_b), tsq.end_ms, alpha,
            max_buckets))
    return items, None, cold_ok


def arena_sketch_items(tsdb, mid: int, start_ms: int, end_ms: int,
                       alpha: float, max_buckets: int) -> list:
    """Live histogram arena rows as ``(tags_names, ts, DDSketch)``:
    each row's bucket counts fold at the bucket midpoints (the value
    ``percentiles_from_counts`` would emit for any rank landing in the
    bucket), so extraction from the sketch answers within alpha of the
    arena engine's midpoint convention."""
    if start_ms > end_ms:
        return []
    with tsdb._histogram_lock:
        arena = tsdb._histogram_arenas.get(mid)
        snaps = [(s.bounds, *s.snapshot())
                 for s in arena.groups.values()] if arena else []
    if not snaps:
        return []
    uids = tsdb.uids
    store = tsdb.histogram_store
    names_of: dict[int, tuple | None] = {}
    out = []
    for bounds, ts_a, sid_a, rows in snaps:
        b = np.asarray(bounds, dtype=np.float64)
        mids = (b[:-1] + b[1:]) / 2.0
        m = (ts_a >= start_ms) & (ts_a <= end_ms)
        if not m.any():
            continue
        for ts, sid, counts in zip(ts_a[m].tolist(),
                                   sid_a[m].tolist(),
                                   np.asarray(rows)[m]):
            if sid not in names_of:
                try:
                    rec = store.series(int(sid))
                    names_of[sid] = tuple(sorted(
                        (uids.tag_names.get_name(k),
                         uids.tag_values.get_name(v))
                        for k, v in rec.tags))
                except LookupError:
                    names_of[sid] = None
            names = names_of[sid]
            if names is None:
                continue
            sk = DDSketch(alpha)
            sk.add_weighted(mids, counts)
            if max_buckets:
                sk.collapse(max_buckets)
            if sk.count:
                out.append((names, int(ts), sk))
    return out


def _emit(tsdb, tsq, sub, tag_mat, group_ids, num_groups, acc,
          partials, cold_ok):
    from opentsdb_tpu.query.engine import QueryResult
    from opentsdb_tpu.query.plan import _common_tags
    uids = tsdb.uids
    order = np.argsort(group_ids, kind="stable")
    sorted_gids = np.asarray(group_ids)[order]
    gid_range = np.arange(num_groups,
                          dtype=np.asarray(group_ids).dtype)
    starts = np.searchsorted(sorted_gids, gid_range, side="left")
    ends = np.searchsorted(sorted_gids, gid_range, side="right")
    by_gid: dict[int, list[tuple[int, DDSketch]]] = {}
    for (gid, slot), sk in acc.items():
        by_gid.setdefault(gid, []).append((slot, sk))
    out = []
    for gid in range(num_groups):
        slots = by_gid.get(gid)
        if not slots:
            continue
        members = order[starts[gid]:ends[gid]]
        if len(members) == 0:
            continue
        slots.sort(key=lambda p: p[0])
        tags, agg_tags = _common_tags(tag_mat, members, uids)
        if partials:
            r = QueryResult(metric=sub.metric, tags=tags,
                            aggregated_tags=agg_tags, dps=[],
                            sub_query_index=sub.index)
            r.sketches = [(t, sk.to_bytes()) for t, sk in slots]
            out.append(r)
            continue
        ts_list = [t if tsq.ms_resolution else (t // 1000) * 1000
                   for t, _ in slots]
        for q in sub.percentiles:
            dps = [(ts_list[k], float(sk.quantile(q)))
                   for k, (_t, sk) in enumerate(slots)]
            out.append(QueryResult(
                metric=f"{sub.metric}_pct_{q:g}", tags=tags,
                aggregated_tags=agg_tags, dps=dps,
                sub_query_index=sub.index))
    return out


def merge_pct_rows(a: list, b: list) -> list:
    """Splice two percentile row sets covering disjoint time windows
    (live arena rows + spilled-history sketch rows) by (metric, tags,
    sub index): dps concatenate and re-sort; rows unique to either
    side pass through. Later values win exact-timestamp collisions
    (live data over spilled history — only possible mid-sweep)."""
    if not a:
        return b
    if not b:
        return a
    keyed: dict[tuple, object] = {}
    out = []
    for r in a:
        key = (r.metric, tuple(sorted(r.tags.items())),
               r.sub_query_index)
        keyed[key] = r
        out.append(r)
    for r in b:
        key = (r.metric, tuple(sorted(r.tags.items())),
               r.sub_query_index)
        cur = keyed.get(key)
        if cur is None:
            keyed[key] = r
            out.append(r)
            continue
        merged = dict(cur.dps)
        merged.update(dict(r.dps))
        cur.dps = sorted(merged.items())
    return out
