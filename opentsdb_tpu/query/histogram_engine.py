"""Histogram / percentile query path.

(ref: ``TsdbQuery.isHistogramQuery`` :776 routes queries with
``percentiles`` set to the HistogramSpan/HistogramAggregationIterator
pipeline; merge is bucket-wise SUM, then ``SimpleHistogram.percentile``)

TPU formulation: the histogram points of all series in the window stack
into a dense ``[points, buckets]`` count matrix; merge-by-timestamp and
group-by are segment-sums over the leading axis, and percentile
extraction is a vectorized cumsum + searchsorted over the bucket axis —
see :func:`percentiles_from_counts`.

Downsampling (ref: ``HistogramDownsampler.java`` wrapping each span
before the group merge): histogram aggregation is bucket-wise SUM both
across series and across time (``HistogramAggregation.java:20`` — SUM is
the only defined merge), so downsample-then-merge collapses into ONE
segment-sum keyed by (group, time-bucket) — the time axis just uses
downsample bucket indices instead of distinct-timestamp indices.
"""

from __future__ import annotations

import numpy as np

from opentsdb_tpu.query.model import BadRequestError, TSQuery, TSSubQuery


def percentiles_from_counts(counts: np.ndarray, bounds: np.ndarray,
                            qs: list[float]) -> np.ndarray:
    """counts[T, nbuckets], bounds[nbuckets+1] -> [len(qs), T].

    Midpoint convention matches SimpleHistogram.percentile (:133): the
    bucket whose cumulative count crosses rank contributes its midpoint.
    """
    totals = counts.sum(axis=1)  # [T]
    cum = np.cumsum(counts, axis=1)  # [T, B]
    mids = (bounds[:-1] + bounds[1:]) / 2.0
    out = np.empty((len(qs), counts.shape[0]), dtype=np.float64)
    for qi, q in enumerate(qs):
        target = totals * (q / 100.0)
        idx = np.sum(cum < target[:, None], axis=1)
        idx = np.clip(idx, 0, len(mids) - 1)
        out[qi] = np.where(totals > 0, mids[idx], 0.0)
    return out


def _time_axis(point_ts: np.ndarray, tsq: TSQuery, sub: TSSubQuery
               ) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """(time_idx[N], ts_out[T], in_range[N]) for the histogram batch:
    downsample bucket indices when the sub-query has a downsample spec
    (ref: HistogramDownsampler), else one slot per distinct timestamp
    (ref: the raw HistogramAggregationIterator union merge)."""
    if sub.ds_spec is not None:
        from opentsdb_tpu.ops import downsample as ds_mod
        bucket_idx, bucket_ts = ds_mod.assign_buckets(
            point_ts, sub.ds_spec, tsq.start_ms, tsq.end_ms)
        bucket_idx = np.asarray(bucket_idx)
        bucket_ts = np.asarray(bucket_ts)
        # points are pre-filtered to the window, but guard the bucket
        # range anyway (assign_buckets assumes in-range input)
        return (bucket_idx, bucket_ts,
                (bucket_idx >= 0) & (bucket_idx < len(bucket_ts)))
    ts_sorted, ts_idx = np.unique(point_ts, return_inverse=True)
    return ts_idx, ts_sorted, np.ones(len(point_ts), dtype=bool)


def run_histogram_subquery(tsdb, tsq: TSQuery, sub: TSSubQuery) -> list:
    """Execute a percentile sub-query over stored histogram datapoints."""
    from opentsdb_tpu.query.engine import QueryEngine, TagMatrix
    uids = tsdb.uids
    try:
        metric_id = uids.metrics.get_id(sub.metric)
    except LookupError:
        raise BadRequestError(
            f"No such name for 'metrics': '{sub.metric}'") from None
    store = tsdb.histogram_store
    sids = store.series_ids_for_metric(metric_id)
    if len(sids) == 0:
        return []
    # filters reuse the scalar evaluator over the histogram store's index
    from opentsdb_tpu.query.filters import FilterEvaluator
    idx = store.metric_index(metric_id)
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

    # collect the window's histogram points as one flat [N, NB] batch.
    # The collected batch (counts matrix device-resident) is cached by
    # write version: the per-series object walk and the upload are the
    # whole cost at scale (ref analogue: scan result block caching).
    cache = tsdb.device_grid_cache
    ckey = cver = None
    counts = point_sidx = point_ts_arr = None
    bounds: tuple | None = None
    if cache is not None:
        from opentsdb_tpu.query.device_cache import array_digest
        ckey = ("hist", array_digest(np.ascontiguousarray(sids)),
                tsq.start_ms, tsq.end_ms)
        cver = tsdb._histogram_version
        hit = cache.get(ckey, cver)
        if hit is not None:
            (counts,), meta = hit
            point_sidx = meta["point_sidx"]
            point_ts_arr = meta["point_ts"]
            bounds = meta["bounds"]
    if counts is None:
        # columnar arena slice (no per-point or per-series Python):
        # membership + window masks over flat arrays, one fancy-index
        # gather for the rows (ref analogue: SaltScanner streaming
        # histogram cells; HistogramSpan assembly collapses into this).
        # Snapshots are captured under the lock (the append-side lock);
        # see HistogramArena._Sub.snapshot for why the views stay
        # stable afterwards.
        with tsdb._histogram_lock:
            arena = tsdb._histogram_arenas.get(metric_id)
            snaps = [(s.bounds, *s.snapshot())
                     for s in arena.groups.values()] if arena else []
        if not snaps:
            return []
        order = np.argsort(sids, kind="stable")
        sorted_sids = np.asarray(sids)[order]

        def member_mask(ts_a, sid_a):
            pos = np.searchsorted(sorted_sids, sid_a)
            pos = np.clip(pos, 0, len(sorted_sids) - 1)
            return pos, ((sorted_sids[pos] == sid_a)
                         & (ts_a >= tsq.start_ms)
                         & (ts_a <= tsq.end_ms))

        masked = [(snap, *member_mask(snap[1], snap[2]))
                  for snap in snaps]
        active = [(snap, pos, m) for snap, pos, m in masked
                  if m.any()]
        if not active:
            return []
        if len(active) > 1:
            # bounds genuinely disagree INSIDE the window: host merge
            # path with per-slot bounds checks. A bounds class with no
            # points in the window must not disable the device path
            # (a single stray historic migration would otherwise
            # penalize every future query).
            return _run_mixed_bounds(tsdb, tsq, sub, active, sids,
                                     tag_mat, group_ids, num_groups)
        (bounds, ts_a, sid_a, rows), pos, member = active[0]
        counts = rows[member]
        # index into the caller's sids array (group_ids aligns to it)
        point_sidx = order[pos[member]].astype(np.int64)
        point_ts_arr = ts_a[member]
        if cache is not None:
            import jax
            import jax.numpy as jnp
            from opentsdb_tpu.ops import shapes
            # cache the counts matrix PRE-PADDED to its shape bucket:
            # warm queries then skip both the pad alloc and the
            # re-upload (histogram_percentile_pipeline pads seg_ids to
            # the row count)
            n_pad = shapes.shape_bucket(len(counts))
            counts = shapes.pad_2d_host(counts, n_pad,
                                        counts.shape[1], 0.0)
            counts = jax.device_put(
                jnp.asarray(counts, dtype=jnp.float32))
            cache.put(ckey, cver, (counts,), {
                "point_sidx": point_sidx, "point_ts": point_ts_arr,
                "bounds": bounds})

    # device path (uniform bounds): merge = one-hot MXU contraction,
    # percentiles = cumsum + rank compare — ops.histogram_kernels.
    # The time axis is downsample buckets when ds_spec is set
    # (HistogramDownsampler parity), else the distinct-timestamp union.
    from opentsdb_tpu.ops.histogram_kernels import \
        histogram_percentile_pipeline
    time_idx, ts_out_arr, in_range = _time_axis(point_ts_arr, tsq, sub)
    gvec = np.asarray(group_ids, dtype=np.int64)[point_sidx]
    if not in_range.all():
        # partial-range: filter the REAL rows (cached counts may carry
        # shape-bucket padding past len(point_sidx))
        counts = np.asarray(counts)[:len(point_sidx)][in_range]
        gvec = gvec[in_range]
        time_idx = time_idx[in_range]
    if len(gvec) == 0:
        return []
    num_ts = len(ts_out_arr)
    seg = (gvec * num_ts + time_idx).astype(np.int32)
    pcts = histogram_percentile_pipeline(
        counts, seg, num_groups * num_ts, np.asarray(bounds),
        sub.percentiles)                       # [Q, G*T]
    pcts = pcts.reshape(len(sub.percentiles), num_groups, num_ts)
    present = np.bincount(seg, minlength=num_groups * num_ts) \
        .reshape(num_groups, num_ts) > 0

    return _emit_groups(tsdb, tsq, sub, tag_mat, group_ids, num_groups,
                        ts_out_arr, present, pcts)


def _emit_groups(tsdb, tsq, sub, tag_mat, group_ids, num_groups,
                 ts_arr, present, pcts) -> list:
    """Shared emission: one QueryResult per (group, percentile)."""
    from opentsdb_tpu.query.engine import QueryResult, _common_tags
    uids = tsdb.uids
    order = np.argsort(group_ids, kind="stable")
    sorted_gids = group_ids[order]
    gid_range = np.arange(num_groups, dtype=group_ids.dtype)
    starts = np.searchsorted(sorted_gids, gid_range, side="left")
    ends = np.searchsorted(sorted_gids, gid_range, side="right")
    ts_list = (ts_arr if tsq.ms_resolution
               else (ts_arr // 1000) * 1000).tolist()
    out = []
    for gid in range(num_groups):
        members = order[starts[gid]:ends[gid]]
        if len(members) == 0 or not present[gid].any():
            continue
        tags, agg_tags = _common_tags(tag_mat, members, uids)
        sel = np.nonzero(present[gid])[0]
        for qi, q in enumerate(sub.percentiles):
            vals = pcts[qi, gid, sel].tolist()
            dps = [(ts_list[t], v) for t, v in zip(sel.tolist(), vals)]
            out.append(QueryResult(
                metric=f"{sub.metric}_pct_{q:g}", tags=tags,
                aggregated_tags=agg_tags, dps=dps,
                sub_query_index=sub.index))
    return out


def _run_mixed_bounds(tsdb, tsq, sub, active, sids, tag_mat, group_ids,
                      num_groups) -> list:
    """Host fallback when the window's histograms disagree on bucket
    bounds: per-group merge keyed on the output timestamp, each slot
    keeping its own bounds (the reference merges Histogram objects per
    emitted timestamp; bounds must agree across series AT one ts — ref
    HistogramAggregationIterator). Slot assignment and per-point group
    ids are computed ONCE per bounds-class; the per-group work is a
    mask + segment-sum, no per-point Python.

    ``active`` carries pre-masked snapshots:
    [((bounds, ts, sid, rows), pos, window_member_mask), ...].
    """
    from opentsdb_tpu.query.engine import QueryResult, _common_tags
    from opentsdb_tpu.ops import downsample as ds_mod
    uids = tsdb.uids
    sids = np.asarray(sids)
    sid_order = np.argsort(sids, kind="stable")
    sorted_sids = sids[sid_order]
    gids_sorted = np.asarray(group_ids)[sid_order]

    # per bounds-class precompute: filtered points, their group ids,
    # and their output slot (group-independent)
    pre = []
    for (bounds, ts_a, sid_a, rows), _pos, m in active:
        ts_f, sid_f, rows_f = ts_a[m], sid_a[m], rows[m]
        pos = np.searchsorted(sorted_sids, sid_f)
        point_gid = gids_sorted[np.clip(pos, 0, len(sorted_sids) - 1)]
        if sub.ds_spec is not None:
            bidx, bts = ds_mod.assign_buckets(
                ts_f, sub.ds_spec, tsq.start_ms, tsq.end_ms)
            bidx = np.asarray(bidx)
            bts = np.asarray(bts)
            ok = (bidx >= 0) & (bidx < len(bts))
            slots = bts[np.clip(bidx, 0, len(bts) - 1)]
            ts_f, rows_f = ts_f[ok], rows_f[ok]
            point_gid, slots = point_gid[ok], slots[ok]
        else:
            slots = ts_f
        pre.append((bounds, point_gid, slots, rows_f))

    # one argsort for per-group member recovery (same pattern as
    # _emit_groups; an == scan per group would be O(G x S))
    gid_order = np.argsort(group_ids, kind="stable")
    gids_in_order = np.asarray(group_ids)[gid_order]
    gid_range = np.arange(num_groups, dtype=np.asarray(group_ids).dtype)
    g_starts = np.searchsorted(gids_in_order, gid_range, side="left")
    g_ends = np.searchsorted(gids_in_order, gid_range, side="right")

    out = []
    for gid in range(num_groups):
        merged: dict[int, tuple[tuple, np.ndarray]] = {}
        for b, point_gid, slots_all, rows_f in pre:
            gmask = point_gid == gid
            if not gmask.any():
                continue
            slots = slots_all[gmask]
            uniq, inv = np.unique(slots, return_inverse=True)
            acc = np.zeros((len(uniq), rows_f.shape[1]),
                           dtype=np.float64)
            np.add.at(acc, inv, rows_f[gmask])
            for k, slot in enumerate(uniq.tolist()):
                if slot in merged:
                    b0, prev = merged[slot]
                    if b0 != b:
                        raise BadRequestError(
                            "cannot merge histograms with different "
                            f"buckets at timestamp {slot}")
                    merged[slot] = (b0, prev + acc[k])
                else:
                    merged[slot] = (b, acc[k])
        if not merged:
            continue
        members = gid_order[g_starts[gid]:g_ends[gid]]
        ts_sorted = sorted(merged)
        pcts = np.stack([
            percentiles_from_counts(
                merged[t][1][None, :],
                np.asarray(merged[t][0], dtype=np.float64),
                sub.percentiles)[:, 0]
            for t in ts_sorted], axis=1)       # [Q, T]
        tags, agg_tags = _common_tags(tag_mat, members, uids)
        for qi, q in enumerate(sub.percentiles):
            dps = [((t // 1000) * 1000 if not tsq.ms_resolution else t,
                    float(pcts[qi, ti]))
                   for ti, t in enumerate(ts_sorted)]
            out.append(QueryResult(
                metric=f"{sub.metric}_pct_{q:g}", tags=tags,
                aggregated_tags=agg_tags, dps=dps,
                sub_query_index=sub.index))
    return out
