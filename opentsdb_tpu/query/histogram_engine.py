"""Histogram / percentile query path.

(ref: ``TsdbQuery.isHistogramQuery`` :776 routes queries with
``percentiles`` set to the HistogramSpan/HistogramAggregationIterator
pipeline; merge is bucket-wise SUM, then ``SimpleHistogram.percentile``)

The window's histogram points of a metric live on the device ONCE, as
``[series x slot x bin]`` counts (:class:`ResidentCounts`: a series
with a point in the window a row, a distinct timestamp a slot), keyed
in the HBM cache by metric, window and ``TSDB._histogram_version``:
never by the series a request selects, so a request that excludes
another rack reads the same resident counts and uploads one int32
group label a series. A write bumps the version and drops them.

A request is the five stages of every other query under
``query.execute``: ``query.plan`` (the filters and the group labels
from the histogram store's :class:`~opentsdb_tpu.query.plan.PlanIndex`,
as the scalar path), ``query.upload`` / ``query.program`` /
``query.download`` (:func:`~opentsdb_tpu.ops.pipeline.run_staged` around
:func:`~opentsdb_tpu.ops.histogram_kernels.histogram_percentiles`,
``class=histogram``) and ``query.assemble``.

Downsampling (ref: ``HistogramDownsampler.java`` wrapping each span
before the group merge): histogram aggregation is bucket-wise SUM both
across series and across time (``HistogramAggregation.java:20`` — SUM is
the only defined merge), so the time merge is a second, small
contraction of the group-merged counts: each slot into its downsample
bucket, or into itself without a downsample (ref: the raw
HistogramAggregationIterator union merge).

Three ways leave the resident program and answer from the float64
arena on the host instead (:func:`_host_merge`, ``placement=host``):
bounds that disagree inside the window (:func:`_run_mixed_bounds`), a
window too sparse or too large to lay out densely, and a merged total
at or above 2**24, which float32 may have rounded
(``tsd.query.histogram.wide_counts``).
"""

from __future__ import annotations

from functools import partial
from typing import NamedTuple

import jax
import numpy as np

from opentsdb_tpu.core.histogram import RESIDENT_KEY
from opentsdb_tpu.obs.trace import trace_span
from opentsdb_tpu.query import device_cache
from opentsdb_tpu.query.model import BadRequestError, TSQuery, TSSubQuery
from opentsdb_tpu.query.plan import (_common_tags, _UidNameCache,
                                     group_tag_summary)

#: a dense layout may hold this many cells a stored point (a gappy
#: fleet fills nearly all of its cells; series that share no
#: timestamps fill one in their count) ...
DENSE_MAX_SPREAD = 8
#: ... unless it is this small anyway (cells of one bin)
DENSE_SMALL_CELLS = 1 << 16
#: the host's share of the dense layout while it is put on the device
_BLOCK_BYTES = 64 << 20
#: the largest dense layout a request builds where no HBM cache would
#: keep it (``tsd.query.device_cache_mb = 0``): the engine's cell
#: budget in float32
DEFAULT_DENSE_BYTES = 4 << 26
#: what the resident program's closure returns for a merged total that
#: float32 may have rounded
_WIDE = object()


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
    """(time_idx[N], ts_out[T], in_range[N]) for timestamps of the
    window: downsample bucket indices when the sub-query has a
    downsample spec (ref: HistogramDownsampler), else one slot per
    distinct timestamp (ref: the raw HistogramAggregationIterator
    union merge)."""
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


class ResidentCounts(NamedTuple):
    """One metric's histogram points of one window, laid out for
    :func:`histogram_percentiles` on the device ``counts`` is
    committed to."""
    counts: object          # [S_pad, slots_pad * bins] float32
    present: object         # [S_pad, slots_pad] float32, 1 = a point
    row_of_sid: np.ndarray  # int32 [max sid + 1], -1 = no point here
    slot_ts: np.ndarray     # int64 [slots], ascending
    bounds: tuple
    host: bool              # placed on the host CPU backend

    @property
    def nbytes(self) -> int:
        return self.counts.nbytes + self.present.nbytes


def _window_points(tsdb, metric_id: int, start_ms: int, end_ms: int):
    """The bounds classes of the metric with a point in the window:
    ``[(bounds, ts, sid, rows, window mask)]`` over stable snapshot
    views (captured under the append-side lock; see
    ``HistogramArena._Sub.snapshot``)."""
    with tsdb._histogram_lock:
        arena = tsdb._histogram_arenas.get(metric_id)
        snaps = [(s.bounds, *s.snapshot())
                 for s in arena.groups.values()] if arena else []
    out = []
    for bounds, ts, sid, rows in snaps:
        inside = (ts >= start_ms) & (ts <= end_ms)
        if inside.any():
            out.append((bounds, ts, sid, rows, inside))
    return out


def _windowed(ts, sid, rows, inside):
    """The snapshot cut to the window; the views themselves where the
    window holds every point (a deployment's usual dashboard)."""
    if inside.all():
        return ts, sid, rows
    return ts[inside], sid[inside], rows[inside]


def _make_resident(engine, bounds, ts, sid, rows, budget_bytes: int
                   ) -> "ResidentCounts | None":
    """Lay the window's points out densely and put them on the device
    the engine's placement budgets name; None where a dense layout
    would be mostly empty or larger than ``budget_bytes``."""
    from opentsdb_tpu.ops import shapes
    from opentsdb_tpu.query.engine import host_tail_for_dims
    nb = rows.shape[1]
    sids, slot_ts = np.unique(sid), np.unique(ts)
    s_pad = shapes.shape_bucket(len(sids))
    p_pad = shapes.shape_bucket(len(slot_ts))
    cells = s_pad * p_pad
    if cells * nb * 4 > budget_bytes or (
            cells > DENSE_SMALL_CELLS
            and cells > DENSE_MAX_SPREAD * len(ts)):
        return None
    # the budgets of a linear tail (a contraction, no sort); an open
    # breaker pins it to the host or refuses (DegradedError)
    dims = (s_pad, p_pad * nb, 1)
    device = engine._tail_device(*dims, False, rank_class=False)
    if device is not None and host_tail_for_dims(
            engine.tsdb.config, *dims, rank_class=False) is None:
        return None     # too large for the host backend: the arena
    row_of_sid = np.full(int(sids[-1]) + 1, -1, dtype=np.int32)
    row_of_sid[sids] = np.arange(len(sids), dtype=np.int32)
    row = row_of_sid[sid]
    slot = np.searchsorted(slot_ts, ts).astype(np.int32)
    present = np.bincount(row.astype(np.int64) * p_pad + slot,
                          minlength=cells) \
        .reshape(s_pad, p_pad).astype(np.float32)
    return ResidentCounts(
        _put_dense(row, slot, rows, present, device),
        jax.device_put(present, device), row_of_sid, slot_ts, bounds,
        device is not None)


@partial(jax.jit, donate_argnums=(0,))
def _place_rows(counts, block, first_row):
    """``block`` written into ``counts`` from ``first_row`` on, where
    it stands (the argument is donated)."""
    return jax.lax.dynamic_update_slice(counts, block, (first_row, 0))


def _put_dense(row, slot, rows, present, device):
    """The dense float32 counts ``[series, slots * bins]`` on
    ``device``, assembled there a block of series at a time: the host
    holds one block of :data:`_BLOCK_BYTES`, written again and again,
    never the layout (3.8 GB at 12M points: fresh memory is the dear
    thing on a sandboxed host, 5-20 us a page). Two points of one
    series at one timestamp (``present`` above 1) are summed: SUM
    merges them, here as well as later."""
    import jax.numpy as jnp
    s_pad, p_pad = present.shape
    nb = rows.shape[1]
    width = p_pad * nb
    if len(row) > 1 and not (row[1:] >= row[:-1]).all():
        order = np.argsort(row, kind="stable")      # a loader writes
        row, slot, rows = row[order], slot[order], rows[order]
    # s_pad is {4..7} * 2**k: a power of two of rows divides it
    block_rows = s_pad
    while block_rows * width * 4 > _BLOCK_BYTES and block_rows % 2 == 0:
        block_rows //= 2
    doubled = bool((present > 1.0).any())
    counts = jnp.zeros((s_pad, width), dtype=jnp.float32, device=device)
    block = np.empty((block_rows, p_pad, nb), dtype=np.float32)
    edges = np.searchsorted(row, np.arange(0, s_pad + 1, block_rows))
    for first, lo, hi in zip(range(0, s_pad, block_rows), edges[:-1],
                             edges[1:]):
        if lo == hi:
            continue            # padded rows: zeros as they stand
        block[:] = 0.0
        at = (row[lo:hi] - first, slot[lo:hi])
        if doubled:
            np.add.at(block, at, rows[lo:hi])
        else:
            block[at] = rows[lo:hi]
        # the block is read by the transfer: wait before it is reused
        counts = jax.block_until_ready(_place_rows(
            counts, jax.device_put(block.reshape(block_rows, width),
                                   device), first))
    return counts


def _host_merge(ts, sid, rows, bounds, label_of_sid, num_groups: int,
                tsq, sub):
    """The float64 answer from the arena's own rows: (values
    [Q, G, T], points [G, T], ts_out [T]). One sort of the selected
    points by (group, time) and one ``reduceat``: exact below 2**53."""
    known = sid < len(label_of_sid)
    label = np.where(known, label_of_sid[np.where(known, sid, 0)], -1)
    time_idx, ts_out, in_range = _time_axis(ts, tsq, sub)
    keep = (label >= 0) & in_range
    num_ts = len(ts_out)
    nb = rows.shape[1]
    seg = label[keep].astype(np.int64) * num_ts + time_idx[keep]
    merged = np.zeros((num_groups * num_ts, nb), dtype=np.float64)
    points = np.bincount(seg, minlength=num_groups * num_ts)
    if len(seg):
        order = np.argsort(seg, kind="stable")
        seg = seg[order]
        starts = np.flatnonzero(np.diff(seg, prepend=-1))
        merged[seg[starts]] = np.add.reduceat(
            rows[keep][order].astype(np.float64, copy=False), starts,
            axis=0)
    values = percentiles_from_counts(
        merged, np.asarray(bounds, dtype=np.float64), sub.percentiles)
    return (values.reshape(len(sub.percentiles), num_groups, num_ts),
            points.reshape(num_groups, num_ts), ts_out)


def run_histogram_subquery(engine, tsq: TSQuery, sub: TSSubQuery
                           ) -> list:
    """Execute a percentile sub-query over stored histogram datapoints
    (``engine``: the :class:`~opentsdb_tpu.query.engine.QueryEngine`
    that runs it)."""
    from opentsdb_tpu.ops.histogram_kernels import HistogramSpec
    from opentsdb_tpu.ops.pipeline import run_staged
    tsdb = engine.tsdb
    uids = tsdb.uids
    try:
        metric_id = uids.metrics.get_id(sub.metric)
    except LookupError:
        raise BadRequestError(
            f"No such name for 'metrics': '{sub.metric}'") from None
    store = tsdb.histogram_store
    with trace_span("query.plan", sub=sub.index) as span:
        sids = store.series_ids_for_metric(metric_id)
        if len(sids) == 0:
            return []
        # filters and group labels as the scalar path plans them: the
        # plan index of the histogram store's tag index
        sids, tag_mat, plan_tags = engine._apply_filters(store, sub,
                                                         sids)
        if span is not None:
            span.tag(**plan_tags)
        if len(sids) == 0:
            return []
        gb_kids = sorted({uids.tag_names.get_id(f.tagk)
                          for f in sub.filters if f.group_by
                          and uids.tag_names.has_name(f.tagk)})
        group_ids, num_groups = engine._group_ids(tag_mat, gb_kids)
        if span is not None:
            span.tag(series=len(sids), groups=num_groups)

    stats = tsdb.histogram_stats
    cache = tsdb.device_grid_cache
    window = None

    def lay_out():
        """The window's counts laid out on the device now: the cache's
        ``hist`` kind (nothing to keep: the window has no dense
        layout)."""
        nonlocal window
        window = _window_points(tsdb, metric_id, tsq.start_ms,
                                tsq.end_ms)
        made = None
        if len(window) == 1:
            bounds, ts_a, sid_a, rows_a, inside = window[0]
            with trace_span("query.upload", resident="built"):
                made = _make_resident(
                    engine, bounds,
                    *_windowed(ts_a, sid_a, rows_a, inside),
                    cache.max_bytes if cache is not None
                    else DEFAULT_DENSE_BYTES)
        if made is None:
            return None, {"resident": None}
        stats.add(upload_bytes=made.nbytes)
        return (made.counts, made.present), {"resident": made}

    # one layout at a time: two requests that miss together would put
    # the counts up twice (3.8 GB each at 12M points). Of one window,
    # the second waits for the first's and reads it (the key's
    # flight); of two windows, for its turn (the kind takes turns:
    # device_cache.SERIAL_BUILD_KINDS)
    _, meta, _ = device_cache.resident(
        cache, (RESIDENT_KEY, metric_id, tsq.start_ms, tsq.end_ms),
        lambda: tsdb._histogram_version, lay_out)
    resident = meta["resident"]
    if window is not None and not window:
        return []
    if window is not None and len(window) > 1:
        # bounds genuinely disagree INSIDE the window: host merge with
        # per-slot bounds checks. A bounds class with no points in the
        # window must not disable the device path (a single stray
        # historic migration would otherwise penalize every future
        # query).
        return _run_mixed_bounds(tsdb, tsq, sub, window, sids, tag_mat,
                                 group_ids, num_groups)

    def on_host():
        """The float64 host path, through the same three stages."""
        points = window if window is not None else _window_points(
            tsdb, metric_id, tsq.start_ms, tsq.end_ms)
        if len(points) != 1:       # a write changed the window's
            return None            # classes since the plan
        bounds, ts_a, sid_a, rows_a, inside = points[0]
        label_of_sid = np.full(int(sids.max()) + 1, -1, dtype=np.int32)
        label_of_sid[sids] = group_ids
        args = (*_windowed(ts_a, sid_a, rows_a, inside), bounds,
                label_of_sid, num_groups, tsq, sub)
        spec = HistogramSpec(len(sids), 0, 0, num_groups,
                             rows_a.shape[1], host=True)
        return run_staged("hist_host", _host_merge, lambda: args,
                          spec=spec)

    if resident is None:
        out = on_host()
    else:
        out = _on_resident(engine, resident, sids, group_ids,
                           num_groups, tsq, sub, on_host)
    if out is None:
        return []
    values, points, ts_out = out
    stats.add(query_points=int(points.sum()))
    with trace_span("query.assemble", sub=sub.index,
                    groups=num_groups) as span:
        return _emit_groups(tsdb, tsq, sub, tag_mat, group_ids,
                            num_groups, gb_kids, ts_out, points > 0,
                            values, span)


def _on_resident(engine, resident: ResidentCounts, sids, group_ids,
                 num_groups: int, tsq, sub, on_host):
    """One request over the resident counts: (values [Q, G, T], points
    [G, T], ts_out [T]). What it uploads depends on its filter alone:
    one int32 label a resident row (excluded series and padded rows on
    the dummy group) and each slot's bucket."""
    from opentsdb_tpu.ops import shapes
    from opentsdb_tpu.ops.histogram_kernels import (EXACT_BELOW,
                                                    HistogramSpec,
                                                    histogram_percentiles)
    from opentsdb_tpu.ops.pipeline import run_staged
    stats = engine.tsdb.histogram_stats
    s_pad, p_pad = resident.present.shape
    nb = len(resident.bounds) - 1
    time_idx, ts_out, in_range = _time_axis(resident.slot_ts, tsq, sub)
    num_ts = len(ts_out)
    merge_time = sub.ds_spec is not None
    spec = HistogramSpec(
        num_series=s_pad, num_slots=p_pad,
        num_buckets=shapes.shape_bucket(num_ts + 1) if merge_time
        else p_pad, merge_time=merge_time,
        num_groups=shapes.shape_bucket(num_groups + 1), num_bins=nb,
        host=resident.host)
    device = next(iter(resident.counts.devices()))

    def operands():
        labels = np.full(s_pad, spec.num_groups - 1, dtype=np.int32)
        known = sids < len(resident.row_of_sid)
        row = resident.row_of_sid[sids[known]]
        labels[row[row >= 0]] = group_ids[known][row >= 0]
        slot_bucket = np.full(p_pad, spec.num_buckets - 1,
                              dtype=np.int32)
        slot_bucket[:len(time_idx)] = np.where(
            in_range, time_idx, spec.num_buckets - 1)
        b = np.asarray(resident.bounds, dtype=np.float64)
        # q / 100 in float64, rounded once: the program multiplies
        small = (labels, slot_bucket,
                 ((b[:-1] + b[1:]) / 2.0).astype(np.float32),
                 (np.asarray(sub.percentiles, dtype=np.float64)
                  / 100.0).astype(np.float32))
        stats.add(upload_bytes=sum(a.nbytes for a in small))
        return (resident.counts, resident.present,
                *(jax.device_put(a, device) for a in small), spec)

    def compute():
        values, points, widest = run_staged(
            "hist", histogram_percentiles, operands, spec=spec)
        if widest >= EXACT_BELOW:
            return _WIDE
        return (values[:, :num_groups, :num_ts],
                points[:num_groups, :num_ts], ts_out)

    out = engine._run_device(compute, host_retry=on_host,
                             on_device=not resident.host)
    if out is _WIDE:
        # float32 may have rounded a merged count: the answer again,
        # from the float64 arena
        stats.add(wide_counts=1)
        return on_host()
    return out


def _emit_groups(tsdb, tsq, sub, tag_mat, group_ids, num_groups,
                 gb_kids, ts_arr, present, pcts, span) -> list:
    """One QueryResult per (group, percentile), a group's tags by the
    SpanGroup rule from :func:`~opentsdb_tpu.query.plan.
    group_tag_summary` (the plan index's cached layout where the
    selection came from one), as the scalar path's assemble stage."""
    from opentsdb_tpu.query.engine import QueryResult
    way, minv, maxv, _members, _source = group_tag_summary(
        tag_mat, group_ids, num_groups, gb_kids)
    if span is not None:
        span.tag(tags=way)
    kname = _UidNameCache(tsdb.uids.tag_names)
    vname = _UidNameCache(tsdb.uids.tag_values)
    ts_list = (ts_arr if tsq.ms_resolution
               else (ts_arr // 1000) * 1000).tolist()
    out = []
    for gid in range(num_groups):
        sel = np.nonzero(present[gid])[0]
        if len(sel) == 0:
            continue
        tags: dict[str, str] = {}
        agg_tags: list[str] = []
        for j, kid in enumerate(tag_mat.kids):
            lo = minv[gid, j]
            if lo < 0:
                continue    # absent on some member: the key vanishes
            if lo == maxv[gid, j]:
                tags[kname(int(kid))] = vname(int(lo))
            else:
                agg_tags.append(kname(int(kid)))
        for qi, q in enumerate(sub.percentiles):
            vals = pcts[qi, gid, sel].tolist()
            dps = [(ts_list[t], v) for t, v in zip(sel.tolist(), vals)]
            out.append(QueryResult(
                metric=f"{sub.metric}_pct_{q:g}", tags=dict(tags),
                aggregated_tags=list(agg_tags), dps=dps,
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

    ``active`` is :func:`_window_points`' list:
    [(bounds, ts, sid, rows, window mask), ...].
    """
    from opentsdb_tpu.query.engine import QueryResult
    from opentsdb_tpu.ops import downsample as ds_mod
    uids = tsdb.uids
    sids = np.asarray(sids)
    sid_order = np.argsort(sids, kind="stable")
    sorted_sids = sids[sid_order]
    gids_sorted = np.asarray(group_ids)[sid_order]

    # per bounds-class precompute: filtered points, their group ids,
    # and their output slot (group-independent)
    pre = []
    for bounds, ts_a, sid_a, rows, inside in active:
        pos = np.clip(np.searchsorted(sorted_sids, sid_a), 0,
                      len(sorted_sids) - 1)
        m = inside & (sorted_sids[pos] == sid_a)
        if not m.any():
            continue
        ts_f, rows_f = ts_a[m], rows[m]
        point_gid = gids_sorted[pos[m]]
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
