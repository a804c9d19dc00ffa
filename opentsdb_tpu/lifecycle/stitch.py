"""Stitched read view: cold disk segments + rollup-tier history + raw
tail across the spill and demotion boundaries.

After age-based demotion, raw points older than a metric's demotion
boundary exist only in the rollup tiers; the raw store keeps the tail.
After a cold spill, the oldest tier history lives in mmap-backed disk
segments (:mod:`opentsdb_tpu.coldstore`) instead of RAM. A query
spanning the boundaries must read all three — this module exposes one
``TimeSeriesStore``-shaped object the query engine can select exactly
like a plain tier store:

- series identity (sids, metric index, tag matrices, shards) is the
  RAW store's: every live series has a raw record even when all its
  points were demoted, so filters/group-by/result assembly are
  unchanged;
- reads split at ``spill_boundary_ms`` and ``boundary_ms``: cold
  segments serve ``[start, spill)``, the in-RAM tier serves
  ``[spill, boundary)`` (raw sids mapped to tier sids by (metric,
  tags) identity; the cold view does its own identity mapping) and
  the raw store serves ``[boundary, end]``;
- ``bucket_reduce`` combines the two halves channel-wise so the
  engine's grid path (and the avg sum/count division) is
  value-identical to an undemoted store for decomposable
  downsample functions — each query bucket receives tier cells whose
  source points it fully contains plus raw tail points, and sums of
  sums / mins of mins / counts of counts are exact (the same
  decomposition ``rollup/job.py`` writes). Queries whose start is not
  tier-aligned inherit the pre-existing rollup divergence (a tier
  cell is attributed to the bucket holding its edge).

``tail_stat`` names the statistic the tier's point VALUES carry, so
the raw tail contributes the matching channel: a ``count`` tier's
stitched view materializes tail points with value 1.0 (summing them
counts them) and adds raw bucket counts into the sums channel of
``bucket_reduce``.

Versioning: ``points_written`` / ``mutation_epoch`` are the sums of
all stitched parts, so every read-side cache (result cache, device
grid cache, prepared-batch pools) invalidates on a write or sweep to
any of them. Instances are cached per (metric, tier, boundary) by the
lifecycle manager — a moved boundary mints a fresh ``instance_id``,
orphaning stale cache entries instead of aliasing them.

Degradation: the cold third runs behind :meth:`StitchedStore._cold`
— a failed cold read (corrupt segment, disk error, armed
``coldstore.read`` fault) or an open cold read breaker degrades that
request to tier/raw serving (partial history, 200) instead of a 500,
and bumps the cold ``mutation_epoch`` so the degraded result is
already stale for every later result-cache lookup. ``delete_range``
deliberately does NOT degrade — a delete that silently skipped the
cold rows would report success for points still on disk.
"""

from __future__ import annotations

import threading

import numpy as np

from opentsdb_tpu.core.store import (PaddedBatch, PointBatch,
                                     STORE_INSTANCE_IDS,
                                     padded_from_batch)

_TAIL_STATS = ("sum", "count", "min", "max")


def guarded_sketch_rows(cold, metric: str, start_ms: int, end_ms: int
                        ) -> tuple[list, bool]:
    """Cold sketch-column read behind the same degradation guard as
    :meth:`StitchedStore._cold`: an open read breaker or a failed read
    degrades to ``([], False)`` — the caller serves the remaining
    zones (partial history, 200) and the epoch bump in the notes makes
    the partial result stale for later cache lookups."""
    breaker = getattr(cold, "read_breaker", None)
    if breaker is not None and not breaker.allow():
        cold.note_degraded_serve()
        return [], False
    try:
        rows = cold.sketch_rows(metric, None, start_ms, end_ms)
    except Exception as exc:  # noqa: BLE001 - degrade, never 500
        if breaker is not None:
            breaker.record_failure()
        cold.note_read_error(exc)
        return [], False
    if breaker is not None:
        breaker.record_success()
    return rows, True


def sketch_zone_read(tsdb, metric: str, metric_id: int,
                     start_ms: int, end_ms: int):
    """The sketch twin of the stitched three-way read: per-series
    quantile sketches split at the spill and demotion boundaries.

    Returns ``(items, raw_rng, cold_ok)``:

    - ``items``: ``(tags_names_tuple, cell_ts, DDSketch)`` rows from
      the cold segments' sketch column (``cell_ts < spill_b``) and the
      in-RAM sketch tier (``spill_b <= cell_ts < demote_b``). The zone
      split is by cell timestamp, so a RAM cell whose spilled disk
      duplicate still lingers (crash reconciliation) is counted once.
    - ``raw_rng``: the ``[demote_b, end]`` raw-tail window the caller
      folds itself (None when the window ends before the boundary).
    - ``cold_ok``: False when the cold zone degraded (breaker open,
      read error, undecodable blob) — partial history, never a 500.
    """
    from opentsdb_tpu.sketch.ddsketch import DDSketch, SketchError
    lc = tsdb.lifecycle
    sketches = getattr(lc, "sketches", None) if lc is not None \
        else None
    demote_b = lc.demote_boundary(metric_id) if lc is not None else 0
    cold = getattr(lc, "coldstore", None) if lc is not None else None
    spill_b = 0
    if cold is not None and sketches is not None and demote_b:
        # same clamp as StitchedStore: cold never serves past the
        # demotion boundary
        spill_b = min(cold.spill_boundary(metric), demote_b)
    items: list[tuple[tuple, int, DDSketch]] = []
    cold_ok = True
    if spill_b and start_ms < spill_b:
        rows, cold_ok = guarded_sketch_rows(
            cold, metric, start_ms, min(end_ms, spill_b - 1))
        for tags, cts, blob in rows:
            try:
                items.append((tags, cts, DDSketch.from_bytes(blob)))
            except (SketchError, ValueError):
                cold_ok = False  # corrupt blob: serve the rest
    if sketches is not None and demote_b:
        lo = max(start_ms, spill_b)
        hi = min(end_ms, demote_b - 1)
        if lo <= hi:
            items.extend(sketches.cells(metric, lo, hi))
    raw_lo = max(start_ms, demote_b)
    raw_rng = (raw_lo, end_ms) if raw_lo <= end_ms else None
    return items, raw_rng, cold_ok


class StitchedStore:
    """(see module docstring)"""

    fault_site = "store"

    def __init__(self, raw_store, tier_store, metric_id: int,
                 boundary_ms: int, tail_stat: str, cold=None,
                 spill_boundary_ms: int = 0, cold_store=None):
        if tail_stat not in _TAIL_STATS:
            raise ValueError(f"bad tail_stat {tail_stat!r}")
        self.instance_id = next(STORE_INSTANCE_IDS)
        self.raw = raw_store
        self.tier = tier_store
        self.metric_id = metric_id
        self.boundary_ms = int(boundary_ms)
        self.tail_stat = tail_stat
        # cold third (ColdStatView) + its owning ColdStore (breaker,
        # degradation counters). The spill boundary is CLAMPED to the
        # demotion boundary: a manifest claiming more would make cold
        # and raw both serve [boundary, spill) — the one invariant a
        # corrupt manifest must not break (fsck reports the excess).
        self.cold = cold
        self.cold_store = cold_store
        self.spill_boundary_ms = min(int(spill_boundary_ms),
                                     self.boundary_ms) \
            if cold is not None else 0
        self.num_shards = raw_store.num_shards
        self._map_lock = threading.Lock()
        # raw sid -> tier sid map, versioned by both stores' series
        # counts (identity indexes are append-only)
        self._sid_map: tuple | None = None

    # -- identity surface: the RAW store's ---------------------------------

    @property
    def fault_injector(self):
        return self.raw.fault_injector

    @property
    def points_written(self) -> int:
        n = self.raw.points_written + self.tier.points_written
        if self.cold is not None:
            n += self.cold.points_written
        return n

    @property
    def mutation_epoch(self) -> int:
        e = (getattr(self.raw, "mutation_epoch", 0)
             + getattr(self.tier, "mutation_epoch", 0))
        if self.cold is not None:
            e += self.cold.mutation_epoch
        return e

    def series(self, series_id: int):
        return self.raw.series(series_id)

    def num_series(self) -> int:
        return self.raw.num_series()

    def metric_ids(self):
        return self.raw.metric_ids()

    def metric_index(self, metric_id: int):
        return self.raw.metric_index(metric_id)

    def series_ids_for_metric(self, metric_id: int) -> np.ndarray:
        return self.raw.series_ids_for_metric(metric_id)

    def shards_of(self, series_ids):
        return self.raw.shards_of(series_ids)

    def total_points(self) -> int:
        n = self.raw.total_points() + self.tier.total_points()
        if self.cold is not None:
            n += self.cold.total_points()
        return n

    # -- sid mapping --------------------------------------------------------

    def _tier_sids(self, sids: np.ndarray) -> np.ndarray:
        """Tier sid per raw sid (-1 when the tier never saw the
        series). Cached over the full metric, invalidated by either
        index growing."""
        from opentsdb_tpu.query.plan import _match_series_by_tags
        key = (self.raw.num_series(), self.tier.num_series())
        with self._map_lock:
            cached = self._sid_map
            if cached is None or cached[0] != key:
                all_raw = self.raw.series_ids_for_metric(self.metric_id)
                mapped = _match_series_by_tags(
                    self.raw, self.tier, all_raw, self.metric_id)
                order = np.argsort(all_raw, kind="stable")
                cached = (key, all_raw[order], mapped[order])
                self._sid_map = cached
        _, sorted_raw, sorted_tier = cached
        sids = np.asarray(sids, dtype=np.int64)
        if len(sorted_raw) == 0:
            return np.full(len(sids), -1, dtype=np.int64)
        pos = np.searchsorted(sorted_raw, sids)
        pos_c = np.minimum(pos, len(sorted_raw) - 1)
        hit = sorted_raw[pos_c] == sids
        return np.where(hit, sorted_tier[pos_c], -1)

    def _split(self, start_ms: int, end_ms: int):
        """(cold_range | None, tier_range | None, raw_range | None)
        for one request. With no cold third the spill boundary is 0
        and the cold range is always None."""
        b = self.boundary_ms
        s = self.spill_boundary_ms
        cold_rng = (start_ms, min(end_ms, s - 1)) \
            if s and start_ms < s else None
        tier_lo = max(start_ms, s)
        tier_rng = (tier_lo, min(end_ms, b - 1)) \
            if tier_lo < b and tier_lo <= end_ms else None
        raw_rng = (max(start_ms, b), end_ms) if end_ms >= b else None
        return cold_rng, tier_rng, raw_rng

    def _cold(self, fn_name: str, *args):
        """Run one cold read behind the degradation guard: an open
        read breaker skips the call, a failure records it — either way
        the caller serves tier/raw only (None return). The cold
        mutation epoch bump inside the notes makes the partial result
        stale for every later result-cache lookup."""
        cs = self.cold_store
        breaker = getattr(cs, "read_breaker", None) \
            if cs is not None else None
        if breaker is not None and not breaker.allow():
            cs.note_degraded_serve()
            return None
        try:
            out = getattr(self.cold, fn_name)(*args)
        except Exception as exc:  # noqa: BLE001 - degrade, never 500
            if breaker is not None:
                breaker.record_failure()
            if cs is not None:
                cs.note_read_error(exc)
            return None
        if breaker is not None:
            breaker.record_success()
        return out

    # -- reads --------------------------------------------------------------

    def count_range(self, series_ids, start_ms: int,
                    end_ms: int) -> np.ndarray:
        sids = np.asarray(series_ids, dtype=np.int64)
        out = np.zeros(len(sids), dtype=np.int64)
        cold_rng, tier_rng, raw_rng = self._split(start_ms, end_ms)
        if raw_rng is not None:
            out += self.raw.count_range(sids, *raw_rng)
        if tier_rng is not None:
            tsids = self._tier_sids(sids)
            present = np.nonzero(tsids >= 0)[0]
            if len(present):
                out[present] += self.tier.count_range(
                    tsids[present], *tier_rng)
        if cold_rng is not None:
            got = self._cold("count_range", sids, *cold_rng)
            if got is not None:
                out += got
        return out

    def bucket_reduce(self, series_ids, start_ms: int, end_ms: int,
                      t0: int, interval_ms: int, nbuckets: int,
                      want_minmax: bool = False):
        """Channel-wise combination of the cold segments, the tier
        part and the raw tail over ONE shared bucket grid (same
        t0/interval/nbuckets for all, so a bucket straddling a
        boundary sums exactly)."""
        sids = np.asarray(series_ids, dtype=np.int64)
        s = len(sids)
        sums = np.zeros((s, nbuckets))
        cnts = np.zeros((s, nbuckets))
        mins = maxs = None
        if want_minmax:
            mins = np.full((s, nbuckets), np.inf)
            maxs = np.full((s, nbuckets), -np.inf)
        cold_rng, tier_rng, raw_rng = self._split(start_ms, end_ms)
        if cold_rng is not None:
            # cold cells carry the same statistic as the tier's (the
            # segment stores all four stat columns; this view reads
            # the matching one), so they combine exactly like tier
            # cells — no tail_stat conversion
            got = self._cold("bucket_reduce", sids, cold_rng[0],
                             cold_rng[1], t0, interval_ms, nbuckets,
                             want_minmax)
            if got is not None:
                c_sums, c_cnts, c_mins, c_maxs = got
                sums += c_sums
                cnts += c_cnts
                if want_minmax:
                    np.minimum(mins, c_mins, out=mins)
                    np.maximum(maxs, c_maxs, out=maxs)
        if tier_rng is not None:
            tsids = self._tier_sids(sids)
            present = np.nonzero(tsids >= 0)[0]
            if len(present):
                t_sums, t_cnts, t_mins, t_maxs = \
                    self.tier.bucket_reduce(
                        tsids[present], tier_rng[0], tier_rng[1], t0,
                        interval_ms, nbuckets, want_minmax=want_minmax)
                sums[present] += t_sums
                cnts[present] += t_cnts
                if want_minmax:
                    # fancy indexing copies: assign back, don't `out=`
                    mins[present] = np.minimum(mins[present], t_mins)
                    maxs[present] = np.maximum(maxs[present], t_maxs)
        if raw_rng is not None:
            r_sums, r_cnts, r_mins, r_maxs = self.raw.bucket_reduce(
                sids, raw_rng[0], raw_rng[1], t0, interval_ms,
                nbuckets, want_minmax=want_minmax)
            # the raw tail contributes the statistic this tier's point
            # values carry: counting a count-tier's tail means adding
            # raw bucket COUNTS into the sums channel
            sums += r_cnts if self.tail_stat == "count" else r_sums
            cnts += r_cnts
            if want_minmax:
                np.minimum(mins, r_mins, out=mins)
                np.maximum(maxs, r_maxs, out=maxs)
        return sums, cnts, mins, maxs

    def materialize(self, series_ids, start_ms: int,
                    end_ms: int) -> PointBatch:
        """Flat merged batch: per series, cold points (oldest) precede
        tier points precede raw tail points, so per-series time order
        is preserved by one stable sort on the series index."""
        sids = np.asarray(series_ids, dtype=np.int64)
        parts: list[tuple[np.ndarray, np.ndarray, np.ndarray]] = []
        cold_rng, tier_rng, raw_rng = self._split(start_ms, end_ms)
        if cold_rng is not None:
            cb = self._cold("materialize", sids, *cold_rng)
            if cb is not None and cb.num_points:
                parts.append((cb.series_idx, cb.ts_ms, cb.values))
        if tier_rng is not None:
            tsids = self._tier_sids(sids)
            present = np.nonzero(tsids >= 0)[0]
            if len(present):
                tb = self.tier.materialize(tsids[present], *tier_rng)
                parts.append((present[tb.series_idx].astype(np.int32),
                              tb.ts_ms, tb.values))
        if raw_rng is not None:
            rb = self.raw.materialize(sids, *raw_rng)
            vals = rb.values
            if self.tail_stat == "count" and len(vals):
                # summing the tail must COUNT it (count-tier cells
                # hold counts; see module docstring)
                vals = np.ones_like(vals)
            parts.append((rb.series_idx, rb.ts_ms, vals))
        if not parts:
            return PointBatch(sids,
                              np.empty(0, dtype=np.int32),
                              np.empty(0, dtype=np.int64),
                              np.empty(0, dtype=np.float64))
        series_idx = np.concatenate([p[0] for p in parts])
        ts_ms = np.concatenate([p[1] for p in parts])
        values = np.concatenate([p[2] for p in parts])
        order = np.argsort(series_idx, kind="stable")
        return PointBatch(sids, series_idx[order], ts_ms[order],
                          values[order])

    def materialize_padded(self, series_ids, start_ms: int,
                           end_ms: int) -> PaddedBatch:
        return padded_from_batch(
            self.materialize(series_ids, start_ms, end_ms))

    # -- destructive ops (delete=true queries) ------------------------------

    def delete_range(self, series_ids, start_ms: int,
                     end_ms: int) -> int:
        """delete=true over a stitched view removes the range from ALL
        parts (cold segments, tier history, raw tail). The cold delete
        is NOT behind the degradation guard: silently skipping it
        would report success for points still on disk."""
        sids = np.asarray(series_ids, dtype=np.int64)
        deleted = self.raw.delete_range(sids, start_ms, end_ms)
        tsids = self._tier_sids(sids)
        present = tsids[tsids >= 0]
        if len(present):
            deleted += self.tier.delete_range(present, start_ms,
                                              end_ms)
        if self.cold is not None and self.spill_boundary_ms \
                and start_ms < self.spill_boundary_ms:
            deleted += self.cold.delete_range(sids, start_ms, end_ms)
        return deleted
