"""The fused query pipeline: downsample -> rate -> interpolate ->
aggregate -> group-by as ONE jit-compiled array program.

This inverts the reference's architecture (SURVEY.md §7): OpenTSDB pulls
one datapoint at a time through an iterator chain interleaved with
serialization (``SpanGroup.iterator`` -> ``AggregationIterator`` ->
``Downsampler`` -> ``RateSpan``, ref AggregationIterator.java:253-280);
here the whole working set is materialized as a flat point batch and the
entire chain compiles to a handful of fused XLA ops over a
``[series, bucket]`` grid. The per-query shapes (S, B, G, N) are traced
once per shape bucket and cached by XLA.
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from opentsdb_tpu.obs.trace import RUNTIME, trace_span
from opentsdb_tpu.ops import aggregators as aggs_mod
from opentsdb_tpu.ops import downsample as ds_mod
from opentsdb_tpu.ops import groupby as gb_mod
from opentsdb_tpu.ops import interp as interp_mod
from opentsdb_tpu.ops.rate import RateOptions, _rate_kernel


_SHARED_NAN = float("nan")


@dataclass(frozen=True)
class PipelineSpec:
    """Static (trace-time) configuration of one sub-query's compute."""
    num_series: int
    num_buckets: int
    num_groups: int
    ds_function: str          # downsample function ('sum', 'avg', ...)
    agg_name: str             # group aggregator name ('sum', 'p99', ...)
    fill_policy: ds_mod.FillPolicy = ds_mod.FillPolicy.NONE
    fill_value: float = _SHARED_NAN
    rate: bool = False
    rate_counter: bool = False
    rate_drop_resets: bool = False
    emit_raw: bool = False    # agg 'none': emit per-series, skip group stage
    # True when this program is placed on the host CPU backend (the
    # host-tail path): the group stage then lowers to segment ops, one
    # linear pass, instead of the one-hot contraction that costs the
    # CPU cells x groups flops and that the MXU wins by ~300x on TPU.
    # Static, so host and device programs compile separately.
    host: bool = False
    # True when the CALLER verified every (series, bucket) cell holds
    # a real value (no pads, no NaNs — the regular-cadence dashboard
    # case): cross-series interpolation and the per-group emission
    # reduction are provably no-ops and are skipped (fill_gaps alone
    # is ~190 ms of a [114688, 30] host-tail query on one core).
    complete: bool = False

    @property
    def tail_class(self) -> str:
        """Which class of group stage the program runs: ``rank`` (an
        order statistic a group and bucket: median, percentiles; by
        selection or by one sort of the grid,
        :func:`opentsdb_tpu.ops.groupby.rank_lowering`) or ``linear`` (a
        contraction or a segment reduction; ``none`` has no group
        stage and counts here)."""
        return "rank" if aggs_mod.get(self.agg_name).rank_class \
            else "linear"

    def __post_init__(self):
        # CPython >= 3.10 hashes each NaN object by identity, so a spec
        # built with a fresh float("nan") never compares/hashes equal to
        # the previous query's spec and the jit cache (static arg) would
        # recompile on EVERY query. Canonicalize to one shared NaN.
        if isinstance(self.fill_value, float) and \
                self.fill_value != self.fill_value:
            object.__setattr__(self, "fill_value", _SHARED_NAN)


@partial(jax.jit, static_argnames=("spec",))
def run_pipeline(values, series_idx, bucket_idx, bucket_ts, group_ids,
                 rate_params, fill_value, spec: PipelineSpec):
    """values[N] f32/f64, series_idx[N] i32, bucket_idx[N] i32,
    bucket_ts[B] i64, group_ids[S] i32, rate_params = (counter_max,
    reset_value) -> (result[G,B] or [S,B], emit_mask same shape).

    NaN in the result means "no value" (fill policy NONE/NULL);
    ``emit_mask`` marks buckets that exist in the output per the
    reference's emission rules (union of contributing series' buckets
    for NONE, everything otherwise).
    """
    s, b = spec.num_series, spec.num_buckets

    # 1. downsample: flat points -> [S,B] grid with NaN holes
    grid, cnt = ds_mod.bucketize(values, series_idx, bucket_idx, s, b,
                                 spec.ds_function)
    return _finish_pipeline(grid, cnt > 0, bucket_ts, group_ids,
                            rate_params, fill_value, spec)


@partial(jax.jit, static_argnames=("spec", "pts_per_bucket"))
def run_pipeline_dense(values2d, bucket_ts, group_ids, rate_params,
                       fill_value, spec: PipelineSpec,
                       pts_per_bucket: int):
    """Regular-cadence fast path: every series has the same P
    timestamps and each bucket covers exactly ``pts_per_bucket``
    consecutive points, so downsampling is a dense reshape reduction
    (``[S, B, k]`` over the last axis) — no scatter at all. This is the
    common shape of monitoring data (fixed collection interval) and the
    layout the benchmarks use; wall-clock is pure memory bandwidth.

    values2d: [S, P] with NaN for missing points, P = B * k.
    """
    s, b, k = spec.num_series, spec.num_buckets, pts_per_bucket
    x = values2d.reshape(s, b, k)
    valid = ~jnp.isnan(x)
    cnt = jnp.sum(valid, axis=-1)
    fn = spec.ds_function
    if fn in ("sum", "zimsum", "pfsum"):
        out = jnp.nansum(x, axis=-1)
    elif fn == "avg":
        out = jnp.nansum(x, axis=-1) / jnp.maximum(cnt, 1)
    elif fn in ("min", "mimmin"):
        out = jnp.min(jnp.where(valid, x, jnp.inf), axis=-1)
    elif fn in ("max", "mimmax"):
        out = jnp.max(jnp.where(valid, x, -jnp.inf), axis=-1)
    elif fn == "count":
        out = cnt.astype(values2d.dtype)
    elif fn == "last":
        idx = jnp.max(jnp.where(valid, jnp.arange(k), -1), axis=-1)
        out = jnp.take_along_axis(
            x, jnp.clip(idx, 0, k - 1)[..., None], axis=-1)[..., 0]
    elif fn == "first":
        idx = jnp.min(jnp.where(valid, jnp.arange(k), k), axis=-1)
        out = jnp.take_along_axis(
            x, jnp.clip(idx, 0, k - 1)[..., None], axis=-1)[..., 0]
    else:
        raise ValueError(
            f"dense path does not support downsample fn {fn!r}")
    grid = jnp.where(cnt > 0, out, jnp.nan)
    return _finish_pipeline(grid, cnt > 0, bucket_ts, group_ids,
                            rate_params, fill_value, spec)


@partial(jax.jit, static_argnames=("spec",))
def run_pipeline_padded(values2d, bucket_idx2d, bucket_ts, group_ids,
                        rate_params, fill_value, spec: PipelineSpec):
    """Irregular-data fast path over the row-padded layout
    (:class:`opentsdb_tpu.core.store.PaddedBatch`): scatter-free
    bucketization (see :func:`opentsdb_tpu.ops.downsample.bucketize_padded`),
    then the shared rate/interpolate/aggregate tail.

    values2d: [S, Pmax] NaN-padded; bucket_idx2d: [S, Pmax] int32 with
    -1 marking pads.
    """
    grid, cnt = ds_mod.bucketize_padded(values2d, bucket_idx2d,
                                        spec.num_buckets,
                                        spec.ds_function)
    return _finish_pipeline(grid, cnt > 0, bucket_ts, group_ids,
                            rate_params, fill_value, spec)


def apply_fill_policy(grid, has_data, fill_value, spec: "PipelineSpec"):
    """Downsample fill policy: ZERO/SCALAR substitute before rate,
    matching FillingDownsampler feeding RateSpan. Shared by the full
    and the time-blocked (ops.blocked) executors."""
    if spec.fill_policy == ds_mod.FillPolicy.ZERO:
        grid = jnp.where(jnp.isnan(grid), 0.0, grid)
        has_data = jnp.ones_like(has_data)
    elif spec.fill_policy == ds_mod.FillPolicy.SCALAR:
        grid = jnp.where(jnp.isnan(grid), fill_value, grid)
        has_data = jnp.ones_like(has_data)
    return grid, has_data


def _finish_pipeline(grid, has_data, bucket_ts, group_ids, rate_params,
                     fill_value, spec: PipelineSpec):
    g, b = spec.num_groups, spec.num_buckets

    # the scopes name the device trace's operations by stage, so a
    # reduction finds them after a refactor

    # 2. downsample fill policy
    with jax.named_scope("tail.fill"):
        grid, has_data = apply_fill_policy(grid, has_data, fill_value,
                                           spec)

    # 3. rate conversion per series (ref: Downsampler -> RateSpan order)
    if spec.rate:
        counter_max, reset_value = rate_params
        with jax.named_scope("tail.rate"):
            grid = _rate_kernel(grid, bucket_ts, spec.rate_counter,
                                counter_max, reset_value,
                                spec.rate_drop_resets)
            has_data = has_data & ~jnp.isnan(grid)

    if spec.emit_raw:
        return grid, has_data

    # 4.+5. interpolate at merge + aggregate over series within groups.
    # NAN/NULL fill policies emit explicit NaN points, which the
    # reference's merge loop skips WITHOUT interpolating (runDouble NaN
    # guard); only fill NONE leaves true gaps that interpolate.
    agg = aggs_mod.get(spec.agg_name)
    interpolate = spec.fill_policy == ds_mod.FillPolicy.NONE \
        and not spec.complete
    result = gb_mod.group_aggregate(grid, bucket_ts, group_ids, g, agg,
                                    interpolate=interpolate,
                                    prefer_segment=spec.host)

    # emission: fill NONE emits the union of the group's series' buckets
    # (plain Downsampler skips empty buckets); any other policy emits
    # every bucket (FillingDownsampler semantics). A verified-complete
    # grid emits everywhere by construction (every group has >= 1
    # member series and every cell is filled).
    with jax.named_scope("tail.emit_mask"):
        if spec.complete and not spec.rate:
            emit = jnp.ones((g, b), dtype=bool)
        elif spec.fill_policy == ds_mod.FillPolicy.NONE:
            emit = gb_mod._group_sum(
                has_data.astype(grid.dtype), group_ids, g,
                prefer_segment=spec.host) > 0
        else:
            emit = jnp.ones((g, b), dtype=bool)
    return result, emit


@partial(jax.jit, static_argnames=("spec",))
def run_pipeline_grid(grid, has_data, bucket_ts, group_ids, rate_params,
                      fill_value, spec: PipelineSpec):
    """Tail entry for host-pre-bucketized data: the storage engine's
    fused range-scan already produced the ``[S, B]`` downsample grid
    (NaN holes), so the trace starts at the fill/rate/aggregate chain —
    no per-point upload at all."""
    return _finish_pipeline(grid, has_data, bucket_ts, group_ids,
                            rate_params, fill_value, spec)


@partial(jax.jit, static_argnames=("spec",))
def run_pipeline_columns(cols, masks, bucket_ts, group_ids, rate_params,
                         fill_value, spec: PipelineSpec):
    """:func:`run_pipeline_grid` over a metric's buckets held a column
    each (``cols`` and ``masks``: as many ``[S]`` vectors as the
    window has buckets, some resident in HBM, some this request's
    own): the ``[S, B]`` grid and mask are put together here, under
    the scope ``tail.assemble_columns``, in the module that runs the
    tail, so a request over columns still executes ONE module. Returns
    ``(result, emit, grid, has_data)``: the assembled operands come
    back for the caller to keep (they are on the device already), so
    the same window asked again runs :func:`run_pipeline_grid` over
    them."""
    with jax.named_scope("tail.assemble_columns"):
        pad = spec.num_buckets - len(cols)
        grid = jnp.stack(
            cols + (jnp.full_like(cols[0], jnp.nan),) * pad, axis=1)
        has_data = jnp.stack(
            masks + (jnp.zeros_like(masks[0]),) * pad, axis=1)
    return (*_finish_pipeline(grid, has_data, bucket_ts, group_ids,
                              rate_params, fill_value, spec),
            grid, has_data)


def pipeline_dtype():
    """The compute dtype every host entry uses (f64 only under x64)."""
    return jnp.float64 if jax.config.read("jax_enable_x64") \
        else jnp.float32


def as_operand(x, dtype=None):
    """Prepare one jit operand without touching the default device.

    Host values are numpy-cast and handed to jit as-is — jax places
    them WITH the call's committed operands, so they never materialize
    on the default device first. (``jnp.asarray`` would: when the
    computation is bound for the host CPU backend the data would
    travel host -> accelerator -> host for nothing.) Device arrays
    pass through, cast on their own device."""
    if isinstance(x, jax.Array):
        return x if dtype is None or x.dtype == jnp.dtype(dtype) \
            else x.astype(dtype)
    return np.asarray(x, dtype=dtype)


def host_cpu_device():
    """The committed host CPU device that host-placed tails, the
    degraded fallback and continuous-query pulls run on. A TSD on an
    accelerator needs the CPU backend BESIDE it; TSDB construction
    refuses a platform list that leaves it out (see
    ``TSDB._check_host_backend``), so this lookup cannot fail on a
    booted server."""
    return jax.devices("cpu")[0]


def run_staged(path: str, program, operands,
               spec: PipelineSpec | None = None, download=np.asarray,
               stays: int = 0):
    """Upload, run, download: the one way a host entry reaches a
    compiled program, so that every path names the same three stages.

    ``operands()`` makes the program's positional arguments (casts,
    pads and ``device_put`` calls: ``query.upload``, host side only —
    nothing waits for a transfer). ``query.program`` is the call up to
    ``block_until_ready`` on its outputs, tagged with ``path``,
    ``placement`` (``host`` for a tail pinned to the CPU backend,
    ``spec.host``), ``class`` (``spec.tail_class``: ``rank`` |
    ``linear``, or ``histogram`` for the percentile program's
    :class:`~opentsdb_tpu.ops.histogram_kernels.HistogramSpec`),
    ``rank`` (``select`` | ``sort``: which lowering a ``class=rank``
    program's group stage takes, from the predicate the jitted code
    applies to the same padded shape; the mesh step has an estimator
    of its own and carries none), ``carry`` (``unrolled`` | ``loop``:
    which form a nearest-present carry along the program's padded
    buckets takes, :func:`opentsdb_tpu.ops.interp.carry_form`, the
    predicate the jitted fill and rate apply; every
    :class:`PipelineSpec` program carries it but the mesh step, which
    sweeps a time shard's buckets and not the spec's), the
    padded ``shape`` SxBxG and ``compiled`` when
    JAX compiled (or loaded from its cache) inside it; a
    device-placed program occupies :data:`RUNTIME`'s clock for that
    stretch. ``query.download`` is ``download`` (``np.asarray``) of
    each output but the last ``stays``, which are handed back as the
    device arrays they are. ``spec`` defaults to the :class:`PipelineSpec` among
    the operands."""
    with trace_span("query.upload"):
        args = operands()
    if spec is None:
        spec = next(a for a in args if isinstance(a, PipelineSpec))
    on_device = not spec.host
    tags = {"class": spec.tail_class}
    if spec.tail_class == "rank" and path != "mesh":
        tags["rank"] = gb_mod.rank_lowering(
            spec.num_series, spec.num_groups, pipeline_dtype(),
            spec.host)
    if isinstance(spec, PipelineSpec) and path != "mesh":
        tags["carry"] = interp_mod.carry_form(spec.num_buckets)
    with trace_span(
            "query.program", path=path,
            placement="device" if on_device else "host",
            shape=f"{spec.num_series}x{spec.num_buckets}"
                  f"x{spec.num_groups}", **tags) as span:
        compiles = RUNTIME.compiles
        if on_device:
            RUNTIME.clock.enter()
        try:
            out = jax.block_until_ready(program(*args))
        finally:
            if on_device:
                RUNTIME.clock.exit()
        if span is not None and RUNTIME.compiles != compiles:
            span.tag(compiled=True)
    with trace_span("query.download"):
        if stays:
            return (*jax.tree_util.tree_map(download, out[:-stays]),
                    *out[-stays:])
        return jax.tree_util.tree_map(download, out)


def put_grid(grid, has_data, device=None):
    """Upload a [S, B] grid + presence mask once, in the compute dtype
    — callers cache the returned DEVICE arrays so repeated queries
    skip the host scan and the transfer entirely."""
    dtype = pipeline_dtype()
    with trace_span("query.upload"):
        return (jax.device_put(as_operand(grid, dtype), device=device),
                jax.device_put(as_operand(has_data, bool),
                               device=device))


def put_pair(grid_sum, grid_cnt, device=None):
    """Upload a rollup average's SUM and COUNT grids (padded [S, B],
    NaN where a bucket holds no cell) once, in the compute dtype: the
    :func:`put_grid` of :func:`run_pipeline_avg_div`'s operands."""
    dtype = pipeline_dtype()
    with trace_span("query.upload"):
        return (jax.device_put(as_operand(grid_sum, dtype), device=device),
                jax.device_put(as_operand(grid_cnt, dtype), device=device))


def _pad_2d(arr, s_pad: int, b_pad: int, fill):
    """Pad a [S, B] array to [s_pad, b_pad]. DEVICE arrays pad on
    device (an eager jnp.pad — never a host round trip: the engine's
    grids are often HBM-resident from the native reduce or the device
    cache); host arrays pad in numpy."""
    from opentsdb_tpu.ops import shapes
    s, b = arr.shape
    if (s_pad, b_pad) == (s, b):
        return arr
    if isinstance(arr, jax.Array):
        return jnp.pad(arr, ((0, s_pad - s), (0, b_pad - b)),
                       constant_values=fill)
    return shapes.pad_2d_host(arr, s_pad, b_pad, fill)


def _bucket_dims_and_aux(bucket_ts, group_ids, spec: PipelineSpec,
                         s: int, b: int):
    """Shared shape-bucketing of one grid query: returns
    (s_pad, b_pad, padded bucket_ts, padded group_ids, padded spec)."""
    from opentsdb_tpu.ops import shapes
    from dataclasses import replace
    g = spec.num_groups
    s_pad = shapes.shape_bucket(s)
    b_pad = shapes.shape_bucket(b)
    g_pad = shapes.shape_bucket(g + 1)  # room for the dummy group
    bts = shapes.pad_bucket_ts(np.asarray(bucket_ts), b_pad)
    gids = shapes.pad_group_ids(np.asarray(group_ids), s_pad, g)
    return s_pad, b_pad, bts, gids, replace(
        spec, num_series=s_pad, num_buckets=b_pad, num_groups=g_pad)


def _tail_operands(bts, gids, ro: RateOptions, pspec: PipelineSpec,
                   dtype) -> tuple:
    """What every grid-shaped program takes after its grids: the
    padded bucket timestamps and group ids, the rate's parameters, the
    fill value and the padded spec, as numpy (they ride along with the
    committed operands: no eager default-device round trips)."""
    return (as_operand(device_bucket_ts(bts)),
            as_operand(gids, np.int32),
            (as_operand(ro.counter_max, dtype),
             as_operand(ro.reset_value, dtype)),
            as_operand(pspec.fill_value, dtype), pspec)


def bucket_grid_shapes(grid, has_data, bucket_ts, group_ids,
                       spec: PipelineSpec):
    """Pad (S, B, G) up to geometric shape buckets (ops.shapes) so
    repeat traffic with drifting shapes hits a bounded jit-program
    set. Returns (grid, has_data, bucket_ts, group_ids, spec_padded);
    callers trim the result back to the true (G, B) / (S, B)."""
    s, b = grid.shape
    s_pad, b_pad, bts, gids, pspec = _bucket_dims_and_aux(
        bucket_ts, group_ids, spec, s, b)
    gp = _pad_2d(grid, s_pad, b_pad, np.nan)
    hp = _pad_2d(has_data, s_pad, b_pad, False)
    return gp, hp, bts, gids, pspec


def execute_grid(grid: np.ndarray, has_data: np.ndarray,
                 bucket_ts: np.ndarray, group_ids: np.ndarray,
                 spec: PipelineSpec,
                 rate_options: RateOptions | None = None,
                 dtype=None, device=None
                 ) -> tuple[np.ndarray, np.ndarray]:
    """Host entry over a pre-bucketized [S, B] grid -> (result, emit).
    Shapes are geometrically bucketed (ops.shapes) before jit."""
    if dtype is None:
        dtype = pipeline_dtype()
    ro = rate_options or RateOptions()
    s, b, g = spec.num_series, spec.num_buckets, spec.num_groups
    put = partial(jax.device_put, device=device)

    def operands():
        gp, hp, bts, gids, pspec = bucket_grid_shapes(
            grid if isinstance(grid, jax.Array) else np.asarray(grid),
            has_data if isinstance(has_data, jax.Array)
            else np.asarray(has_data), bucket_ts, group_ids, spec)
        # the grid is the committed operand deciding placement
        return (put(as_operand(gp, dtype)), put(as_operand(hp, bool)),
                *_tail_operands(bts, gids, ro, pspec, dtype))

    result, emit = run_staged("grid", run_pipeline_grid, operands)
    rows = s if spec.emit_raw else g
    return result[:rows, :b], emit[:rows, :b]


def put_columns(cols: np.ndarray, masks: np.ndarray, device=None):
    """Upload the ``[wanted, S]`` columns and masks a storage pass
    wrote, a pair of ``[S]`` device arrays a bucket, in the compute
    dtype: what :func:`execute_columns` runs over and the HBM cache
    keeps a bucket at a time."""
    dtype = pipeline_dtype()
    with trace_span("query.upload"):
        # one call for all of them: a transfer a column, one dispatch
        up = jax.device_put(
            [as_operand(c, dtype) for c in cols]
            + [as_operand(m, bool) for m in masks], device=device)
    return list(zip(up[:len(cols)], up[len(cols):]))


def execute_columns(columns, bucket_ts: np.ndarray,
                    group_ids: np.ndarray, spec: PipelineSpec,
                    rate_options: RateOptions | None = None,
                    dtype=None, device=None):
    """:func:`execute_grid` over ``columns``, a ``(values, mask)`` pair
    of padded ``[S]`` vectors a bucket (:func:`put_columns`) ->
    ``(result, emit, grid, has_data)``: the last two are the assembled
    padded operands, left on the device
    (:func:`run_pipeline_columns`)."""
    if dtype is None:
        dtype = pipeline_dtype()
    ro = rate_options or RateOptions()
    s, b, g = spec.num_series, spec.num_buckets, spec.num_groups

    def operands():
        _, _, bts, gids, pspec = _bucket_dims_and_aux(
            bucket_ts, group_ids, spec, s, b)
        grids = (tuple(as_operand(c, dtype) for c, _ in columns),
                 tuple(as_operand(m, bool) for _, m in columns))
        if device is not None:  # elsewhere than where they lie
            grids = jax.device_put(grids, device)
        return (*grids, *_tail_operands(bts, gids, ro, pspec, dtype))

    result, emit, grid, has_data = run_staged(
        "columns", run_pipeline_columns, operands, stays=2)
    rows = s if spec.emit_raw else g
    return result[:rows, :b], emit[:rows, :b], grid, has_data


def avg_divide_grid(grid_sum, grid_cnt, xp=jnp):
    """The rollup-average derivation shared by the single-device trace
    (:func:`run_pipeline_avg_div`) and the mesh path's host-side
    divide (engine._avg_rollup_pipeline): SUM-tier cells / COUNT-tier
    cells where both tiers have data (ref: RollupSpan agg-prefixed
    sum+count qualifiers). Returns (grid, valid_mask)."""
    valid = (~xp.isnan(grid_sum)) & (~xp.isnan(grid_cnt)) \
        & (grid_cnt > 0)
    grid = xp.where(valid, grid_sum / xp.where(valid, grid_cnt, 1.0),
                    xp.nan)
    return grid, valid


@partial(jax.jit, static_argnames=("spec",))
def run_pipeline_avg_div(grid_sum, grid_cnt, bucket_ts, group_ids,
                         rate_params, fill_value, spec: PipelineSpec):
    """Tail entry for the avg-rollup derivation: divides a bucketized
    SUM-tier grid by a bucketized COUNT-tier grid in-trace (no host
    round-trip for the [S,B] grids), then runs the shared
    rate/interpolate/aggregate chain."""
    with jax.named_scope("tail.avg_divide"):
        grid, valid = avg_divide_grid(grid_sum, grid_cnt, xp=jnp)
    return _finish_pipeline(grid, valid, bucket_ts, group_ids,
                            rate_params, fill_value, spec)


def execute_avg_divide(grid_sum, grid_cnt, bucket_ts: np.ndarray,
                       group_ids: np.ndarray, spec: PipelineSpec,
                       rate_options: RateOptions | None = None,
                       dtype=None, device=None
                       ) -> tuple[np.ndarray, np.ndarray]:
    """Host entry: sum/count tier grids (device arrays straight from
    ``bucketize`` are fine) -> (result, emit). Shapes are geometrically
    bucketed (ops.shapes) before jit."""
    if dtype is None:
        dtype = pipeline_dtype()
    ro = rate_options or RateOptions()
    s, b, g = spec.num_series, spec.num_buckets, spec.num_groups
    put = partial(jax.device_put, device=device)

    def operands():
        s_pad, b_pad, bts_p, gids_p, pspec = _bucket_dims_and_aux(
            bucket_ts, group_ids, spec, grid_sum.shape[0],
            grid_sum.shape[1])
        gsum = _pad_2d(grid_sum, s_pad, b_pad, np.nan)
        gcnt = _pad_2d(grid_cnt, s_pad, b_pad, np.nan)
        return (put(as_operand(gsum, dtype)),
                put(as_operand(gcnt, dtype)),
                *_tail_operands(bts_p, gids_p, ro, pspec, dtype))

    result, emit = run_staged("avg_div", run_pipeline_avg_div, operands)
    rows = s if spec.emit_raw else g
    return result[:rows, :b], emit[:rows, :b]


_DENSE_FNS = frozenset(("sum", "zimsum", "pfsum", "avg", "min", "mimmin",
                        "max", "mimmax", "count", "first", "last"))


def detect_dense(num_series: int, num_buckets: int,
                 series_idx: np.ndarray, bucket_idx: np.ndarray,
                 ds_function: str) -> int | None:
    """Detect the regular-cadence layout: every series contributes the
    same P points in the same bucket pattern, with each bucket covering
    exactly k = P / B consecutive points. Returns k, or None.
    """
    if ds_function not in _DENSE_FNS:
        return None
    n = len(series_idx)
    if num_series == 0 or n == 0 or n % num_series != 0:
        return None
    p = n // num_series
    if p % num_buckets != 0:
        return None
    k = p // num_buckets
    sgrid = series_idx.reshape(num_series, p)
    if not (sgrid == np.arange(num_series, dtype=sgrid.dtype)[:, None]).all():
        return None
    bgrid = bucket_idx.reshape(num_series, p)
    expected = np.repeat(np.arange(num_buckets, dtype=bgrid.dtype), k)
    if not (bgrid == expected[None, :]).all():
        return None
    return k


# traffic budget for the padded einsum contraction: S * Pmax * B cells
_PADDED_EINSUM_MAX_CELLS = 2 * 10**9


def detect_regular_padded(counts: np.ndarray, bucket_idx2d: np.ndarray,
                          num_buckets: int) -> int | None:
    """Regular-cadence check on the padded layout: every row full to the
    same P with the identical k-contiguous bucket pattern. Returns k
    (points per bucket) or None."""
    if len(counts) == 0:
        return None
    # tsdlint: allow[kernel-hygiene] ONE scalar probe per call (the
    # first row's count), not a per-element pull
    p = int(counts[0])
    if p == 0 or not (counts == p).all() or \
            bucket_idx2d.shape[1] != p or p % num_buckets != 0:
        return None
    k = p // num_buckets
    expected = np.repeat(np.arange(num_buckets, dtype=bucket_idx2d.dtype),
                         k)
    if not (bucket_idx2d[0] == expected).all():
        return None
    if not (bucket_idx2d == bucket_idx2d[0]).all():
        return None
    return k


def flatten_padded(values2d: np.ndarray, bucket_idx2d: np.ndarray,
                   counts: np.ndarray
                   ) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Padded -> flat (values, series_idx, bucket_idx) for the scatter
    and blocked executors."""
    from opentsdb_tpu.core.store import pad_mask
    mask = ~pad_mask(counts, values2d.shape[1])
    series_idx = np.repeat(
        np.arange(values2d.shape[0], dtype=np.int32),
        counts.astype(np.int64))
    return (values2d[mask], series_idx,
            bucket_idx2d[mask].astype(np.int32))


def device_bucket_ts(bucket_ts: np.ndarray) -> np.ndarray:
    """Bucket timestamps in device form: relative int32 ms offsets.

    Absolute epoch-ms values (~1.4e12) overflow int32, and TPU runtimes
    have no int64/float64 — uploading raw int64 silently truncates and
    corrupts every rate/lerp time delta. The kernels only ever use ts
    DIFFERENCES, so relative offsets are exact. Spans too long for
    int32 ms (> ~24 days) degrade to float (f32 on TPU: <= 128 ms
    rounding at the far end, negligible against the wide buckets such
    spans imply).
    """
    rel = np.asarray(bucket_ts, dtype=np.int64)
    if len(rel):
        rel = rel - rel[0]
    if len(rel) == 0 or rel[-1] < 2**31:
        return rel.astype(np.int32)
    return rel.astype(np.float64)


@dataclass(frozen=True)
class PreparedBatch:
    """Device-resident upload of one sub-query's point data, ready to
    execute repeatedly — the engine caches these so a warm query pays
    neither the host materialize nor the transfer.

    kind 'dense': arrays = (values2d,), k = points per bucket;
    kind 'padded': arrays = (values2d, bucket_idx2d);
    kind 'flat': arrays = (values, series_idx, bucket_idx).

    ``pad`` = (s_pad, b_pad): the geometric shape buckets the arrays
    were padded to at upload (ops.shapes) — run_prepared swaps them
    into the spec and trims the result, bounding the compile space.
    """
    kind: str
    arrays: tuple
    k: int | None = None
    pad: tuple | None = None

    @property
    def nbytes(self) -> int:
        return sum(getattr(a, "nbytes", 0) for a in self.arrays)


@dataclass(frozen=True)
class GridColumns:
    """A window's operands as :func:`execute_columns` takes them: a
    ``(values, mask)`` pair of padded ``[S]`` device vectors a bucket.
    What the HBM cache holds under a window's key from the request
    that put the window together out of the metric's columns until its
    program has assembled the grid (most of the pairs are the cache's
    own per-bucket entries, counted here again)."""
    columns: tuple

    @property
    def arrays(self) -> tuple:
        return tuple(x for pair in self.columns for x in pair)


def _pad_rows(arr2d: np.ndarray, s_pad: int, fill) -> np.ndarray:
    s, p = arr2d.shape
    if s_pad == s:
        return arr2d
    out = np.full((s_pad, p), fill, dtype=arr2d.dtype)
    out[:s] = arr2d
    return out


def _prepared_dense(values2d: np.ndarray, k: int, s_pad: int, b: int,
                    dtype, put) -> "PreparedBatch":
    """Upload a regular-cadence [S, B * k] batch for the dense reshape
    program (NaN rows up to the series bucket)."""
    with trace_span("query.upload"):
        return PreparedBatch(
            "dense",
            (put(as_operand(_pad_rows(values2d, s_pad, np.nan), dtype)),),
            k, pad=(s_pad, b))


def prepare_auto(padded, bucket_idx2d: np.ndarray, spec: PipelineSpec,
                 dtype=None, device=None) -> PreparedBatch:
    """Layout-detect + upload a PaddedBatch: the dense reshape program
    for regular-cadence data, the scatter-free padded kernel for
    irregular data it supports, the flat scatter layout
    (:func:`prepare_flat`) otherwise. Shapes pad to geometric buckets
    (ops.shapes): NaN rows for extra series, -1 bucket sentinels for
    extra point columns."""
    from opentsdb_tpu.ops import shapes
    if dtype is None:
        dtype = pipeline_dtype()
    put = partial(jax.device_put, device=device)
    values2d = np.asarray(padded.values2d)
    counts = np.asarray(padded.counts)
    bucket_idx2d = np.asarray(bucket_idx2d)
    s, b = spec.num_series, spec.num_buckets
    s_pad = shapes.shape_bucket(s)
    k = detect_regular_padded(counts, bucket_idx2d, spec.num_buckets)
    if k is not None and spec.ds_function in _DENSE_FNS:
        return _prepared_dense(values2d, k, s_pad, b, dtype, put)
    cells = s_pad * values2d.shape[1] * spec.num_buckets
    if ds_mod.padded_supported(spec.ds_function, spec.num_buckets) \
            and cells <= _PADDED_EINSUM_MAX_CELLS:
        with trace_span("query.upload"):
            return PreparedBatch(
                "padded",
                (put(as_operand(_pad_rows(values2d, s_pad, np.nan),
                                dtype)),
                 put(as_operand(_pad_rows(bucket_idx2d, s_pad, -1),
                                np.int32))),
                pad=(s_pad, b))
    values, series_idx, bucket_idx = flatten_padded(
        values2d, bucket_idx2d, counts)
    return prepare_flat(values, series_idx, bucket_idx, spec,
                        dtype=dtype, device=device)


def prepare_flat(values: np.ndarray, series_idx: np.ndarray,
                 bucket_idx: np.ndarray, spec: PipelineSpec,
                 dtype=None, device=None) -> PreparedBatch:
    """Layout-detect + upload a flat point batch, padded to geometric
    shape buckets (dummy points land on a padded series row and a
    padded bucket column, both trimmed by run_prepared)."""
    from opentsdb_tpu.ops import shapes
    if dtype is None:
        dtype = pipeline_dtype()
    put = partial(jax.device_put, device=device)
    s, b = spec.num_series, spec.num_buckets
    s_pad = shapes.shape_bucket(s)
    k = detect_dense(spec.num_series, spec.num_buckets,
                     np.asarray(series_idx), np.asarray(bucket_idx),
                     spec.ds_function)
    if k is not None:
        values2d = np.asarray(values).reshape(spec.num_series, -1)
        return _prepared_dense(values2d, k, s_pad, b, dtype, put)
    with trace_span("query.upload"):
        n = len(values)
        s_pad = shapes.shape_bucket(s + 1)
        b_pad = shapes.shape_bucket(b + 1)
        n_pad = shapes.shape_bucket(n)
        v = np.zeros(n_pad, dtype=np.asarray(values).dtype)
        v[:n] = values
        si = np.full(n_pad, s_pad - 1, dtype=np.int32)
        si[:n] = series_idx
        bi = np.full(n_pad, b_pad - 1, dtype=np.int32)
        bi[:n] = bucket_idx
        return PreparedBatch(
            "flat", (put(as_operand(v, dtype)),
                     put(si), put(bi)),
            pad=(s_pad, b_pad))


def run_prepared(prep: PreparedBatch, bucket_ts: np.ndarray,
                 group_ids: np.ndarray, spec: PipelineSpec,
                 rate_options: RateOptions | None = None,
                 dtype=None) -> tuple[np.ndarray, np.ndarray]:
    """Execute a (possibly cached) PreparedBatch -> (result, emit),
    trimming off the shape-bucket padding the prepare step added.
    Placement follows the PreparedBatch's committed device arrays
    (decided by prepare_* at upload); the small per-query operands
    ride along as numpy."""
    from dataclasses import replace
    from opentsdb_tpu.ops import shapes
    if dtype is None:
        dtype = pipeline_dtype()
    ro = rate_options or RateOptions()
    s, b, g = spec.num_series, spec.num_buckets, spec.num_groups
    if prep.pad is not None:
        s_pad, b_pad = prep.pad
        g_pad = shapes.shape_bucket(g + 1)
        bucket_ts = shapes.pad_bucket_ts(
            np.asarray(bucket_ts), b_pad)
        group_ids = shapes.pad_group_ids(np.asarray(group_ids),
                                         s_pad, g)
        spec = replace(spec, num_series=s_pad, num_buckets=b_pad,
                       num_groups=g_pad)
    program, static = {
        "dense": (run_pipeline_dense, (prep.k,)),
        "padded": (run_pipeline_padded, ()),
        "flat": (run_pipeline, ())}[prep.kind]
    # numpy operands ride with the committed prepared arrays — no
    # eager default-device materialization per query
    result, emit = run_staged(prep.kind, program, lambda: (
        *prep.arrays, as_operand(device_bucket_ts(bucket_ts)),
        as_operand(group_ids, np.int32),
        (as_operand(ro.counter_max, dtype),
         as_operand(ro.reset_value, dtype)),
        as_operand(spec.fill_value, dtype), spec, *static))
    rows = s if spec.emit_raw else g
    return result[:rows, :b], emit[:rows, :b]


def execute(batch_values: np.ndarray, series_idx: np.ndarray,
            bucket_idx: np.ndarray, bucket_ts: np.ndarray,
            group_ids: np.ndarray, spec: PipelineSpec,
            rate_options: RateOptions | None = None,
            dtype=None, device=None) -> tuple[np.ndarray, np.ndarray]:
    """Upload, run, download a flat point batch in one call: the
    tests' reference entry. The engine prepares and runs in two steps
    so that the upload can stay resident."""
    prep = prepare_flat(batch_values, series_idx, bucket_idx, spec,
                        dtype=dtype, device=device)
    return run_prepared(prep, bucket_ts, group_ids, spec, rate_options,
                        dtype=dtype)
