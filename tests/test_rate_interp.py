"""Rate and interpolation kernel tests (ref: test/core/TestRateSpan.java,
TestAggregationIterator.java interpolation cases).

Since PR 49 the carries, ``fill_gaps`` and the rate kernel are also
held, bit for bit, to a row-by-row NumPy reference written here, over
bucket counts on both sides of the bound between the sweep's two forms
(``ops.interp.carry_form``)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from opentsdb_tpu.ops.interp import (_sweep_unroll, carry_form,
                                     carry_next, carry_prev, fill_gaps)
from opentsdb_tpu.ops.rate import RateOptions, _rate_kernel, compute_rate


def grid_of(*rows):
    return np.asarray(rows, dtype=np.float64)


class TestRate:
    TS = np.arange(0, 5) * 10_000  # 10s buckets

    def test_simple_rate(self):
        g = grid_of([0.0, 10.0, 30.0, 60.0, 100.0])
        out = np.asarray(compute_rate(g, self.TS, RateOptions()))
        assert np.isnan(out[0, 0])  # first point has no rate
        np.testing.assert_allclose(out[0, 1:], [1.0, 2.0, 3.0, 4.0])

    def test_rate_skips_holes(self):
        g = grid_of([0.0, np.nan, 30.0, np.nan, 100.0])
        out = np.asarray(compute_rate(g, self.TS, RateOptions()))
        assert np.isnan(out[0, 0]) and np.isnan(out[0, 1])
        np.testing.assert_allclose(out[0, 2], 30.0 / 20.0)  # dt=20s
        assert np.isnan(out[0, 3])
        np.testing.assert_allclose(out[0, 4], 70.0 / 20.0)

    def test_counter_rollover(self):
        opts = RateOptions(counter=True, counter_max=100.0)
        g = grid_of([90.0, 95.0, 5.0])  # rolls over 100
        out = np.asarray(compute_rate(g, self.TS[:3], opts))
        np.testing.assert_allclose(out[0, 1], 0.5)
        # (100 - 95 + 5) / 10s = 1.0
        np.testing.assert_allclose(out[0, 2], 1.0)

    def test_counter_drop_resets(self):
        opts = RateOptions(counter=True, counter_max=100.0,
                           drop_resets=True)
        g = grid_of([90.0, 95.0, 5.0, 15.0])
        out = np.asarray(compute_rate(g, self.TS[:4], opts))
        np.testing.assert_allclose(out[0, 1], 0.5)
        assert np.isnan(out[0, 2])  # dropped reset
        np.testing.assert_allclose(out[0, 3], 1.0)

    def test_counter_reset_value(self):
        # corrected rate above reset_value emits 0
        opts = RateOptions(counter=True, counter_max=2**16,
                           reset_value=10.0)
        g = grid_of([60000.0, 20.0])  # huge rollover rate
        out = np.asarray(compute_rate(g, self.TS[:2], opts))
        assert out[0, 1] == 0.0

    def test_multiseries_independent(self):
        g = grid_of([0.0, 10.0, 20.0], [100.0, 80.0, 60.0])
        out = np.asarray(compute_rate(g, self.TS[:3], RateOptions()))
        np.testing.assert_allclose(out[0, 1:], [1.0, 1.0])
        np.testing.assert_allclose(out[1, 1:], [-2.0, -2.0])

    def test_rate_options_parse(self):
        assert RateOptions.parse(None) == RateOptions()
        opts = RateOptions.parse("rate{counter,100,10}")
        assert opts.counter and opts.counter_max == 100.0 \
            and opts.reset_value == 10.0
        opts = RateOptions.parse("rate{dropcounter}")
        assert opts.counter and opts.drop_resets
        with pytest.raises(ValueError):
            RateOptions.parse("rate{")


class TestFillGaps:
    TS = np.arange(4) * 1000

    def test_lerp_interior(self):
        g = grid_of([10.0, np.nan, np.nan, 40.0])
        out = np.asarray(fill_gaps(g, self.TS, "lerp"))
        np.testing.assert_allclose(out[0], [10.0, 20.0, 30.0, 40.0])

    def test_lerp_edges_stay_nan(self):
        g = grid_of([np.nan, 10.0, 20.0, np.nan])
        out = np.asarray(fill_gaps(g, self.TS, "lerp"))
        assert np.isnan(out[0, 0]) and np.isnan(out[0, 3])
        np.testing.assert_allclose(out[0, 1:3], [10.0, 20.0])

    def test_lerp_uneven_timestamps(self):
        ts = np.array([0, 1000, 5000, 6000])
        g = grid_of([0.0, np.nan, np.nan, 60.0])
        out = np.asarray(fill_gaps(g, ts, "lerp"))
        np.testing.assert_allclose(out[0], [0.0, 10.0, 50.0, 60.0])

    def test_zim_fills_zero_everywhere(self):
        g = grid_of([np.nan, 5.0, np.nan, np.nan])
        out = np.asarray(fill_gaps(g, self.TS, "zim"))
        np.testing.assert_array_equal(out[0], [0.0, 5.0, 0.0, 0.0])

    def test_prev(self):
        g = grid_of([np.nan, 5.0, np.nan, 7.0])
        out = np.asarray(fill_gaps(g, self.TS, "prev"))
        assert np.isnan(out[0, 0])
        np.testing.assert_array_equal(out[0, 1:], [5.0, 5.0, 7.0])

    def test_max_min_extremes(self):
        g = grid_of([1.0, np.nan, 3.0])
        out_max = np.asarray(fill_gaps(g, self.TS[:3], "max"))
        out_min = np.asarray(fill_gaps(g, self.TS[:3], "min"))
        assert out_max[0, 1] == np.inf
        assert out_min[0, 1] == -np.inf
        # outside the series range stays NaN
        g2 = grid_of([np.nan, 2.0, 3.0])
        assert np.isnan(np.asarray(fill_gaps(g2, self.TS[:3], "max"))[0, 0])

    def test_multi_series(self):
        g = grid_of([0.0, np.nan, 20.0], [np.nan, 1.0, np.nan])
        out = np.asarray(fill_gaps(g, self.TS[:3], "lerp"))
        np.testing.assert_allclose(out[0], [0.0, 10.0, 20.0])
        assert np.isnan(out[1, 0]) and out[1, 1] == 1.0 \
            and np.isnan(out[1, 2])


# ---------------------------------------------------------------------------
# the sweep against a row-by-row reference, bit for bit (PR 49)
# ---------------------------------------------------------------------------

F32 = np.float32
SWEEP_BUCKETS = (1, 2, 3, 12, 14, 16, 32, 64, 96, 100, 400, 720, 768)


def _sweep_case(b: int):
    """float32 ``[rows, b]`` with NaN holes and int32 ``bucket_ts``
    with a dropped bucket: all NaN, complete, hole first, hole last, a
    single value (at each end and inside), alternating both ways,
    counter-like rows that roll over, and seeded 40% / 90% holes."""
    rng = np.random.default_rng(49 * b)
    full = rng.integers(-5000, 100000, (12, b)).astype(F32) / F32(100)
    g = full.copy()
    g[0] = np.nan
    g[2, 0] = np.nan
    g[3, -1] = np.nan
    g[4] = np.nan
    g[4, 0] = full[4, 0]
    g[5] = np.nan
    g[5, -1] = full[5, -1]
    g[6] = np.nan
    g[6, b // 2] = full[6, b // 2]
    g[7, 0::2] = np.nan
    g[8, 1::2] = np.nan
    g[9] = np.cumsum(np.abs(full[9])) % F32(700)      # rolls over
    g[10, rng.random(b) < 0.4] = np.nan
    g[11, rng.random(b) < 0.9] = np.nan
    ts = np.arange(b, dtype=np.int64) * 60_000
    ts[b // 2:] += 60_000                              # a dropped bucket
    return g, ts.astype(np.int32)


def _ref_carry(g, ts, reverse=False, exclusive=False):
    """(value, ts, flag) of the nearest present cell at-or-before
    (after, when ``reverse``) each cell, one cell at a time; where
    there is none the flag is False over the walk's first cell's
    values, a hole counting as 0."""
    rows, b = g.shape
    v, t = np.zeros((rows, b), F32), np.zeros((rows, b), ts.dtype)
    has = np.zeros((rows, b), bool)
    order = range(b - 1, -1, -1) if reverse else range(b)
    for r in range(rows):
        first = order[0]
        cv = F32(0) if np.isnan(g[r, first]) else g[r, first]
        ct, ch = ts[first], False
        for k in order:
            if exclusive:
                v[r, k], t[r, k], has[r, k] = cv, ct, ch
            if not np.isnan(g[r, k]):
                cv, ct, ch = g[r, k], ts[k], True
            if not exclusive:
                v[r, k], t[r, k], has[r, k] = cv, ct, ch
    return v, t, has


def _ref_fill(g, ts, mode):
    v0, t0, has0 = _ref_carry(g, ts)
    v1, t1, has1 = _ref_carry(g, ts, reverse=True)
    out = g.copy()
    for r, k in zip(*np.nonzero(np.isnan(g))):
        if mode == "zim":
            out[r, k] = 0
        elif mode == "prev":
            out[r, k] = v0[r, k] if has0[r, k] else np.nan
        elif not (has0[r, k] and has1[r, k]):
            out[r, k] = np.nan
        elif mode in ("max", "min"):
            out[r, k] = np.inf if mode == "max" else -np.inf
        else:
            num = F32(ts[k] - t0[r, k])
            den = F32(t1[r, k] - t0[r, k])
            out[r, k] = v0[r, k] + (v1[r, k] - v0[r, k]) * num \
                / (den if den > 0 else F32(1))
    return out


COUNTER_MAX, RESET_VALUE = F32(700), F32(40)


@jax.jit
def _seconds(ms):
    """Milliseconds to seconds as the backend's compiler rounds the
    division by a constant (XLA may multiply by the reciprocal): one
    elementwise operation with no carry in it, so the reference takes
    it from the backend and everything else from NumPy."""
    return ms / 1000.0


def _ref_rate(g, ts, counter, drop_resets):
    v, t, has = _ref_carry(g, ts, exclusive=True)
    secs = np.asarray(_seconds(jnp.asarray((ts[None, :] - t).astype(F32))))
    out = np.full(g.shape, np.nan, F32)
    for r, k in zip(*np.nonzero(~np.isnan(g) & has)):
        dt = secs[r, k] if secs[r, k] > 0 else F32(1)
        delta = g[r, k] - v[r, k]
        rate = delta / dt
        if counter:
            if delta < 0:
                rate = F32(np.nan) if drop_resets \
                    else (COUNTER_MAX - v[r, k] + g[r, k]) / dt
            if rate > RESET_VALUE:
                rate = F32(0)
        out[r, k] = rate
    return out


def _run_sweep(what, g, ts):
    """(what the program gives, what the reference gives), each a
    tuple of arrays."""
    mask = ~np.isnan(g)
    gz = np.where(mask, g, F32(0))
    if what.startswith("carry"):
        fn, kw, ref = {
            "carry_prev": (carry_prev, {}, {}),
            "carry_prev.exclusive": (carry_prev, {"exclusive": True},
                                     {"exclusive": True}),
            "carry_next": (carry_next, {}, {"reverse": True}),
        }[what]
        return fn((jnp.asarray(gz), jnp.asarray(ts)), jnp.asarray(mask),
                  **kw), _ref_carry(g, ts, **ref)
    if what.startswith("fill."):
        mode = what.split(".")[1]
        return (fill_gaps(jnp.asarray(g), jnp.asarray(ts), mode),), \
            (_ref_fill(g, ts, mode),)
    counter, drop = {"rate.plain": (False, False),
                     "rate.plain.drop": (False, True),
                     "rate.counter": (True, False),
                     "rate.counter.drop": (True, True)}[what]
    return (_rate_kernel(jnp.asarray(g), jnp.asarray(ts), counter,
                         jnp.asarray(COUNTER_MAX),
                         jnp.asarray(RESET_VALUE), drop),), \
        (_ref_rate(g, ts, counter, drop),)


@pytest.mark.parametrize("b", SWEEP_BUCKETS)
@pytest.mark.parametrize("what", [
    "carry_prev", "carry_prev.exclusive", "carry_next",
    "fill.lerp", "fill.prev", "fill.max", "fill.min", "fill.zim",
    "rate.plain", "rate.plain.drop", "rate.counter",
    "rate.counter.drop"])
def test_sweep_is_the_row_by_row_reference_bit_for_bit(what, b):
    g, ts = _sweep_case(b)
    got, want = _run_sweep(what, g, ts)
    assert len(got) == len(want)
    for mine, ref in zip(got, want):
        mine = np.asarray(mine)
        assert mine.dtype == ref.dtype and mine.shape == ref.shape
        assert np.array_equal(mine, ref, equal_nan=True), (what, b)


def test_sweep_cases_lie_on_both_sides_of_the_form_bound():
    forms = {b: carry_form(b) for b in SWEEP_BUCKETS}
    assert {forms[b] for b in (1, 12, 14, 16)} == {"unrolled"}
    # whole trips, a remainder after them, the rollup cell's class
    assert {forms[b] for b in (32, 64, 96, 100, 400, 720, 768)} == {"loop"}
    # and every number of rows a trip the loop takes
    assert [_sweep_unroll(b) for b in (16, 64, 100, 400, 720, 768)] \
        == [16, 8, 8, 16, 24, 32]
