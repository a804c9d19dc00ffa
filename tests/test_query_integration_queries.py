"""Core query-integration matrix — the analogue of
``TestTsdbQueryQueries.java`` (55 scenarios: data types, ms
resolution, rates and counters, duplicates, TSUID queries,
annotations, interpolation, time-window edges), each run
single-device AND on the 8-device mesh via ``engine_mode``.
"""

from __future__ import annotations

import numpy as np
import pytest

from opentsdb_tpu.query.model import BadRequestError, TSQuery
from query_integration_base import (BASE, METRIC, METRIC_B,
                                    assert_points, dps_of, engine_mode,
                                    make_tsdb, run_query,
                                    store_float_seconds, store_long_ms,
                                    store_long_seconds, sub_query)

_ = engine_mode

END = BASE + 43200


# ---------------------------------------------------------------------------
# data types and windows
# ---------------------------------------------------------------------------

def test_long_single_ts(engine_mode):
    """(ref: runLongSingleTS) identity values 1..300 @30s."""
    t = make_tsdb(engine_mode)
    ts1, asc, _, _ = store_long_seconds(t, two_metrics=True)
    r = run_query(t, sub_query("sum", tags={"host": "web01"}))
    assert_points(dps_of(r), ts1 * 1000, asc)
    # the second metric must not leak in
    assert all(x.metric == METRIC for x in r)


def test_long_single_ts_ms(engine_mode):
    """(ref: runLongSingleTSMs) 500ms cadence with msResolution."""
    t = make_tsdb(engine_mode)
    ts_ms, asc, _ = store_long_ms(t)
    r = run_query(t, sub_query("sum", tags={"host": "web01"}),
                  ms_resolution=True)
    assert_points(dps_of(r), ts_ms, asc)


def test_no_data(engine_mode):
    """(ref: runLongSingleTSNoData)."""
    t = make_tsdb(engine_mode)
    store_long_seconds(t)
    r = run_query(t, sub_query("sum", metric=METRIC,
                               tags={"host": "web01"}),
                  start_s=BASE + 90000, end_s=BASE + 93600)
    assert r == [] or all(x.num_dps == 0 for x in r)


def test_unknown_metric_raises(engine_mode):
    from opentsdb_tpu.query.engine import NoSuchMetricError
    t = make_tsdb(engine_mode)
    store_long_seconds(t)
    with pytest.raises((NoSuchMetricError, BadRequestError,
                        LookupError)):
        run_query(t, sub_query("sum", metric="no.such.metric"))


def test_float_single_ts(engine_mode):
    """(ref: runFloatSingleTS) 1.25..76.0 by quarters."""
    t = make_tsdb(engine_mode)
    ts1, asc, _, _ = store_float_seconds(t)
    r = run_query(t, sub_query("sum", tags={"host": "web01"}))
    assert_points(dps_of(r), ts1 * 1000, asc)


def test_float_two_agg_sum(engine_mode):
    """(ref: runFloatTwoAggSum) asc + desc = 76.25 everywhere."""
    t = make_tsdb(engine_mode)
    ts1, asc, ts2, desc = store_float_seconds(t)
    r = run_query(t, sub_query("sum"))
    assert_points(dps_of(r), ts1 * 1000, asc + desc)


def test_end_time_subset(engine_mode):
    """(ref: runEndTime) a shorter window truncates the series."""
    t = make_tsdb(engine_mode)
    ts1, asc, _, _ = store_long_seconds(t)
    end = BASE + 5000
    r = run_query(t, sub_query("sum", tags={"host": "web01"}),
                  end_s=end)
    inside = ts1 <= end
    assert_points(dps_of(r), ts1[inside] * 1000, asc[inside])


def test_start_not_set_rejected(engine_mode):
    """(ref: runStartNotSet -> 'Invalid start time')."""
    with pytest.raises((BadRequestError, ValueError, TypeError)):
        TSQuery.from_json({"queries": [
            {"metric": METRIC, "aggregator": "sum"}]}).validate()


# ---------------------------------------------------------------------------
# rates and counters (ref: runLongSingleTSRate, runRateCounter*)
# ---------------------------------------------------------------------------

def test_rate_long(engine_mode):
    t = make_tsdb(engine_mode)
    ts1, asc, _, _ = store_long_seconds(t)
    r = run_query(t, sub_query("sum", tags={"host": "web01"},
                               rate=True))
    assert_points(dps_of(r), ts1[1:] * 1000, np.full(299, 1 / 30))


def test_rate_float(engine_mode):
    t = make_tsdb(engine_mode)
    ts1, asc, _, _ = store_float_seconds(t)
    r = run_query(t, sub_query("sum", tags={"host": "web01"},
                               rate=True))
    assert_points(dps_of(r), ts1[1:] * 1000, np.full(299, 0.25 / 30),
                  rel=1e-5)


def test_rate_ms(engine_mode):
    """(ref: runLongSingleTSRateMs) 500ms cadence -> 2/sec."""
    t = make_tsdb(engine_mode)
    ts_ms, asc, _ = store_long_ms(t)
    r = run_query(t, sub_query("sum", tags={"host": "web01"},
                               rate=True), ms_resolution=True)
    assert_points(dps_of(r), ts_ms[1:], np.full(299, 2.0))


def _counter_series(t, vals, tags=None):
    ts = BASE + 30 * np.arange(1, len(vals) + 1, dtype=np.int64)
    t.add_points("ctr.m", ts, np.asarray(vals, dtype=np.float64),
                 tags or {"host": "web01"})
    return ts


def test_rate_counter_wrap_32bit(engine_mode):
    """(ref: runRateCounterDefault, adapted) rollover corrected by the
    counter max. The reference's fixture sits 55 below Long.MAX and
    relies on exact 64-bit integer arithmetic; the float engine cannot
    represent deltas near 2^64 (ulp there is 2048), so the same wrap
    is pinned at the 32-bit counter ceiling where f64 is exact."""
    t = make_tsdb(engine_mode)
    big = float(2**32 - 1)
    ts = _counter_series(t, [big - 55, big - 25, 5.0])
    r = run_query(t, sub_query("sum", metric="ctr.m",
                               tags={"host": "web01"}, rate=True,
                               rateOptions={"counter": True,
                                            "counterMax": 2**32 - 1}))
    dps = dps_of(r)
    assert dps[0] == (int(ts[1]) * 1000, pytest.approx(1.0))
    assert dps[1][0] == int(ts[2]) * 1000
    # (max - (max-25) + 5) / 30 = 1.0
    assert dps[1][1] == pytest.approx(1.0, rel=1e-6)


def test_rate_counter_max_set(engine_mode):
    """(ref: runRateCounterMaxSet) counterMax=70 wraps 60->70->10."""
    t = make_tsdb(engine_mode)
    ts = _counter_series(t, [30.0, 50.0, 10.0])
    r = run_query(t, sub_query("sum", metric="ctr.m",
                               tags={"host": "web01"}, rate=True,
                               rateOptions={"counter": True,
                                            "counterMax": 70}))
    dps = dps_of(r)
    # 30->50: 20/30; 50->(70 wrap)->10: 30/30 = 1
    assert dps[0][1] == pytest.approx(20 / 30)
    assert dps[1][1] == pytest.approx(1.0)


def test_rate_counter_anomaly_reset_value(engine_mode):
    """(ref: runRateCounterAnomally) resetValue clamps an absurd
    corrected rate to zero."""
    t = make_tsdb(engine_mode)
    ts = _counter_series(t, [30.0, 50.0, 10.0])
    r = run_query(t, sub_query(
        "sum", metric="ctr.m", tags={"host": "web01"}, rate=True,
        rateOptions={"counter": True, "counterMax": 2 ** 64 - 1,
                     "resetValue": 1024}))
    dps = dps_of(r)
    assert dps[0][1] == pytest.approx(20 / 30)
    # corrected rate through 2^64 is astronomical > resetValue -> 0
    assert dps[1][1] == 0.0


def test_rate_counter_anomaly_drop(engine_mode):
    """(ref: runRateCounterAnomallyDrop) dropResets removes the point
    entirely instead of emitting 0."""
    t = make_tsdb(engine_mode)
    ts = _counter_series(t, [30.0, 50.0, 10.0, 40.0])
    r = run_query(t, sub_query(
        "sum", metric="ctr.m", tags={"host": "web01"}, rate=True,
        rateOptions={"counter": True, "counterMax": 2 ** 64 - 1,
                     "resetValue": 1024, "dropResets": True}))
    dps = dps_of(r)
    got_ts = [tt for tt, _ in dps]
    assert int(ts[2]) * 1000 not in got_ts
    assert dps[0][1] == pytest.approx(20 / 30)
    assert dict(dps)[int(ts[3]) * 1000] == pytest.approx(30 / 30)


# ---------------------------------------------------------------------------
# duplicate timestamps (ref: multipleValuesAtSameTimestamp*)
# ---------------------------------------------------------------------------

def test_duplicate_timestamp_last_write_wins(engine_mode):
    """Our columnar store resolves duplicate timestamps LAST-WRITE-WINS
    at scan time (ref: tsd.storage.fix_duplicates semantics,
    CompactionQueue.java — the fixed cell keeps the newest write)."""
    t = make_tsdb(engine_mode)
    t.add_point("dup.m", BASE + 30, 69755263, {"host": "web01"})
    t.add_point("dup.m", BASE + 30, 62500.52, {"host": "web01"})
    t.add_point("dup.m", BASE + 30, 2533, {"host": "web01"})
    r = run_query(t, sub_query("sum", metric="dup.m",
                               tags={"host": "web01"}))
    dps = dps_of(r)
    assert dps == [((BASE + 30) * 1000, 2533.0)]


# ---------------------------------------------------------------------------
# TSUID queries (ref: runTSUIDQuery / runTSUIDsAggSum / NSU)
# ---------------------------------------------------------------------------

def _tsuid_of(t, metric, tags):
    mid = t.uids.metrics.get_id(metric)
    tag_ids = [(t.uids.tag_names.get_id(k), t.uids.tag_values.get_id(v))
               for k, v in tags.items()]
    return t.uids.tsuid(mid, tag_ids).hex().upper()


def test_tsuid_query(engine_mode):
    t = make_tsdb(engine_mode)
    ts1, asc, _, _ = store_long_seconds(t)
    tsuid = _tsuid_of(t, METRIC, {"host": "web01"})
    r = run_query(t, {"aggregator": "sum", "tsuids": [tsuid]})
    assert_points(dps_of(r), ts1 * 1000, asc)


def test_tsuids_agg_sum(engine_mode):
    """(ref: runTSUIDsAggSum) two tsuids aggregate like tag queries."""
    t = make_tsdb(engine_mode)
    ts1, asc, ts2, desc = store_long_seconds(t)
    u1 = _tsuid_of(t, METRIC, {"host": "web01"})
    u2 = _tsuid_of(t, METRIC, {"host": "web02"})
    r = run_query(t, {"aggregator": "sum", "tsuids": [u1, u2]})
    assert_points(dps_of(r), ts1 * 1000, asc + desc)


def test_tsuid_query_no_data(engine_mode):
    """(ref: runTSUIDQueryNSU) an unknown tsuid raises or returns
    empty — never a 500-class crash."""
    t = make_tsdb(engine_mode)
    store_long_seconds(t)
    try:
        r = run_query(t, {"aggregator": "sum",
                          "tsuids": ["00DEAD00BEEF00FF"]})
        assert r == [] or all(x.num_dps == 0 for x in r)
    except (BadRequestError, LookupError):
        pass


# ---------------------------------------------------------------------------
# annotations in query responses (ref: runWithAnnotation et al)
# ---------------------------------------------------------------------------

def _annotate(t, tsuid, start, desc):
    from opentsdb_tpu.meta.annotation import Annotation
    t.annotations.store(Annotation(start_time=start, tsuid=tsuid,
                                   description=desc))


def test_with_annotation(engine_mode):
    t = make_tsdb(engine_mode)
    ts1, asc, _, _ = store_long_seconds(t)
    tsuid = _tsuid_of(t, METRIC, {"host": "web01"})
    _annotate(t, tsuid, BASE + 1000, "note1")
    r = run_query(t, sub_query("sum", tags={"host": "web01"}))
    assert_points(dps_of(r), ts1 * 1000, asc)
    assert len(r[0].annotations) == 1
    assert r[0].annotations[0].description == "note1"


def test_annotation_outside_window_excluded(engine_mode):
    t = make_tsdb(engine_mode)
    store_long_seconds(t)
    tsuid = _tsuid_of(t, METRIC, {"host": "web01"})
    _annotate(t, tsuid, BASE + 100000, "far away")
    r = run_query(t, sub_query("sum", tags={"host": "web01"}))
    assert r[0].annotations == []


def test_no_annotations_flag(engine_mode):
    t = make_tsdb(engine_mode)
    store_long_seconds(t)
    tsuid = _tsuid_of(t, METRIC, {"host": "web01"})
    _annotate(t, tsuid, BASE + 1000, "hidden")
    r = run_query(t, sub_query("sum", tags={"host": "web01"}),
                  noAnnotations=True)
    assert r[0].annotations == []


def test_single_data_point(engine_mode):
    """(ref: runSingleDataPoint)."""
    t = make_tsdb(engine_mode)
    t.add_point("one.m", BASE + 30, 42, {"host": "web01"})
    r = run_query(t, sub_query("sum", metric="one.m",
                               tags={"host": "web01"}))
    assert dps_of(r) == [((BASE + 30) * 1000, 42.0)]


# ---------------------------------------------------------------------------
# interpolation (ref: runInterpolationSeconds/Ms) — the doc example of
# AggregationIterator.java:27-119
# ---------------------------------------------------------------------------

def test_interpolation_seconds(engine_mode):
    """Two series offset by 15s; sum lerps each onto the union grid —
    exactly the worked example in the reference's javadoc."""
    t = make_tsdb(engine_mode)
    ts1, asc, ts2, desc = store_long_seconds(t, offset=True)
    r = run_query(t, sub_query("sum"))
    dps = dps_of(r)
    assert len(dps) == 600
    # spot-check the javadoc invariant: interior points sum a real
    # value and the other series' midpoint lerp
    m = dict(dps)
    # at ts1[1] (web01=2 exact), web02 lerps between desc[0]@+15 and
    # desc[1]@+45 -> (300+299)/2 = 299.5 -> 301.5
    assert m[int(ts1[1]) * 1000] == pytest.approx(2 + 299.5)
    # at ts2[0] (web02=300 exact), web01 lerps 1..2 -> 1.5
    assert m[int(ts2[0]) * 1000] == pytest.approx(300 + 1.5)


def test_interpolation_ms(engine_mode):
    """(ref: runInterpolationMs) same at 500ms cadence, offset by
    250ms."""
    t = make_tsdb(engine_mode)
    asc = np.arange(1, 301, dtype=np.float64)
    ts_ms = BASE * 1000 + 500 * np.arange(1, 301, dtype=np.int64)
    sid = t.add_point(METRIC, int(ts_ms[0]), 1.0, {"host": "web01"})
    t.store.append_many(sid, ts_ms[1:], asc[1:], False)
    desc = asc[::-1].copy()
    off = ts_ms + 250
    sid = t.add_point(METRIC, int(off[0]), float(desc[0]),
                      {"host": "web02"})
    t.store.append_many(sid, off[1:], desc[1:], False)
    r = run_query(t, sub_query("sum"), ms_resolution=True)
    m = dict(dps_of(r))
    assert m[int(ts_ms[1])] == pytest.approx(2 + 299.5)
    assert m[int(off[0])] == pytest.approx(300 + 1.5)


# ---------------------------------------------------------------------------
# metric isolation + group-by (ref: runLongTwoGroup)
# ---------------------------------------------------------------------------

def test_two_group(engine_mode):
    t = make_tsdb(engine_mode)
    ts1, asc, ts2, desc = store_long_seconds(t)
    r = run_query(t, sub_query("sum", tags={"host": "*"}))
    assert len(r) == 2
    by = {x.tags["host"]: x for x in r}
    assert_points(by["web01"].dps, ts1 * 1000, asc)
    assert_points(by["web02"].dps, ts2 * 1000, desc)
    for x in r:
        assert x.aggregated_tags == []


def test_two_metrics_two_subqueries(engine_mode):
    """(ref: the two_metrics fixtures) one TSQuery with two sub-queries
    over different metrics keeps results separated by index."""
    t = make_tsdb(engine_mode)
    ts1, asc, _, _ = store_long_seconds(t, two_metrics=True)
    obj = {"start": BASE * 1000, "end": END * 1000, "queries": [
        sub_query("sum", metric=METRIC, tags={"host": "web01"}),
        sub_query("max", metric=METRIC_B, tags={"host": "web01"})]}
    try:
        r = t.execute_query(TSQuery.from_json(obj).validate())
    finally:
        # two subs start the fan-out pool; left alive, its threads
        # show in a later module's thread census (the profiler's)
        t.shutdown()
    assert {x.sub_query_index for x in r} == {0, 1}
    assert {x.metric for x in r} == {METRIC, METRIC_B}
