"""Self-driving control-plane battery (opentsdb_tpu/control/):

- shape-miner determinism oracle: the miner is a pure function of the
  shape-log bytes — same log (in any line order) ⇒ same scores ⇒ same
  materialization set;
- adaptive materialization: hot decomposable shapes auto-register as
  standing shared partials, serve the repeat pull through the
  streaming registry bit-identically to a hand-registered continuous
  query, and retire only after the hysteresis window of cold scans;
- multi-tenant QoS: weighted fair in-flight shares over the existing
  shed idiom — the noisy tenant absorbs the structured 503s while the
  victim keeps being served — plus burn-penalty priority and the
  per-tenant cache/fold byte budgets;
- placement: hot-shard plans are PROPOSED (content-addressed planId),
  never executed without an operator confirm or the auto opt-in;
- chaos: every armed ``control.*`` fault site — and a killed control
  thread — parks the loop loudly and never fails a write, blocks a
  query, or 5xxes.
"""

from __future__ import annotations

import json
import random
import time

import pytest

from opentsdb_tpu import TSDB, Config
from opentsdb_tpu.control.miner import mine_shapes
from opentsdb_tpu.control.shapes import (auto_id, candidate_body,
                                         cq_candidate)
from opentsdb_tpu.query.model import TSQuery
from opentsdb_tpu.tsd.http_api import HttpRequest, HttpRpcRouter

pytestmark = pytest.mark.control

NOW_S = int(time.time())


def _mk_tsdb(tmp_path=None, **extra):
    cfg = {
        "tsd.core.auto_create_metrics": "true",
        "tsd.control.enable": "true",
        "tsd.tpu.warmup": "false",
    }
    if tmp_path is not None:
        cfg["tsd.storage.data_dir"] = str(tmp_path)
        cfg["tsd.trace.enable"] = "true"
        cfg["tsd.trace.sample"] = "1"
    cfg.update(extra)
    return TSDB(Config(**cfg))


def _get(router, path, headers=None, **params):
    return router.handle(HttpRequest(
        "GET", path, {k: [str(v)] for k, v in params.items()},
        headers or {}, b""))


def _post(router, path, obj=None, headers=None):
    body = json.dumps(obj).encode() if obj is not None else b""
    return router.handle(HttpRequest("POST", path, {}, headers or {},
                                     body))


def _seed(tsdb, metric="ctl.cpu", n=40):
    for i in range(n):
        tsdb.add_point(metric, NOW_S - 1500 + i * 30, float(i),
                       {"host": "a" if i % 2 else "b"})


def _query_params(metric="ctl.cpu"):
    return {"start": "30m-ago", "m": f"sum:1m-sum:{metric}"}


def _tsq(metric="ctl.cpu", start="30m-ago", ds="1m-sum"):
    q = TSQuery.from_json({"start": start, "queries": [{
        "metric": metric, "aggregator": "sum", "downsample": ds}]})
    q.validate()
    return q


# ---------------------------------------------------------------------------
# shape-miner determinism oracle
# ---------------------------------------------------------------------------


class TestMinerOracle:

    def _log_lines(self):
        cand_a = cq_candidate(_tsq("m.a"))
        cand_b = cq_candidate(_tsq("m.b"))
        lines = []
        for i in range(12):
            lines.append({"ts": i, "durationMs": 40.0 + i,
                          "cache": "miss" if i % 3 == 0 else "hit",
                          "cq": cand_a})
        for i in range(5):
            lines.append({"ts": i, "durationMs": 5.0,
                          "cache": "miss", "cq": cand_b})
        return lines

    def test_same_log_same_scores(self, tmp_path):
        """Determinism oracle: identical log bytes — and ANY line
        permutation of them — mine to the identical ordered score
        list, so two routers (or two restarts) materialize the same
        set."""
        lines = self._log_lines()
        p1 = tmp_path / "a.jsonl"
        p1.write_text("\n".join(json.dumps(x) for x in lines) + "\n")
        shuffled = list(lines)
        random.Random(7).shuffle(shuffled)
        p2 = tmp_path / "b.jsonl"
        p2.write_text("\n".join(json.dumps(x) for x in shuffled)
                      + "\n")
        key = [(s.candidate, s.count, s.miss_count, s.score)
               for s in mine_shapes(str(p1))]
        assert key == [(s.candidate, s.count, s.miss_count, s.score)
                       for s in mine_shapes(str(p1))]  # rescan
        assert key == [(s.candidate, s.count, s.miss_count, s.score)
                       for s in mine_shapes(str(p2))]  # permutation
        assert len(key) == 2
        # count x miss-cost ranks the hot shape first
        assert key[0][1] == 12

    def test_torn_and_untagged_lines_skipped(self, tmp_path):
        cand = cq_candidate(_tsq("m.a"))
        p = tmp_path / "t.jsonl"
        p.write_text(
            json.dumps({"durationMs": 10.0, "cache": "miss",
                        "cq": cand}) + "\n"
            + '{"torn": \n'              # torn rotation tail
            + "[1, 2]\n"                 # non-dict
            + json.dumps({"durationMs": 3.0}) + "\n"   # untagged
            + json.dumps({"durationMs": 9.0, "cache": "miss",
                          "cq": cand}) + "\n")
        mined = mine_shapes(str(p))
        assert len(mined) == 1 and mined[0].count == 2

    def test_rotated_generation_included(self, tmp_path):
        cand = cq_candidate(_tsq("m.a"))
        line = json.dumps({"durationMs": 10.0, "cache": "miss",
                           "cq": cand}) + "\n"
        (tmp_path / "s.jsonl").write_text(line)
        (tmp_path / "s.jsonl.1").write_text(line * 3)
        mined = mine_shapes(str(tmp_path / "s.jsonl"))
        assert mined[0].count == 4

    def test_missing_log_mines_empty(self, tmp_path):
        assert mine_shapes(str(tmp_path / "nope.jsonl")) == []
        assert mine_shapes("") == []


class TestCandidateDerivation:

    def test_roundtrip_registers(self):
        """candidate_body() rebuilds a body the registry accepts, and
        auto_id is stable across processes (pure hash)."""
        t = _mk_tsdb()
        try:
            _seed(t)
            cand = cq_candidate(_tsq())
            cq = t.streaming.register(
                dict(candidate_body(cand), id=auto_id(cand)))
            assert cq.id == auto_id(cand)
            assert cq.id.startswith("auto-")
        finally:
            t.shutdown()

    def test_non_materializable_shapes_are_none(self):
        # absolute windows never repeat as ingest advances
        assert cq_candidate(_tsq(start=NOW_S * 1000 - 3600_000)) \
            is None
        q = _tsq()
        q.delete = True
        assert cq_candidate(q) is None
        # non-decomposable downsample cannot fold incrementally
        assert cq_candidate(_tsq(ds="1m-p95")) is None

    def test_filter_order_preserved(self):
        """The registry's serve match keys on the ORDERED filter
        tuple — a sorted candidate would register a standing query
        the original request could never hit."""
        def q(filters):
            tsq = TSQuery.from_json({"start": "30m-ago", "queries": [{
                "metric": "m.f", "aggregator": "sum",
                "downsample": "1m-sum", "filters": filters}]})
            tsq.validate()
            return tsq
        fa = {"type": "literal_or", "tagk": "host", "filter": "a",
              "groupBy": True}
        fb = {"type": "literal_or", "tagk": "dc", "filter": "x",
              "groupBy": True}
        c_ab = cq_candidate(q([fa, fb]))
        c_ba = cq_candidate(q([fb, fa]))
        assert c_ab != c_ba
        body = candidate_body(c_ab)
        assert [f["tagk"] for f in body["queries"][0]["filters"]] \
            == ["host", "dc"]


# ---------------------------------------------------------------------------
# adaptive materialization
# ---------------------------------------------------------------------------


def _pump_shapes(router, n=6, metric="ctl.cpu"):
    for _ in range(n):
        r = _get(router, "/api/query", **_query_params(metric))
        assert r.status == 200, r.body
    return r


class TestMaterialization:

    def test_auto_materializes_and_serves(self, tmp_path):
        t = _mk_tsdb(tmp_path,
                     **{"tsd.control.materialize.min_score": "0"})
        try:
            _seed(t)
            router = HttpRpcRouter(t)
            _pump_shapes(router)
            rep = t.control.tick()
            assert rep["errors"] == {}
            assert rep["materialize"]["registered"] == 1
            mats = json.loads(_get(
                router, "/api/control/materialized").body)
            assert len(mats) == 1
            assert mats[0]["id"].startswith("auto-")
            assert mats[0]["score"] > 0
            before = t.streaming.serve_hits
            r = _get(router, "/api/query", **_query_params())
            assert r.status == 200
            assert t.streaming.serve_hits == before + 1
        finally:
            t.shutdown()

    def test_auto_cq_bit_identical_to_hand_registered(self, tmp_path):
        """The serve equivalence oracle: an auto-materialized shape
        answers the repeat pull byte-identically to the same standing
        query registered by hand on an identically-written TSD."""
        t_auto = _mk_tsdb(
            tmp_path / "a",
            **{"tsd.control.materialize.min_score": "0"})
        t_hand = _mk_tsdb(tmp_path / "b")
        try:
            _seed(t_auto)
            _seed(t_hand)
            ra = HttpRpcRouter(t_auto)
            rh = HttpRpcRouter(t_hand)
            _pump_shapes(ra)
            assert t_auto.control.tick()["materialize"][
                "registered"] == 1
            cand = cq_candidate(_tsq())
            t_hand.streaming.register(
                dict(candidate_body(cand), id="hand1"))
            body_auto = _get(ra, "/api/query",
                             **_query_params()).body
            body_hand = _get(rh, "/api/query",
                             **_query_params()).body
            assert t_auto.streaming.serve_hits >= 1
            assert t_hand.streaming.serve_hits >= 1
            assert body_auto == body_hand
        finally:
            t_auto.shutdown()
            t_hand.shutdown()

    def test_retirement_waits_for_hysteresis(self, tmp_path):
        t = _mk_tsdb(tmp_path, **{
            "tsd.control.materialize.min_score": "0",
            "tsd.control.materialize.hysteresis": "2"})
        try:
            _seed(t)
            router = HttpRpcRouter(t)
            _pump_shapes(router)
            t.control.tick()
            cid = json.loads(_get(
                router, "/api/control/materialized").body)[0]["id"]
            # go cold: rotate BOTH shape-log generations away
            import os
            os.unlink(t.tracer.shape_path)
            # one cold scan: still standing (hysteresis = 2)
            t.control.tick()
            assert t.streaming.get(cid) is not None
            # second consecutive cold scan: retired
            t.control.tick()
            assert t.streaming.get(cid) is None
            assert json.loads(_get(
                router, "/api/control/materialized").body) == []
        finally:
            t.shutdown()

    def test_rejected_candidate_blacklisted_not_retried(
            self, tmp_path, monkeypatch):
        t = _mk_tsdb(tmp_path,
                     **{"tsd.control.materialize.min_score": "0"})
        try:
            _seed(t)
            router = HttpRpcRouter(t)
            _pump_shapes(router)
            from opentsdb_tpu.query.model import BadRequestError
            calls = []

            def reject(obj, now_ms=None):
                calls.append(obj)
                raise BadRequestError("not maintainable")

            monkeypatch.setattr(t.streaming, "register", reject)
            rep = t.control.tick()
            assert rep["errors"] == {}     # rejection is not a fault
            assert rep["materialize"]["registered"] == 0
            assert len(calls) == 1
            t.control.tick()
            assert len(calls) == 1         # blacklisted: no retry
        finally:
            t.shutdown()

    def test_cap_keeps_top_scorers_only(self, tmp_path):
        t = _mk_tsdb(tmp_path, **{
            "tsd.control.materialize.min_score": "0",
            "tsd.control.materialize.max": "1"})
        try:
            _seed(t, "ctl.hot")
            _seed(t, "ctl.cold")
            router = HttpRpcRouter(t)
            # score = count x p50 of the durations, and the p50 of two
            # is the slower one: 32 against 2, so that one request
            # stalled 4x (a collection, a busy worker) cannot turn the
            # ranking as it could at 8 against 2
            _pump_shapes(router, n=32, metric="ctl.hot")
            _pump_shapes(router, n=2, metric="ctl.cold")
            t.control.tick()
            mats = json.loads(_get(
                router, "/api/control/materialized").body)
            assert len(mats) == 1
            assert mats[0]["body"]["queries"][0]["metric"] \
                == "ctl.hot"
        finally:
            t.shutdown()


# ---------------------------------------------------------------------------
# multi-tenant QoS
# ---------------------------------------------------------------------------


class TestTenantGovernor:

    def _gov(self, **extra):
        t = _mk_tsdb(**dict({"tsd.control.qos.enable": "true"},
                            **extra))
        return t, t.control.qos

    def test_fair_share_sheds_over_share_tenant_only(self):
        t, g = self._gov()
        try:
            assert g.try_admit("noisy", 4) is None
            assert g.try_admit("victim", 4) is None
            g.started("noisy")
            g.started("noisy")
            # two active tenants x budget 4 -> share 2 each
            assert g.try_admit("noisy", 4) == "tenant"
            assert g.try_admit("victim", 4) is None
            g.finished("noisy")
            assert g.try_admit("noisy", 4) is None
        finally:
            t.shutdown()

    def test_solo_tenant_is_work_conserving(self):
        t, g = self._gov()
        try:
            for _ in range(3):
                assert g.try_admit("only", 4) is None
                g.started("only")
            assert g.try_admit("only", 4) is None  # full budget
            g.started("only")
            assert g.try_admit("only", 4) == "tenant"
        finally:
            t.shutdown()

    def test_weights_skew_shares(self):
        t, g = self._gov(
            **{"tsd.control.qos.weights": "gold:3,bronze:1"})
        try:
            g.try_admit("gold", 4)
            g.try_admit("bronze", 4)
            g.started("bronze")
            # bronze's share = ceil-ish of 4 * 1/4 = 1: it sheds
            assert g.try_admit("bronze", 4) == "tenant"
            for _ in range(2):
                assert g.try_admit("gold", 4) is None
                g.started("gold")
            assert g.try_admit("gold", 4) is None  # share 3
        finally:
            t.shutdown()

    def test_burn_penalty_shrinks_burning_tenants_share(self):
        t, g = self._gov(
            **{"tsd.control.qos.burn_penalty": "0.25"})
        try:
            now = time.time()
            # noisy burns its availability budget (5xx storm)
            for i in range(50):
                g.record("noisy", 10.0, errored=True, now_s=now)
                g.record("victim", 10.0, errored=False, now_s=now)
            penalties = g.refresh(now_s=now)
            assert penalties["noisy"] == 0.25
            assert penalties["victim"] == 1.0
            g.try_admit("noisy", 8, now_s=now)
            g.try_admit("victim", 8, now_s=now)
            g.started("noisy")
            g.started("noisy")
            # weights 0.25 vs 1.0 -> noisy share = 8*0.2 = 1
            assert g.try_admit("noisy", 8, now_s=now) == "tenant"
            assert g.try_admit("victim", 8, now_s=now) is None
        finally:
            t.shutdown()

    def test_overflow_bucket_caps_tenant_table(self):
        t, g = self._gov(**{"tsd.control.qos.max_tenants": "2"})
        try:
            g.try_admit("a", 0)
            g.try_admit("b", 0)
            g.try_admit("c", 0)   # collapses into "other"
            g.try_admit("d", 0)
            doc = g.describe()
            assert set(doc["tenants"]) == {"a", "b", "other"}
            assert doc["tenants"]["other"]["requests"] == 2
        finally:
            t.shutdown()

    def test_cache_gate_bills_bound_tenant(self):
        t, g = self._gov(
            **{"tsd.control.qos.tenant_cache_mb": "1"})
        try:
            g.try_admit("a", 0)
            g.bind("a")
            assert g.cache_gate(512 * 1024) is True
            assert g.cache_gate(512 * 1024) is True
            assert g.cache_gate(512 * 1024) is False  # over 1 MB
            g.unbind()
            assert g.cache_gate(1 << 30) is True  # untenanted passes
            # the control tick resets the per-interval window
            g.refresh()
            g.bind("a")
            assert g.cache_gate(512 * 1024) is True
        finally:
            t.shutdown()

    def test_result_cache_gated_insert_still_serves(self, tmp_path):
        """An over-budget tenant's results keep serving — they just
        are not retained (the gate bounds retention, not service)."""
        t = _mk_tsdb(tmp_path, **{
            "tsd.control.qos.enable": "true",
            "tsd.control.qos.tenant_cache_mb": "1"})
        try:
            _seed(t)
            g = t.control.qos     # building the plane wires the gate
            cache = t.result_cache
            assert cache.insert_gate is not None
            g.try_admit("hog", 0)
            g.bind("hog")
            g._tenants["hog"].cache_bytes = g.cache_budget_bytes
            router = HttpRpcRouter(t)
            r = _get(router, "/api/query", **_query_params())
            assert r.status == 200
            assert cache.gated >= 1
            assert cache.total_entries == 0
            g.unbind()
            r = _get(router, "/api/query", **_query_params())
            assert r.status == 200
            assert cache.total_entries == 1
        finally:
            t.shutdown()

    def test_fold_budget_gates_registration(self, tmp_path):
        t = _mk_tsdb(tmp_path, **{
            "tsd.control.qos.enable": "true",
            "tsd.control.qos.tenant_fold_mb": "1"})
        try:
            _seed(t)
            t.control.qos.fold_budget_bytes = 100  # tiny for test
            router = HttpRpcRouter(t)
            hdr = {"x-tsd-tenant": "hog"}
            body = candidate_body(cq_candidate(_tsq()))
            r = _post(router, "/api/query/continuous", body,
                      headers=hdr)
            assert r.status == 200, r.body
            r = _post(router, "/api/query/continuous",
                      dict(body, id="second"), headers=hdr)
            assert r.status == 400
            assert b"fold-memory budget" in r.body
            # another tenant is not affected by hog's debt
            r = _post(router, "/api/query/continuous",
                      dict(body, id="third"),
                      headers={"x-tsd-tenant": "calm"})
            assert r.status == 200, r.body
        finally:
            t.shutdown()

    def test_stats_surface_tenant_attribution(self):
        t, g = self._gov()
        try:
            g.try_admit("a", 1)
            g.started("a")
            g.try_admit("b", 1)   # second active tenant: share < 1
            collector = t.stats.collect()
            rows = [(n, v, tags) for n, v, tags in collector.records
                    if n.startswith("tsd.control.tenant.")]
            tenants = {tags.get("tenant") for _, _, tags in rows}
            assert {"a", "b"} <= tenants
            doc = json.loads(_get(HttpRpcRouter(t),
                                  "/api/stats/tenants").body)
            assert doc["enabled"] is True
            assert "a" in doc["tenants"]
        finally:
            t.shutdown()


@pytest.mark.robustness
class TestNoisyTenantSockets:
    """The noisy-tenant battery over REAL sockets: the victim keeps
    being served while the noisy tenant absorbs every structured
    tenant-shed 503."""

    def test_noisy_sheds_victim_serves(self):
        import asyncio
        import time as _t
        tsdb = TSDB(Config(**{
            "tsd.core.auto_create_metrics": "true",
            "tsd.tpu.warmup": "false",
            "tsd.control.enable": "true",
            "tsd.control.qos.enable": "true",
            "tsd.query.admission.max_inflight": "8",
            "tsd.query.admission.retry_after_s": "2"}))
        assert tsdb.control is not None  # wire the governor
        tsdb.add_point("nt.m", NOW_S - 60, 1.0, {"host": "a"})

        async def fetch(port, path, tenant):
            reader, writer = await asyncio.open_connection(
                "127.0.0.1", port)
            writer.write((f"GET {path} HTTP/1.0\r\n"
                          f"X-TSD-Tenant: {tenant}\r\n\r\n").encode())
            await writer.drain()
            raw = await asyncio.wait_for(reader.read(), 15)
            writer.close()
            head, _, body = raw.partition(b"\r\n\r\n")
            status = int(head.split(b" ")[1])
            return status, body

        async def scenario():
            from opentsdb_tpu.tsd.server import TSDServer
            server = TSDServer(tsdb, host="127.0.0.1", port=0)
            await server.start()
            port = server._server.sockets[0].getsockname()[1]
            try:
                path = "/api/query?start=1h-ago&m=sum:nt.m"
                # the victim is an ESTABLISHED tenant: one served
                # request puts it in the fair-share active window,
                # capping the noisy tenant at half the budget
                status, _ = await fetch(port, path, "victim")
                assert status == 200
                orig = server.http_router.handle

                def slow_handle(request):
                    if "query" in request.path:
                        _t.sleep(0.4)
                    return orig(request)

                server.http_router.handle = slow_handle
                jobs = [fetch(port, path, "noisy")
                        for _ in range(10)]
                jobs.append(fetch(port, path, "victim"))
                results = await asyncio.gather(*jobs)
                noisy, victim = results[:10], results[10]
                # the victim is served: its fair share was reserved
                assert victim[0] == 200, victim
                # the noisy tenant absorbed structured tenant sheds
                sheds = [json.loads(b)["error"]
                         for s, b in noisy if s == 503]
                tenant_sheds = [e for e in sheds
                                if "shed cause: tenant"
                                in e["details"]]
                assert tenant_sheds
                for err in tenant_sheds:
                    assert "fair in-flight share" in err["message"]
                # attribution: tenant sheds billed to noisy only
                doc = tsdb.control.qos.describe()
                assert doc["tenants"]["noisy"]["shed"] \
                    == len(tenant_sheds)
                assert doc["tenants"]["victim"]["shed"] == 0
            finally:
                await server.stop()

        asyncio.run(scenario())


# ---------------------------------------------------------------------------
# placement
# ---------------------------------------------------------------------------


def _router_tsdb(tmp_path, **extra):
    cfg = {
        "tsd.core.auto_create_metrics": "true",
        "tsd.tpu.warmup": "false",
        "tsd.control.enable": "true",
        "tsd.cluster.role": "router",
        "tsd.cluster.peers":
            "p0=127.0.0.1:1,p1=127.0.0.1:2,p2=127.0.0.1:3",
        "tsd.cluster.spool.dir": str(tmp_path / "spool"),
    }
    cfg.update(extra)
    return TSDB(Config(**cfg))


class TestPlacement:

    def test_plan_proposed_not_executed_without_opt_in(
            self, tmp_path):
        t = _router_tsdb(tmp_path)
        try:
            # p0 is 4x hotter than the mean: hot at the default 2.0
            t.cluster.peers["p0"].forwarded_points = 8000
            t.cluster.peers["p1"].forwarded_points = 100
            t.cluster.peers["p2"].forwarded_points = 100
            rep = t.control.tick()
            assert rep["errors"] == {}
            assert rep["placement"]["hotShards"] == ["p0"]
            assert rep["placement"]["proposal"] is True
            assert "applied" not in rep["placement"]
            # PROPOSED only: no cutover opened, ring untouched
            assert t.cluster.state.active is False
            assert t.cluster.old_ring is None
            router = HttpRpcRouter(t)
            plan = json.loads(_get(router,
                                   "/api/control/plan").body)
            assert plan["proposal"]["vnodes"] > t.cluster.ring.vnodes
            assert plan["planId"]
            assert plan["auto"] is False
        finally:
            t.shutdown()

    def test_confirm_executes_stale_id_rejected(self, tmp_path):
        t = _router_tsdb(tmp_path)
        try:
            t.cluster.peers["p0"].forwarded_points = 8000
            t.cluster.peers["p1"].forwarded_points = 100
            t.cluster.peers["p2"].forwarded_points = 100
            t.control.tick()
            router = HttpRpcRouter(t)
            r = _post(router, "/api/control/plan",
                      {"planId": "deadbeef"})
            assert r.status == 400
            assert t.cluster.state.active is False
            plan = json.loads(_get(router,
                                   "/api/control/plan").body)
            r = _post(router, "/api/control/plan",
                      {"planId": plan["planId"]})
            assert r.status == 200, r.body
            # the confirm ran the EXISTING reshard machinery
            assert t.cluster.state.active is True
            assert t.cluster.ring.vnodes \
                == plan["proposal"]["vnodes"]
        finally:
            t.shutdown()

    def test_auto_opt_in_applies_own_plan(self, tmp_path):
        t = _router_tsdb(tmp_path,
                         **{"tsd.control.placement.auto": "true"})
        try:
            t.cluster.peers["p0"].forwarded_points = 8000
            t.cluster.peers["p1"].forwarded_points = 100
            t.cluster.peers["p2"].forwarded_points = 100
            rep = t.control.tick()
            assert rep["errors"] == {}
            assert "applied" in rep["placement"]
            assert t.cluster.state.active is True
            # a second tick must not stack another reshard on the
            # open cutover window
            rep2 = t.control.tick()
            assert rep2["errors"] == {}
        finally:
            t.shutdown()

    def test_balanced_fleet_proposes_nothing(self, tmp_path):
        t = _router_tsdb(tmp_path)
        try:
            t.cluster.peers["p0"].forwarded_points = 1000
            t.cluster.peers["p1"].forwarded_points = 1100
            t.cluster.peers["p2"].forwarded_points = 1050
            t.control.tick()
            plan = json.loads(_get(HttpRpcRouter(t),
                                   "/api/control/plan").body)
            assert plan["hotShards"] == []
            assert plan["proposal"] is None
        finally:
            t.shutdown()


# ---------------------------------------------------------------------------
# chaos: a broken control loop can never fail the data plane
# ---------------------------------------------------------------------------


@pytest.mark.robustness
class TestControlChaos:

    SITES = ["control.materialize", "control.qos",
             "control.placement"]

    @pytest.mark.parametrize("site", SITES)
    def test_armed_site_parks_loop_not_data_plane(self, site,
                                                  tmp_path):
        t = _mk_tsdb(tmp_path, **{
            "tsd.control.qos.enable": "true",
            "tsd.control.materialize.min_score": "0"})
        try:
            _seed(t)
            router = HttpRpcRouter(t)
            _pump_shapes(router, n=3)
            t.faults.arm(site, error_count=100)
            rep = t.control.tick()
            actuator = site.split(".", 1)[1]
            assert actuator in rep["errors"]
            # the loop parked LOUDLY: health reports the breaker +
            # last error, status degrades past the threshold
            for _ in range(3):
                t.control.tick()
            health = json.loads(_get(router, "/api/health").body)
            assert health["control"]["tickErrors"] >= 1
            assert "control.loop" in health["breakers"]
            # ...and the data plane never noticed: writes ack
            r = _post(router, "/api/put",
                      {"metric": "ctl.cpu", "timestamp": NOW_S,
                       "value": 1.0, "tags": {"host": "z"}})
            assert r.status in (200, 204)
            # queries answer 200 exactly as with the subsystem off
            r = _get(router, "/api/query", **_query_params())
            assert r.status == 200
            r = _get(router, "/api/stats")
            assert r.status == 200
        finally:
            t.shutdown()

    def test_killed_control_thread_leaves_data_plane(self, tmp_path):
        t = _mk_tsdb(tmp_path,
                     **{"tsd.control.qos.enable": "true"})
        try:
            _seed(t)
            t.control.start()
            t.control.stop()   # the loop is dead
            router = HttpRpcRouter(t)
            r = _post(router, "/api/put",
                      {"metric": "ctl.cpu", "timestamp": NOW_S,
                       "value": 1.0, "tags": {"host": "z"}})
            assert r.status in (200, 204)
            r = _get(router, "/api/query", **_query_params())
            assert r.status == 200
            # admission still runs on the last computed penalties
            g = t.control.qos
            assert g.try_admit("a", 2) is None
        finally:
            t.shutdown()

    def test_breaker_gates_ticks_and_recovers(self, tmp_path):
        t = _mk_tsdb(tmp_path, **{
            "tsd.control.breaker.failure_threshold": "2",
            "tsd.control.breaker.reset_timeout_ms": "60000",
            "tsd.control.qos.enable": "true"})
        try:
            t.faults.arm("control.qos", error_count=100)
            t.control.tick()
            t.control.tick()
            assert t.control.breaker.state \
                == t.control.breaker.OPEN
            rep = t.control.tick()
            assert rep.get("skipped") == "breaker open"
        finally:
            t.shutdown()

    def test_disabled_control_is_inert(self):
        t = TSDB(Config(**{
            "tsd.core.auto_create_metrics": "true",
            "tsd.tpu.warmup": "false"}))
        try:
            assert t.control is None
            router = HttpRpcRouter(t)
            r = _get(router, "/api/control")
            assert r.status == 400
            health = json.loads(_get(router, "/api/health").body)
            assert health["control"] == {"enabled": False}
        finally:
            t.shutdown()


# ---------------------------------------------------------------------------
# concurrency hygiene
# ---------------------------------------------------------------------------


class TestControlConcurrency:

    def test_loop_under_witness(self, tmp_path, lock_witness,
                                leak_witness):
        """The control thread starts, ticks concurrently with served
        queries and admission traffic, and stops clean — no lock
        inversions, no leaked thread."""
        t = _mk_tsdb(tmp_path, **{
            "tsd.control.qos.enable": "true",
            "tsd.control.materialize.min_score": "0",
            "tsd.control.interval_s": "0.05"})
        try:
            _seed(t)
            router = HttpRpcRouter(t)
            t.control.start()
            import threading
            stop = threading.Event()
            errs = []

            def pound():
                g = t.control.qos
                while not stop.is_set():
                    try:
                        cause = g.try_admit("x", 4)
                        if cause is None:
                            g.started("x")
                            _get(router, "/api/query",
                                 **_query_params())
                            g.finished("x")
                    except Exception as exc:  # pragma: no cover
                        errs.append(exc)
                        return

            threads = [threading.Thread(target=pound)
                       for _ in range(3)]
            for th in threads:
                th.start()
            time.sleep(0.5)
            stop.set()
            for th in threads:
                th.join(5)
            assert not errs
            assert t.control.ticks >= 2
        finally:
            t.shutdown()
        assert not any(th.is_alive() for th in threads)
