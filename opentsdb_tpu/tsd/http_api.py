"""HTTP API: the full RPC surface of the TSD
(ref: ``src/tsd/RpcManager.java:267-360`` routing table and the
individual ``*Rpc.java`` handlers).

Transport-independent: :class:`HttpRpcRouter` maps parsed requests to
responses; :mod:`opentsdb_tpu.tsd.server` feeds it from asyncio sockets
and tests call it directly (the NettyMocks strategy of the reference,
test/tsd/NettyMocks.java).

Endpoints (as in RpcManager, mode-gated rw/ro/wo like :274-327):
``/api/put``, ``/api/rollup``, ``/api/histogram``, ``/api/query``
(+``/last``, ``/exp``, ``/gexp``), ``/api/suggest``, ``/api/search/*``,
``/api/annotation(s)`` (+bulk), ``/api/uid/*``, ``/api/tree/*``,
``/api/stats/*``, ``/api/aggregators``, ``/api/config(+/filters)``,
``/api/dropcaches``, ``/api/version``, ``/q``, ``/s``, ``/logs``, plus
the legacy unversioned aliases.
"""

from __future__ import annotations

import base64
import json
import re
import time
import urllib.parse
from dataclasses import dataclass, field
from typing import Any, Callable

from opentsdb_tpu import __version__
from opentsdb_tpu.core.tags import parse_put_value as \
    tags_parse_put_value
from opentsdb_tpu.meta.annotation import Annotation
# importing logring attaches the /logs ring buffer as early as the
# HTTP layer loads, so boot-time records are already captured (ref:
# the logback CyclicBufferAppender is configured at startup)
from opentsdb_tpu.utils.logring import ring_buffer
from opentsdb_tpu.ops import aggregators as aggs_mod
from opentsdb_tpu.query import filters as filters_mod
from opentsdb_tpu.query.limits import QueryLimitExceeded
from opentsdb_tpu.obs import trace as trace_mod
from opentsdb_tpu.obs.trace import trace_begin, trace_end
from opentsdb_tpu.query.model import (BadRequestError, TSQuery,
                                      parse_uri_query)
from opentsdb_tpu.stats.stats import QueryStats
from opentsdb_tpu.tsd.json_serializer import HttpJsonSerializer
from opentsdb_tpu.utils.faults import DegradedError


@dataclass
class HttpRequest:
    method: str
    path: str
    params: dict[str, list[str]] = field(default_factory=dict)
    headers: dict[str, str] = field(default_factory=dict)
    body: bytes = b""
    remote: str = ""
    auth: Any = None  # AuthState when authentication is enabled
    serializer: Any = None  # set by the router (?serializer= choice)
    # time.monotonic() when the server finished parsing the request —
    # the trace's query.admission span measures the queue/admission
    # wait from here to handler start (0.0 = unknown, e.g. direct
    # router.handle calls in tests)
    received_at: float = 0.0
    # time.monotonic() when the first bytes of this request were in
    # the server's buffer: query.receive runs from here to
    # received_at (0.0 = unknown, as above)
    first_byte_at: float = 0.0
    # the finished query.http context of a request the socket server
    # serves: its query.respond span starts where finish() ended
    traced: Any = None

    def param(self, key: str, default: str | None = None) -> str | None:
        vals = self.params.get(key)
        return vals[0] if vals else default

    def has_param(self, key: str) -> bool:
        return key in self.params

    def flag(self, key: str) -> bool:
        """true when ?key or ?key=true (ref: HttpQuery.parseBoolean)."""
        if key not in self.params:
            return False
        v = self.params[key][0]
        return v in ("", "true", "1", "yes")

    def _json_body(self, expected: type, noun: str, default):
        """Body as JSON of one expected container type; anything else
        — including valid-JSON scalars like ``null`` or ``42`` that
        would crash handlers calling ``.get()`` — is a clean 400
        (ref: the reference wraps every body-parse failure in
        BadRequestException)."""
        if not self.body:
            if default is not None:
                return default
            raise BadRequestError("Missing request content")
        try:
            obj = json.loads(self.body)
        except Exception as exc:  # noqa: BLE001
            raise BadRequestError(
                f"Unable to parse JSON body: {exc}") from None
        if not isinstance(obj, expected):
            raise BadRequestError(
                f"Request body must be a JSON {noun}, got "
                f"{type(obj).__name__}")
        return obj

    def json_object(self, default: dict | None = None) -> dict:
        return self._json_body(dict, "object", default)

    def json_array(self, default: list | None = None) -> list:
        return self._json_body(list, "array", default)


def as_int(value, name: str, default: int = 0) -> int:
    """Coerce a JSON/query value to int with a clean 400 — bare
    ``int()`` raises TypeError on null/list/bool inputs, which the
    router maps to 500."""
    if value is None:
        return default
    if isinstance(value, bool):
        raise BadRequestError(f"{name} must be an integer")
    try:
        return int(value)
    except (TypeError, ValueError):
        raise BadRequestError(f"{name} must be an integer") from None


@dataclass
class HttpResponse:
    status: int = 200
    body: bytes = b""
    content_type: str = "application/json; charset=UTF-8"
    headers: dict[str, str] = field(default_factory=dict)
    # generator of bytes chunks: set for very large responses so the
    # server streams with Transfer-Encoding: chunked instead of
    # materializing one giant body (ref: formatQueryAsyncV1 writing
    # the response incrementally through Netty)
    body_iter: Any = None
    # force Connection: close after this response (diediedie must not
    # leave a keep-alive handler pinning server shutdown)
    close_connection: bool = False


class HttpError(Exception):
    def __init__(self, status: int, message: str, details: str = ""):
        super().__init__(message)
        self.status = status
        self.message = message
        self.details = details


class HttpRpcRouter:
    """(ref: RpcManager + RpcHandler.java:46)"""

    def __init__(self, tsdb):
        self.tsdb = tsdb
        # pluggable wire format (ref: HttpSerializer.java:93,
        # tsd.http.serializer selection in RpcManager)
        self.serializers: dict[str, Any] = {}
        default_json = HttpJsonSerializer()
        self.serializers[default_json.shortname] = default_json
        ser_path = tsdb.config.get_string("tsd.http.serializer.plugin", "")
        if ser_path:
            from opentsdb_tpu.utils.plugin import load_class
            plugin_ser = load_class(ser_path)()
            # registered under its shortname AND made the default
            # (ref: the shortname registry, HttpSerializer.java:93)
            self.serializers[plugin_ser.shortname] = plugin_ser
            self.serializer = plugin_ser
        else:
            self.serializer = default_json
        mode = tsdb.mode
        self._routes: dict[str, Callable] = {}
        # read RPCs (not registered in write-only mode, RpcManager:274)
        if mode in ("rw", "ro"):
            self._routes.update({
                "query": self._handle_query,
                "suggest": self._handle_suggest,
                "search": self._handle_search,
                "uid": self._handle_uid,
                "annotation": self._handle_annotation,
                "annotations": self._handle_annotations,
                "tree": self._handle_tree,
            })
        # write RPCs (not registered in read-only mode, RpcManager:327)
        if mode in ("rw", "wo"):
            self._routes["put"] = self._handle_put
            self._routes["rollup"] = self._handle_rollup
            self._routes["histogram"] = self._handle_histogram
        self._routes.update({
            "aggregators": self._handle_aggregators,
            "cluster": self._handle_cluster,
            "config": self._handle_config,
            "control": self._handle_control,
            "dropcaches": self._handle_dropcaches,
            "health": self._handle_health,
            "lifecycle": self._handle_lifecycle,
            "profile": self._handle_profile,
            "serializers": self._handle_serializers,
            "stats": self._handle_stats,
            "trace": self._handle_trace,
            "version": self._handle_version,
        })
        # set by TSDServer so HTTP diediedie can request shutdown
        self.server = None
        self.plugin_routes: dict[str, Callable] = {}
        # /plugin/<path> HTTP endpoints (ref: HttpRpcPlugin.java:40,
        # RpcManager tsd.http.rpc.plugins :153)
        self.http_rpc_plugins: dict[str, Any] = {}
        from opentsdb_tpu.utils.plugin import load_plugin_instances
        for plugin in load_plugin_instances(tsdb.config, "tsd.http.rpc",
                                            init_arg=tsdb) or []:
            self.http_rpc_plugins[plugin.path().strip("/")] = plugin
        self.start_time = time.time()

    # ------------------------------------------------------------------

    def handle(self, request: HttpRequest) -> HttpResponse:
        t0 = time.monotonic()
        resp = self._apply_jsonp(request, self._handle_inner(request))
        # stamped by _trace_request when the request's trace was
        # retained — set here so ERROR responses (built by
        # _handle_inner's exception mapping, after the trace wrapper
        # unwound) carry the cross-reference too
        tid = getattr(request, "trace_id_hint", None)
        if tid:
            resp.headers.setdefault("X-TSD-Trace-Id", tid)
        # SLO burn-rate feed (obs/slo.py): every served query/put
        # counts toward the endpoint's latency + availability
        # budgets; a 5xx is the availability violation, 4xx is the
        # client's problem. Recorded here ONLY for direct-handler
        # callers (tests, benches — received_at unset): under the
        # real socket server the SERVER records at response time, so
        # admission sheds (503) and query timeouts (504) — responses
        # built without ever entering this router — still burn the
        # budget, the latency includes the queue wait, and a
        # timed-out query's still-running worker can't later count
        # its abandoned answer as a good event.
        if not request.received_at:
            slo = getattr(self.tsdb, "slo", None)
            if slo is not None and slo.enabled:
                endpoint = self._slo_endpoint(request.path)
                if endpoint is not None:
                    slo.record(endpoint,
                               (time.monotonic() - t0) * 1000.0,
                               resp.status >= 500)
        return resp

    @staticmethod
    def _slo_endpoint(path: str) -> str | None:
        parts = [p for p in path.split("?", 1)[0].split("/") if p]
        if not parts:
            return None
        if parts[0] == "api":
            parts = parts[1:]
            if parts and re.fullmatch(r"v[0-9]+", parts[0]):
                parts = parts[1:]
        if not parts:
            return None
        if parts[0] in ("query", "q"):
            return "query"
        if parts[0] == "put":
            return "put"
        return None

    def _handle_inner(self, request: HttpRequest) -> HttpResponse:
        # content negotiation: ?serializer=<shortname> picks a
        # registered wire format (ref: HttpSerializer.java:93)
        request.serializer = self.serializer
        name = request.param("serializer")
        if name:
            chosen = self.serializers.get(name)
            if chosen is None:
                return HttpResponse(
                    400, self.serializer.format_error(
                        400, f"Unable to find serializer "
                        f"with name '{name}'"))
            request.serializer = chosen
        try:
            # GET-only verb override for clients that cannot send
            # PUT/DELETE — API calls only, like the reference
            # (HttpQuery.getAPIMethod :259-287 is consulted from the
            # api-path handlers; /q, /s etc. ignore the param)
            if request.method == "GET" and \
                    request.path.lstrip("/").startswith("api") and \
                    request.has_param("method_override"):
                override = (request.param("method_override")
                            or "").lower()
                if not override:
                    raise HttpError(405, "Missing method override value")
                if override not in ("get", "post", "put", "delete"):
                    raise HttpError(
                        405,
                        "Unknown or unsupported method override value")
                request.method = override.upper()
            resp = self._dispatch(request)
            if (request.serializer is not None
                    and resp.content_type
                    == HttpResponse.__dataclass_fields__[
                        "content_type"].default):
                resp.content_type = \
                    request.serializer.response_content_type
            return resp
        except HttpError as e:
            return HttpResponse(e.status, request.serializer.format_error(
                e.status, e.message, e.details))
        except BadRequestError as e:
            return HttpResponse(400, request.serializer.format_error(
                400, str(e)))
        except ValueError as e:
            return HttpResponse(400, request.serializer.format_error(
                400, str(e)))
        except QueryLimitExceeded as e:
            # over-budget scans are a client-fixable condition
            return HttpResponse(413, request.serializer.format_error(
                413, str(e)))
        except DegradedError as e:
            # a deliberate degraded-mode refusal (e.g. device breaker
            # open with host fallback disabled): structured 503 +
            # Retry-After, never a 500
            resp = HttpResponse(503, request.serializer.format_error(
                503, str(e)))
            resp.headers["Retry-After"] = str(
                getattr(e, "retry_after_s", 1))
            return resp
        except NotImplementedError as e:
            return HttpResponse(501, request.serializer.format_error(
                501, str(e) or "not implemented"))
        except Exception as e:  # noqa: BLE001 (ref: RpcHandler 500 path)
            import traceback
            details = traceback.format_exc() if self.tsdb.config.get_bool(
                "tsd.http.show_stack_trace") else ""
            return HttpResponse(500, request.serializer.format_error(
                500, f"{type(e).__name__}: {e}", details))

    _JSONP_RE = re.compile(r"^[A-Za-z_$][A-Za-z0-9_$.]*$")

    def _apply_jsonp(self, request: HttpRequest,
                     resp: HttpResponse) -> HttpResponse:
        """``?jsonp=cb`` wraps JSON bodies in ``cb(...)`` (ref:
        HttpQuery.serializeJSONP :647-658 — applied to every JSON
        endpoint, errors included). Streamed responses are exempt
        (script tags can't consume chunked JSONP usefully)."""
        cb = request.param("jsonp")
        if not cb or resp.body_iter is not None or not resp.body \
                or "json" not in (resp.content_type or ""):
            return resp
        if not self._JSONP_RE.fullmatch(cb):
            # a hostile callback name is script injection, drop it
            return resp
        resp.body = cb.encode() + b"(" + resp.body + b")"
        resp.content_type = "application/javascript; charset=UTF-8"
        return resp

    def _dispatch(self, request: HttpRequest) -> HttpResponse:
        path = urllib.parse.unquote(request.path.split("?", 1)[0])
        parts = [p for p in path.split("/") if p]
        if not parts:
            return self._homepage(request)
        # /api[/vN]/endpoint/...  (ref: HttpQuery.explodeAPIPath)
        if parts[0] == "api":
            parts = parts[1:]
            if parts and re.fullmatch(r"v[0-9]+", parts[0]):
                # only v1 exists; an unsupported version is a clear
                # client error (ref: HttpQuery.apiVersion rejects
                # versions above MAX_API_VERSION=1, HttpQuery.java:67)
                if int(parts[0][1:]) != 1:
                    raise HttpError(
                        400, f"Unsupported API version {parts[0]}",
                        "This TSD implements API v1")
                parts = parts[1:]
            if not parts:
                raise HttpError(400, "Missing API endpoint")
            endpoint, rest = parts[0], parts[1:]
        elif parts[0] in ("q",):
            return self._handle_graph(request)
        elif parts[0] in ("s",):
            return self._handle_static(request, parts[1:])
        elif parts[0] == "favicon.ico":
            # (ref: RpcManager http.put("favicon.ico", staticfile))
            try:
                return self._handle_static(request, ["favicon.ico"])
            except HttpError:
                return HttpResponse(204)
        elif parts[0] == "diediedie":
            # graceful shutdown over HTTP (ref: RpcManager
            # enableDieDieDie http map; DieDieDie.execute)
            if self.server is not None:
                body = b"<html><body>Cleanup complete, shutting down" \
                       b"</body></html>"
                self.server.request_shutdown()
                return HttpResponse(200, body,
                                    content_type="text/html",
                                    close_connection=True)
            raise HttpError(404, "Endpoint not found: /diediedie",
                            "No server attached")
        elif parts[0] == "metrics":
            # OpenMetrics exposition (obs/openmetrics.py): the
            # standard scrape surface, deliberately OUTSIDE /api —
            # Prometheus conventionally scrapes /metrics
            return self._handle_metrics(request)
        elif parts[0] == "logs":
            return self._handle_logs(request)
        elif parts[0] == "plugin":
            key = "/".join(parts[1:])
            plugin = self.http_rpc_plugins.get(key)
            if plugin is None:
                raise HttpError(404, f"No HTTP RPC plugin at /{path}",
                                "The requested endpoint was not found")
            return plugin.execute(self.tsdb, request)
        elif parts[0] in ("aggregators", "version", "suggest", "stats",
                          "dropcaches"):
            # legacy unversioned aliases (ref: RpcManager deprecated map)
            endpoint, rest = parts[0], parts[1:]
        else:
            raise HttpError(404, f"Endpoint not found: /{parts[0]}",
                            "The requested endpoint was not found")
        if endpoint in self.plugin_routes:
            return self.plugin_routes[endpoint](request, rest)
        if self.tsdb.cluster is not None and endpoint in (
                "uid", "annotation", "annotations", "tree", "rollup",
                "histogram"):
            # the router owns no data: these endpoints would silently
            # serve from (or write into) its EMPTY local store — an
            # annotation put would be acked somewhere no scattered
            # read ever merges. Refuse loudly until they learn to
            # scatter (ROADMAP follow-up); /api/put forwards,
            # /api/query merges shards, and /api/suggest +
            # /api/search/lookup scatter-union.
            raise HttpError(
                400,
                f"/api/{endpoint} is not supported in router mode",
                "point this request at a shard TSD, or use "
                "/api/put and /api/query")
        handler = self._routes.get(endpoint)
        if handler is None:
            raise HttpError(404, f"Endpoint not found: /api/{endpoint}",
                            "The requested endpoint was not found")
        return handler(request, rest)

    # -- tracing -------------------------------------------------------

    def _trace_request(self, name: str, request: HttpRequest, fn):
        """Root one traced request (``ingest.put`` / ``query.http``):
        bind the context for the handler's whole synchronous stack
        (deep layers — WAL, engine, router — pick it up thread-
        locally), mark errors, and stamp the retained trace's id on
        the response as ``X-TSD-Trace-Id``."""
        tracer = self.tsdb.tracer
        ctx = tracer.start_request(name, request) \
            if tracer.enabled else None
        if ctx is None:
            return fn()
        error: BaseException | None = None
        try:
            with trace_mod.use(ctx):
                resp = fn()
        except BaseException as exc:
            error = exc
            raise
        finally:
            if error is not None:
                ctx.set_error(error)
            tracer.finish(ctx)
            if ctx.committed:
                request.trace_id_hint = ctx.trace_id
            if request.received_at and name == "query.http":
                request.traced = ctx
        return resp

    # -- write path ----------------------------------------------------

    def _check_permission(self, request: HttpRequest, perm) -> None:
        """(ref: Permissions gating in the RPC handlers)"""
        if request.auth is not None and \
                not request.auth.has_permission(perm):
            raise HttpError(403, "Permission denied",
                            f"{perm.name} is not granted")

    def _handle_put(self, request: HttpRequest, rest) -> HttpResponse:
        """(ref: PutDataPointRpc.java:272) Traced as an
        ``ingest.put`` root: decode → store scatter (or cluster
        forward) → WAL group-commit wait."""
        from opentsdb_tpu.auth.simple import Permissions
        self._check_permission(request, Permissions.HTTP_PUT)
        if request.method != "POST":
            raise HttpError(405, "Method not allowed",
                            "The HTTP method is not permitted")
        return self._trace_request(
            "ingest.put", request,
            lambda: self._handle_put_run(request))

    def _put_error_sink(self, errors: list) -> Callable:
        """Per-point error sink shared by the JSON and wire put paths:
        record the error for the response AND hand storage-layer
        failures to the SEH spool for replay."""
        def spool(dp: dict, e: Exception) -> None:
            errors.append({"datapoint": dp, "error": str(e)})
            seh = self.tsdb.storage_exception_handler
            from opentsdb_tpu.core.uid import FailedToAssignUniqueIdError
            if seh is not None and not isinstance(
                    e, (ValueError, LookupError,
                        FailedToAssignUniqueIdError)):
                # spool only storage-layer failures for replay; a bad
                # datapoint (unknown UID, filter veto, bad value) fails
                # identically on every retry
                # (ref: PutDataPointRpc requeue via SEH plugin)
                seh.handle_error(dp, e)
        return spool

    def _handle_put_wire(self, request: HttpRequest,
                         groups: list) -> HttpResponse:
        """Columnar wire delivery (``cluster/wire.py``): the batch
        arrives as pre-decoded ``(metric, tags, refs, ts, values)``
        groups, so it lands through ``add_point_groups`` — one WAL
        write + one group-committed fsync — with ZERO intermediate
        JSON. Validation still happens where it always has: inside
        the store, reported per point through the same error/SEH sink
        as the JSON path, so responses are byte-shaped identically."""
        details = request.flag("details")
        summary = request.flag("summary")
        cluster = self.tsdb.cluster
        if cluster is not None:
            # a wire delivery reached a router (router→router topo):
            # re-partition and forward, exactly like a JSON body would
            points = [dp for g in groups for dp in g[2]]
            success, failed, errors = cluster.forward_writes(points)
            return HttpResponse(
                400 if failed else 200,
                request.serializer.format_put(success, failed, errors,
                                              details))
        errors: list[dict] = []
        spool = self._put_error_sink(errors)
        t = self.tsdb
        use_hooks = (bool(t.write_filters) or t.rt_publisher is not None
                     or t.meta_cache is not None)
        _h = trace_begin("store.scatter", groups=len(groups))
        if use_hooks:
            # per-point hook plugins are inherently per-point: flatten
            # the columns back to tuples for them (rare on shards)
            parsed: list[tuple] = []
            dps: list[dict] = []
            for metric, tags, refs, ts_list, values in groups:
                for dp, ts, value in zip(refs, ts_list, values):
                    parsed.append((metric, ts, value, tags))
                    dps.append(dp)
            success, _ = t.add_point_batch(
                parsed, on_error=lambda i, e: spool(dps[i], e))
        else:
            success, _ = t.add_point_groups(groups, on_error=spool)
        trace_end(_h)
        failed = len(errors)
        if not details and not summary:
            if failed:
                raise HttpError(
                    400, "One or more data points had errors",
                    f"{failed} error(s) storing datapoints")
            return HttpResponse(204)
        return HttpResponse(
            400 if failed else 200,
            request.serializer.format_put(success, failed, errors,
                                          details))

    def _handle_put_run(self, request: HttpRequest) -> HttpResponse:
        wire_groups = getattr(request, "wire_groups", None)
        if wire_groups is not None:
            return self._handle_put_wire(request, wire_groups)
        # ONE decode span: body parse through validate/group (router
        # bodies end it after the parse — forwarding re-validates on
        # the shard, which records its own decode)
        _h = trace_begin("ingest.decode")
        points = request.serializer.parse_put(request.body)
        if _h is not None:
            _h.tag(points=len(points))
        details = request.flag("details")
        summary = request.flag("summary")
        cluster = self.tsdb.cluster
        if cluster is not None:
            trace_end(_h)
            # router mode: partition by the consistent-hash series key
            # and forward one series-grouped body per shard (each
            # lands as ONE WAL write + fsync via add_point_groups on
            # the peer); an unreachable shard's batch is durably
            # spooled and still acknowledged — never lost, never a 5xx
            success, failed, errors = cluster.forward_writes(points)
            if not details and not summary:
                if failed:
                    raise HttpError(
                        400, "One or more data points had errors",
                        f"{failed} error(s) storing datapoints")
                return HttpResponse(204)
            return HttpResponse(
                400 if failed else 200,
                request.serializer.format_put(success, failed, errors,
                                              details))
        errors: list[dict] = []
        spool = self._put_error_sink(errors)
        t = self.tsdb
        use_hooks = (bool(t.write_filters) or t.rt_publisher is not None
                     or t.meta_cache is not None)
        # validate + group in ONE pass straight into per-series
        # columns: no per-point tuple materialization, and the grouped
        # write commits the whole body as a single WAL write + fsync
        # (add_point_groups). Per-point hook plugins force the tuple
        # path below instead — those hooks are inherently per-point.
        groups: dict[tuple, tuple] = {}
        parsed: list[tuple] = []
        dps: list[dict] = []
        for dp in points:
            try:
                metric = dp["metric"]
                ts = int(dp["timestamp"])
                value = dp["value"]
                if isinstance(value, str):
                    # strict parse: int()/float() leniency would store
                    # e.g. "1_0" as 10 instead of erroring
                    value = tags_parse_put_value(value)
                elif value is None or isinstance(value, bool) or \
                        not isinstance(value, (int, float)):
                    # (ref: PutDataPointRpc rejects null/empty values
                    # per datapoint)
                    raise ValueError(f"invalid value: {value!r}")
                tags = dp.get("tags") or {}
                if use_hooks:
                    parsed.append((metric, ts, value, tags))
                    dps.append(dp)
                else:
                    key = (metric, tuple(sorted(tags.items())))
                    g = groups.get(key)
                    if g is None:
                        g = groups[key] = (metric, tags, [], [], [])
                    g[2].append(dp)
                    g[3].append(ts)
                    g[4].append(value)
            except (KeyError, TypeError) as e:
                errors.append({"datapoint": dp,
                               "error": f"missing field: {e}"})
            except ValueError as e:
                errors.append({"datapoint": dp, "error": str(e)})

        trace_end(_h)
        _h = trace_begin("store.scatter", groups=len(groups))
        if use_hooks:
            success, _ = self.tsdb.add_point_batch(
                parsed, on_error=lambda i, e: spool(dps[i], e))
        else:
            success, _ = self.tsdb.add_point_groups(
                groups.values(), on_error=spool)
        trace_end(_h)
        failed = len(errors)
        if not details and not summary:
            if failed:
                raise HttpError(
                    400,
                    f"One or more data points had errors",
                    f"{failed} error(s) storing datapoints")
            return HttpResponse(204)
        return HttpResponse(
            400 if failed else 200,
            request.serializer.format_put(success, failed, errors, details))

    def _handle_rollup(self, request: HttpRequest, rest) -> HttpResponse:
        """(ref: RollupDataPointRpc.java:227)"""
        if request.method != "POST":
            raise HttpError(405, "Method not allowed")
        points = request.serializer.parse_put(request.body)
        errors: list[dict] = []
        # a (tier, aggregator, series) a run: one UID resolution, one
        # append and one WAL record each, one fsync for the body
        from opentsdb_tpu.rollup.store import AggregateRun
        runs: dict[tuple, AggregateRun] = {}
        for dp in points:
            try:
                value = dp["value"]
                if isinstance(value, str):
                    # same strict rule as /api/put: reject underscore/
                    # whitespace forms float() would silently accept
                    # (allow_special keeps the NaN/Infinity spellings
                    # float() always took on this endpoint)
                    value = tags_parse_put_value(value,
                                                 allow_special=True)
                value = float(value)
                ts = int(dp["timestamp"])
                tags = dp.get("tags") or {}
                gb_agg = dp.get("groupByAggregator")
                key = (dp.get("interval"), dp.get("aggregator"),
                       dp["metric"], gb_agg,
                       bool(gb_agg or dp.get("isGroupBy")))
                run = runs.get((*key, tuple(sorted(tags.items()))))
                if run is None:
                    run = runs[(*key, tuple(sorted(tags.items())))] = \
                        AggregateRun(*key[:3], tags, [], [], *key[3:],
                                     refs=[])
            except Exception as e:  # noqa: BLE001
                errors.append({"datapoint": dp, "error": str(e)})
                continue
            run.timestamps.append(ts)
            run.values.append(value)
            run.refs.append(dp)
        success, _ = self.tsdb.add_aggregate_batch(
            runs.values(), on_error=lambda dp, e: errors.append(
                {"datapoint": dp, "error": str(e)}))
        if errors and not request.flag("details") \
                and not request.flag("summary"):
            raise HttpError(400, "One or more data points had errors",
                            "; ".join(e["error"] for e in errors[:5]))
        return HttpResponse(
            400 if errors else 200,
            request.serializer.format_put(success, len(errors), errors,
                                       request.flag("details")))

    def _handle_histogram(self, request: HttpRequest, rest) -> HttpResponse:
        """(ref: HistogramDataPointRpc.java) Value is the base64 codec
        blob (HistogramPojo)."""
        if request.method != "POST":
            raise HttpError(405, "Method not allowed")
        points = request.serializer.parse_put(request.body)
        errors: list[dict] = []
        parsed: list[tuple] = []
        dps: list[dict] = []
        for dp in points:
            try:
                parsed.append((dp["metric"], int(dp["timestamp"]),
                               base64.b64decode(dp["value"]),
                               dp.get("tags") or {}))
                dps.append(dp)
            except Exception as e:  # noqa: BLE001
                errors.append({"datapoint": dp, "error": str(e)})

        def on_error(i: int, e: Exception) -> None:
            errors.append({"datapoint": dps[i], "error": str(e)})

        success, _ = self.tsdb.add_histogram_batch(parsed,
                                                   on_error=on_error)
        if errors and not request.flag("details") \
                and not request.flag("summary"):
            raise HttpError(400, "One or more data points had errors")
        return HttpResponse(
            400 if errors else 200,
            request.serializer.format_put(success, len(errors), errors,
                                       request.flag("details")))

    # -- read path -----------------------------------------------------

    def _handle_query(self, request: HttpRequest, rest) -> HttpResponse:
        """(ref: QueryRpc.java:89-128)"""
        from opentsdb_tpu.auth.simple import Permissions
        self._check_permission(request, Permissions.HTTP_QUERY)
        sub = rest[0] if rest else ""
        if sub in ("exp", "gexp") and self.tsdb.cluster is not None:
            # the router owns no data: these endpoints would silently
            # run against its EMPTY local store and answer "no such
            # name" / empty streams for series that exist in the
            # cluster. Refuse loudly until they learn to scatter
            # (ROADMAP follow-up); plain /api/query merges shards,
            # /api/query/last scatters per shard (newest point wins),
            # /api/query/continuous federates per-shard partials.
            raise HttpError(
                400,
                f"/api/query/{sub} is not supported in router mode",
                "point this request at a shard TSD, or use /api/query")
        if sub == "last":
            return self._handle_query_last(request)
        if sub == "continuous":
            return self._handle_query_continuous(request, rest[1:])
        if sub in ("exp", "gexp"):
            from opentsdb_tpu.query.expression.endpoint import (
                handle_exp, handle_gexp)
            if sub == "exp":
                return handle_exp(self, request)
            return handle_gexp(self, request)
        return self._trace_request(
            "query.http", request,
            lambda: self._handle_query_run(request))

    def _handle_query_run(self, request: HttpRequest) -> HttpResponse:
        if request.method == "POST":
            obj = request.serializer.parse_query(request.body)
            tsq = TSQuery.from_json(obj)
        elif request.method in ("GET", "DELETE"):
            # URI form dedups identical m= specs (ref:
            # QueryRpc.parseQuery :617); POST keeps duplicates
            tsq = parse_uri_query(request.params).dedupe_queries()
        else:
            raise HttpError(405, "Method not allowed")
        tsq.validate()
        if request.method == "DELETE" or tsq.delete:
            if not self.tsdb.config.get_bool(
                    "tsd.http.query.allow_delete"):
                raise HttpError(400, "Deleting data is not enabled",
                                "set tsd.http.query.allow_delete")
            tsq.delete = True
        stats = QueryStats(
            request.remote, tsq,
            allow_duplicates=self.tsdb.config.get_bool(
                "tsd.query.allow_simultaneous_duplicates", True))
        from opentsdb_tpu.query.model import effective_pixels
        px = max((effective_pixels(tsq, s)[0] for s in tsq.queries),
                 default=0)
        tctx = trace_mod.current()
        if tctx is not None:
            # query-shape tags: what the offline workload miner
            # (ROADMAP item 5 / Storyboard) slices on
            s0 = tsq.queries[0] if tsq.queries else None
            tctx.tag(
                metrics=",".join(sorted({s.metric or "<tsuid>"
                                         for s in tsq.queries})),
                subs=len(tsq.queries),
                aggregator=s0.aggregator if s0 is not None else "",
                downsample=(s0.downsample or "")
                if s0 is not None else "",
                filters=sum(len(s.filters) for s in tsq.queries),
                pixels=px,
                start=tsq.start_ms, end=tsq.end_ms,
                delete=bool(tsq.delete))
            try:
                # canonical CQ-candidate tag: the shape log line the
                # control plane's miner groups on (control/shapes.py);
                # None (untaggable shape) is simply not logged
                from opentsdb_tpu.control.shapes import cq_candidate
                cand = cq_candidate(tsq)
                if cand:
                    tctx.tag(cq=cand)
            except Exception:  # tsdlint: allow[swallow] shape tagging feeds the miner; a derivation bug must not fail the query it describes
                pass
        streamed = False
        cluster = self.tsdb.cluster
        wire_sink = getattr(request, "wire_sink", None)
        degraded_shards: list[str] = []
        try:
            if cluster is not None:
                # router mode: scatter to every shard, merge group
                # partials. A dead/hung/tripped peer yields a 200
                # PARTIAL carrying the shardsDegraded marker (appended
                # by the serializer below) — never a 5xx — and a
                # degraded answer is never retained by the result
                # cache (ClusterRouter.run_cached).
                results, degraded_shards = cluster.run_cached(tsq)
            else:
                results = self.tsdb.new_query().run(tsq, stats)
            from opentsdb_tpu.stats.stats import QueryStat
            if px:
                stats.add_stat(QueryStat.DOWNSAMPLE_PIXELS, px)
            if tctx is not None:
                s = stats.stats
                tctx.tag(cache=(
                    "streaming" if s.get("streamingHit")
                    else "hit" if s.get("resultCacheHit")
                    else "coalesced" if s.get("resultCacheCoalesced")
                    else "miss"))
            t_ser = time.monotonic()
            total_dps = sum(r.num_dps if hasattr(r, "num_dps")
                            else len(r.dps) for r in results)
            stats.add_stat(QueryStat.EMITTED_DPS, total_dps)
            if tsq.show_stats or request.flag("show_stats"):
                # the NaN census walks every emitted point: only when
                # the caller asked for stats (ref: nanDPs). Columnar
                # results count vectorized; only list-backed ones walk
                import numpy as _np
                nan_dps = 0
                for r in results:
                    if getattr(r, "dps_arrays", None) is not None:
                        nan_dps += int(
                            _np.isnan(r.dps_arrays[1]).sum())
                    else:
                        nan_dps += sum(1 for _, v in r.dps if v != v)
                stats.add_stat(QueryStat.NAN_DPS, nan_dps)
            # very large responses stream per-series with chunked
            # transfer encoding instead of materializing one body
            # (ref: formatQueryAsyncV1 incremental writes)
            stream_after = self.tsdb.config.get_int(
                "tsd.http.query.stream_threshold_dps", 1_000_000)
            if stream_after and total_dps > stream_after \
                    and cluster is None and wire_sink is None \
                    and not (tsq.show_summary or tsq.show_stats
                             or request.flag("show_summary")
                             or request.flag("show_stats")) \
                    and hasattr(request.serializer, "stream_query"):
                inner = request.serializer.stream_query(
                    tsq, results, as_arrays=request.flag("arrays"))

                def body_iter(inner=inner, stats=stats, t_ser=t_ser,
                              px=px):
                    # the stream IS the serialization: success, timing
                    # AND completion are marked when it exhausts (or
                    # aborts), so /api/stats/query reports the real
                    # totalTime of streamed queries, not the
                    # pre-serialization slice
                    nbytes = 0
                    try:
                        for chunk in inner:
                            nbytes += len(chunk)
                            yield chunk
                        ser_ms = (time.monotonic() - t_ser) * 1e3
                        stats.add_stat(QueryStat.SERIALIZATION_TIME,
                                       ser_ms)
                        stats.add_stat(QueryStat.PAYLOAD_BYTES, nbytes)
                        self.tsdb.payload_stats.record(nbytes, ser_ms,
                                                       px)
                        stats.mark_serialization_successful()
                    finally:
                        stats.mark_complete()

                stats.add_stat(
                    QueryStat.PROCESSING_PRE_WRITE_TIME,
                    (time.monotonic_ns() - stats.start_ns) / 1e6)
                streamed = True
                return HttpResponse(200, b"", body_iter=body_iter())
            _h = trace_begin("query.serialize")
            if wire_sink is not None:
                # columnar wire leg (cluster/wire.py): ship each sub's
                # grids straight onto the socket as framed column
                # blocks the moment this handler reaches them — no
                # JSON serialization on the read path at all
                by_sub: dict[int, list] = {}
                for r in results:
                    by_sub.setdefault(r.sub_query_index, []).append(r)
                for idx, rs in sorted(by_sub.items()):
                    wire_sink(tsq, idx, rs)
                body = b""
            else:
                body = request.serializer.format_query(
                    tsq, results, as_arrays=request.flag("arrays"),
                    show_summary=tsq.show_summary
                    or request.flag("show_summary"),
                    show_stats=tsq.show_stats
                    or request.flag("show_stats"),
                    summary_extra=stats.stats,
                    degraded_shards=degraded_shards)
            trace_end(_h)
            ser_ms = (time.monotonic() - t_ser) * 1e3
            stats.add_stat(QueryStat.SERIALIZATION_TIME, ser_ms)
            stats.add_stat(QueryStat.PAYLOAD_BYTES, len(body))
            self.tsdb.payload_stats.record(len(body), ser_ms, px)
            stats.add_stat(QueryStat.PROCESSING_PRE_WRITE_TIME,
                           (time.monotonic_ns() - stats.start_ns) / 1e6)
            stats.mark_serialization_successful()
        finally:
            # a raise above lands here with executed still False; the
            # streaming path completes inside its body iterator instead
            if not streamed:
                stats.mark_complete()
        resp = HttpResponse(200, body)
        if degraded_shards:
            # header twin of the body marker so load balancers and
            # probes can spot partials without parsing the body
            resp.headers["X-OpenTSDB-Shards-Degraded"] = \
                ",".join(degraded_shards)
        return resp

    def _handle_query_continuous(self, request: HttpRequest,
                                 rest) -> HttpResponse:
        """Continuous (standing) queries
        (:mod:`opentsdb_tpu.streaming`): register / list / inspect /
        delete standing TSQueries and attach SSE push streams.

        - ``POST /api/query/continuous`` — register (body: TSQuery
          JSON + optional ``id`` + optional ``window`` object:
          ``{"type": "tumbling"}`` (default), ``{"type": "sliding",
          "size": "5m"}`` or ``{"type": "session", "gap": "2m"}`` —
          size/gap must be multiples of the downsample interval);
          400 when the query is not incrementally maintainable.
        - ``GET /api/query/continuous`` — list registered queries.
        - ``GET /api/query/continuous/<id>`` — one query + plan stats.
        - ``GET /api/query/continuous/<id>/result`` — the current
          windowed results (drains pending folds first; the only
          pull surface for sliding/session windows, which a plain
          TSQuery cannot express).
        - ``DELETE /api/query/continuous/<id>`` — deregister.
        - ``GET /api/query/continuous/<id>/deltas`` — one incremental
          update batch (the federated router's dirty-window drain; a
          pull twin of one SSE ``windows`` frame).
        - ``GET /api/query/continuous/<id>/stream`` — Server-Sent
          Events: an initial ``snapshot`` event, then incremental
          ``windows`` events; slow consumers are shed with a terminal
          ``shed`` event (bounded queues, never backpressure into
          ingest).

        In router mode the same surface serves FEDERATED continuous
        queries (:mod:`opentsdb_tpu.cluster.cq`): registrations
        scatter to every shard, pulls merge per-shard partials, and
        the SSE stream pushes merged cross-shard frames."""
        if self.tsdb.cluster is not None:
            registry = self.tsdb.cluster.cqs
        else:
            registry = self.tsdb.streaming
        if registry is None:
            raise HttpError(400, "Continuous queries are disabled",
                            "set tsd.streaming.enable = true")
        if not rest:
            if request.method == "POST":
                obj = request.json_object()
                ctl = self.tsdb._control
                tenant = None
                if ctl is not None and ctl.qos.enabled:
                    # per-tenant fold-memory budget: standing rings
                    # are the one resource a tenant holds FOREVER, so
                    # the quota gates registration, not serving (and
                    # the candidate body feeds the projected-size
                    # refusal of never-fitting shapes)
                    tenant = ctl.qos.tenant_of(request.headers)
                    if not ctl.qos.fold_budget_allows(tenant,
                                                      registry,
                                                      body=obj):
                        raise HttpError(
                            400, "tenant fold-memory budget "
                            "exhausted",
                            f"tenant {tenant!r} already holds "
                            "tsd.control.qos.tenant_fold_mb of "
                            "standing continuous-query state; "
                            "delete one or raise the budget")
                cq = registry.register(obj)
                if tenant is not None:
                    cq.tenant = tenant
                return HttpResponse(
                    200, json.dumps(cq.describe()).encode())
            if request.method == "GET":
                return HttpResponse(200, json.dumps(
                    [cq.describe() for cq in registry.list()]).encode())
            raise HttpError(405, "Method not allowed")
        cid = rest[0]
        if len(rest) > 1 and rest[1] == "result":
            if request.method != "GET":
                raise HttpError(405, "Method not allowed")
            cq = registry.get(cid)
            if cq is None:
                raise HttpError(
                    404, f"No continuous query with id {cid!r}")
            return HttpResponse(200, json.dumps(
                registry.current_results(cq)).encode())
        if len(rest) > 1 and rest[1] == "deltas":
            if request.method != "GET":
                raise HttpError(405, "Method not allowed")
            if not hasattr(registry, "delta_updates"):
                raise HttpError(
                    400, "deltas is a shard-local drain surface",
                    "the router consumes it; use /stream or /result")
            cq = registry.get(cid)
            if cq is None:
                raise HttpError(
                    404, f"No continuous query with id {cid!r}")
            return HttpResponse(200, json.dumps(
                registry.delta_updates(cq)).encode())
        if len(rest) > 1 and rest[1] == "stream":
            if request.method != "GET":
                raise HttpError(405, "Method not allowed")
            cq = registry.get(cid)
            if cq is None:
                raise HttpError(
                    404, f"No continuous query with id {cid!r}")
            from opentsdb_tpu.streaming.sse import sse_stream
            # SSE resume: browsers send Last-Event-ID on reconnect;
            # ?last_event_id= is the curl/test convenience. A
            # non-integer id is ignored (full snapshot), not a 400 —
            # refusing the reconnect would strand the dashboard.
            raw_id = request.headers.get(
                "last-event-id", request.param("last_event_id"))
            last_event_id = None
            if raw_id:
                try:
                    last_event_id = int(raw_id)
                except ValueError:
                    last_event_id = None
            resp = HttpResponse(
                200, b"",
                body_iter=sse_stream(
                    registry, cq,
                    max_lifetime_s=self.tsdb.config.get_float(
                        "tsd.streaming.sse.max_lifetime_s", 0.0),
                    last_event_id=last_event_id),
                content_type="text/event-stream; charset=UTF-8")
            resp.headers["Cache-Control"] = "no-cache"
            # an SSE stream is single-use by construction
            resp.close_connection = True
            return resp
        if request.method == "GET":
            cq = registry.get(cid)
            if cq is None:
                raise HttpError(
                    404, f"No continuous query with id {cid!r}")
            return HttpResponse(
                200, json.dumps(cq.describe(verbose=True)).encode())
        if request.method == "DELETE":
            if not registry.delete(cid):
                raise HttpError(
                    404, f"No continuous query with id {cid!r}")
            return HttpResponse(204)
        raise HttpError(405, "Method not allowed")

    def _handle_query_last(self, request: HttpRequest) -> HttpResponse:
        """(ref: QueryRpc.java:346 /api/query/last via TSUIDQuery).
        On a cluster router the request scatters to every read-ring
        shard and the newest point per series wins the merge; tsuid
        specs are refused (UIDs are per shard) and degraded shards
        ride the trailing body marker + header, the /api/query
        idiom."""
        from opentsdb_tpu.search.lookup import last_data_points
        if request.method == "POST":
            obj = request.json_object(default={})
            specs = obj.get("queries", [])
            if not isinstance(specs, list) or not all(
                    isinstance(q, dict) for q in specs):
                raise HttpError(
                    400, "queries must be an array of objects")
            for q in specs:
                ts = q.get("tsuids")
                if ts is not None and (not isinstance(ts, list)
                                       or not all(isinstance(x, str)
                                                  for x in ts)):
                    raise HttpError(
                        400, "tsuids must be a list of strings")
            back_scan = as_int(obj.get("backScan"), "backScan")
            resolve = bool(obj.get("resolveNames", False))
        else:
            specs = [{"uri": m} for m in request.params.get(
                "timeseries", [])]
            back_scan = int(request.param("back_scan", "0"))
            resolve = request.flag("resolve")
        cluster = self.tsdb.cluster
        if cluster is not None:
            if any(q.get("tsuids") for q in specs):
                raise HttpError(
                    400,
                    "tsuid specs are not supported in router mode",
                    "UIDs are assigned per shard — query by metric "
                    "and tags instead")
            points, degraded = cluster.scatter_last(
                specs, back_scan, resolve)
            if degraded:
                points = points + [{"shardsDegraded": degraded}]
            resp = HttpResponse(
                200, request.serializer.format_last_points(points))
            if degraded:
                resp.headers["X-OpenTSDB-Shards-Degraded"] = \
                    ",".join(degraded)
            return resp
        points = last_data_points(self.tsdb, specs, back_scan, resolve)
        return HttpResponse(200,
                            request.serializer.format_last_points(points))

    def _handle_suggest(self, request: HttpRequest, rest) -> HttpResponse:
        """(ref: SuggestRpc.java:30). On a cluster router the suggest
        scatters to every read-ring shard and the union answers
        (names live wherever their series landed); degraded shards
        ride the ``X-OpenTSDB-Shards-Degraded`` header — the body
        shape (a bare name array) has no room for a marker."""
        if request.method == "POST":
            obj = request.json_object(default={})
            stype = obj.get("type", "")
            q = obj.get("q", "")
            max_results = as_int(obj.get("max"), "max", 25)
        else:
            stype = request.param("type", "")
            q = request.param("q", "") or ""
            max_results = int(request.param("max", "25"))
        if stype not in ("metrics", "tagk", "tagv"):
            raise BadRequestError(f"Invalid 'type' parameter: {stype}")
        cluster = self.tsdb.cluster
        if cluster is not None:
            names, degraded = cluster.scatter_suggest(stype, q,
                                                      max_results)
            resp = HttpResponse(
                200, request.serializer.format_suggest(names))
            if degraded:
                resp.headers["X-OpenTSDB-Shards-Degraded"] = \
                    ",".join(degraded)
            return resp
        if stype == "metrics":
            names = self.tsdb.suggest_metrics(q, max_results)
        elif stype == "tagk":
            names = self.tsdb.suggest_tag_names(q, max_results)
        else:
            names = self.tsdb.suggest_tag_values(q, max_results)
        return HttpResponse(200, request.serializer.format_suggest(names))

    def _handle_search(self, request: HttpRequest, rest) -> HttpResponse:
        """(ref: SearchRpc.java; /api/search/lookup via
        TimeSeriesLookup.java:83). On a cluster router ``lookup``
        scatters to every read-ring shard; the union merges deduped
        on (metric, tags) — per-shard TSUIDs are not cluster
        identities — and degraded shards ride the header marker.
        Plugin search stays refused in router mode (the router has no
        index of its own)."""
        sub = rest[0] if rest else ""
        if self.tsdb.cluster is not None and sub != "lookup":
            raise HttpError(
                400,
                f"/api/search/{sub} is not supported in router mode",
                "point this request at a shard TSD, or use "
                "/api/search/lookup")
        from opentsdb_tpu.search.lookup import time_series_lookup
        if sub == "lookup":
            if request.method == "POST":
                obj = request.json_object(default={})
                metric = obj.get("metric") or ""
                if not isinstance(metric, str):
                    raise HttpError(400, "metric must be a string")
                raw_tags = obj.get("tags") or []
                if not isinstance(raw_tags, list) or not all(
                        isinstance(t, dict) for t in raw_tags):
                    raise HttpError(
                        400, "tags must be a list of {key, value}")
                tags = [(t.get("key"), t.get("value"))
                        for t in raw_tags]
                limit = as_int(obj.get("limit"), "limit", 25)
                use_meta = bool(obj.get("useMeta", False))
            else:
                m = request.param("m", "") or ""
                from opentsdb_tpu.core import tags as tags_mod
                metric, tag_map = tags_mod.parse_with_metric(m) \
                    if m else ("", {})
                tags = list(tag_map.items())
                limit = int(request.param("limit", "25"))
                use_meta = request.flag("use_meta")
            cluster = self.tsdb.cluster
            if cluster is not None:
                results, degraded = cluster.scatter_lookup(
                    metric, tags, limit, use_meta)
                resp = HttpResponse(
                    200, request.serializer.format_search(results))
                if degraded:
                    resp.headers["X-OpenTSDB-Shards-Degraded"] = \
                        ",".join(degraded)
                return resp
            results = time_series_lookup(self.tsdb, metric, tags, limit,
                                         use_meta)
            return HttpResponse(200, request.serializer.format_search(results))
        if self.tsdb.search_plugin is None:
            raise BadRequestError(
                "Searching is not enabled on this TSD")
        obj = request.json_object(default={})
        results = self.tsdb.search_plugin.execute_query(sub, obj)
        return HttpResponse(200, request.serializer.format_search(results))

    # -- annotations (ref: AnnotationRpc.java) -------------------------

    def _handle_serializers(self, request: HttpRequest, rest
                            ) -> HttpResponse:
        """Registered wire formats (ref: HttpSerializer listing,
        TestHttpJsonSerializer.formatSerializersV1)."""
        out = [{
            "serializer": s.shortname,
            "class": type(s).__name__,
            "version": getattr(s, "version", "2.0.0"),
            "request_content_type": getattr(
                s, "request_content_type", "application/json"),
            "response_content_type": getattr(
                s, "response_content_type",
                "application/json; charset=UTF-8"),
        } for s in self.serializers.values()]
        return HttpResponse(200, json.dumps(out).encode())

    def _handle_annotation(self, request: HttpRequest, rest
                           ) -> HttpResponse:
        if rest and rest[0] == "bulk":
            return self._handle_annotation_bulk(request)
        store = self.tsdb.annotations
        if request.method == "GET":
            tsuid = request.param("tsuid", "") or ""
            start = int(request.param("start_time", "0"))
            note = store.get(tsuid.upper() if tsuid else "", start)
            if note is None:
                raise HttpError(404, "Unable to locate annotation in storage")
            return HttpResponse(200, request.serializer.format_annotation(note))
        if request.method in ("POST", "PUT"):
            obj = request.json_object(default={})
            note = Annotation.from_json(obj)
            note.tsuid = note.tsuid.upper()
            existing = store.get(note.tsuid, note.start_time)
            if request.method == "POST" and existing is not None:
                # POST merges into existing (ref: AnnotationRpc syncToStorage)
                if not note.description:
                    note.description = existing.description
                if not note.notes:
                    note.notes = existing.notes
                if not note.end_time:
                    note.end_time = existing.end_time
                merged_custom = dict(existing.custom)
                merged_custom.update(note.custom)
                note.custom = merged_custom
            store.store(note)
            if self.tsdb.search_plugin is not None:
                self.tsdb.search_plugin.index_annotation(note)
            return HttpResponse(200, request.serializer.format_annotation(note))
        if request.method == "DELETE":
            tsuid = (request.param("tsuid", "") or "").upper()
            start = int(request.param("start_time", "0"))
            note = store.get(tsuid, start)
            if note is None or not store.delete(tsuid, start):
                raise HttpError(404, "Unable to locate annotation in storage")
            if self.tsdb.search_plugin is not None:
                self.tsdb.search_plugin.delete_annotation(note)
            return HttpResponse(204)
        raise HttpError(405, "Method not allowed")

    def _handle_annotation_bulk(self, request: HttpRequest) -> HttpResponse:
        store = self.tsdb.annotations
        if request.method in ("POST", "PUT"):
            objs = request.json_array(default=[])
            if not all(isinstance(o, dict) for o in objs):
                raise HttpError(
                    400, "Each annotation must be an object")
            notes = []
            for obj in objs:
                note = Annotation.from_json(obj)
                note.tsuid = note.tsuid.upper()
                store.store(note)
                notes.append(note)
            return HttpResponse(200,
                                request.serializer.format_annotations(notes))
        if request.method == "DELETE":
            obj = request.json_object(default={})
            tsuids = obj.get("tsuids")
            if obj.get("global"):
                tsuids = [""]
            elif not tsuids:
                # ref: Annotation.deleteRange requires tsuids or global
                raise HttpError(
                    400, "Please supply either the global flag or tsuids")
            if not isinstance(tsuids, list) or not all(
                    isinstance(t, str) for t in tsuids):
                raise HttpError(400, "tsuids must be a list of strings")
            start = as_int(obj.get("startTime"), "startTime")
            end = as_int(obj.get("endTime"), "endTime",
                         int(time.time()))
            count = store.delete_range(
                [t.upper() for t in tsuids], start, end)
            obj["totalDeleted"] = count
            return HttpResponse(200, json.dumps(obj).encode())
        raise HttpError(405, "Method not allowed")

    def _handle_annotations(self, request: HttpRequest, rest
                            ) -> HttpResponse:
        """Global annotation range query (ref: AnnotationRpc). Bulk
        edits live at /api/annotation/bulk; a write-verb here would
        otherwise silently run the GET range query."""
        if request.method != "GET":
            raise HttpError(405, "Method not allowed",
                            "Use /api/annotation/bulk for bulk edits")
        start = as_int(request.param("start_time"), "start_time")
        end = as_int(request.param("end_time"), "end_time",
                     int(time.time()))
        notes = self.tsdb.annotations.global_range(start, end)
        return HttpResponse(200, request.serializer.format_annotations(notes))

    # -- uid (ref: UniqueIdRpc.java) -----------------------------------

    def _handle_uid(self, request: HttpRequest, rest) -> HttpResponse:
        sub = rest[0] if rest else ""
        if sub == "assign":
            return self._uid_assign(request)
        if sub == "rename":
            return self._uid_rename(request)
        if sub == "uidmeta":
            return self._uid_meta(request)
        if sub == "tsmeta":
            return self._ts_meta(request)
        raise HttpError(404, "Endpoint not found",
                        f"/api/uid/{sub} is not a valid endpoint")

    def _uid_assign(self, request: HttpRequest) -> HttpResponse:
        if request.method == "POST":
            obj = request.json_object(default={})
        else:
            obj = {k: (request.param(k) or "").split(",")
                   for k in ("metric", "tagk", "tagv")
                   if request.has_param(k)}
            unknown = [k for k in request.params
                       if k not in ("metric", "tagk", "tagv",
                                    "serializer", "jsonp")]
            if unknown:
                # a typo'd type silently assigning nothing is how UIDs
                # get lost (ref: TestUniqueIdRpc.assignQsTypo -> 400)
                raise HttpError(
                    400, f"Unknown parameter(s): {unknown}",
                    "Recognized types: metric, tagk, tagv")
        if not any(obj.get(k) for k in ("metric", "tagk", "tagv")):
            raise HttpError(
                400, "Missing values to assign UIDs",
                "Supply metric, tagk and/or tagv name lists")
        response: dict[str, Any] = {}
        had_error = False
        from opentsdb_tpu.auth.simple import Permissions
        create_perm = {"metric": Permissions.CREATE_METRIC,
                       "tagk": Permissions.CREATE_TAGK,
                       "tagv": Permissions.CREATE_TAGV}
        # every requested kind's creation permission is checked BEFORE
        # any assignment commits, so a 403 can't discard partial work
        # (ref: Permissions.java:27 CREATE_TAGK/TAGV/METRIC)
        for kind in ("metric", "tagk", "tagv"):
            if obj.get(kind):
                self._check_permission(request, create_perm[kind])
        for kind in ("metric", "tagk", "tagv"):
            names = obj.get(kind) or []
            if isinstance(names, str):
                names = [names]
            if not isinstance(names, list) or not all(
                    isinstance(n, str) for n in names):
                raise HttpError(
                    400, f"{kind} must be a name or list of names")
            good: dict[str, str] = {}
            bad: dict[str, str] = {}
            registry = self.tsdb.uids.by_kind(kind)
            for name in names:
                try:
                    uid = self.tsdb.assign_uid(kind, name)
                    good[name] = registry.int_to_uid(uid).hex().upper()
                except Exception as e:  # noqa: BLE001
                    bad[name] = str(e)
                    had_error = True
            if names:
                response[kind] = good
                if bad:
                    response[f"{kind}_errors"] = bad
        return HttpResponse(400 if had_error else 200,
                            request.serializer.format_uid_assign(response))

    def _uid_rename(self, request: HttpRequest) -> HttpResponse:
        obj = request.json_object(default={}) \
            if request.method == "POST" else \
            {k: request.param(k) for k in ("metric", "tagk", "tagv",
                                           "name")}
        new_name = obj.get("name") or ""
        if not new_name:
            raise BadRequestError("Missing 'name' parameter")
        for kind in ("metric", "tagk", "tagv"):
            old = obj.get(kind)
            if old:
                try:
                    self.tsdb.uids.by_kind(kind).rename(old, new_name)
                    return HttpResponse(200, json.dumps(
                        {"result": "true"}).encode())
                except Exception as e:  # noqa: BLE001
                    return HttpResponse(400, json.dumps(
                        {"result": "false", "error": str(e)}).encode())
        raise BadRequestError("Missing uid type/name to rename")

    def _uid_meta(self, request: HttpRequest) -> HttpResponse:
        if request.method == "GET":
            uid = (request.param("uid", "") or "").upper()
            kind = (request.param("type", "") or "").lower()
            meta = self.tsdb.meta.get_uid_meta(kind, uid)
            if meta is None:
                # fall back to a default doc for existing UIDs (ref:
                # UIDMeta.getUIDMeta returning skeleton docs)
                try:
                    registry = self.tsdb.uids.by_kind(kind)
                    name = registry.get_name(bytes.fromhex(uid))
                except Exception:  # noqa: BLE001
                    raise HttpError(
                        404, "Could not find the requested UID") from None
                from opentsdb_tpu.meta.meta_store import UIDMeta
                meta = UIDMeta(uid=uid, type=kind.upper(), name=name)
            return HttpResponse(200, json.dumps(meta.to_json()).encode())
        from opentsdb_tpu.meta.meta_store import MetaStore
        fields = self._meta_request_fields(request)
        uid = (fields.get("uid") or request.param("uid", "")
               or "").upper()
        kind = (fields.get("type") or request.param("type", "")
                or "").lower()
        if not uid or kind not in ("metric", "tagk", "tagv"):
            raise BadRequestError("Missing/invalid uid or type")
        if request.method in ("POST", "PUT"):
            # merge-on-POST, replace-on-PUT
            # (ref: UniqueIdRpc.java:179-226 syncToStorage overwrite)
            try:
                meta = self.tsdb.meta.sync_uid_meta(
                    kind, uid, fields, request.method == "PUT")
            except MetaStore.NotModified:
                return HttpResponse(304, b"")
            except LookupError:
                raise HttpError(
                    404, "Could not find the requested UID") from None
            return HttpResponse(200,
                                json.dumps(meta.to_json()).encode())
        if request.method == "DELETE":
            self.tsdb.meta.delete_uid_meta(kind, uid)
            return HttpResponse(204, b"")
        raise HttpError(405, "Method not allowed")

    @staticmethod
    def _meta_request_fields(request: HttpRequest) -> dict:
        """Body JSON, or the query-string form of the same fields
        (ref: parseUIDMetaQS / parseTSMetaQS)."""
        if request.body:
            return request.json_object()
        out = {}
        for key in ("uid", "type", "tsuid", "m", "displayName",
                    "display_name", "description", "notes", "units",
                    "dataType", "retention", "max", "min"):
            val = request.param(key)
            if val is not None:
                out["displayName" if key == "display_name"
                    else key] = val
        return out

    def _ts_meta(self, request: HttpRequest) -> HttpResponse:
        from opentsdb_tpu.meta.meta_store import MetaStore
        if request.method == "GET":
            tsuid = (request.param("tsuid", "") or "").upper()
            meta = self.tsdb.meta.get_ts_meta(tsuid)
            if meta is None:
                raise HttpError(
                    404, "Could not find Timeseries meta data")
            return HttpResponse(200, json.dumps(meta.to_json()).encode())
        fields = self._meta_request_fields(request)
        tsuid = (fields.get("tsuid") or request.param("tsuid", "")
                 or "").upper()
        create = False
        if not tsuid:
            # "m=metric{tagk=tagv,...}" spec form; create=true
            # materializes the doc (ref: UniqueIdRpc getTSUIDForMetric)
            mspec = fields.get("m") or request.param("m")
            if not mspec:
                raise BadRequestError("Missing tsuid or m parameter")
            try:
                tsuid = self._tsuid_for_metric(mspec)
            except LookupError as e:
                # unknown metric/tag name in the spec is a client error
                raise HttpError(404, str(e)) from None
            create = (fields.get("create") or request.param(
                "create", "") or "") in ("true", True)
        if request.method in ("POST", "PUT"):
            try:
                meta = self.tsdb.meta.sync_ts_meta(
                    tsuid, fields, request.method == "PUT",
                    create=create)
            except MetaStore.NotModified:
                return HttpResponse(304, b"")
            except LookupError as e:
                raise HttpError(404, str(e)) from None
            return HttpResponse(200,
                                json.dumps(meta.to_json()).encode())
        if request.method == "DELETE":
            self.tsdb.meta.delete_ts_meta(tsuid)
            return HttpResponse(204, b"")
        raise HttpError(405, "Method not allowed")

    def _tsuid_for_metric(self, mspec: str) -> str:
        """``metric{tagk=tagv,...}`` -> tsuid hex
        (ref: UniqueIdRpc.getTSUIDForMetric)."""
        m = re.match(r"^([^{]+)(?:\{([^}]*)\})?$", mspec.strip())
        if not m:
            raise BadRequestError(f"Invalid metric spec {mspec!r}")
        uids = self.tsdb.uids
        metric_id = uids.metrics.get_id(m.group(1))
        tag_ids = []
        for pair in (m.group(2) or "").split(","):
            if not pair:
                continue
            k, _, v = pair.partition("=")
            tag_ids.append((uids.tag_names.get_id(k.strip()),
                            uids.tag_values.get_id(v.strip())))
        return uids.tsuid(metric_id, sorted(tag_ids)).hex().upper()

    # -- tree (ref: TreeRpc.java) --------------------------------------

    def _handle_tree(self, request: HttpRequest, rest) -> HttpResponse:
        from opentsdb_tpu.tree.rpc import handle_tree_request
        return handle_tree_request(self, request, rest)

    # -- monitoring ----------------------------------------------------

    def _handle_aggregators(self, request: HttpRequest, rest
                            ) -> HttpResponse:
        return HttpResponse(
            200, request.serializer.format_aggregators(aggs_mod.names()))

    def _handle_config(self, request: HttpRequest, rest) -> HttpResponse:
        if rest and rest[0] == "filters":
            return HttpResponse(200, json.dumps(
                filters_mod.filter_types()).encode())
        return HttpResponse(200, request.serializer.format_config(
            self.tsdb.config.dump_configuration()))

    def _handle_dropcaches(self, request: HttpRequest, rest
                           ) -> HttpResponse:
        self.tsdb.drop_caches()
        return HttpResponse(200, request.serializer.format_dropcaches(
            {"status": "200", "message": "Caches dropped"}))

    def _handle_metrics(self, request: HttpRequest) -> HttpResponse:
        """``GET /metrics`` — OpenMetrics exposition of the full
        stats registry: counters, gauges, the latency ``Histogram``s
        as native cumulative ``_bucket``/``_sum``/``_count`` series,
        and the SLO burn-rate gauges. Prometheus scrapes this
        directly; no self-telemetry pump required."""
        if request.method != "GET":
            raise HttpError(405, "Method not allowed")
        from opentsdb_tpu.obs import openmetrics
        return HttpResponse(
            200, openmetrics.render(self.tsdb),
            content_type=openmetrics.CONTENT_TYPE)

    def _handle_profile(self, request: HttpRequest, rest
                        ) -> HttpResponse:
        """``GET /api/profile?seconds=N`` — the continuous sampling
        profiler's trailing window (:mod:`opentsdb_tpu.obs.profiler`)
        as flamegraph-ready collapsed text (default; pipe straight
        into flamegraph.pl or paste into speedscope) or
        ``?format=json``. ``?role=query`` filters one thread role."""
        if request.method != "GET":
            raise HttpError(405, "Method not allowed")
        profiler = self.tsdb.profiler
        if not profiler.enabled or profiler.hz <= 0:
            raise HttpError(400, "Profiling is disabled",
                            "set tsd.profile.enable = true and "
                            "tsd.profile.hz > 0")
        seconds = as_int(request.param("seconds"), "seconds",
                         profiler.ring_s)
        role = request.param("role", "") or ""
        fmt = request.param("format", "collapsed") or "collapsed"
        if fmt == "json":
            return HttpResponse(200, json.dumps({
                "seconds": min(max(seconds, 1), profiler.ring_s),
                "hz": profiler.hz,
                "roles": profiler.report(seconds, role),
                "profiler": profiler.health_info(),
            }).encode())
        if fmt != "collapsed":
            raise HttpError(400, "format must be collapsed or json")
        return HttpResponse(
            200, profiler.collapsed(seconds, role).encode(),
            content_type="text/plain; charset=UTF-8")

    def _handle_stats(self, request: HttpRequest, rest) -> HttpResponse:
        """(ref: StatsRpc.java; /api/stats + /query /jvm /threads
        /region_clients; grown here: /raw — the per-node fleet-merge
        source, /fleet — the router's cluster-wide aggregation,
        /query_shapes — the mined query-shape summary)"""
        sub = rest[0] if rest else ""
        if sub == "query":
            return HttpResponse(200, request.serializer.format_query_stats(
                QueryStats.running_and_completed()))
        if sub == "raw":
            # counters/gauges as records plus FULL-resolution
            # histogram snapshots: what the fleet merge consumes
            # (bucket-summing needs the real buckets — percentiles
            # don't merge)
            collector = self.tsdb.stats.collect(
                latency_percentiles=False)
            self.tsdb.collect_stats(collector)
            return HttpResponse(200, json.dumps({
                "ts": int(time.time()),
                "records": [
                    {"metric": name, "value": value, "tags": tags}
                    for name, value, tags in collector.records],
                "histograms": [
                    {"name": name, "labels": labels, **hist.snapshot()}
                    for name, labels, hist
                    in self.tsdb.stats.histograms()],
            }).encode())
        if sub == "fleet":
            cluster = self.tsdb.cluster
            if cluster is None:
                raise HttpError(
                    400, "/api/stats/fleet requires tsd.cluster.role "
                    "= router",
                    "per-node stats live at /api/stats[/raw]")
            return HttpResponse(200, json.dumps(
                cluster.fleet_stats()).encode())
        if sub == "query_shapes":
            return self._handle_query_shapes(request)
        if sub == "tenants":
            # per-tenant admission/SLO attribution (control-plane
            # QoS); the raw attribute — stats must not instantiate
            # the control plane just to report it absent
            ctl = getattr(self.tsdb, "_control", None)
            doc = ctl.qos.describe() if ctl is not None else {
                "enabled": self.tsdb.config.get_bool(
                    "tsd.control.qos.enable", False)}
            return HttpResponse(200, json.dumps(doc).encode())
        if sub == "jvm":
            return HttpResponse(200, json.dumps(
                self._runtime_stats()).encode())
        if sub == "threads":
            import threading
            return HttpResponse(200, json.dumps([
                {"name": t.name, "state": "ALIVE" if t.is_alive()
                 else "DEAD", "daemon": t.daemon}
                for t in threading.enumerate()]).encode())
        if sub == "region_clients":
            # storage is in-process: one logical "region client"
            return HttpResponse(200, json.dumps([{
                "id": 0, "backend": self.tsdb.config.get_string(
                    "tsd.storage.backend", "memory"),
                "pendingRPCs": 0, "dead": False,
            }]).encode())
        collector = self.tsdb.stats.collect()
        self.tsdb.collect_stats(collector)
        return HttpResponse(200, request.serializer.format_stats(
            collector.as_json()))

    def _handle_query_shapes(self, request: HttpRequest
                             ) -> HttpResponse:
        """``GET /api/stats/query_shapes`` — the ROADMAP item-5
        mining input made inspectable without shell access: a top-N
        summary over ``query_shapes.jsonl`` (current + one rotated
        generation), grouped by shape key (metrics, aggregator,
        downsample, filter count, pixel budget) with per-shape
        counts, the cache-outcome mix, and p50/p95 of total duration
        and each stage."""
        if request.method != "GET":
            raise HttpError(405, "Method not allowed")
        tracer = self.tsdb.tracer
        path = getattr(tracer, "shape_path", "")
        if not path:
            raise HttpError(
                400, "Query-shape logging is disabled",
                "needs tsd.trace.enable + tsd.trace.shapes.enable "
                "and a tsd.storage.data_dir")
        limit = as_int(request.param("limit"), "limit", 20)
        import os
        shapes: dict[tuple, dict[str, Any]] = {}
        lines_read = 0
        # rotated generation first so per-shape samples stay in time
        # order (not that percentiles care)
        for p in (path + ".1", path):
            if not os.path.isfile(p):
                continue
            try:
                with open(p, "r", encoding="utf-8") as fh:
                    for line in fh:
                        try:
                            doc = json.loads(line)
                        except ValueError:
                            continue  # torn tail of a rotation
                        if not isinstance(doc, dict):
                            continue
                        lines_read += 1
                        key = (doc.get("metrics", ""),
                               doc.get("aggregator", ""),
                               doc.get("downsample", ""),
                               doc.get("filters", 0),
                               doc.get("pixels", 0))
                        s = shapes.get(key)
                        if s is None:
                            s = shapes[key] = {
                                "count": 0, "cache": {},
                                "durations": [], "stages": {}}
                        s["count"] += 1
                        outcome = str(doc.get("cache", "unknown"))
                        s["cache"][outcome] = \
                            s["cache"].get(outcome, 0) + 1
                        s["durations"].append(
                            float(doc.get("durationMs", 0.0)))
                        for stage, ms in (doc.get("stages")
                                          or {}).items():
                            s["stages"].setdefault(stage, []).append(
                                float(ms))
            except OSError:
                continue
        def _pct(vals: list, q: float) -> float:
            if not vals:
                return 0.0
            vs = sorted(vals)
            return round(vs[min(int(len(vs) * q / 100.0),
                                len(vs) - 1)], 3)
        top = sorted(shapes.items(), key=lambda kv:
                     (-kv[1]["count"], kv[0]))[:max(limit, 1)]
        out = []
        for (metrics, agg, ds, nfilters, px), s in top:
            out.append({
                "metrics": metrics, "aggregator": agg,
                "downsample": ds, "filters": nfilters, "pixels": px,
                "count": s["count"],
                "cacheOutcomes": s["cache"],
                "durationMs": {"p50": _pct(s["durations"], 50),
                               "p95": _pct(s["durations"], 95)},
                "stagesMs": {
                    stage: {"p50": _pct(vals, 50),
                            "p95": _pct(vals, 95)}
                    for stage, vals in sorted(s["stages"].items())},
            })
        return HttpResponse(200, json.dumps({
            "shapes": out,
            "distinctShapes": len(shapes),
            "linesRead": lines_read,
            "source": path,
        }).encode())

    def _handle_trace(self, request: HttpRequest, rest
                      ) -> HttpResponse:
        """Request-trace surface (:mod:`opentsdb_tpu.obs.trace`):

        - ``GET /api/trace`` — recent retained roots, newest first;
          filters: ``?status=ok|error``, ``?min_duration_ms=N``,
          ``?slow=true`` (the slow-request ring only), ``?limit=N``.
        - ``GET /api/trace/<id>`` — one trace's full span tree. On a
          cluster router the shards' subtrees are fetched and
          stitched under their ``cluster.peer`` spans; unreachable
          peers are listed in ``stitchIncomplete`` (their scatter
          legs already carry the error span from query time).
          ``?local=true`` skips stitching (what the router sends to
          shards, so stitching can never recurse)."""
        if request.method != "GET":
            raise HttpError(405, "Method not allowed")
        tracer = self.tsdb.tracer
        if not tracer.enabled:
            raise HttpError(400, "Tracing is disabled",
                            "set tsd.trace.enable = true")
        if not rest:
            limit = as_int(request.param("limit"), "limit", 50)
            min_ms = float(request.param("min_duration_ms", "0")
                           or "0")
            status = request.param("status", "") or ""
            if status not in ("", "ok", "error"):
                raise HttpError(400, "status must be ok or error")
            return HttpResponse(200, json.dumps(tracer.recent(
                status=status, min_duration_ms=min_ms,
                slow_only=request.flag("slow"),
                limit=limit)).encode())
        trace_id = rest[0]
        from opentsdb_tpu.obs.trace import SpanRecord, build_tree
        data = tracer.get(trace_id)
        spans = list(data.spans) if data is not None else []
        incomplete: list[str] = []
        cluster = self.tsdb.cluster
        if cluster is not None and not request.flag("local"):
            # ask the shards even when the router's own copy was
            # evicted: their subtrees may survive longer (build_tree
            # renders them as orphan roots)
            extra, incomplete = cluster.fetch_peer_trace(trace_id)
            spans.extend(SpanRecord.from_json(d) for d in extra)
        if not spans:
            raise HttpError(404, f"No trace with id {trace_id!r}",
                            "evicted from the ring, or never "
                            "retained (see tsd.trace.sample)")
        doc: dict[str, Any] = {
            "traceId": trace_id,
            "slow": bool(data is not None and data.slow),
            "spanCount": len(spans),
            "spans": [s.to_json() for s in spans],
            "tree": build_tree(spans),
        }
        if incomplete:
            doc["stitchIncomplete"] = incomplete
        return HttpResponse(200, json.dumps(doc).encode())

    def _handle_cluster(self, request: HttpRequest, rest
                        ) -> HttpResponse:
        """Cluster admin surface (router role only):

        - ``GET /api/cluster`` — ring/replication/reshard status
          (epoch, rf, peers, backfill progress, repair debt);
        - ``POST /api/cluster/reshard`` — install a new ring at a
          fenced epoch (body: ``{"peers": "[name=]host:port,...",
          "vnodes": 64}``). The cutover window dual-writes old+new
          owners, keeps reads on the old ring, and backfills moved
          keyspace in the background; the epoch finalizes itself when
          the copy completes. 400 while another reshard is open.
        - ``GET /api/cluster/reshard`` — the same status document
          (operators poll it to watch the window close)."""
        cluster = self.tsdb.cluster
        if cluster is None:
            raise HttpError(400,
                            "/api/cluster requires tsd.cluster.role "
                            "= router",
                            "this TSD is not a cluster router")
        sub = rest[0] if rest else ""
        if sub == "status":
            # consolidated operator progress surface: reshard epoch +
            # backfill done-markers + retire progress + per-peer
            # spool backlog and dirty-debt age, with ETA estimates
            if request.method != "GET":
                raise HttpError(405, "Method not allowed")
            return HttpResponse(200, json.dumps(
                cluster.cluster_status()).encode())
        if sub == "gossip":
            # sibling-router version bus (cluster/gossip.py): POST
            # applies one sibling's delta push and answers the ack —
            # the receive half of the multi-router cache-coherence
            # story; never exposed without tsd.cluster.routers
            if request.method != "POST":
                raise HttpError(405, "Method not allowed")
            if cluster.gossip is None:
                raise HttpError(
                    400, "gossip is not configured on this router",
                    "set tsd.cluster.routers to the sibling list")
            try:
                ack = cluster.gossip.apply_remote(
                    request.json_object())
            except ValueError as exc:
                raise BadRequestError(str(exc)) from None
            return HttpResponse(200, json.dumps(ack).encode())
        if sub == "reshard":
            if request.method == "POST":
                obj = request.json_object(default={})
                peers = obj.get("peers")
                if not isinstance(peers, str) or not peers.strip():
                    raise BadRequestError(
                        "reshard body needs a peers spec string")
                info = cluster.begin_reshard(
                    peers, as_int(obj.get("vnodes"), "vnodes", 0))
                return HttpResponse(200, json.dumps(info).encode())
            if request.method == "GET":
                return HttpResponse(200, json.dumps(
                    cluster.reshard_info()).encode())
            raise HttpError(405, "Method not allowed")
        if rest:
            raise HttpError(404, f"Endpoint not found: "
                            f"/api/cluster/{sub}")
        if request.method != "GET":
            raise HttpError(405, "Method not allowed")
        return HttpResponse(200, json.dumps(
            cluster.health_info()).encode())

    def _handle_lifecycle(self, request: HttpRequest, rest
                          ) -> HttpResponse:
        """Data-lifecycle admin surface
        (:mod:`opentsdb_tpu.lifecycle`):

        - ``GET /api/lifecycle`` — policies, demotion boundaries and
          sweep counters;
        - ``POST/PUT /api/lifecycle`` — replace the policy table
          (body: ``{"policies": [{"metric": "*", "retention": "90d",
          "demoteAfter": "6h", "demoteTiers": ["1m"]}, ...]}``);
        - ``POST /api/lifecycle/sweep`` — run one sweep synchronously
          and return its report (operators and tests; the background
          sweeper runs on ``tsd.lifecycle.interval_s``)."""
        lc = self.tsdb.lifecycle
        if lc is None:
            raise HttpError(400, "Data lifecycle is disabled",
                            "set tsd.lifecycle.enable = true")
        if rest and rest[0] == "sweep":
            if request.method != "POST":
                raise HttpError(405, "Method not allowed",
                                "POST runs one sweep")
            return HttpResponse(200, json.dumps(lc.sweep()).encode())
        if rest:
            raise HttpError(404, f"Endpoint not found: "
                            f"/api/lifecycle/{rest[0]}")
        if request.method == "GET":
            return HttpResponse(200, json.dumps(lc.describe()).encode())
        if request.method in ("POST", "PUT"):
            lc.update_policies(request.json_object())
            return HttpResponse(200, json.dumps(lc.describe()).encode())
        raise HttpError(405, "Method not allowed")

    def _handle_control(self, request: HttpRequest, rest
                        ) -> HttpResponse:
        """Self-driving control plane
        (:mod:`opentsdb_tpu.control`):

        - ``GET /api/control`` — loop + per-actuator summary
          (breaker state, materialization counts, tenant table,
          placement knobs);
        - ``GET /api/control/materialized`` — the standing
          auto-materialized continuous queries with scores and serve
          hits;
        - ``GET /api/control/plan`` — the current placement
          assessment (per-shard loads, hot shards, proposed ring
          spec + planId);
        - ``POST /api/control/plan`` — confirm the standing proposal
          (body: ``{"planId": "..."}``); executes through the
          existing reshard machinery, 400 on a stale or missing
          planId. With ``tsd.control.placement.auto = true`` the loop
          confirms its own plans and this endpoint is only needed for
          out-of-band pushes;
        - ``POST /api/control/tick`` — run one control tick
          synchronously and return its report (operators and tests;
          the background loop runs on ``tsd.control.interval_s``)."""
        ctl = self.tsdb.control
        if ctl is None:
            raise HttpError(400, "The control plane is disabled",
                            "set tsd.control.enable = true")
        sub = rest[0] if rest else ""
        if sub == "materialized":
            if request.method != "GET":
                raise HttpError(405, "Method not allowed")
            return HttpResponse(200, json.dumps(
                ctl.materialized_info()).encode())
        if sub == "plan":
            if request.method == "GET":
                return HttpResponse(200, json.dumps(
                    ctl.plan_info()).encode())
            if request.method == "POST":
                obj = request.json_object(default={})
                result = ctl.apply_plan(str(obj.get("planId", "")))
                return HttpResponse(200,
                                    json.dumps(result).encode())
            raise HttpError(405, "Method not allowed")
        if sub == "tick":
            if request.method != "POST":
                raise HttpError(405, "Method not allowed",
                                "POST runs one control tick")
            return HttpResponse(200, json.dumps(ctl.tick()).encode())
        if rest:
            raise HttpError(404, f"Endpoint not found: "
                            f"/api/control/{sub}")
        if request.method != "GET":
            raise HttpError(405, "Method not allowed")
        return HttpResponse(200, json.dumps(ctl.describe()).encode())

    def _handle_health(self, request: HttpRequest, rest) -> HttpResponse:
        """Operator-facing degradation report (``/api/health``): WAL
        durability lag + degraded flag, circuit-breaker states,
        connection/admission/shed counters and armed fault sites —
        every graceful-degradation decision the serve path can take is
        observable here (and asserted by the ``robustness`` suite).
        Always 200: a degraded TSD is still serving; the ``status``
        field carries the verdict so health checks don't eject a node
        that is answering queries from the host fallback."""
        t = self.tsdb
        causes: list[str] = []
        wal = getattr(t, "wal", None)
        wal_info: dict[str, Any] = {"enabled": wal is not None}
        if wal is not None:
            wal_info.update(wal.health_info())
            if wal_info.get("degraded"):
                causes.append("wal_sync")
            if wal_info.get("durability_hole"):
                causes.append("wal_durability_hole")
        breakers: dict[str, Any] = {}
        breaker = getattr(t, "device_breaker", None)
        if breaker is not None:
            breakers[breaker.name] = breaker.health_info()
            if breaker.state != breaker.CLOSED:
                causes.append(f"breaker:{breaker.name}")
        faults = getattr(t, "faults", None)
        # the raw attribute, not the property: health must not force
        # the lazy cache into existence just to report on it
        rcache = getattr(t, "_result_cache", None)
        if rcache is not None:
            cache_info = rcache.health_info()
            cache_info["enabled"] = t.config.get_bool(
                "tsd.query.cache.enable", True)
        else:
            cache_info = {"enabled": t.config.get_bool(
                "tsd.query.cache.enable", True)
                and t.config.get_int("tsd.query.cache.mb", 256) > 0}
        # the raw attribute again: health must not instantiate the
        # continuous-query registry just to report it absent
        streaming = getattr(t, "_streaming", None)
        if streaming is not None:
            streaming_info = streaming.health_info()
            sbreaker = streaming.breaker
            if sbreaker is not None:
                breakers[sbreaker.name] = sbreaker.health_info()
                if sbreaker.state != sbreaker.CLOSED:
                    causes.append(f"breaker:{sbreaker.name}")
        else:
            streaming_info = {"enabled": t.config.get_bool(
                "tsd.streaming.enable", True), "queries": 0}
        # the raw attribute: health must not instantiate the lifecycle
        # manager just to report it absent
        lifecycle = getattr(t, "_lifecycle", None)
        if lifecycle is not None:
            lifecycle_info = lifecycle.health_info()
            lbreaker = lifecycle.breaker
            if lbreaker is not None:
                breakers[lbreaker.name] = lbreaker.health_info()
                if lbreaker.state != lbreaker.CLOSED:
                    causes.append(f"breaker:{lbreaker.name}")
            cold = getattr(lifecycle, "coldstore", None)
            cbreaker = getattr(cold, "read_breaker", None) \
                if cold is not None else None
            if cbreaker is not None:
                breakers[cbreaker.name] = cbreaker.health_info()
                if cbreaker.state != cbreaker.CLOSED:
                    # cold reads are degrading to tier/raw serving
                    causes.append(f"breaker:{cbreaker.name}")
        else:
            lifecycle_info = {"enabled": t.config.get_bool(
                "tsd.lifecycle.enable", False)}
        # the raw attribute: health must not instantiate the cluster
        # router just to report it absent
        clus = getattr(t, "_cluster", None)
        if clus is not None:
            cluster_info = clus.health_info()
            # fleet roll-up: one status row per shard (scattered
            # /api/health, breaker-aware — an unreachable shard is a
            # row, never a 5xx out of THIS endpoint)
            cluster_info["fleet"] = clus.fleet_health()
            if cluster_info["fleet"]["degraded"]:
                causes.append("fleet_shards_degraded")
            dirty_age = cluster_info.get("replica_dirty", {}).get(
                "oldest_age_s", 0)
            rr_age = cluster_info.get("read_repair", {}).get(
                "oldest_pending_age_s", 0)
            if dirty_age > 3600 or rr_age > 3600:
                # silent week-old divergence debt must not look like
                # a seconds-old blip — whether anti-entropy marked it
                # or a read observed it (the staged-hint pipeline)
                causes.append("replica_dirty_debt_stale")
            gossip_info = cluster_info.get("gossip")
            if gossip_info and gossip_info.get("degraded"):
                # a sibling router is partitioned: this router is
                # serving cache-bypassed (exact, never stale) until
                # its gossip pushes land again
                causes.append("cluster_gossip_degraded")
            for _pname, peer in sorted(clus.peers.items()):
                pb = peer.breaker
                breakers[pb.name] = pb.health_info()
                if pb.state != pb.CLOSED:
                    # the shard is being served around (degraded
                    # partials + spooled writes), not failed
                    causes.append(f"breaker:{pb.name}")
            if cluster_info.get("spool_backlog_records"):
                causes.append("cluster_spool_backlog")
        else:
            cluster_info = {"role": t.config.get_string(
                "tsd.cluster.role", "") or "standalone"}
        # the raw attribute: health must not instantiate the control
        # plane just to report it absent
        ctl = getattr(t, "_control", None)
        if ctl is not None:
            control_info = ctl.describe()
            breakers[ctl.breaker.name] = ctl.breaker.health_info()
            if ctl.breaker.state != ctl.breaker.CLOSED:
                # the loop is parked; the data plane keeps serving on
                # the last computed penalties and materializations
                causes.append(f"breaker:{ctl.breaker.name}")
        else:
            control_info = {"enabled": t.config.get_bool(
                "tsd.control.enable", False)}
        hook_errors = dict(getattr(t, "hook_errors", {}))
        doc: dict[str, Any] = {
            "status": "degraded" if causes else "ok",
            "degraded": bool(causes),
            "causes": causes,
            "uptime_seconds": int(time.time() - t.start_time),
            "wal": wal_info,
            "breakers": breakers,
            "faults": (faults.health_info() if faults is not None
                       else {"armed": False, "sites": {}}),
            "query_cache": cache_info,
            "streaming": streaming_info,
            "lifecycle": lifecycle_info,
            # per-store memory footprint (resident vs live vs dead
            # capacity) so lifecycle reclamation is observable
            # before/after sweeps
            "storage": t.storage_memory_info(),
            # serve-path payload aggregates: response bytes +
            # serialization time, so the pixel-downsampling bytes win
            # is measurable in production
            "query_payload": t.payload_stats.health_info(),
            # request-level + per-stage latency percentiles
            # (p50/p95/p99/p999; stages fed by the tracer)
            "latency": t.stats.latency_summary(),
            # SLO burn rates: "are we eating the error budget" per
            # endpoint, per window (obs/slo.py; also at /metrics)
            "slo": t.slo.health_info(),
            # continuous sampling profiler state (obs/profiler.py;
            # the samples themselves serve at GET /api/profile)
            "profiler": t.profiler.health_info(),
            # tracing subsystem state (ring depths, sampling,
            # slowlog, query-shape log)
            "trace": t.tracer.health_info(),
            # self-telemetry pump (tsd.stats.self_interval)
            "telemetry": t.telemetry.health_info(),
            # sharded cluster tier: per-peer breaker/spool state,
            # degraded-query and handoff counters (router role only)
            "cluster": cluster_info,
            # self-driving control plane: loop/breaker state, standing
            # materializations, tenant shares, placement plan counters
            "control": control_info,
            "hook_errors": hook_errors,
            # what this process runs on: platform, device kind and
            # count, compile cache, storage backend, mesh, warm-up,
            # kernel execution counts (TSDB.device_info)
            "device": t.device_info(),
            # start-up timed from inside, {phase: seconds} in the
            # order the phases ran (tools/cli.py, TSDB.__init__;
            # warm-up runs beside serving and lands when it ends)
            "startup": {k: round(v, 3) for k, v
                        in list(trace_mod.RUNTIME.startup.items())},
        }
        server = self.server
        if server is not None:
            cm = server.connections
            doc["connections"] = {
                "open": cm.open_connections,
                "total": cm.total_connections,
                "refused": cm.rejected_connections,
                "idle_closed": cm.idle_closed,
                "limit": cm.max_connections,
            }
            doc["admission"] = server.admission.health_info(
                server.query_queue_depth())
        return HttpResponse(200, json.dumps(doc).encode())

    def _runtime_stats(self) -> dict[str, Any]:
        import gc
        import resource
        ru = resource.getrusage(resource.RUSAGE_SELF)
        return {
            "os": {"systemLoadAverage": __import__("os").getloadavg()[0]},
            "runtime": {"uptime": int((time.time() - self.start_time)
                                      * 1000)},
            "memory": {"maxRssKb": ru.ru_maxrss},
            "gc": {"collections": sum(s["collections"]
                                      for s in gc.get_stats())},
        }

    def _handle_version(self, request: HttpRequest, rest) -> HttpResponse:
        return HttpResponse(200, request.serializer.format_version(
            version_info()))

    # -- misc ----------------------------------------------------------

    def _homepage(self, request: HttpRequest) -> HttpResponse:
        """The dashboard (ref: HomePage in RpcManager serving the GWT
        QueryUi; here a self-contained static page)."""
        import os
        page = os.path.join(self._static_root(), "index.html")
        if os.path.isfile(page):
            with open(page, "rb") as fh:
                return HttpResponse(200, fh.read(),
                                    content_type="text/html; charset=UTF-8")
        body = (b"<html><head><title>opentsdb-tpu</title></head><body>"
                b"<h1>opentsdb-tpu " + __version__.encode() +
                b"</h1><p>TPU-native time series database.</p>"
                b"<p>See /api/version, /api/aggregators, /api/query"
                b"</p></body></html>")
        return HttpResponse(200, body, content_type="text/html")

    def _static_root(self) -> str:
        import os
        root = self.tsdb.config.get_string("tsd.http.staticroot", "")
        if not root:
            root = os.path.join(os.path.dirname(__file__), "static")
        return root

    def _handle_graph(self, request: HttpRequest) -> HttpResponse:
        from opentsdb_tpu.tsd.graph import handle_graph
        return handle_graph(self, request)

    def _handle_static(self, request: HttpRequest, rest) -> HttpResponse:
        """(ref: StaticFileRpc.java:20)"""
        import os
        root = self._static_root()
        rel = "/".join(rest)
        root_real = os.path.realpath(root)
        full = os.path.realpath(os.path.join(root, rel))
        # containment needs the separator: a bare prefix check lets a
        # SIBLING directory sharing the root's name prefix through
        # (static_private passes startswith(".../static"))
        if (full != root_real
                and not full.startswith(root_real + os.sep)) \
                or not os.path.isfile(full):
            raise HttpError(404, "File not found")
        import mimetypes
        ctype = mimetypes.guess_type(full)[0] or "application/octet-stream"
        with open(full, "rb") as fh:
            return HttpResponse(200, fh.read(), content_type=ctype)

    def _handle_logs(self, request: HttpRequest) -> HttpResponse:
        """(ref: LogsRpc — logback ring buffer; here the in-process
        logging ring)"""
        lines = ring_buffer.lines()
        if request.flag("json"):
            return HttpResponse(200, json.dumps(lines).encode())
        return HttpResponse(200, "\n".join(lines).encode(),
                            content_type="text/plain")


def version_info() -> dict[str, str]:
    """(ref: BuildData emitted by VersionRpc)"""
    import platform

    return {
        "version": __version__,
        "short_revision": "tpu",
        "full_revision": "opentsdb_tpu",
        "timestamp": str(int(time.time())),
        "repo_status": "MODIFIED",
        "user": "tsd",
        "host": platform.node(),
        "repo": "opentsdb_tpu",
    }
