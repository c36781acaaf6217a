"""REST handlers over the stdlib HTTP server.

Endpoint behavior is a 1:1 mapping of the reference REST surface:

- ``GET /check`` decodes the tuple from the URL query; a nil subject is a
  400 with "Subject has to be specified." (reference
  internal/check/handler.go:85-107); the *status code mirrors the
  decision*: 200 allowed / 403 denied, body ``{"allowed": bool}``.
- ``POST /check`` takes the tuple as JSON (handler.go:128-146).
- ``POST /check/batch`` takes ``{"tuples": [...]}`` and answers
  ``{"results": [bool, ...]}`` in order — big payloads ride the
  batcher's BATCH priority lane and dispatch in bounded sub-slices that
  interleave with interactive checks. An ``X-Keto-Priority`` header
  (``interactive`` | ``batch``) pins the lane on any check route;
  without it, request size classifies.
- ``GET /expand`` requires ``max-depth`` plus a subject-set query and
  returns the tree JSON (reference internal/expand/handler.go:79-92).
- ``GET /relation-tuples`` decodes a RelationQuery + ``page_token`` /
  ``page_size`` and returns ``{"relation_tuples": [...],
  "next_page_token": "..."}`` (reference
  internal/relationtuple/read_server.go:77-117).
- ``PUT /relation-tuples`` creates from a JSON body → 201 + Location
  (reference transact_server.go:130-153); ``DELETE`` by URL query → 204
  (transact_server.go:173-187); ``PATCH`` applies
  ``[{"action": "insert"|"delete", "relation_tuple": {...}}]``
  atomically → 204 (transact_server.go:217-242).
- ``GET /health/alive`` → ``{"status": "ok"}`` (process liveness, the
  reference's static answer, registry_default.go:97-103);
  ``GET /health/ready`` is *real* readiness: the health state machine
  (keto_tpu/driver/health.py) answers 200 ``{"status": "ok"}`` /
  ``{"status": "degraded", ...}`` when traffic should flow and **503 +
  JSON reason** when the snapshot is beyond its staleness budget or
  maintenance died; ``GET /version``; ``GET /metrics`` serves the
  Prometheus text exposition of the process-wide MetricsRegistry
  (keto_tpu/x/metrics.py) on BOTH API ports — one scrape config covers
  read and write processes.

Deadline propagation: an ``X-Request-Timeout-Ms`` header (or
``timeout_ms`` query parameter) on ``/check`` rides into the batcher as
an absolute deadline — expired requests shed with **504** before they
occupy a device slice, and a full check queue (or the adaptive
admission window, keto_tpu/driver/admission.py) sheds with **429 +
Retry-After** (keto_tpu/driver/batch.py). Every overload response (429,
and 503 while NOT_SERVING) carries a ``Retry-After`` header with the
server's backoff advice.

Multi-tenant serving: an ``X-Keto-Tenant`` header scopes the request to
one tenant's engine, batcher, store view, and watch hub (the TenantPool,
keto_tpu/driver/tenants.py). Absent header → the default tenant, which
IS the pre-tenancy registry — every existing contract (snaptokens,
replica gating, idempotency, watch) is untouched. Tenant-scoped sheds
echo the tenant on ``X-Keto-Tenant`` so clients can attribute 429s, and
Retry-After reflects THAT tenant's overload run, not the machine's.

Request correlation: every non-health request gets (or echoes) an
``X-Request-Id``, joins the caller's trace when a W3C ``traceparent``
header is present, and binds both ids into the logging context
(keto_tpu/x/logging.request_context) for the handler's duration — log
lines, spans, response headers, and latency exemplars all carry the same
ids. Route labels on the request metrics are cardinality-bounded: paths
outside the declared surface count as ``other``.

Errors render the herodot-style envelope from keto_tpu/x/errors.py.
"""

from __future__ import annotations

import json
import math
import re
import threading
import time
import uuid
from dataclasses import dataclass
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Any, Callable, Optional
from urllib.parse import parse_qs, urlsplit

from keto_tpu.check.frame import QueryFrame, check_frame_metrics
from keto_tpu.expand.tree import Tree
from keto_tpu.graph.native import FrameTable
from keto_tpu.relationtuple.model import (
    RelationQuery,
    RelationTuple,
    subject_set_from_url_query,
)
from keto_tpu.x.errors import ErrBadRequest, ErrNilSubject, KetoError
from keto_tpu.x.logging import request_context
from keto_tpu.x.metrics import normalize_route
from keto_tpu.x.pagination import with_size, with_token
from keto_tpu.x.tracing import current_traceparent, parse_traceparent

#: routes whose handling is excluded from request-timeline recording —
#: scrapes and the debug surfaces themselves would otherwise churn the
#: ring the operator is trying to read
_TIMELINE_EXCLUDED = frozenset({"/metrics", "/debug/requests", "/slo"})

READ = "read"
WRITE = "write"


#: upper bound on one /check/batch payload — bigger requests should page
#: (the batcher would serve it, but a single response holding >64k bools
#: is a client bug more often than a workload)
MAX_BATCH_CHECK = 65536


def _error_headers(err: KetoError) -> dict[str, str]:
    """Overload errors carry the server's backoff advice: a Retry-After
    header (integer seconds) on 429/503/412 responses. A replica's 412
    additionally surfaces its current applied watermark as
    ``X-Keto-Watermark`` so callers can re-pin or route to the primary."""
    out: dict[str, str] = {}
    ra = getattr(err, "retry_after_s", None)
    if ra:
        out["Retry-After"] = str(max(1, math.ceil(ra)))
    wm = (getattr(err, "details", None) or {}).get("watermark")
    if wm is not None:
        out["X-Keto-Watermark"] = str(wm)
    # tenant-scoped sheds name the tenant: a client multiplexing many
    # tenants over one pool attributes the 429 without parsing the body
    tn = (getattr(err, "details", None) or {}).get("tenant")
    if tn:
        out["X-Keto-Tenant"] = str(tn)
    return out


@dataclass
class RawBody:
    """A non-JSON response payload (``/metrics`` exposition): the server
    backends write ``data`` verbatim under ``content_type`` instead of
    JSON-encoding."""

    data: bytes
    content_type: str


@dataclass
class StreamBody:
    """A chunked streaming response (``GET /watch``): the server
    backends write ``Transfer-Encoding: chunked`` and iterate ``chunks``
    (bytes per chunk) until exhaustion, then close the connection.
    Closing the iterator on client disconnect releases its resources
    (the watch hub's stream slot)."""

    chunks: Any  # iterator of bytes
    content_type: str = "application/x-ndjson"


class RestApp:
    """Routes requests for one server role against the registry."""

    def __init__(self, registry, role: str):
        self.registry = registry
        self.role = role
        self._log = registry.logger()
        # request metrics, declared once per app (creation is idempotent
        # across the two roles; recording is the per-request hot path)
        m = registry.metrics()
        self._req_count = m.counter(
            "keto_http_requests_total",
            "REST requests served, by role/method/route/status code "
            "(health endpoints excluded; undeclared routes count as 'other').",
            ("role", "method", "route", "code"),
        )
        self._req_latency = m.histogram(
            "keto_http_request_duration_seconds",
            "REST request handling latency; the slowest sample per route "
            "carries a trace_id exemplar.",
            ("role", "method", "route"),
        )
        # the listener's two stages around a timeline (x/timeline
        # LISTENER_STAGES) go straight into the stage histogram the
        # recorder mirrors timelines into (declared with the registry;
        # None when metrics are off)
        self._stage_hist = m.family("keto_timeline_stage_duration_seconds")
        self._batch_tuples, self._frame_declines = check_frame_metrics(m)
        #: (namespace manager, its table for the native query framer —
        #: None: this manager's bodies are never framed); rebuilt when a
        #: reload has put another manager in its place
        self._frame_table: tuple = (None, None)

    # -- dispatch ------------------------------------------------------------

    def handle(
        self,
        method: str,
        path: str,
        query: dict[str, list[str]],
        body: bytes,
        headers: Optional[dict[str, str]] = None,
    ):
        """Returns (status, payload-dict | None, headers-dict).
        ``headers`` are the request headers, lowercase-keyed (deadline
        propagation, trace context); absent for callers that don't carry
        them."""
        return self.handle_timed(None, method, path, query, body, headers)[:3]

    def handle_timed(
        self,
        t_read: Optional[float],
        method: str,
        path: str,
        query: dict[str, list[str]],
        body: bytes,
        headers: Optional[dict[str, str]] = None,
    ):
        """``handle`` for a listener that keeps time: ``t_read`` is its
        ``perf_counter()`` reading once head and body were read, and the
        wait from there to here (for a pool thread) is observed as stage
        ``pool_wait``. Returns ``(status, payload, headers, t_handled)``;
        the listener hands ``t_handled`` to ``note_written`` once the
        response is flushed. 0.0 where no timeline was recorded (health,
        scrapes): nothing is observed for those."""
        # request span + usage counter + metrics (health endpoints
        # excluded), matching the reference's middleware placement
        # (registry_default.go:288-300)
        if path.startswith("/health/"):
            return (*self._route(method, path, query, body, headers), 0.0)
        hdrs = headers or {}
        route = normalize_route(path)
        # correlation: echo the caller's request id or mint one; join the
        # caller's trace when a well-formed traceparent came in
        req_id = (hdrs.get("x-request-id") or "").strip() or uuid.uuid4().hex
        remote = parse_traceparent(hdrs.get("traceparent", ""))
        self.registry.telemetry().record(f"{self.role} {method} {route}")
        recorder = self.registry.timeline_recorder()
        t0 = time.perf_counter()
        with self.registry.tracer().span(
            f"http.{method} {route}", remote_parent=remote, role=self.role
        ) as span:
            trace_id = (
                span.trace_id if span is not None else (remote[0] if remote else "")
            )
            # the request timeline is born INSIDE the server span so the
            # stage spans it emits at finish parent under it
            tl = (
                None
                if path in _TIMELINE_EXCLUDED
                else recorder.begin(
                    f"{method} {route}", trace_id=trace_id,
                    request_id=req_id, surface="http",
                    tenant=(hdrs.get("x-keto-tenant") or "").strip() or "default",
                )
            )
            with request_context(request_id=req_id, trace_id=trace_id):
                with recorder.activate(tl):
                    status, payload, resp_headers = self._route(
                        method, path, query, body, headers
                    )
                if span is not None:
                    span.tags["status"] = status
                    span.tags["request_id"] = req_id
                # access log INSIDE the bound context: the formatters
                # stamp request_id/trace_id onto the record, same ids as
                # the span and the response headers
                self._log.debug("%s %s %s -> %d", self.role, method, path, status)
        t_handled = time.perf_counter()
        dur_s = t_handled - t0
        self._req_count.inc((self.role, method, route, str(status)))
        self._req_latency.observe((self.role, method, route), dur_s, trace_id=trace_id)
        resp_headers = dict(resp_headers)
        resp_headers.setdefault("X-Request-Id", req_id)
        if tl is not None:
            recorder.finish(
                tl, status=status,
                snaptoken=resp_headers.get("X-Keto-Snaptoken"),
            )
            # the caller-visible stage breakdown (W3C Server-Timing);
            # streaming responses (watch) carry no timing — the exchange
            # has no end
            if not isinstance(payload, StreamBody):
                resp_headers.setdefault(
                    "Server-Timing", recorder.server_timing(tl)
                )
            if self._stage_hist is not None:
                if t_read is not None:
                    self._stage_hist.observe(("pool_wait",), t0 - t_read)
                return status, payload, resp_headers, t_handled
        return status, payload, resp_headers, 0.0

    def note_written(self, t_handled: float) -> None:
        """The listener flushed the response of a request whose
        ``handle_timed`` returned ``t_handled``: stage ``encode_write``
        (the hand-back to the listener, ``json.dumps``, the write). It
        ends after the timeline's ``deliver``, so it goes straight into
        the histogram."""
        if t_handled:
            self._stage_hist.observe(
                ("encode_write",), time.perf_counter() - t_handled
            )

    def note_listener_shed(self, method: str, path: str) -> None:
        """Record a listener-level 429 (shed on the event loop before any
        handler ran) into the request metrics, so overload refusals stay
        visible per route."""
        self._req_count.inc((self.role, method, normalize_route(path), "429"))

    def _route(
        self,
        method: str,
        path: str,
        query: dict[str, list[str]],
        body: bytes,
        headers: Optional[dict[str, str]] = None,
    ):
        try:
            route = (method, path)
            if path == "/health/alive":
                return 200, {"status": "ok"}, {}
            if path == "/health/ready":
                return self._health_ready()
            if path == "/version":
                return 200, {"version": self.registry.version()}, {}
            if route == ("GET", "/metrics"):
                return self._get_metrics(headers)
            if route == ("GET", "/debug/requests"):
                return self._get_debug_requests(query)
            if route == ("GET", "/slo"):
                return self._get_slo()
            if route == ("GET", "/fleet"):
                return self._get_fleet()

            if self.role == READ:
                if route == ("GET", "/check"):
                    return self._get_check(query, headers)
                if route == ("POST", "/check"):
                    return self._post_check(body, query, headers)
                if route == ("POST", "/check/batch"):
                    return self._post_check_batch(body, query, headers)
                if route == ("GET", "/check/explain"):
                    return self._get_explain(query, headers)
                if route == ("GET", "/expand"):
                    return self._get_expand(query, headers)
                if route == ("GET", "/relation-tuples"):
                    return self._get_relation_tuples(query, headers)
                if route == ("GET", "/relation-tuples/list-objects"):
                    return self._get_list_objects(query, headers)
                if route == ("GET", "/relation-tuples/list-subjects"):
                    return self._get_list_subjects(query, headers)
                if route == ("GET", "/watch"):
                    return self._get_watch(query, headers)
                if route == ("GET", "/snapshot/export"):
                    return self._get_snapshot_export(query)
            else:
                if self.registry.is_replica() and method in (
                    "PUT", "DELETE", "PATCH",
                ):
                    # replicas hold no authority over the tuple log:
                    # every mutation surface refuses before dispatch
                    from keto_tpu.x.errors import ErrReplicaReadOnly

                    raise ErrReplicaReadOnly()
                if route == ("PUT", "/relation-tuples"):
                    return self._put_relation_tuple(body, headers)
                if route == ("DELETE", "/relation-tuples"):
                    return self._delete_relation_tuple(query, headers)
                if route == ("PATCH", "/relation-tuples"):
                    return self._patch_relation_tuples(body, headers)

            err = KetoError("404 page not found")
            err.status_code = 404
            return 404, err.to_json(), {}
        except KetoError as e:
            return e.status_code, e.to_json(), _error_headers(e)
        except Exception as e:  # unexpected → 500 envelope
            err = KetoError(str(e) or "internal server error")
            return 500, err.to_json(), {}

    # -- observability -------------------------------------------------------

    def _get_metrics(self, headers):
        """Prometheus text exposition of every registered family. A
        scraper negotiating ``Accept: application/openmetrics-text`` (the
        way Prometheus asks for exemplars) gets the OpenMetrics rendering
        with trace-id exemplars on the latency histograms. 404 when
        ``metrics.enabled: false``."""
        m = self.registry.metrics()
        if not m.enabled:
            err = KetoError("metrics disabled by configuration")
            err.status_code = 404
            return 404, err.to_json(), {}
        openmetrics = "application/openmetrics-text" in (headers or {}).get("accept", "")
        content_type = (
            "application/openmetrics-text; version=1.0.0; charset=utf-8"
            if openmetrics
            else "text/plain; version=0.0.4; charset=utf-8"
        )
        return 200, RawBody(m.render(openmetrics=openmetrics).encode(), content_type), {}

    @staticmethod
    def _int_param(query, key: str, default: int) -> int:
        raw = (query.get(key) or [""])[0]
        if not raw:
            return default
        try:
            return max(0, int(raw))
        except ValueError:
            raise ErrBadRequest(f"invalid {key} {raw!r}") from None

    def _get_debug_requests(self, query):
        """``GET /debug/requests`` — recent + top-K-slowest request
        timelines from the bounded ring (keto_tpu/x/timeline.py),
        filterable by ``?trace_id=``, ``?snaptoken=``, and ``?tenant=``
        (noisy-neighbor forensics: one tenant's requests, isolated);
        ``?n=`` / ``?slowest=`` bound the result sizes. On a replica the
        body also carries the per-commit replication timelines."""
        rec = self.registry.timeline_recorder()
        body = rec.snapshot(
            recent=self._int_param(query, "n", 50),
            slowest=self._int_param(query, "slowest", 20),
            trace_id=(query.get("trace_id") or [""])[0] or None,
            snaptoken=(query.get("snaptoken") or [""])[0] or None,
            tenant=(query.get("tenant") or [""])[0] or None,
        )
        rep = self.registry.replica_controller()
        if rep is not None:
            body["replication"] = rep.replication_timelines()
        return 200, body, {}

    def _get_slo(self):
        """``GET /slo`` — the SLO engine's multi-window availability and
        latency burn-rate report (keto_tpu/x/slo.py); the same numbers
        the ``keto_slo_*`` families expose at scrape time. The body also
        carries the fleet coordinates (epoch, primaryship, size, reshard
        state) so one poll answers both "how are we burning" and "who is
        serving"."""
        body = self.registry.slo_engine().to_json()
        self._add_fleet_health(body)
        return 200, body, {}

    def _get_fleet(self):
        """``GET /fleet`` — the fleet control plane's view of this node:
        lease epoch, role, membership with per-replica lag/watermark,
        the lag-aware routing weights the SDK steers reads by, plus the
        autoscaler and live-reshard snapshots. Answers on both ports
        (the SDK re-resolves the primary through ANY reachable member
        after a failover). 404 without ``serve.fleet_enabled``."""
        fleet = self.registry.fleet_controller()
        if fleet is None:
            err = KetoError("fleet control plane disabled by configuration")
            err.status_code = 404
            return 404, err.to_json(), {}
        body = fleet.snapshot()
        scaler = self.registry.peek("autoscaler")
        if scaler is not None:
            body["autoscaler"] = scaler.snapshot()
        # instantiating the coordinator is closure wiring, not an engine
        # build — peek() would hide the state machine until the first
        # reshard call
        reshard = self.registry.reshard_coordinator()
        if reshard is not None:
            body["reshard"] = reshard.snapshot()
        return 200, body, {}

    def _add_fleet_health(self, body: dict) -> None:
        """Fleet coordinates every readiness/SLO answer carries when the
        control plane runs: the fence epoch this node last observed,
        whether it is the serving primary, live membership size, and the
        reshard state machine's position. Probes and the SDK both read
        these without a second round trip."""
        fleet = self.registry.peek("fleet")
        if fleet is None:
            return
        snap = fleet.snapshot()
        reshard = self.registry.peek("reshard")
        body.update(
            {
                "epoch": int(snap.get("epoch", 0)),
                "is_primary": bool(snap.get("is_primary", False)),
                "fleet_size": int(snap.get("fleet_size", 0)),
                "reshard_state": (
                    reshard.snapshot()["state"] if reshard is not None else "idle"
                ),
            }
        )

    # -- snapshot export (replica bootstrap source) ---------------------------

    #: rows per ndjson chunk of the tuple export stream
    _EXPORT_CHUNK = 2048

    _CACHE_TAG_RE = re.compile(r"^v\d+-w\d+$")
    _SEGMENT_NAME_RE = re.compile(r"^[A-Za-z0-9._-]+$")

    def _get_snapshot_export(self, query):
        """``GET /snapshot/export`` — the replica bootstrap surface.

        - bare: manifest JSON ``{watermark, format, cache}`` where
          ``cache`` lists the newest current-format snapshot-cache
          directory's segments (or null) — replicas mirror the segments
          when the cache watermark matches the export watermark, and the
          probe loop polls this for the primary's watermark;
        - ``?stream=tuples``: chunked ndjson of the FULL tuple state at
          one consistent watermark — a header line ``{"watermark",
          "count"}`` then one ``{"relation_tuple"}`` line per tuple;
        - ``?cache=<tag>&segment=<name>``: raw bytes of one cache
          segment (names validated against the manifest grammar)."""
        store = self.registry.relation_tuple_manager()
        cache_dir = str(
            self.registry.config().get("serve.snapshot_cache_dir", "") or ""
        )
        tag = (query.get("cache") or [""])[0]
        seg = (query.get("segment") or [""])[0]
        if tag or seg:
            if not (tag and seg):
                raise ErrBadRequest(
                    "segment fetch needs both ?cache=<tag> and ?segment=<name>"
                )
            if not self._CACHE_TAG_RE.match(tag):
                raise ErrBadRequest(f"malformed cache tag {tag!r}")
            if not self._SEGMENT_NAME_RE.match(seg):
                raise ErrBadRequest(f"malformed segment name {seg!r}")
            from pathlib import Path

            from keto_tpu.x.errors import ErrNotFound

            path = Path(cache_dir) / tag / seg if cache_dir else None
            if path is None or not path.is_file():
                raise ErrNotFound(f"no cache segment {tag}/{seg}")
            return 200, RawBody(path.read_bytes(), "application/octet-stream"), {}
        stream = (query.get("stream") or [""])[0]
        if stream and stream != "tuples":
            raise ErrBadRequest(f"unknown export stream {stream!r}")
        if stream == "tuples":
            from keto_tpu.replica.store import row_to_tuple

            rows, wm = store.snapshot_rows()
            nm = self.registry.namespace_manager()

            def gen():
                head = json.dumps({"watermark": str(wm), "count": len(rows)})
                buf = [head]
                for row in rows:
                    buf.append(
                        json.dumps(
                            {"relation_tuple": row_to_tuple(nm, row).to_json()}
                        )
                    )
                    if len(buf) >= self._EXPORT_CHUNK:
                        yield ("\n".join(buf) + "\n").encode()
                        buf = []
                if buf:
                    yield ("\n".join(buf) + "\n").encode()

            return 200, StreamBody(gen()), {"X-Keto-Snaptoken": str(wm)}
        wm = store.watermark()
        cache = None
        if cache_dir:
            from keto_tpu.graph.snapcache import export_manifest

            cache = export_manifest(cache_dir, max_watermark=wm)
        return 200, {"watermark": str(wm), "format": 1, "cache": cache}, {}

    # -- health --------------------------------------------------------------

    def _health_ready(self):
        """Readiness from the health state machine: ready states answer
        200 (with the state surfaced so probes can alert on ``degraded``);
        NOT_SERVING answers 503 with the machine's reason — a k8s
        readiness probe pulls the pod from rotation while the snapshot is
        beyond its staleness budget, and puts it back when maintenance
        catches up."""
        from keto_tpu.driver.health import READY_STATES, HealthState

        monitor = self.registry.health_monitor()
        state, reason = monitor.status()
        if state not in READY_STATES:
            body = {"status": "unavailable", "reason": reason or state.value}
            self._add_replica_health(body)
            self._add_fleet_health(body)
            self._add_tenant_health(body)
            # backoff advice rides the 503: probes already poll on their
            # own period, but ad-hoc clients should not hammer a server
            # that just told them its snapshot is stale
            return 503, body, {"Retry-After": "1"}
        if state is HealthState.SERVING:
            body = {"status": "ok"}
            self._add_replica_health(body)
            self._add_fleet_health(body)
            self._add_tenant_health(body)
            return 200, body, {}
        body = {"status": state.value}
        if reason:
            body["reason"] = reason
        if state is HealthState.STARTING:
            # a multi-minute streaming build narrates itself: the body
            # carries {phase, pct} from the pipeline's progress tracker
            # instead of leaving probes staring at a bare state
            body.update(monitor.starting_detail())
        self._add_replica_health(body)
        self._add_fleet_health(body)
        self._add_tenant_health(body)
        return 200, body, {}

    def _add_tenant_health(self, body: dict) -> None:
        """Per-tenant health rides readiness WITHOUT flipping it: a
        ``DEGRADED(tenant=…)`` reason names the hurting tenant so its
        operator can act, while every other tenant's traffic — and the
        machine-level status the probes act on — stays untouched."""
        pool = self.registry.peek("tenants")
        if pool is None:
            return
        out = {
            "known": pool.known_count(),
            "resident": pool.resident_count(),
        }
        degraded = pool.degraded()
        if degraded:
            out["degraded"] = degraded
        body["tenants"] = out

    def _add_replica_health(self, body: dict) -> None:
        """On a replica, every readiness answer carries the replication
        picture: role, applied watermark, lag, and primary connectivity
        — the operator's one-glance view of a read-tier member."""
        rep = self.registry.replica_controller()
        if rep is None:
            return
        body.update(
            {
                "role": "replica",
                "watermark": str(rep.watermark),
                "lag_s": round(rep.lag_s(), 3),
                "primary_connected": rep.primary_connected,
            }
        )

    # -- tenancy --------------------------------------------------------------

    @staticmethod
    def _tenant_from(headers) -> str:
        """The validated tenant id the request addressed: the
        ``X-Keto-Tenant`` header, absent/blank → the default tenant;
        a malformed id is a 400."""
        from keto_tpu.driver.tenants import validate_tenant_id

        return validate_tenant_id((headers or {}).get("x-keto-tenant", ""))

    def _scope(self, headers):
        """The registry-shaped object serving this request: the registry
        itself for the default tenant (every pre-tenancy contract stays
        byte-identical), or the tenant's pool context — its own engine,
        batcher, store view, and watch hub — when ``X-Keto-Tenant``
        addresses another tenant. Tenant-scoped requests are primary-only
        (replicas mirror only the default tenant's state) and gated on
        ``serve.tenant_enabled``."""
        from keto_tpu.driver.tenants import DEFAULT_TENANT

        tenant = self._tenant_from(headers)
        if tenant == DEFAULT_TENANT:
            return self.registry
        if not bool(self.registry.config().get("serve.tenant_enabled", True)):
            raise ErrBadRequest(
                "multi-tenant serving is disabled (serve.tenant_enabled)"
            )
        if self.registry.is_replica():
            raise ErrBadRequest(
                "tenant-scoped requests are served by the primary only"
            )
        return self.registry.tenant_pool().get(tenant)

    # -- read ----------------------------------------------------------------

    @staticmethod
    def _deadline_from(query, headers) -> Optional[float]:
        """Request deadline as absolute ``time.monotonic()`` seconds, from
        ``X-Request-Timeout-Ms`` / ``?timeout_ms=`` (whichever is
        present; malformed values are a 400, not a silent default)."""
        raw = (query.get("timeout_ms") or [""])[0]
        if not raw and headers:
            raw = headers.get("x-request-timeout-ms", "")
        if not raw:
            return None
        try:
            ms = float(raw)
        except ValueError:
            raise ErrBadRequest(f"invalid timeout_ms {raw!r}") from None
        if ms <= 0:
            raise ErrBadRequest(f"timeout_ms must be > 0, got {raw!r}")
        return time.monotonic() + ms / 1e3

    @staticmethod
    def _lane_from(headers) -> Optional[str]:
        """The optional ``X-Keto-Priority`` lane hint (``interactive`` |
        ``batch``); absent → None (the batcher classifies by size),
        anything else is a 400."""
        raw = (headers or {}).get("x-keto-priority", "").strip().lower()
        if not raw:
            return None
        if raw in ("interactive", "batch"):
            return raw
        raise ErrBadRequest(
            f"invalid X-Keto-Priority {raw!r} (expected interactive|batch)"
        )

    @staticmethod
    def _consistency_from(query):
        """(at_least, latest) from ``?snaptoken=`` / ``?latest=`` — the
        REST face of the gRPC snaptoken/latest fields; default is the
        never-stalling serving mode."""
        raw_token = (query.get("snaptoken") or [""])[0]
        at_least = None
        if raw_token:
            try:
                at_least = int(raw_token)
            except ValueError:
                raise ErrBadRequest(f"malformed snaptoken {raw_token!r}") from None
        latest = (query.get("latest") or [""])[0].lower() in ("1", "true")
        return at_least, latest

    def _check(self, tuple_: RelationTuple, query, headers=None):
        scope = self._scope(headers)
        at_least, latest = self._consistency_from(query)
        # replica mode: admit the pin against the applied watermark
        # (block-then-412 above it), then try the Watch-invalidated
        # check cache before paying a device dispatch
        rep = scope.replica_controller()
        cache = rep.checkcache if rep is not None else None
        key = None
        if rep is not None:
            rep.gate_read(at_least, latest)
            if cache is not None:
                key = str(tuple_)
                got = cache.get(key, at_least)
                if got is not None:
                    allowed, token = got
                    from keto_tpu.x.timeline import current_timeline

                    tl = current_timeline()
                    if tl is not None:
                        tl.stamp("cache_hit")
                    dl = scope.decision_log()
                    if dl is not None and dl.sampled():
                        self._record_decision(dl, tuple_, allowed, token, headers)
                    return (
                        (200 if allowed else 403),
                        {"allowed": allowed},
                        {
                            "X-Keto-Snaptoken": str(token),
                            "X-Keto-Checkcache": "hit",
                        },
                    )
        allowed, token = scope.check_batcher().check_with_token(
            tuple_, at_least=at_least, latest=latest,
            deadline=self._deadline_from(query, headers),
            lane=self._lane_from(headers),
        )
        if cache is not None and key is not None:
            cache.put(key, allowed, token)
        # sampled decision-audit record: one None check when the log is
        # off, one RNG draw when on — witness-free either way (the
        # snaptoken makes the decision re-explainable later)
        dl = scope.decision_log()
        if dl is not None and dl.sampled():
            self._record_decision(dl, tuple_, allowed, token, headers)
        resp_headers = {} if token is None else {"X-Keto-Snaptoken": str(token)}
        return (200 if allowed else 403), {"allowed": allowed}, resp_headers

    def _record_decision(self, dl, tuple_, allowed, token, headers):
        """Append one hot-path check decision to the decision log
        (keto_tpu/explain/decision_log.py). The route is read off the
        request timeline's device stamp when timelines are on; "" when
        they are off — the record stays re-explainable either way."""
        from keto_tpu.x.timeline import current_timeline

        route = ""
        trace_id = ""
        tl = current_timeline()
        if tl is not None:
            # the trace id when a traceparent joined us; the always-minted
            # request id otherwise — the record stays correlatable
            trace_id = tl.trace_id or tl.request_id
            for stage, _t, attrs in reversed(tl.stamps):
                if stage == "device" and attrs and "route" in attrs:
                    route = str(attrs["route"])
                    break
                if stage == "cache_hit":
                    route = "cache"
                    break
        dl.record(
            self._tenant_from(headers),
            {
                "kind": "check",
                "tuple": tuple_.to_json(),
                "decision": bool(allowed),
                "route": route,
                "witness": None,
                "snaptoken": str(token) if token is not None else "",
                "trace_id": trace_id,
            },
        )

    def _get_explain(self, query, headers=None):
        """``GET /check/explain``: the Check decision plus its provenance
        — a Manager-verified witness path (grant) or frontier-exhaustion
        certificate (deny), the route that decided it, and the label
        route's winning landmark (docs/concepts/explain.md). Always 200
        (the body carries ``allowed``); same 400 tuple contract and
        412 replica snaptoken gate as ``/check``; 404 when
        ``serve.explain_enabled`` is false."""
        scope = self._scope(headers)
        if not bool(scope.config().get("serve.explain_enabled", True)):
            err = KetoError("explain disabled by configuration")
            err.status_code = 404
            return 404, err.to_json(), {}
        try:
            tuple_ = RelationTuple.from_url_query(query)
        except ErrNilSubject:
            raise ErrBadRequest("Subject has to be specified.") from None
        at_least, latest = self._consistency_from(query)
        rep = scope.replica_controller()
        if rep is not None:
            rep.gate_read(at_least, latest)
        from keto_tpu.x.timeline import current_timeline

        tl = current_timeline()
        resp = scope.explain_engine().explain(
            tuple_,
            at_least=at_least,
            trace_id=tl.trace_id if tl is not None else "",
            tenant=self._tenant_from(headers),
        )
        if tl is not None:
            tl.stamp(
                "explain",
                route=resp.get("route", ""),
                verified=bool(resp.get("verified")),
            )
        resp_headers = {}
        if resp.get("snaptoken"):
            resp_headers["X-Keto-Snaptoken"] = resp["snaptoken"]
        return 200, resp, resp_headers

    @staticmethod
    def _stamp_decoded() -> None:
        """Stage ``decode`` ends here: the body or query is relation
        tuples now. One stamp, whatever the batch size."""
        from keto_tpu.x.timeline import current_timeline

        tl = current_timeline()
        if tl is not None:
            tl.stamp("decode")

    def _get_check(self, query, headers=None):
        try:
            tuple_ = RelationTuple.from_url_query(query)
        except ErrNilSubject:
            raise ErrBadRequest("Subject has to be specified.") from None
        self._stamp_decoded()
        return self._check(tuple_, query, headers)

    def _post_check(self, body: bytes, query, headers=None):
        try:
            obj = json.loads(body or b"{}")
        except json.JSONDecodeError as e:
            raise ErrBadRequest(f"Unable to decode JSON payload: {e}") from None
        tuple_ = RelationTuple.from_json(obj)
        self._stamp_decoded()
        return self._check(tuple_, query, headers)

    def _frame_body(self, scope, body: bytes):
        """``body`` as a ``QueryFrame`` when it has the plain form the
        native framer takes (native/ingest.cpp ``check_frame_body``), else
        None — and the body is then decoded as it always was, so every
        error a request can get comes from there. Counts the decline by
        reason. A frame is resolved to raw node ids here, on this pool
        thread, against the tables of whatever ``scope``'s engine is
        serving: the dispatch thread uses them where its round's snapshot
        still has those tables and resolves the records itself where not."""
        manager = scope.namespace_manager()
        cached = self._frame_table
        if cached[0] is not manager:
            cached = self._frame_table = (manager, FrameTable.build(manager))
        table = cached[1]
        if table is None:
            reason = "unavailable"
        elif not body or not isinstance(body, bytes):
            reason = "shape"
        else:
            got = table.frame(body, MAX_BATCH_CHECK)
            if not isinstance(got, str):
                frame = QueryFrame(*got, body, manager)
                frame.resolve_at_door(scope.check_batcher().peek_snapshot())
                return frame
            reason = got
        self._frame_declines.inc((reason,))
        return None

    def _post_check_batch(self, body: bytes, query, headers=None):
        """Many checks in one request: ``{"tuples": [...]}`` →
        ``{"results": [bool, ...]}`` in order. Large payloads classify
        into the batcher's BATCH lane (override with ``X-Keto-Priority``)
        and dispatch in bounded sub-slices, so they never convoy
        interactive checks; shed with 429 + Retry-After past the
        admission window.

        A body in the plain form goes to the batcher as one framed buffer
        of query records, no object per tuple; any other body is decoded
        into ``RelationTuple``s. The answers are the same either way."""
        scope = self._scope(headers)
        lane_hint = self._lane_from(headers)
        batcher = scope.check_batcher()
        if lane_hint != "interactive":
            # pre-parse shed: an over-window batch lane refuses BEFORE
            # paying the JSON decode — during a brownout the 429s must
            # cost microseconds or the parsing itself becomes the load
            batcher.admission_precheck()
        tuples = self._frame_body(scope, body)
        if tuples is None:
            tuples = self._decode_check_batch(body)
            self._batch_tuples.inc(("objects",), by=len(tuples))
        else:
            self._batch_tuples.inc(("framed",), by=len(tuples))
        self._stamp_decoded()
        at_least, latest = self._consistency_from(query)
        rep = scope.replica_controller()
        if rep is not None:
            rep.gate_read(at_least, latest)
        results, token = batcher.check_batch_with_token(
            tuples, at_least=at_least, latest=latest,
            deadline=self._deadline_from(query, headers),
            lane=lane_hint,
        )
        resp_headers = {} if token is None else {"X-Keto-Snaptoken": str(token)}
        return 200, {"results": results}, resp_headers

    @staticmethod
    def _decode_check_batch(body: bytes) -> list:
        """The general decode of a ``/check/batch`` body, and the source
        of every 400 the endpoint answers."""
        try:
            obj = json.loads(body or b"{}")
        except json.JSONDecodeError as e:
            raise ErrBadRequest(f"Unable to decode JSON payload: {e}") from None
        raw = obj.get("tuples") if isinstance(obj, dict) else None
        if not isinstance(raw, list) or not raw:
            raise ErrBadRequest('expected a non-empty "tuples" array')
        if len(raw) > MAX_BATCH_CHECK:
            raise ErrBadRequest(
                f"too many tuples in one batch check ({len(raw)} > "
                f"{MAX_BATCH_CHECK}); split the request"
            )
        return [RelationTuple.from_json(t) for t in raw]

    def _get_expand(self, query, headers=None):
        # the reference parses max-depth unconditionally — absent/invalid
        # is a 400 (tests/test_rest_api.py asserts this). An explicit 0
        # means "use the configured limit.max_read_depth", matching the
        # gRPC path where 0 is the proto default for an omitted field.
        scope = self._scope(headers)
        raw_depth = (query.get("max-depth") or [""])[0]
        try:
            depth = int(raw_depth)
        except ValueError:
            raise ErrBadRequest(f"invalid max-depth {raw_depth!r}") from None
        subject = subject_set_from_url_query(query)
        rep = scope.replica_controller()
        if rep is not None:
            rep.gate_read(None)  # 503 until the first bootstrap lands
        from keto_tpu.servers.grpc_api import _expand_metrics
        from keto_tpu.x.timeline import current_timeline

        counter, latency = _expand_metrics(self.registry.metrics())
        eff_depth = scope.expand_depth(depth)
        t0 = time.perf_counter()
        tree = scope.expand_engine().build_tree(subject, eff_depth)
        dur_s = time.perf_counter() - t0
        counter.inc(("http",))
        latency.observe(("http",), dur_s)
        tl = current_timeline()
        if tl is not None:
            tl.stamp("expand", depth=eff_depth)
        if tree is None:
            return 200, None, {}
        return 200, tree.to_json(), {}

    def _get_relation_tuples(self, query, headers=None):
        scope = self._scope(headers)
        rq = RelationQuery.from_url_query(query)
        rep = scope.replica_controller()
        if rep is not None:
            rep.gate_read(None)  # 503 until the first bootstrap lands
        opts = []
        token = (query.get("page_token") or [""])[0]
        if token:
            opts.append(with_token(token))
        raw_size = (query.get("page_size") or [""])[0]
        if raw_size:
            try:
                opts.append(with_size(int(raw_size)))
            except ValueError:
                raise ErrBadRequest(f"invalid page_size {raw_size!r}") from None
        rels, next_page = scope.relation_tuple_manager().get_relation_tuples(rq, *opts)
        return (
            200,
            {
                "relation_tuples": [r.to_json() for r in rels],
                "next_page_token": next_page,
            },
            {},
        )

    # -- reverse queries (keto_tpu/list/) ------------------------------------

    @staticmethod
    def _page_opts(query) -> tuple[int, str]:
        """(page_size, page_token) from the query; malformed sizes are a
        400 like the tuple-listing endpoint's."""
        token = (query.get("page_token") or [""])[0]
        raw_size = (query.get("page_size") or [""])[0]
        size = 0
        if raw_size:
            try:
                size = int(raw_size)
            except ValueError:
                raise ErrBadRequest(f"invalid page_size {raw_size!r}") from None
            if size < 0:
                raise ErrBadRequest(f"page_size must be >= 0, got {raw_size!r}")
        return size, token

    def _get_list_objects(self, query, headers=None):
        """``GET /relation-tuples/list-objects`` — every object the
        subject can (transitively) access under namespace+relation, as a
        paginated, sorted result with a snaptoken-pinned page token."""
        rq = RelationQuery.from_url_query(query)
        if rq.namespace == "":
            raise ErrBadRequest("namespace has to be specified")
        if rq.relation == "":
            raise ErrBadRequest("relation has to be specified")
        sub = rq.subject
        if sub is None:
            raise ErrBadRequest("Subject has to be specified.")
        scope = self._scope(headers)
        at_least, latest = self._consistency_from(query)
        rep = scope.replica_controller()
        if rep is not None:
            rep.gate_read(at_least, latest)
        size, token = self._page_opts(query)
        objs, nxt, snaptoken = scope.list_engine().page_objects(
            rq.namespace, rq.relation, sub,
            page_size=size, page_token=token, at_least=at_least, latest=latest,
        )
        return (
            200,
            {"objects": objs, "next_page_token": nxt, "snaptoken": str(snaptoken)},
            {"X-Keto-Snaptoken": str(snaptoken)},
        )

    def _get_list_subjects(self, query, headers=None):
        """``GET /relation-tuples/list-subjects`` — every subject id
        (transitively) allowed on namespace:object#relation."""
        rq = RelationQuery.from_url_query(query)
        if rq.namespace == "":
            raise ErrBadRequest("namespace has to be specified")
        if rq.object == "":
            raise ErrBadRequest("object has to be specified")
        if rq.relation == "":
            raise ErrBadRequest("relation has to be specified")
        scope = self._scope(headers)
        at_least, latest = self._consistency_from(query)
        rep = scope.replica_controller()
        if rep is not None:
            rep.gate_read(at_least, latest)
        size, token = self._page_opts(query)
        subs, nxt, snaptoken = scope.list_engine().page_subjects(
            rq.namespace, rq.object, rq.relation,
            page_size=size, page_token=token, at_least=at_least, latest=latest,
        )
        return (
            200,
            {
                "subject_ids": subs,
                "next_page_token": nxt,
                "snaptoken": str(snaptoken),
            },
            {"X-Keto-Snaptoken": str(snaptoken)},
        )

    def _get_watch(self, query, headers=None):
        """``GET /watch?snaptoken=N`` — chunked ndjson changefeed: one
        line per committed transaction, ``{"snaptoken", "changes":
        [{"action", "relation_tuple"}]}``, resumable from any retained
        snaptoken (410 past the horizon), ended by server drain."""
        from keto_tpu.x.errors import ErrTooManyRequests

        hub = self._scope(headers).watch_hub()
        raw = (query.get("snaptoken") or [""])[0] or "0"
        try:
            since = int(raw)
        except ValueError:
            raise ErrBadRequest(f"malformed snaptoken {raw!r}") from None
        # validate the resume horizon BEFORE committing a 200: an expired
        # token must answer 410, not die mid-stream
        hub.changes_since(since)
        if not hub.try_acquire_stream():
            raise ErrTooManyRequests(
                "too many concurrent watch streams; retry with backoff",
                retry_after_s=1.0,
            )

        def gen():
            try:
                for token, changes in hub.subscribe(since, own_slot=False):
                    msg = hub.enrich_group(
                        token,
                        {
                            "snaptoken": str(token),
                            "changes": [
                                {"action": action, "relation_tuple": rt.to_json()}
                                for action, rt in changes
                            ],
                        },
                    )
                    yield (json.dumps(msg) + "\n").encode()
            finally:
                hub.release_stream()

        return 200, StreamBody(gen()), {}

    # -- write ---------------------------------------------------------------

    @staticmethod
    def _idempotency_key_from(headers) -> Optional[str]:
        """``X-Idempotency-Key`` on a write request opts into exactly-once
        semantics: retried keys replay the original response (snaptoken +
        ``X-Keto-Idempotent-Replay: true``) instead of re-applying."""
        if not headers:
            return None
        return headers.get("x-idempotency-key") or None

    def _note_commit(self, result, scope=None) -> None:
        """Register the committed transaction's trace context with the
        watch hub (replication-aware tracing): the commit group emitted
        at this snaptoken will carry the writer's traceparent, so one
        trace spans primary transact → watch emit → replica apply.
        Idempotent replays re-answer an OLD commit — never re-register."""
        if result is None or getattr(result, "replayed", False):
            return
        token = getattr(result, "snaptoken", None)
        if token is None:
            return
        try:
            (scope or self.registry).watch_hub().note_commit_trace(
                int(token), current_traceparent()
            )
        except Exception:
            # tracing enrichment must never fail a write
            self._log.debug("commit-trace registration failed", exc_info=True)

    @staticmethod
    def _write_headers(result) -> dict[str, str]:
        """Response headers for a write: the snaptoken the transaction
        committed at (pin follow-up checks with ``?snaptoken=``; the
        durability contract says an acknowledged token survives server
        death) and the replay marker on deduplicated retries."""
        if result is None:
            return {}
        out = {"X-Keto-Snaptoken": str(result.snaptoken)}
        if result.replayed:
            out["X-Keto-Idempotent-Replay"] = "true"
        return out

    def _put_relation_tuple(self, body: bytes, headers=None):
        try:
            obj = json.loads(body or b"{}")
        except json.JSONDecodeError as e:
            raise ErrBadRequest(str(e)) from None
        rel = RelationTuple.from_json(obj)
        # routed through the group-commit coordinator when enabled (one
        # durable transaction per batch of concurrent writers, same
        # per-writer snaptoken/replay semantics)
        scope = self._scope(headers)
        result = scope.transact_writes()(
            [rel], (), idempotency_key=self._idempotency_key_from(headers)
        )
        self._note_commit(result, scope)
        resp = {"Location": "/relation-tuples?" + rel.to_url_query()}
        resp.update(self._write_headers(result))
        return 201, rel.to_json(), resp

    def _delete_relation_tuple(self, query, headers=None):
        rel = RelationTuple.from_url_query(query)
        scope = self._scope(headers)
        result = scope.transact_writes()(
            (), [rel], idempotency_key=self._idempotency_key_from(headers)
        )
        self._note_commit(result, scope)
        return 204, None, self._write_headers(result)

    def _patch_relation_tuples(self, body: bytes, headers=None):
        try:
            deltas = json.loads(body or b"[]")
        except json.JSONDecodeError as e:
            raise ErrBadRequest(str(e)) from None
        if not isinstance(deltas, list):
            raise ErrBadRequest("expected a JSON array of patch deltas")
        insert, delete = [], []
        for d in deltas:
            raw = d.get("relation_tuple") if isinstance(d, dict) else None
            if raw is None:
                raise ErrBadRequest("relation_tuple is missing")
            action = d.get("action")
            if action == "insert":
                insert.append(RelationTuple.from_json(raw))
            elif action == "delete":
                delete.append(RelationTuple.from_json(raw))
            else:
                raise ErrBadRequest(f"unknown action {action}")
        scope = self._scope(headers)
        result = scope.transact_writes()(
            insert, delete, idempotency_key=self._idempotency_key_from(headers)
        )
        self._note_commit(result, scope)
        return 204, None, self._write_headers(result)


def _make_handler(app: RestApp):
    logger = app.registry.logger()

    class Handler(BaseHTTPRequestHandler):
        protocol_version = "HTTP/1.1"
        server_version = "keto-tpu"

        def _serve(self, method: str):
            # in-flight accounting for the SIGTERM drain: the exchange
            # counts until the response bytes are handed to the kernel
            with self.server.active_lock:
                self.server.active_count += 1
            try:
                parts = urlsplit(self.path)
                query = parse_qs(parts.query, keep_blank_values=True)
                length = int(self.headers.get("Content-Length") or 0)
                body = self.rfile.read(length) if length else b""
                req_headers = {k.lower(): v for k, v in self.headers.items()}
                # no pool on this backend: the connection's own thread
                # handles, so there is no pool_wait to observe
                status, payload, headers, t_handled = app.handle_timed(
                    None, method, parts.path, query, body, req_headers
                )
                if isinstance(payload, StreamBody):
                    self._serve_stream(status, payload, headers)
                    return
                if isinstance(payload, RawBody):
                    data, content_type = payload.data, payload.content_type
                else:
                    data = b"" if payload is None else json.dumps(payload).encode()
                    content_type = "application/json"
                self.send_response(status)
                self.send_header("Content-Type", content_type)
                self.send_header("Content-Length", str(len(data)))
                for k, v in headers.items():
                    self.send_header(k, v)
                self.end_headers()
                if data:
                    self.wfile.write(data)
                app.note_written(t_handled)
            finally:
                with self.server.active_lock:
                    self.server.active_count -= 1

        def _serve_stream(self, status: int, payload: StreamBody, headers) -> None:
            """Chunked transfer: frame each generator chunk, flush so
            subscribers see events as they commit, close on exhaustion
            (stream responses never keep-alive). A client disconnect
            closes the generator, releasing its watch slot."""
            self.send_response(status)
            self.send_header("Content-Type", payload.content_type)
            self.send_header("Transfer-Encoding", "chunked")
            for k, v in headers.items():
                self.send_header(k, v)
            self.send_header("Connection", "close")
            self.end_headers()
            chunks = payload.chunks
            try:
                for chunk in chunks:
                    if not chunk:
                        continue
                    self.wfile.write(b"%x\r\n" % len(chunk) + chunk + b"\r\n")
                    self.wfile.flush()
                self.wfile.write(b"0\r\n\r\n")
            except (BrokenPipeError, ConnectionResetError):
                pass  # subscriber went away; the finally releases the slot
            finally:
                close = getattr(chunks, "close", None)
                if close is not None:
                    close()
                self.close_connection = True

        def log_message(self, fmt, *args):  # per-request logging, health excluded
            if not self.path.startswith("/health/"):
                logger.debug("%s", fmt % args)

        def do_GET(self):
            self._serve("GET")

        def do_POST(self):
            self._serve("POST")

        def do_PUT(self):
            self._serve("PUT")

        def do_DELETE(self):
            self._serve("DELETE")

        def do_PATCH(self):
            self._serve("PATCH")

    return Handler


class RestServer:
    """One role's REST server on its own port, served from a thread."""

    def __init__(self, registry, role: str, host: str = "127.0.0.1", port: int = 0):
        self.app = RestApp(registry, role)
        self.httpd = ThreadingHTTPServer((host or "0.0.0.0", port), _make_handler(self.app))
        self.httpd.daemon_threads = True
        self.httpd.active_count = 0
        self.httpd.active_lock = threading.Lock()
        self._thread: Optional[threading.Thread] = None

    @property
    def port(self) -> int:
        return self.httpd.server_address[1]

    def drain(self, timeout_s: float) -> bool:
        """Wait until every accepted request has had its response written
        (the SIGTERM drain seam). True when idle within ``timeout_s``."""
        deadline = time.monotonic() + max(0.0, timeout_s)
        while time.monotonic() < deadline:
            with self.httpd.active_lock:
                if self.httpd.active_count == 0:
                    return True
            time.sleep(0.01)
        with self.httpd.active_lock:
            return self.httpd.active_count == 0

    def start(self) -> None:
        self._thread = threading.Thread(
            target=self.httpd.serve_forever, name=f"rest-{self.app.role}", daemon=True
        )
        self._thread.start()

    def stop(self) -> None:
        self.httpd.shutdown()
        self.httpd.server_close()
        if self._thread:
            self._thread.join(timeout=5)
