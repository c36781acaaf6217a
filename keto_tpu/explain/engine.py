"""The explain engine: decision + witness + verification + audit record.

``ExplainEngine.explain`` answers "why" for one Check:

1. **Decide** through the serving engine itself — the TPU engine's streaming
   path with ``with_info=True`` (so the genuine route that decided —
   label / hybrid / bfs / host / cpu — is reported, not re-derived), or the
   reference engine when that is what serves the scope (tenants, fallback).
2. **Reconstruct** the witness: device routes back-trace the subject-set
   closure against the Manager (``build_witness`` — BFS with parent
   pointers, shortest path); the cpu route threads the reference engine's
   own traversal (``oracle_witness``). Denies carry the BFS's
   frontier-exhaustion certificate.
3. **Verify** edge-by-edge against the Manager before returning. A witness
   that fails verification is a bug: counted
   (``keto_witness_verify_failures_total``), recorded for the flight
   recorder, and the response falls back to the CPU oracle's witness.
4. **Enrich** label-route grants with the intersection's winning landmark
   (``TpuCheckEngine.label_witness_info`` — the argmin the device kernel
   extracts), naming the hub node the 2-hop proof went through.
5. **Record** the decision in the durable decision log when one is
   configured, witness included, so the audit trail carries provenance.

The engine is scope-shaped: the default tenant's instance wraps the TPU
engine + root Manager; tenant instances wrap that tenant's fault-in engine +
store view (keto_tpu/driver/tenants.py). None of this ever runs on the check
hot path — explain is its own endpoint, and hot-path decision-log sampling
is a separate, witness-free record (servers/rest.py).
"""

from __future__ import annotations

import threading
from collections import deque
from typing import Any, Callable, Optional

import numpy as np

from keto_tpu.explain.decision_log import DecisionLog
from keto_tpu.explain.witness import (
    DEFAULT_MAX_HEADS,
    GatedClosure,
    build_witness,
    oracle_witness,
    verify_witness,
)
from keto_tpu.namespace.rewrites import schema_for
from keto_tpu.relationtuple.manager import Manager
from keto_tpu.relationtuple.model import RelationTuple


class ExplainEngine:
    def __init__(
        self,
        engine: Any,
        manager: Manager,
        *,
        decision_log: Optional[DecisionLog] = None,
        page_size: int = 0,
        max_heads: int = DEFAULT_MAX_HEADS,
        on_verify_failure: Optional[Callable[[dict[str, Any]], None]] = None,
        decide: Optional[Callable[..., tuple[bool, str, Optional[int]]]] = None,
    ):
        self._engine = engine
        self._manager = manager
        #: optional decide override — tenant contexts route the decision
        #: through their dispatch guard so engine eviction can never leave
        #: an explain call holding a closed engine
        self._decide_fn = decide
        self._decision_log = decision_log
        self._page_size = page_size
        self._max_heads = max_heads
        self._on_verify_failure = on_verify_failure
        self._lock = threading.Lock()
        #: per-route request counts — keto_explain_requests_total{route}
        self.requests_by_route: dict[str, int] = {}
        #: witnesses that failed edge-by-edge verification — each one is a
        #: bug in the producing route; keto_witness_verify_failures_total
        self.verify_failures = 0
        #: recent verify failures, flight-recorder section material
        self.recent_failures: deque = deque(maxlen=8)

    # -- decision -------------------------------------------------------------

    def _decide(self, rt: RelationTuple, at_least) -> tuple[bool, str, Optional[int]]:
        if self._decide_fn is not None:
            return self._decide_fn(rt, at_least)
        return self.decide_with(self._engine, self._manager, rt, at_least)

    @staticmethod
    def decide_with(
        eng: Any, manager: Manager, rt: RelationTuple, at_least
    ) -> tuple[bool, str, Optional[int]]:
        """One check through ``eng``, returning ``(allowed, route,
        snaptoken)`` with the route that actually decided it (the
        stream's with_info route label; "cpu" for the reference engine)."""
        if hasattr(eng, "batch_check_stream_with_token"):
            allowed = False
            route = "host"
            if getattr(eng, "STREAM_INFO", False):
                gen, token = eng.batch_check_stream_with_token(
                    [rt], at_least=at_least, ordered=False, with_info=True
                )
                for _off, out, info in gen:
                    allowed = bool(np.asarray(out).reshape(-1)[0])
                    route = str(info.get("route", route))
            else:
                gen, token = eng.batch_check_stream_with_token(
                    [rt], at_least=at_least, ordered=False
                )
                for _off, out in gen:
                    allowed = bool(np.asarray(out).reshape(-1)[0])
            return allowed, route, token
        allowed = bool(eng.subject_is_allowed(rt))
        token = None
        wm = getattr(manager, "watermark", None)
        if callable(wm):
            try:
                token = int(wm())
            except Exception:
                token = None
        return allowed, "cpu", token

    # -- explain --------------------------------------------------------------

    def explain(
        self,
        requested: RelationTuple,
        *,
        at_least=None,
        trace_id: str = "",
        tenant: str = "default",
    ) -> dict[str, Any]:
        """Decide + reconstruct + verify + record; returns the response
        body for ``GET /check/explain`` (docs/concepts/explain.md)."""
        allowed, route, token = self._decide(requested, at_least)
        with self._lock:
            self.requests_by_route[route] = self.requests_by_route.get(route, 0) + 1
        rewrites = self._rewrites()
        if rewrites is not None:
            try:
                return self._explain(requested, allowed, route, token, at_least,
                                     trace_id, tenant, rewrites)
            except GatedClosure as e:
                # an intersection or an exclusion decided: no path is a
                # witness of that, and none is made up
                return {
                    "allowed": allowed, "route": route,
                    "snaptoken": str(token) if token is not None else "",
                    "tuple": requested.to_json(), "witness": None, "certificate": None,
                    "verified": False, "witness_source": "",
                    "rewrite": f"no witness: {e} holds an intersection or an exclusion",
                }
        return self._explain(requested, allowed, route, token, at_least, trace_id, tenant, None)

    def _rewrites(self):
        """``(namespace manager, schema)`` where the store's namespaces
        carry userset rewrites, else None."""
        nm, schema = schema_for(None, self._manager)
        return (nm, schema) if schema else None

    def _explain(self, requested, allowed, route, token, at_least, trace_id, tenant, rewrites):

        path = None
        certificate = None
        witness_source = ""
        divergence = False

        if route == "cpu":
            # the oracle decided; its own traversal IS the witness
            path = oracle_witness(
                self._manager, requested, page_size=self._page_size, rewrites=rewrites)
            witness_source = "oracle"
            if allowed != (path is not None):
                divergence = True
            if path is None and not allowed:
                _, _, certificate = build_witness(
                    self._manager,
                    requested,
                    page_size=self._page_size,
                    max_heads=self._max_heads,
                    rewrites=rewrites,
                )
        else:
            found, path, certificate = build_witness(
                self._manager,
                requested,
                page_size=self._page_size,
                max_heads=self._max_heads,
                rewrites=rewrites,
            )
            witness_source = "backtrace"
            if found != allowed:
                # the device route and the store-closure back-trace disagree
                # — a real bug (or an injected one); surface it loudly
                divergence = True

        verified = False
        if allowed:
            ok, reason = (
                verify_witness(self._manager, requested, path, rewrites)
                if path
                else (False, "no witness path found for an allowed decision")
            )
            if not ok:
                self._note_failure(requested, route, tenant, path, reason)
                path = oracle_witness(
                    self._manager, requested, page_size=self._page_size, rewrites=rewrites
                )
                witness_source = "oracle-fallback"
                if path:
                    ok, _ = verify_witness(self._manager, requested, path, rewrites)
            verified = bool(ok and path)
        elif divergence:
            # denied by the engine but the closure holds a path: count it
            # like a verify failure — it is the same class of bug
            self._note_failure(
                requested, route, tenant, path, "engine denied but closure grants"
            )
            certificate = None

        witness = [t.to_json() for t in path] if path else None
        resp: dict[str, Any] = {
            "allowed": allowed,
            "route": route,
            "snaptoken": str(token) if token is not None else "",
            "tuple": requested.to_json(),
            "witness": witness,
            "certificate": certificate,
            "verified": verified,
            "witness_source": witness_source if path else "",
        }
        if divergence:
            resp["decision_divergence"] = True
        if allowed and route in ("label", "hybrid"):
            lw = getattr(self._engine, "label_witness_info", None)
            if lw is not None:
                try:
                    landmark = lw(requested, at_least=at_least)
                except Exception:
                    landmark = None
                if landmark:
                    resp["landmark"] = landmark

        dl = self._decision_log
        if dl is not None:
            # explain calls are explicit audit actions: always recorded
            # (the 1-in-N sampling applies to hot-path checks only)
            dl.record(
                tenant,
                {
                    "kind": "explain",
                    "tuple": requested.to_json(),
                    "decision": allowed,
                    "route": route,
                    "witness": witness,
                    "certificate": certificate,
                    "snaptoken": resp["snaptoken"],
                    "trace_id": trace_id,
                },
            )
        return resp

    def _note_failure(
        self,
        requested: RelationTuple,
        route: str,
        tenant: str,
        path,
        reason: str,
    ) -> None:
        with self._lock:
            self.verify_failures += 1
            note = {
                "tuple": str(requested),
                "route": route,
                "tenant": tenant,
                "reason": reason,
                "witness": [str(t) for t in path] if path else None,
            }
            self.recent_failures.append(note)
        cb = self._on_verify_failure
        if cb is not None:
            try:
                cb(note)
            except Exception:  # keto-analyze: ignore[KTA401] the callback is the flight recorder; a recorder fault must not mask the verify-failure accounting above
                pass
