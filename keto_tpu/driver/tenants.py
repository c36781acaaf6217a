"""Multi-tenant fleet mode: thousands of namespaces on one mesh.

Every subsystem below the serving surface — engine, batcher, admission,
watch, write path — was built for ONE graph. This module multiplies that
stack per *tenant* without multiplying the blast radius: a tenant id
rides the ``X-Keto-Tenant`` header (gRPC: ``x-keto-tenant`` metadata),
and the ``TenantPool`` keys a full per-tenant serving context off it.
The **default tenant is the registry itself** — a request without the
header takes exactly the pre-tenancy code path, so every existing
contract (REST/gRPC bodies, snaptokens, health, metrics) is preserved
bit-for-bit.

Isolation model (what a noisy neighbor can and cannot do):

- **State**: each tenant's tuples live under its own ``network_id`` in
  the shared store (``store.with_network``) — the same physical isolation
  two server deployments sharing one database get. A tenant's engine,
  snapshot/overlay/labels lifecycle, watch feed, and write path see only
  its network.
- **Load**: each tenant has its OWN two-lane ``CheckBatcher`` with its
  OWN AIMD ``AdmissionController`` and a quota-bounded queue
  (``serve.tenant_quota_share`` of the global queue bound). One tenant's
  10x storm saturates *its* window and sheds 429 *for that tenant only*
  — with ``Retry-After`` scaled by that tenant's consecutive overloaded
  ticks and an ``X-Keto-Tenant`` header naming the shed tenant — while
  every other tenant's interactive lane never sees the burst.
- **Memory**: hot tenants keep device-resident engines; cold tenants are
  evicted WHOLE (engine closed, ledger-accounted) and faulted back in on
  first touch via the segmented snapcache (each tenant caches under
  ``serve.snapshot_cache_dir/tenants/<id>``). The pool enforces
  ``serve.tenant_max_resident`` with a tenant-LRU, and the default
  engine's HBM governor gets a ``tenant-lru`` eviction rung so real
  device pressure can reclaim tenant residency too. The tenant currently
  dispatching is never an eviction victim (checked under its context
  lock; eviction uses try-lock, so it can never deadlock against a
  fault-in either).
- **Health**: a tenant engine's degradation surfaces as a per-tenant
  reason (``DEGRADED(tenant=...)``) on ``/health/ready`` and
  ``keto_tenant_degraded`` — it never flips the global health machine.
- **Forensics**: request timelines and flight-recorder bundles carry the
  tenant id; a per-tenant shed-rate spike is itself an anomaly trigger
  (``tenant-shed-spike`` bundles).

Engine backend per tenant (``serve.tenant_backend``): ``oracle``
(default) serves each tenant from the recursive CPU reference engine —
zero device footprint, bit-identical decisions by construction, the
right shape for thousands of mostly-cold tenants; ``device`` builds a
full ``TpuCheckEngine`` per resident tenant (own snapshot, overlay,
labels, snapcache, HBM governor) — the hot-tenant shape the fault-in
fuzz test exercises; ``auto`` picks device exactly when the default
tenant's engine is the device one.
"""

from __future__ import annotations

import contextlib
import logging
import re
import threading
import time
from collections import deque
from typing import Callable, Optional

from keto_tpu.x.errors import ErrBadRequest

_log = logging.getLogger("keto_tpu.tenants")

#: the tenant every request without a header belongs to; resolves to the
#: registry itself, i.e. the exact pre-tenancy serving stack
DEFAULT_TENANT = "default"

#: the REST header / gRPC metadata key carrying the tenant id
TENANT_HEADER = "X-Keto-Tenant"

#: tenant ids are path- and label-safe: they name snapcache directories,
#: metric label values, and store network ids
_TENANT_RE = re.compile(r"^[A-Za-z0-9][A-Za-z0-9._-]{0,63}$")


def validate_tenant_id(raw: str) -> str:
    """The validated tenant id for ``raw`` (absent/blank -> default).
    Anything outside the 64-char ``[A-Za-z0-9._-]`` grammar is a 400 —
    tenant ids become directory names and metric labels, so the grammar
    is enforced at the door, not at the filesystem."""
    tenant = (raw or "").strip()
    if not tenant:
        return DEFAULT_TENANT
    if not _TENANT_RE.match(tenant):
        raise ErrBadRequest(
            f"invalid {TENANT_HEADER} {tenant!r} (expected 1-64 chars of "
            "[A-Za-z0-9._-], starting alphanumeric)"
        )
    return tenant


class _TenantEngineProxy:
    """The engine handle a tenant's batcher dispatches through. It
    resolves the REAL engine per call under the tenant's dispatch guard,
    so eviction can close the engine between rounds and the next round
    transparently faults it back in — the batcher never holds a stale
    engine reference and never needs to stop for an eviction."""

    __slots__ = ("_ctx",)

    def __init__(self, ctx: "TenantContext"):
        self._ctx = ctx

    def peek_snapshot(self):
        """The resident engine's serving snapshot, None while the tenant
        is cold: looking never faults an engine in."""
        peek = getattr(self._ctx._engine, "peek_snapshot", None)
        return None if peek is None else peek()

    def batch_check_with_token(self, tuples, **kw):
        ctx = self._ctx
        with ctx.dispatch() as engine:
            if hasattr(engine, "batch_check_with_token"):
                out = engine.batch_check_with_token(tuples, **kw)
            elif hasattr(engine, "batch_check"):
                out = engine.batch_check(tuples), None
            else:
                # the recursive oracle reads the store per traversal
                # step: always fresh, no snapshot concept, so no token
                out = [engine.subject_is_allowed(t) for t in tuples], None
        ctx.checks_total += len(tuples)
        return out


class TenantContext:
    """One tenant's serving context. Exposes the same accessor names the
    REST/gRPC handlers call on the registry (``check_batcher``,
    ``expand_engine``, ...), so ``RestApp._scope`` can hand either back
    without the handlers caring which they got."""

    def __init__(self, name: str, pool: "TenantPool"):
        self.name = name
        self._pool = pool
        self._registry = pool.registry
        # ordering: a thread may take the POOL lock while holding this
        # context lock (counter updates), never the reverse — eviction
        # paths that already hold the pool lock use try-lock here
        self._lock = threading.RLock()  # guards: _engine, _batcher, _expand, _list, _watch_hub, _dispatching, resident
        self._store = None
        self._engine = None
        self._batcher = None
        self._expand = None
        self._explain = None
        self._list = None
        self._watch_hub = None
        self._dispatching = 0
        #: device-resident right now (an engine exists)
        self.resident = False
        #: monotonic of the last dispatch/touch — the pool's LRU key
        self.last_touch = time.monotonic()
        self.created_unix = time.time()
        #: counters (scraped via keto_tenant_*; ints under the GIL)
        self.checks_total = 0
        self.faultins = 0
        self.evictions = 0
        self.last_faultin_ms = 0.0

    # -- registry-shaped accessors (what the serving handlers call) ----------

    def config(self):
        return self._registry.config()

    def logger(self):
        return self._registry.logger()

    def version(self) -> str:
        return self._registry.version()

    def is_replica(self) -> bool:
        return False  # tenants are primary-only (enforced at _scope)

    def namespace_manager(self):
        return self._registry.namespace_manager()

    def namespaces_source(self):
        return self._registry.namespaces_source()

    def expand_depth(self, requested: int) -> int:
        return self._registry.expand_depth(requested)

    def replica_controller(self):
        return None

    def timeline_recorder(self):
        return self._registry.timeline_recorder()

    def relation_tuple_manager(self):
        """The tenant's view over the shared physical store, bound to its
        network id — host-side state, survives engine eviction."""
        with self._lock:
            if self._store is None:
                base = self._registry.relation_tuple_manager()
                self._store = base.with_network(self.name)
            return self._store

    def permission_engine(self):
        """The tenant's live engine, faulting it in when cold."""
        with self._lock:
            return self._engine_locked()

    def _engine_locked(self):  # holds: _lock
        if self._engine is None:
            t0 = time.perf_counter()
            self._engine = self._pool.build_engine(
                self.relation_tuple_manager(), self.name
            )
            self.last_faultin_ms = (time.perf_counter() - t0) * 1e3
            self.faultins += 1
            self.resident = True
            self._pool.note_faultin(self)
            _log.info(
                "tenant %r faulted in (%.1f ms, engine=%s)",
                self.name, self.last_faultin_ms,
                type(self._engine).__name__,
            )
        self.last_touch = time.monotonic()
        return self._engine

    @contextlib.contextmanager
    def dispatch(self):
        """Fault-in + dispatch guard: while any dispatch is in flight the
        pool's eviction paths skip this tenant (the ladder rung that "can
        never evict the tenant currently dispatching")."""
        with self._lock:
            engine = self._engine_locked()
            self._dispatching += 1
        try:
            yield engine
        finally:
            with self._lock:
                self._dispatching -= 1
                self.last_touch = time.monotonic()

    def check_batcher(self):
        with self._lock:
            if self._batcher is None:
                self._batcher = self._pool.build_batcher(
                    _TenantEngineProxy(self), self.name
                )
            return self._batcher

    def expand_engine(self):
        """Tenant expand rides the Manager-backed recursion over the
        tenant's store view: correct against the same network the check
        engine reads, with zero extra device residency."""
        with self._lock:
            if self._expand is None:
                from keto_tpu.expand.engine import ExpandEngine

                self._expand = ExpandEngine(self.relation_tuple_manager())
            return self._expand

    def list_engine(self):
        with self._lock:
            if self._list is None:
                from keto_tpu.list.engine import ListEngine

                self._list = ListEngine(self.relation_tuple_manager())
            return self._list

    def decision_log(self):
        """The shared decision log (one per process, tenant-scoped
        subdirectories — this context's records carry its tenant name)."""
        return self._registry.decision_log()

    def explain_engine(self):
        """The tenant's decision-provenance engine: decides through the
        tenant's own engine UNDER THE DISPATCH GUARD (so eviction can
        close and re-fault the engine between explains, never during
        one) and back-traces witnesses against the tenant's store view,
        sharing the process-wide decision log."""
        with self._lock:
            if self._explain is None:
                from keto_tpu.explain.engine import ExplainEngine

                store = self.relation_tuple_manager()

                def decide(rt, at_least):
                    with self.dispatch() as engine:
                        got = ExplainEngine.decide_with(engine, store, rt, at_least)
                    self.checks_total += 1
                    return got

                def on_verify_failure(note):
                    fr = self._registry.flight_recorder()
                    if fr is not None:
                        fr.trigger(
                            "witness-verify-failure",
                            detail=note.get("tuple", ""),
                        )

                self._explain = ExplainEngine(
                    None,
                    store,
                    decision_log=self._registry.decision_log(),
                    on_verify_failure=on_verify_failure,
                    decide=decide,
                )
            return self._explain

    def watch_hub(self):
        with self._lock:
            if self._watch_hub is None:
                from keto_tpu.list.watch import WatchHub

                cfg = self.config()
                self._watch_hub = WatchHub(
                    self.relation_tuple_manager(),
                    poll_s=float(cfg.get("serve.watch_poll_ms", 100.0)) / 1e3,
                    max_streams=int(cfg.get("serve.watch_max_streams", 64)),
                )
            return self._watch_hub

    def transact_writes(self):
        """Per-tenant writes go straight to the tenant's store view (solo
        durable transact; the group-commit coordinator batches only the
        default tenant's writers). Same TransactResult contract."""
        store = self.relation_tuple_manager()

        def solo(insert, delete, idempotency_key=None):
            return store.transact_relation_tuples(
                insert, delete, idempotency_key=idempotency_key
            )

        return solo

    # -- residency ------------------------------------------------------------

    def resident_bytes(self) -> int:
        """This tenant's device-ledger bytes (0 for oracle engines and
        while cold) — the pool's cross-tenant residency account."""
        with self._lock:
            gov = getattr(self._engine, "hbm", None)
        return int(gov.resident_bytes()) if gov is not None else 0

    def try_evict(self, reason: str) -> int:
        """Evict this tenant whole if it is idle: close the engine
        (snapcache keeps the on-disk fault-in path warm), drop residency,
        return the ledger bytes freed. Non-blocking: a tenant mid-dispatch
        or mid-fault-in (context lock held) is skipped with 0 — eviction
        can therefore never deadlock against a fault-in."""
        if not self._lock.acquire(blocking=False):
            return 0
        try:
            if self._engine is None or self._dispatching > 0:
                return 0
            freed = self.resident_bytes()
            engine, self._engine = self._engine, None  # keto-analyze: ignore[KTA201] lock held via the non-blocking acquire above
            self.resident = False  # keto-analyze: ignore[KTA201] lock held via the non-blocking acquire above
            self.evictions += 1
            # the batcher keeps running against the proxy; the expand /
            # list engines hold only the host-side store view
            try:
                if hasattr(engine, "close"):
                    engine.close()
            except Exception:
                _log.warning(
                    "tenant %r engine close failed during eviction",
                    self.name, exc_info=True,
                )
            _log.info(
                "tenant %r evicted (%s, ~%d bytes freed)",
                self.name, reason, freed,
            )
            return freed
        finally:
            self._lock.release()

    def health_reason(self) -> str:
        """A per-tenant degradation reason, or "". Derived from the
        tenant engine's health inputs; NEVER fed into the global health
        machine — one tenant's degraded device path must not pull the
        whole server out of rotation."""
        with self._lock:
            engine = self._engine
        if engine is None or not hasattr(engine, "health"):
            return ""
        try:
            h = engine.health()
        except Exception as e:
            return f"DEGRADED(tenant={self.name}): health probe failed: {e}"
        if int(h.get("audit_mismatches", 0) or 0) > 0:
            return (
                f"DEGRADED(tenant={self.name}): audit observed "
                f"{int(h['audit_mismatches'])} device/oracle mismatches"
            )
        if h.get("degraded"):
            return (
                f"DEGRADED(tenant={self.name}): device path failing; "
                "serving from the CPU fallback"
            )
        if h.get("memory_pressure"):
            return (
                f"DEGRADED(tenant={self.name}): memory_pressure "
                "(eviction ladder spent); serving stale within budget"
            )
        return ""

    def snapshot(self) -> dict:
        """The flight-recorder / debug view of this tenant."""
        with self._lock:
            batcher = self._batcher
            out = {
                "tenant": self.name,
                "resident": self.resident,
                "dispatching": self._dispatching,
                "idle_s": round(time.monotonic() - self.last_touch, 3),
                "checks_total": self.checks_total,
                "faultins": self.faultins,
                "evictions": self.evictions,
                "last_faultin_ms": round(self.last_faultin_ms, 3),
                "resident_bytes": 0,
                "engine": (
                    type(self._engine).__name__ if self._engine else None
                ),
            }
        out["resident_bytes"] = self.resident_bytes()
        reason = self.health_reason()
        if reason:
            out["degraded"] = reason
        if batcher is not None:
            adm = batcher.admission
            out["batcher"] = {
                "queue_depth": batcher.queue_depth,
                "shed_count": batcher.shed_count,
                "admission_window": (
                    getattr(adm, "window", None) if adm is not None else None
                ),
            }
        return out

    def close(self) -> None:
        with self._lock:
            batcher, self._batcher = self._batcher, None
            hub, self._watch_hub = self._watch_hub, None
            engine, self._engine = self._engine, None
            self.resident = False
        for obj, op in ((batcher, "stop"), (hub, "close"), (engine, "close")):
            if obj is None:
                continue
            try:
                getattr(obj, op, lambda: None)()
            except Exception:
                _log.warning(
                    "tenant %r %s during close failed", self.name, op,
                    exc_info=True,
                )


class TenantPool:
    """The keyed pool of tenant contexts plus the cross-tenant residency
    ledger (see module docstring). Owned by the registry; built lazily on
    the first non-default tenant request."""

    def __init__(
        self,
        registry,
        *,
        max_resident: int = 8,
        quota_share: float = 0.25,
        backend: str = "oracle",
        shed_spike: int = 50,
        shed_spike_window_s: float = 10.0,
    ):
        self.registry = registry
        self.max_resident = max(1, int(max_resident))
        self.quota_share = min(1.0, max(0.01, float(quota_share)))
        self.backend = str(backend or "oracle")
        self.shed_spike = max(0, int(shed_spike))
        self.shed_spike_window_s = max(0.1, float(shed_spike_window_s))
        # ordering: never take a context lock while holding this lock
        # (evictions use the context's try-lock instead)
        self._lock = threading.RLock()  # guards: _tenants, _shed_events, shed_totals, evictions, faultins, spike_triggers
        self._tenants: dict[str, TenantContext] = {}
        #: per-tenant shed timestamps inside the spike window
        self._shed_events: dict[str, deque] = {}
        #: per-tenant shed totals (includes the default tenant, whose
        #: batcher the registry wires into note_shed)
        self.shed_totals: dict[str, int] = {DEFAULT_TENANT: 0}
        self.evictions = 0
        self.faultins = 0
        self.spike_triggers = 0
        #: anomaly seam (the flight recorder's tenant-shed-spike trigger)
        self._shed_trigger: Optional[Callable[[str, str], None]] = None

    # -- lookup ---------------------------------------------------------------

    def get(self, tenant: str) -> TenantContext:
        """The context for ``tenant`` (creating it on first touch), with
        residency capacity enforced after any fault-in this may cause."""
        name = validate_tenant_id(tenant)
        if name == DEFAULT_TENANT:
            raise ValueError(
                "the default tenant is the registry itself, not a pool entry"
            )
        with self._lock:
            ctx = self._tenants.get(name)
            if ctx is None:
                ctx = TenantContext(name, self)
                self._tenants[name] = ctx
        ctx.last_touch = time.monotonic()
        return ctx

    def peek(self, tenant: str) -> Optional[TenantContext]:
        with self._lock:
            return self._tenants.get(tenant)

    def tenants(self) -> list[TenantContext]:
        with self._lock:
            return list(self._tenants.values())

    # -- component builders (called by TenantContext under ITS lock) ---------

    def build_engine(self, store, tenant: str):
        self.enforce_capacity(exclude=tenant)
        return self.registry.build_tenant_engine(store, tenant)

    def build_batcher(self, engine_proxy, tenant: str):
        return self.registry.build_tenant_batcher(engine_proxy, tenant)

    # -- residency ledger -----------------------------------------------------

    def note_faultin(self, ctx: TenantContext) -> None:
        with self._lock:
            self.faultins += 1

    def resident_count(self) -> int:
        with self._lock:
            return sum(1 for c in self._tenants.values() if c.resident)

    def known_count(self) -> int:
        with self._lock:
            return len(self._tenants)

    def enforce_capacity(self, exclude: str = "") -> None:
        """Evict least-recently-touched resident tenants until the pool
        is back under ``max_resident`` (leaving room for ``exclude``, the
        tenant about to fault in). Victims mid-dispatch or mid-fault-in
        are skipped (try-lock) — capacity is then enforced on the next
        touch instead of deadlocking now."""
        while True:
            with self._lock:
                resident = [
                    c for c in self._tenants.values()
                    if c.resident and c.name != exclude
                ]
                # the incoming tenant occupies one slot
                if len(resident) < self.max_resident:
                    return
                resident.sort(key=lambda c: c.last_touch)
                victims = list(resident)
            evicted_one = False
            for victim in victims:
                if victim.try_evict("tenant-lru capacity") or not victim.resident:
                    with self._lock:
                        self.evictions += 1
                    evicted_one = True
                    break
            if not evicted_one:
                return  # everyone busy: over-resident until next touch

    def evict_coldest(self) -> int:
        """The default engine's ``tenant-lru`` HBM rung: free device
        bytes by evicting the coldest idle tenant. Returns bytes freed
        (0 when every tenant is busy or nothing is resident)."""
        with self._lock:
            resident = sorted(
                (c for c in self._tenants.values() if c.resident),
                key=lambda c: c.last_touch,
            )
        for victim in resident:
            freed = victim.try_evict("tenant-lru hbm pressure")
            if freed or not victim.resident:
                with self._lock:
                    self.evictions += 1
                return freed
        return 0

    # -- shed-rate anomaly tracking ------------------------------------------

    def set_shed_trigger(self, fn: Callable[[str, str], None]) -> None:
        """``fn(tenant, detail)`` fires when a tenant's shed rate spikes
        (the flight recorder's ``tenant-shed-spike`` bundle seam)."""
        self._shed_trigger = fn

    def note_shed(self, tenant: str, lane: str) -> None:
        """Every per-tenant batcher (and the default one) reports sheds
        here; crossing ``shed_spike`` sheds inside the window fires the
        anomaly trigger once per window."""
        name = tenant or DEFAULT_TENANT
        fire = False
        now = time.monotonic()
        with self._lock:
            self.shed_totals[name] = self.shed_totals.get(name, 0) + 1
            if self.shed_spike <= 0:
                return
            events = self._shed_events.setdefault(name, deque())
            cutoff = now - self.shed_spike_window_s
            while events and events[0] < cutoff:
                events.popleft()
            events.append(now)
            if len(events) >= self.shed_spike:
                events.clear()  # one trigger per window crossing
                self.spike_triggers += 1
                fire = True
        if fire and self._shed_trigger is not None:
            try:
                self._shed_trigger(
                    name,
                    f"tenant {name!r} shed >= {self.shed_spike} requests "
                    f"in {self.shed_spike_window_s:.0f}s ({lane} lane)",
                )
            except Exception:
                _log.warning("tenant shed-spike trigger failed", exc_info=True)

    # -- health / introspection ----------------------------------------------

    def degraded(self) -> dict[str, str]:
        """{tenant: reason} for every tenant currently degraded — the
        ``/health/ready`` extra section and ``keto_tenant_degraded``."""
        out = {}
        for ctx in self.tenants():
            reason = ctx.health_reason()
            if reason:
                out[ctx.name] = reason
        return out

    def ledger(self) -> dict[str, int]:
        """{tenant: resident device bytes} — sums with the default
        engine's own governor ledger to the whole process's account."""
        return {c.name: c.resident_bytes() for c in self.tenants()}

    def snapshot(self) -> dict:
        """The flight-recorder ``tenants`` section / operator view."""
        with self._lock:
            shed = dict(self.shed_totals)
        return {
            "known": self.known_count(),
            "resident": self.resident_count(),
            "max_resident": self.max_resident,
            "backend": self.backend,
            "evictions": self.evictions,
            "faultins": self.faultins,
            "spike_triggers": self.spike_triggers,
            "shed_totals": shed,
            "degraded": self.degraded(),
            "tenants": [c.snapshot() for c in self.tenants()],
        }

    def close(self) -> None:
        with self._lock:
            ctxs = list(self._tenants.values())
            self._tenants.clear()
        for ctx in ctxs:
            ctx.close()


__all__ = [
    "DEFAULT_TENANT",
    "TENANT_HEADER",
    "TenantContext",
    "TenantPool",
    "validate_tenant_id",
]
