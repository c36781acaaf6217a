"""Dependency-injection registry.

Mirrors the reference's ``driver.Registry`` contract and its lazily
constructed singletons (reference internal/driver/registry.go:26-58,
registry_default.go:158-170): config in, everything else memoized on first
access. ``permission_engine()`` is the seam where the TPU check engine plugs
in instead of the recursive one (reference registry_default.go:158-163 — the
spot the survey marks as "where a TPU CheckEngine plugs in").
"""

from __future__ import annotations

import threading
from typing import Any, Callable, Optional

from keto_tpu import namespace as namespace_pkg
from keto_tpu.check.engine import CheckEngine
from keto_tpu.config.provider import Config
from keto_tpu.driver.batch import CheckBatcher
from keto_tpu.expand.engine import ExpandEngine
from keto_tpu.persistence.memory import MemoryPersister
from keto_tpu.version import __version__ as VERSION
from keto_tpu.x.logging import new_logger


class Registry:
    def __init__(
        self,
        config: Config,
        network_id: str = "default",
        *,
        use_default_compile_cache: bool = False,
    ):
        self._config = config
        self._network_id = network_id
        # the CLI ``serve`` path sets this: with no directory requested,
        # the persistent compilation cache falls back to the fixed
        # in-checkout path (keto_tpu/driver/compile_cache.py) instead of
        # staying off, as it does for in-process daemons
        self._use_default_compile_cache = use_default_compile_cache
        #: platform / device_kind / count of the devices the check engine
        #: runs on, set when the device engine is built (keto_device_info)
        self._device_info: Optional[dict] = None
        self._lock = threading.RLock()  # guards: _singletons, _promoted
        self._singletons: dict[str, Any] = {}
        # fleet promotion flag: a process booted as serve.role=replica
        # that won the lease election serves as a primary from then on —
        # is_replica() consults this at call time, so the write path,
        # group-commit construction and REST refusals all flip without
        # a rebuild (keto_tpu/fleet/controller.py)
        self._promoted = False
        # engines see namespace hot-reloads through this indirection
        config.on_namespace_change(self._on_namespace_change)

    def _memo(self, key: str, build: Callable[[], Any]) -> Any:
        got = self._singletons.get(key)
        if got is None:
            with self._lock:
                got = self._singletons.get(key)
                if got is None:
                    got = build()
                    self._singletons[key] = got
        return got

    def _on_namespace_change(self) -> None:
        # nothing to invalidate: stores/engines resolve the namespace
        # manager through the callable below on every use
        pass

    def peek(self, key: str):
        """An already-built singleton, or None — shutdown paths use this
        to avoid constructing a dependency just to tear it down."""
        return self._singletons.get(key)

    # -- leaf dependencies ---------------------------------------------------

    def config(self) -> Config:
        return self._config

    def logger(self):
        return self._memo(
            "logger",
            lambda: new_logger(
                self._config.get("log.level", "info"), self._config.get("log.format", "text")
            ),
        )

    def namespace_manager(self) -> namespace_pkg.Manager:
        return self._config.namespace_manager()

    def namespaces_source(self) -> Callable[[], namespace_pkg.Manager]:
        return self._config.namespace_manager

    # -- storage -------------------------------------------------------------

    def is_replica(self) -> bool:
        """True when this process serves as a read replica
        (``serve.role: replica``): no SQL access, state fed by the
        primary's Watch changefeed (keto_tpu/replica/). A replica the
        fleet controller promoted reads False from then on — every
        write-path branch consults this at call time."""
        if self._promoted:
            return False
        return str(self._config.get("serve.role", "primary")) == "replica"

    def _build_direct_store(self):
        """A tuple store with direct SQL (or in-process memory) access,
        built from the configured dsn — the primary's store, and the
        store a promoted replica installs over the durable-watermark
        handoff (promote_to_primary)."""
        dsn = self._config.dsn
        if dsn == "memory":
            store = MemoryPersister(
                self.namespaces_source(), network_id=self._network_id
            )
        elif dsn.startswith("sqlite://"):
            from keto_tpu.persistence.sqlite import SQLitePersister

            store = SQLitePersister(
                dsn, self.namespaces_source(), network_id=self._network_id
            )
        elif dsn.startswith(("postgres://", "postgresql://", "cockroach://")):
            from keto_tpu.persistence.postgres import PostgresPersister

            store = PostgresPersister(
                dsn, self.namespaces_source(), network_id=self._network_id
            )
        else:
            raise ValueError(f"unsupported dsn {dsn!r}")
        # idempotency keys dedup write retries for this long before GC
        store.idempotency_ttl_s = float(
            self._config.get("serve.idempotency_ttl_s", 86400.0)
        )
        # time-based GC of the durable change logs feeding /watch and
        # the delta path (serve.watch_log_retention_s; 0 disables)
        store.watch_log_retention_s = float(
            self._config.get("serve.watch_log_retention_s", 3600.0)
        )
        # one piggybacked watch-GC pass prunes at most this many rows
        # (a group commit must never stall behind an unbounded sweep)
        store.watch_gc_max_rows = int(
            self._config.get("serve.watch_gc_max_rows", 10000)
        )
        return store

    def relation_tuple_manager(self):
        def build():
            if self.is_replica():
                # replicas hold NO SQL access: the store is a local
                # materialization of the primary's commit log, installed
                # by the replica controller (dsn is ignored by design)
                from keto_tpu.replica.store import ReplicaStore

                store = ReplicaStore(
                    self.namespaces_source(), network_id=self._network_id
                )
                store.idempotency_ttl_s = float(
                    self._config.get("serve.idempotency_ttl_s", 86400.0)
                )
                # a replica's own logs feed chained watchers and its
                # engine's delta path: same retention hygiene as primary
                store.watch_log_retention_s = float(
                    self._config.get("serve.watch_log_retention_s", 3600.0)
                )
                store.watch_gc_max_rows = int(
                    self._config.get("serve.watch_gc_max_rows", 10000)
                )
                return store
            return self._build_direct_store()

        return self._memo("manager", build)

    def write_coordinator(self):
        """The group-commit coordinator
        (keto_tpu/driver/group_commit.py): batches concurrent write
        transactions into one durable ``transact_many`` group. ``None``
        on replicas (read-only) and when
        ``serve.group_commit_enabled: false`` — callers fall back to
        per-commit ``transact_relation_tuples``."""
        if self.is_replica():
            return None
        if not bool(self._config.get("serve.group_commit_enabled", True)):
            return None

        def build():
            from keto_tpu.driver.group_commit import GroupCommitCoordinator

            co = GroupCommitCoordinator(
                self.relation_tuple_manager(),
                max_writers=int(
                    self._config.get("serve.group_commit_max_writers", 128)
                ),
                window_ms=float(
                    self._config.get("serve.group_commit_window_ms", 2.0)
                ),
                max_pending=int(
                    self._config.get("serve.group_commit_max_pending", 4096)
                ),
                wait_histogram=self.metrics().histogram(
                    "keto_group_commit_wait_seconds",
                    "Time a writer spent queued in the group-commit "
                    "coordinator before its group's durable transaction "
                    "started (the coalescing cost the "
                    "serve.group_commit_window_ms knob trades against "
                    "fsyncs).",
                    buckets=(0.0005, 0.001, 0.0025, 0.005, 0.01, 0.025,
                             0.05, 0.1, 0.25, 1.0),
                ),
                batch_histogram=self.metrics().histogram(
                    "keto_group_commit_batch_size",
                    "Writers coalesced per durable group transaction "
                    "(1 = no batching benefit; the ceiling is "
                    "serve.group_commit_max_writers).",
                    buckets=(1, 2, 4, 8, 16, 32, 64, 128, 256),
                ),
            )
            co.start()
            return co

        return self._memo("group_commit", build)

    def transact_writes(self):
        """The write-path entry point the serving layers call: a
        ``(insert, delete, idempotency_key=None) -> TransactResult``
        callable routed through the group-commit coordinator when one is
        enabled, else straight to the store's solo transact. Per-writer
        results (snaptoken, replay flag) are identical either way."""
        co = self.write_coordinator()
        if co is not None:
            def route(insert, delete, idempotency_key=None):
                return co.transact(
                    insert, delete, idempotency_key=idempotency_key
                )

            return route
        store = self.relation_tuple_manager()

        def solo(insert, delete, idempotency_key=None):
            return store.transact_relation_tuples(
                insert, delete, idempotency_key=idempotency_key
            )

        return solo

    def replica_controller(self):
        """The replica lifecycle owner (keto_tpu/replica/controller.py):
        bootstrap from the primary's /snapshot/export, the supervised
        Watch feed with its durable applied-watermark, the 412 read gate,
        and the Watch-invalidated check cache. ``None`` on a primary —
        serving layers branch on that."""
        if not self.is_replica():
            return None

        def build():
            from keto_tpu.replica.controller import ReplicaController

            engine = self.permission_engine()
            return ReplicaController(
                self.relation_tuple_manager(),
                self.permission_engine,
                str(self._config.get("serve.primary_url", "")),
                # replication-aware tracing: applies join the writer's
                # trace, and the commit→visible delay histogram carries
                # the writer's trace id as its exemplar
                tracer=self.tracer(),
                apply_delay_histogram=self.metrics().histogram(
                    "keto_replication_apply_delay_seconds",
                    "Replica mode: wall time from the primary's commit to "
                    "the change being visible through this replica's 412 "
                    "gate (cross-clock; slowest sample carries the "
                    "writer's trace_id exemplar).",
                    buckets=(0.001, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25,
                             0.5, 1.0, 2.5, 5.0, 15.0, 60.0),
                ),
                replica_dir=str(self._config.get("serve.replica_dir", "") or ""),
                snapshot_cache_dir=str(
                    self._config.get("serve.snapshot_cache_dir", "") or ""
                ),
                staleness_wait_ms=float(
                    self._config.get("serve.staleness_wait_ms", 200.0)
                ),
                staleness_budget_s=float(
                    self._config.get("serve.replica_staleness_budget_s", 30.0)
                ),
                checkcache_entries=int(
                    self._config.get("serve.checkcache_entries", 65536)
                ),
                probe_s=max(
                    0.25,
                    float(self._config.get("serve.watch_poll_ms", 100.0)) / 1e3,
                ),
                stats=getattr(engine, "maintenance", None),
            )

        return self._memo("replica", build)

    # -- fleet control plane (keto_tpu/fleet/) -------------------------------

    def fleet_enabled(self) -> bool:
        return bool(self._config.get("serve.fleet_enabled", False))

    def _fleet_lease_store(self):
        """The store the lease election runs through. Replicas hold no
        tuple-store SQL access by design, so the lease channel is a
        DEDICATED persister built from the dsn — the one SQL surface a
        replica touches pre-promotion. Primaries with a memory dsn share
        the tuple store itself (same in-process state)."""

        def build():
            if self._config.dsn == "memory" and not self.is_replica():
                return self.relation_tuple_manager()
            return self._build_direct_store()

        return self._memo("fleet_lease_store", build)

    def fleet_controller(self):
        """The lease-election / membership / promotion loop
        (keto_tpu/fleet/controller.py), or None without
        ``serve.fleet_enabled``. Started by the daemon after the serving
        components exist."""
        if not self.fleet_enabled():
            return None

        def build():
            import os
            import socket

            from keto_tpu.fleet.controller import FleetController

            node_id = str(self._config.get("serve.fleet_node_id", "") or "")
            if not node_id:
                node_id = f"{socket.gethostname()}-{os.getpid()}"
            role = "replica" if self.is_replica() else "primary"

            def watermark_fn():
                rep = self.peek("replica")
                if rep is not None and self.is_replica():
                    return int(rep.watermark)
                store = self.peek("manager")
                try:
                    return int(store.watermark()) if store is not None else 0
                except Exception:
                    return 0

            def lag_fn():
                rep = self.peek("replica")
                if rep is not None and self.is_replica():
                    try:
                        return float(rep.lag_s())
                    except Exception:
                        return 0.0
                return 0.0

            def fence_fn(epoch):
                # primaries fence their own store on (re)acquire; a
                # promoted replica's new store was already fenced inside
                # promote_to_primary before this runs
                store = self.peek("manager")
                if store is not None and hasattr(store, "fence_epoch"):
                    store.fence_epoch = int(epoch)

            return FleetController(
                self._fleet_lease_store(),
                node_id,
                advertise_url=str(
                    self._config.get("serve.fleet_advertise_url", "") or ""
                ),
                role=role,
                lease_ttl_s=float(
                    self._config.get("serve.fleet_lease_ttl_s", 2.0)
                ),
                heartbeat_s=float(
                    self._config.get("serve.fleet_heartbeat_s", 0.5)
                ),
                promotion_grace_s=float(
                    self._config.get("serve.fleet_promotion_grace_s", 0.5)
                ),
                lag_budget_s=float(
                    self._config.get("serve.replica_staleness_budget_s", 30.0)
                ),
                watermark_fn=watermark_fn,
                lag_fn=lag_fn,
                on_promote=self.promote_to_primary,
                fence_fn=fence_fn,
                stats=getattr(self.peek("permission_engine"), "maintenance", None),
            )

        return self._memo("fleet", build)

    def promote_to_primary(self, epoch: int) -> None:
        """The durable-watermark handoff: called by the fleet controller
        when this replica wins the lease at ``epoch``. The replica's
        applied watermark IS a store watermark over the same tuple
        history, so the device snapshot stays valid — only the backing
        store swaps:

        1. build a direct SQL store from the dsn, fenced at the won
           epoch BEFORE any write can route through it,
        2. install it as the ``manager`` singleton and into the engine
           (``set_store`` — no snapshot rebuild; the next maintenance
           pass catches up via the delta path),
        3. retire the replication feed (the primary it followed is
           dead) and detach it from health derivation,
        4. flip ``_promoted`` so is_replica() — and with it the write
           coordinator, REST/gRPC write refusals, and the 412 gate
           branch — reads primary from then on.

        Idempotent: the controller's install-retry path (crash between
        winning and installing) re-runs this at the same epoch."""
        with self._lock:
            if self._promoted:
                store = self._singletons.get("manager")
                if store is not None and hasattr(store, "fence_epoch"):
                    store.fence_epoch = int(epoch)
                return
            new_store = self._build_direct_store()
            new_store.fence_epoch = int(epoch)
            old_store = self._singletons.get("manager")
            self._singletons["manager"] = new_store
            self._promoted = True
        self.logger().warning(
            "fleet promotion: serving as primary at epoch %d "
            "(store handoff at watermark %s)",
            int(epoch), new_store.watermark(),
        )
        engine = self.peek("permission_engine")
        if engine is not None and hasattr(engine, "set_store"):
            engine.set_store(new_store)
        # the replication feed followed a primary that no longer owns
        # the lease: stop it without blocking the promotion path (its
        # threads are daemons; a hung HTTP read dies with them)
        rep = None
        with self._lock:
            rep = self._singletons.pop("replica", None)
        if rep is not None:
            try:
                rep.stop(timeout=0.5)
            except Exception:
                self.logger().warning(
                    "replica controller stop failed during promotion",
                    exc_info=True,
                )
        monitor = self.peek("health_monitor")
        if monitor is not None:
            monitor.set_replica(None)
        # the watch hub polled the old replica store, which stops
        # advancing now: close it so chained watchers reconnect and the
        # next subscriber gets a hub over the new store
        hub = None
        with self._lock:
            hub = self._singletons.pop("watch_hub", None)
        if hub is not None:
            try:
                hub.close()
            except Exception:
                self.logger().warning(
                    "watch hub close failed during promotion", exc_info=True
                )
        if old_store is not None and old_store is not new_store:
            closer = getattr(old_store, "close", None)
            if closer is not None:
                try:
                    closer()
                except Exception:
                    self.logger().warning(
                        "old replica store close failed during promotion",
                        exc_info=True,
                    )

    def reshard_coordinator(self):
        """The live shard split/merge coordinator
        (keto_tpu/fleet/reshard.py): builds a complete engine at the
        target graph-mesh width while the current engine keeps serving,
        then installs it atomically under the registry lock."""

        def build():
            from keto_tpu.fleet.reshard import ReshardCoordinator

            def current():
                # shard_count is a property on the TPU engine (0 = not
                # sharded) and absent on the oracle fallback
                eng = self.peek("permission_engine")
                val = getattr(eng, "shard_count", None)
                if callable(val):
                    val = val()
                try:
                    return max(1, int(val)) if val is not None else 1
                except (TypeError, ValueError):
                    return 1

            def build_new(target):
                eng = self._build_permission_engine(
                    mesh_graph_override=(None if target <= 1 else target)
                )
                # warm the snapshot BEFORE install so the handoff swaps
                # one serving engine for another, not for a cold build
                if hasattr(eng, "snapshot"):
                    eng.snapshot()
                return eng

            return ReshardCoordinator(
                build_new, self._install_resharded_engine, current_fn=current
            )

        return self._memo("reshard", build)

    def _install_resharded_engine(self, new_engine, target: int) -> None:
        """Swap the serving engine for the resharded one. In-flight
        rounds finish on the old engine (the batcher reads its engine
        attribute per dispatch); the old engine closes only after the
        batcher drains, off this thread."""
        with self._lock:
            old = self._singletons.get("permission_engine")
            self._singletons["permission_engine"] = new_engine
            # lazily rebuilt over the new engine on next use
            self._singletons.pop("expand_engine", None)
            self._singletons.pop("list_engine", None)
        batcher = self.peek("check_batcher")
        if batcher is not None and hasattr(batcher, "set_engine"):
            batcher.set_engine(new_engine)
        monitor = self.peek("health_monitor")
        if monitor is not None and hasattr(monitor, "set_engine"):
            monitor.set_engine(new_engine)
        if old is not None and old is not new_engine and hasattr(old, "close"):
            def close_old():
                try:
                    if batcher is not None and hasattr(batcher, "drain"):
                        batcher.drain(30.0)
                    old.close()
                except Exception:
                    self.logger().warning(
                        "old engine close failed after reshard", exc_info=True
                    )

            threading.Thread(
                target=close_old, name="reshard-engine-close", daemon=True
            ).start()

    def autoscaler(self):
        """The SLO-burn autoscale loop (keto_tpu/fleet/autoscale.py), or
        None without ``serve.fleet_autoscale_enabled``. Advisory unless
        a spawner is attached (the daemon wires one when launched with a
        replica argv template; tests attach their own)."""
        if not bool(self._config.get("serve.fleet_autoscale_enabled", False)):
            return None

        def build():
            from keto_tpu.fleet.autoscale import Autoscaler

            def signals():
                # one broken component must not blind the others: each
                # signal reads under its own guard, logging the failure
                # (a stuck-at-default signal biases decisions, silently)
                out = {
                    "availability_burn_rate": 0.0,
                    "latency_burn_rate": 0.0,
                    "queue_depth_ratio": 0.0,
                    "hbm_rung": 0,
                    "replica_lag_s": 0.0,
                }
                slo = self.peek("slo")
                if slo is not None:
                    try:
                        rep = slo.to_json()
                        burns = [
                            float(w.get("availability_burn_rate", 0) or 0)
                            for w in rep.get("windows", [])
                        ]
                        lat = [
                            float(w.get("latency_burn_rate", 0) or 0)
                            for w in rep.get("windows", [])
                        ]
                        if burns:
                            out["availability_burn_rate"] = max(burns)
                        if lat:
                            out["latency_burn_rate"] = max(lat)
                    except Exception:
                        self.logger().warning(
                            "autoscale burn-rate signal read failed",
                            exc_info=True,
                        )
                b = self.peek("check_batcher")
                if b is not None:
                    depth = float(getattr(b, "queue_depth", 0) or 0)
                    cap = float(getattr(b, "max_pending", 0) or 0)
                    if cap > 0:
                        out["queue_depth_ratio"] = depth / cap
                gov = getattr(self.peek("permission_engine"), "hbm", None)
                if gov is not None:
                    try:
                        out["hbm_rung"] = int(gov.snapshot().get("rung", 0) or 0)
                    except Exception:
                        self.logger().warning(
                            "autoscale hbm-rung signal read failed",
                            exc_info=True,
                        )
                rep = self.peek("replica")
                if rep is not None:
                    try:
                        out["replica_lag_s"] = float(rep.lag_s())
                    except Exception:
                        self.logger().warning(
                            "autoscale replica-lag signal read failed",
                            exc_info=True,
                        )
                return out

            return Autoscaler(
                signals,
                min_replicas=int(
                    self._config.get("serve.fleet_min_replicas", 0)
                ),
                max_replicas=int(
                    self._config.get("serve.fleet_max_replicas", 4)
                ),
                sustain_s=float(
                    self._config.get("serve.fleet_scale_sustain_s", 5.0)
                ),
                cooldown_s=float(
                    self._config.get("serve.fleet_scale_cooldown_s", 30.0)
                ),
            )

        return self._memo("autoscaler", build)

    # -- engines -------------------------------------------------------------

    def permission_engine(self):
        """The check engine: TPU snapshot engine when the store supports it
        and config allows, else the recursive oracle."""
        return self._memo("permission_engine", self._build_permission_engine)

    def _build_permission_engine(self, mesh_graph_override: Optional[int] = None):
        """Construct a check engine from config. ``mesh_graph_override``
        replaces ``serve.mesh_graph`` — the live-reshard seam
        (keto_tpu/fleet/reshard.py): the coordinator builds a complete
        engine at the target shard count while the current one keeps
        serving, then installs it via _install_resharded_engine."""

        def build():
            backend = self._config.get("engine.backend", "auto")
            store = self.relation_tuple_manager()
            if backend != "oracle" and hasattr(store, "snapshot_rows"):
                self._device_info = self._select_device(backend)
                # persistent XLA compilation cache: compiled kernel
                # geometries survive restarts, so the boot warmup
                # (Daemon._warm_snapshot → engine.warm_compile) hits disk
                # instead of recompiling the whole width ladder
                from keto_tpu.driver import compile_cache
                from keto_tpu.x import profiling

                # both before the first kernel compiles: what compiling
                # costs is counted from the runtime, and a profiler
                # session — whoever opens it — is seen by the dispatch
                # thread's state clock
                compile_cache.install_listener()
                profiling.install_trace_hook()
                try:
                    cc_dir = compile_cache.configure(
                        str(self._config.get("serve.compile_cache_dir", "") or ""),
                        allow_default=self._use_default_compile_cache,
                    )
                    if cc_dir:
                        self.logger().info(
                            "persistent compilation cache: %s", cc_dir
                        )
                except Exception:
                    self.logger().warning(
                        "persistent compilation cache unavailable; "
                        "continuing without it", exc_info=True,
                    )
                from keto_tpu.check.tpu_engine import TpuCheckEngine

                # multi-chip serving (keto_tpu/parallel/sharded.py): a
                # (graph, data) mesh over the local devices; graph > 1
                # partitions the CSR/bitmap/label rows into row-range
                # shards served by the explicit shard_map program
                # (serve.mesh_sharded=false keeps the legacy GSPMD path)
                mesh = None
                mesh_sharded = False
                mesh_graph = int(self._config.get("serve.mesh_graph", 1))
                if mesh_graph_override is not None:
                    mesh_graph = int(mesh_graph_override)
                mesh_data = int(self._config.get("serve.mesh_data", 0))
                if mesh_graph > 1 or mesh_data > 1:
                    from keto_tpu.parallel import make_mesh

                    mesh = make_mesh(
                        graph=max(1, mesh_graph),
                        data=mesh_data if mesh_data > 0 else None,
                    )
                    mesh_sharded = bool(
                        self._config.get("serve.mesh_sharded", True)
                    )
                engine = TpuCheckEngine(
                    store,
                    self.namespaces_source(),
                    mesh=mesh,
                    shard_rows=mesh is not None,
                    sharded=mesh_sharded,
                    it_cap=int(self._config.get("engine.it_cap", 4096)),
                    peel_seed_cap=float(self._config.get("engine.peel_seed_cap", 4.0)),
                    sync_rebuild_budget_s=float(
                        self._config.get("engine.sync_rebuild_budget_s", 0.25)
                    ),
                    stream_slice_target_ms=float(
                        self._config.get("serve.stream_slice_target_ms", 40.0)
                    ),
                    overlay_edge_budget=int(
                        self._config.get("serve.overlay_edge_budget", 4096)
                    ),
                    fold_segment_edges=int(
                        self._config.get("serve.fold_segment_edges", 2048)
                    ),
                    snapshot_cache_dir=(
                        str(self._config.get("serve.snapshot_cache_dir", "") or "")
                        or None
                    ),
                    degraded_probe_s=float(
                        self._config.get("serve.degraded_probe_s", 5.0)
                    ),
                    labels_enabled=bool(
                        self._config.get("serve.labels_enabled", True)
                    ),
                    labels_max_width=int(
                        self._config.get("serve.labels_max_width", 64)
                    ),
                    labels_landmarks=int(
                        self._config.get("serve.labels_landmarks", 0)
                    ),
                    labels_device_build=bool(
                        self._config.get("serve.labels_device_build", True)
                    ),
                    labels_min_gain=float(
                        self._config.get("serve.labels_min_gain", 0.0)
                    ),
                    labels_batch=int(
                        self._config.get("serve.labels_batch", 64)
                    ),
                    labels_device_min_edges=int(
                        self._config.get("serve.labels_device_min_edges", 65536)
                    ),
                    hbm_budget_bytes=int(
                        self._config.get("serve.hbm_budget_bytes", 0)
                    ),
                    audit_sample_rate=float(
                        self._config.get("serve.audit_sample_rate", 0.0)
                    ),
                    device_build_enabled=bool(
                        self._config.get("serve.device_build_enabled", True)
                    ),
                    build_chunk_rows=int(
                        self._config.get("serve.build_chunk_rows", 262144)
                    ),
                    stream_tail_ratio=float(
                        self._config.get("serve.stream_tail_ratio", 5.0)
                    ),
                )
                # mirror per-slice service times into /metrics — the same
                # numbers the adaptive width controller steers by
                engine.stream_slice_stats.attach_histogram(
                    self.metrics().histogram(
                        "keto_engine_stream_slice_duration_seconds",
                        "Per-slice device service time of the streaming "
                        "check pipeline (what StreamSliceController steers by).",
                    )
                )
                # mirror build-pipeline phase durations the same way —
                # the phases bench grades are the phases operators scrape
                engine.build_progress.attach_histogram(
                    self.metrics().histogram(
                        "keto_build_phase_duration_seconds",
                        "Wall time per streaming-build pipeline phase "
                        "(scan / intern / rewrites / device_build / labels / "
                        "cache_save), one histogram series per phase; "
                        "rewrites (the expansion of userset rewrites into "
                        "edges) only under a schema that has some.",
                        ("phase",),
                        buckets=(0.01, 0.05, 0.25, 1.0, 5.0, 15.0, 60.0,
                                 300.0, 1200.0),
                    )
                )
                return engine
            return CheckEngine(store, namespaces=self.namespaces_source())

        return build()

    def _select_device(self, backend: str) -> dict:
        """Name the devices the engine will run on and hold
        ``engine.backend: tpu`` to its word: on any other platform that
        setting is a boot error, not a quiet CPU-backend deployment.
        ``auto`` takes whatever JAX found."""
        import jax

        devices = jax.devices()
        info = {
            "platform": devices[0].platform,
            "device_kind": devices[0].device_kind,
            "count": len(devices),
        }
        if backend == "tpu" and info["platform"] != "tpu":
            raise RuntimeError(
                f'engine.backend is "tpu" but JAX found platform '
                f'{info["platform"]!r} ({info["count"]} x {info["device_kind"]}); '
                f'refusing to serve checks from it. Use engine.backend: '
                f'"auto" to accept whatever backend JAX selects.'
            )
        self.logger().info(
            "check engine devices: platform=%s device_kind=%s count=%d "
            "(engine.backend=%s)",
            info["platform"], info["device_kind"], info["count"], backend,
        )
        return info

    def expand_depth(self, requested: int) -> int:
        """Clamp a request's max-depth to the configured global cap
        (``limit.max_read_depth``): a request asking for 0 — or more than
        the cap — gets the cap."""
        cap = int(self._config.get("limit.max_read_depth", 5))
        return cap if requested <= 0 or requested > cap else requested

    def expand_engine(self):
        """The expand engine: snapshot-backed (sharing the TPU check
        engine's device snapshots and freshness semantics) when the check
        engine is the TPU one, else the Manager-backed recursion."""

        def build():
            check = self.permission_engine()
            if hasattr(check, "snapshot"):
                from keto_tpu.expand.tpu_engine import SnapshotExpandEngine

                return SnapshotExpandEngine(check, self.namespaces_source())
            return ExpandEngine(
                self.relation_tuple_manager(), namespaces=self.namespaces_source()
            )

        return self._memo("expand_engine", build)

    def explain_enabled(self) -> bool:
        return bool(self._config.get("serve.explain_enabled", True))

    def decision_log(self):
        """The durable decision-audit log (keto_tpu/explain/decision_log.py),
        or None when ``serve.decision_log_dir`` is unset — the hot path's
        entire cost in that case is this None check."""
        d = str(self._config.get("serve.decision_log_dir", "") or "")
        if not d:
            return None

        def build():
            from keto_tpu.explain.decision_log import DecisionLog

            return DecisionLog(
                d,
                sample=float(self._config.get("serve.decision_log_sample", 0.0)),
                segment_bytes=int(
                    self._config.get("serve.decision_log_segment_bytes", 1 << 20)
                ),
                retention=int(self._config.get("serve.decision_log_retention", 8)),
            )

        return self._memo("decision_log", build)

    def explain_engine(self):
        """The decision-provenance engine (keto_tpu/explain): decides
        through the serving check engine (so the reported route is the one
        that actually answered), back-traces the witness against the
        Manager, verifies it edge-by-edge, and records to the decision
        log. Verify failures — each one a bug in the producing route —
        fire the flight recorder with the failing witness attached."""

        def build():
            from keto_tpu.explain.engine import ExplainEngine

            def on_verify_failure(note):
                fr = self.flight_recorder()
                if fr is not None:
                    fr.trigger("witness-verify-failure", detail=note.get("tuple", ""))

            return ExplainEngine(
                self.permission_engine(),
                self.relation_tuple_manager(),
                decision_log=self.decision_log(),
                on_verify_failure=on_verify_failure,
            )

        return self._memo("explain_engine", build)

    def list_engine(self):
        """The reverse-query engine (keto_tpu/list/): snapshot-backed
        (sharing the TPU check engine's device snapshots, transposed
        layouts, and snaptoken semantics) when the check engine is the
        TPU one, else the Manager-backed oracle."""

        def build():
            check = self.permission_engine()
            if hasattr(check, "snapshot"):
                from keto_tpu.list.tpu_engine import SnapshotListEngine

                return SnapshotListEngine(
                    check,
                    self.namespaces_source(),
                    cache_entries=int(
                        self._config.get("serve.list_cache_entries", 64)
                    ),
                )
            from keto_tpu.list.engine import ListEngine

            return ListEngine(
                self.relation_tuple_manager(), namespaces=self.namespaces_source()
            )

        return self._memo("list_engine", build)

    def watch_hub(self):
        """The Watch changefeed hub (keto_tpu/list/watch.py) over the
        tuple store's durable change log."""
        from keto_tpu.list.watch import WatchHub

        return self._memo(
            "watch_hub",
            lambda: WatchHub(
                self.relation_tuple_manager(),
                poll_s=float(self._config.get("serve.watch_poll_ms", 100.0)) / 1e3,
                max_streams=int(self._config.get("serve.watch_max_streams", 64)),
            ),
        )

    def check_batcher(self) -> CheckBatcher:
        def build():
            engine = self.permission_engine()
            batch_size = int(self._config.get("engine.batch_size", 4096))
            max_pending = 8 * batch_size
            # adaptive admission control: AIMD over the batch lane, keyed
            # off the SAME slice service-time stats the stream width
            # controller steers by, plus the batcher's own queue-delay
            # estimate (keto_tpu/driver/admission.py)
            admission = None
            if bool(self._config.get("serve.admission_enabled", True)):
                from keto_tpu.driver.admission import AdmissionController

                budget = float(
                    self._config.get("serve.admission_latency_budget_ms", 0.0)
                )
                admission = AdmissionController(
                    stats=getattr(engine, "stream_slice_stats", None),
                    target_ms=float(
                        self._config.get("serve.stream_slice_target_ms", 40.0)
                    ),
                    budget_ms=budget or None,
                    min_window=int(
                        self._config.get("serve.admission_min_window", 64)
                    ),
                    max_window=max_pending,
                )
                # declared with the registry (metrics()); None when
                # metrics are off
                admission.attach_queue_delay_histogram(
                    self.metrics().family("keto_admission_queue_delay_seconds")
                )
            b = CheckBatcher(
                engine,
                batch_size=batch_size,
                window_ms=float(self._config.get("engine.batch_window_ms", 1.0)),
                max_pending=max_pending,
                # serving processes shed on a full queue (429 /
                # RESOURCE_EXHAUSTED) instead of letting callers block
                # into their own timeouts — backpressure with an answer
                shed_on_full=bool(self._config.get("serve.shed_on_full", True)),
                interactive_max_tuples=int(
                    self._config.get("serve.interactive_max_tuples", 16)
                ),
                batch_sub_slice=int(self._config.get("serve.batch_sub_slice", 1024)),
                admission=admission,
                # every shed response names its tenant (X-Keto-Tenant) —
                # requests without the header belong to the default tenant
                tenant="default",
            )
            pool = self.peek("tenants")
            if pool is not None:
                b.on_shed = pool.note_shed
            # declared with the registry (x/timeline.py
            # dispatch_clock_metrics); None when metrics are off
            b.clock.long_stays = self.metrics().family("keto_dispatch_long_stay_seconds")
            b.start()
            return b

        return self._memo("check_batcher", build)

    # -- multi-tenant serving (keto_tpu/driver/tenants.py) --------------------

    def tenant_pool(self):
        """The keyed TenantPool behind the ``X-Keto-Tenant`` header:
        per-tenant engine/batcher/admission/watch contexts, the
        cross-tenant residency ledger with its tenant-LRU, and the
        per-tenant shed-spike anomaly tracker. Built lazily on the first
        non-default tenant request — a process that never sees the header
        never constructs it."""

        def build():
            from keto_tpu.driver.tenants import TenantPool

            pool = TenantPool(
                self,
                max_resident=int(
                    self._config.get("serve.tenant_max_resident", 8)
                ),
                quota_share=float(
                    self._config.get("serve.tenant_quota_share", 0.25)
                ),
                backend=str(
                    self._config.get("serve.tenant_backend", "oracle")
                ),
                shed_spike=int(
                    self._config.get("serve.tenant_shed_spike", 50)
                ),
            )
            # cross-tenant residency arbitration: the default engine's
            # governor gets a tenant-LRU rung BELOW its own ladder, so
            # real device pressure reclaims cold tenants' engines whole
            # (they fault back in via the segmented snapcache)
            gov = getattr(self.permission_engine(), "hbm", None)
            if gov is not None:
                gov.append_rung("tenant-lru", pool.evict_coldest, lambda: None)
            fr = self.flight_recorder()
            if fr is not None:
                # a per-tenant shed-rate spike is an anomaly in its own
                # right: capture the bundle while the storm is visible
                pool.set_shed_trigger(
                    lambda tenant, detail: fr.trigger(
                        "tenant-shed-spike", detail, defer_s=0.2
                    )
                )
            batcher = self.peek("check_batcher")
            if batcher is not None:
                # the default tenant's sheds feed the same spike tracker
                batcher.on_shed = pool.note_shed
            return pool

        return self._memo("tenants", build)

    def build_tenant_engine(self, store, tenant: str):
        """Engine factory for TenantPool fault-ins. ``serve.tenant_backend``:
        ``oracle`` (default) serves the tenant from the recursive CPU
        reference engine — zero device footprint, bit-identical answers
        by construction, the right shape for thousands of mostly-cold
        tenants; ``device`` builds a full TpuCheckEngine over the
        tenant's store view with a per-tenant snapcache directory (the
        sub-500ms cold fault-in path); ``auto`` follows the default
        engine's kind."""
        backend = str(self._config.get("serve.tenant_backend", "oracle"))
        if backend == "auto":
            backend = (
                "device"
                if hasattr(self.peek("permission_engine"), "snapshot")
                else "oracle"
            )
        if backend == "device" and hasattr(store, "snapshot_rows"):
            import os

            from keto_tpu.check.tpu_engine import TpuCheckEngine

            cache_root = str(
                self._config.get("serve.snapshot_cache_dir", "") or ""
            )
            return TpuCheckEngine(
                store,
                self.namespaces_source(),
                sync_rebuild_budget_s=float(
                    self._config.get("engine.sync_rebuild_budget_s", 0.25)
                ),
                overlay_edge_budget=int(
                    self._config.get("serve.overlay_edge_budget", 4096)
                ),
                # each tenant caches its snapshots under its own subdir:
                # eviction closes the engine, the on-disk segments stay,
                # and the next touch faults in from cache, not a rebuild
                snapshot_cache_dir=(
                    os.path.join(cache_root, "tenants", tenant)
                    if cache_root
                    else None
                ),
                labels_enabled=bool(
                    self._config.get("serve.labels_enabled", True)
                ),
                # per-tenant governor budget (0 = auto). The POOL bounds
                # how many such engines exist at once; this bounds each.
                hbm_budget_bytes=int(
                    self._config.get("serve.tenant_hbm_budget_bytes", 0)
                ),
                audit_sample_rate=float(
                    self._config.get("serve.audit_sample_rate", 0.0)
                ),
            )
        return CheckEngine(store)

    def build_tenant_batcher(self, engine, tenant: str) -> CheckBatcher:
        """Per-tenant CheckBatcher + AIMD admission — the quota/fairness
        half of noisy-neighbor isolation. Each tenant's queue bound is
        ``serve.tenant_quota_share`` of the global bound, and its
        admission controller tracks ITS consecutive overloaded ticks, so
        Retry-After scales per tenant (no cross-tenant backoff bleed)."""
        batch_size = int(self._config.get("engine.batch_size", 4096))
        share = min(
            1.0,
            max(0.01, float(self._config.get("serve.tenant_quota_share", 0.25))),
        )
        max_pending = max(64, int(8 * batch_size * share))
        admission = None
        if bool(self._config.get("serve.admission_enabled", True)):
            from keto_tpu.driver.admission import AdmissionController

            budget = float(
                self._config.get("serve.admission_latency_budget_ms", 0.0)
            )
            admission = AdmissionController(
                stats=None,  # tenant rounds are timed by observe_round
                target_ms=float(
                    self._config.get("serve.stream_slice_target_ms", 40.0)
                ),
                budget_ms=budget or None,
                min_window=int(
                    self._config.get("serve.admission_min_window", 64)
                ),
                max_window=max_pending,
            )
        b = CheckBatcher(
            engine,
            batch_size=batch_size,
            window_ms=float(self._config.get("engine.batch_window_ms", 1.0)),
            max_pending=max_pending,
            shed_on_full=bool(self._config.get("serve.shed_on_full", True)),
            interactive_max_tuples=int(
                self._config.get("serve.interactive_max_tuples", 16)
            ),
            batch_sub_slice=int(self._config.get("serve.batch_sub_slice", 1024)),
            admission=admission,
            tenant=tenant,
        )
        b.on_shed = self.tenant_pool().note_shed
        b.start()
        return b

    def health_monitor(self):
        """The serving health state machine (keto_tpu/driver/health.py):
        REST ``/health/ready``, gRPC ``grpc.health.v1``, and operator
        introspection all read the same derived state."""
        from keto_tpu.driver.health import HealthMonitor

        return self._memo(
            "health_monitor",
            lambda: HealthMonitor(
                self.permission_engine(),
                staleness_budget_s=float(
                    self._config.get("serve.staleness_budget_s", 60.0)
                ),
                # replica mode: feed lag / primary loss past the budget
                # reports DEGRADED(replication_lag); pre-bootstrap reads
                # as STARTING (keto_tpu/replica/controller.py)
                replica=self.replica_controller(),
            ),
        )

    # -- request timelines / flight recorder / SLOs ---------------------------

    def timeline_recorder(self):
        """The per-request timeline recorder (keto_tpu/x/timeline.py):
        REST/gRPC begin+finish timelines around every non-health
        request, the batcher/engine stamp stages through the context
        variable, ``GET /debug/requests`` reads the ring. Disabled
        (``serve.timeline_enabled: false``) it hands out None timelines
        and every stamp site short-circuits."""

        def build():
            from keto_tpu.x.timeline import TimelineRecorder

            rec = TimelineRecorder(
                capacity=int(self._config.get("serve.timeline_ring", 512)),
                enabled=bool(self._config.get("serve.timeline_enabled", True)),
            )
            rec.set_tracer(self.tracer())
            rec.attach_stage_histogram(
                self.metrics().histogram(
                    "keto_timeline_stage_duration_seconds",
                    "Per-request time attributed to each pipeline stage "
                    "(admit/pack/dispatch/device/land/deliver, from the "
                    "request timelines); slowest sample per stage carries "
                    "a trace_id exemplar.",
                    ("stage",),
                )
            )
            return rec

        return self._memo("timeline", build)

    def slo_engine(self):
        """The SLO engine (keto_tpu/x/slo.py): availability + latency
        burn rates over the live request counters, multi-window, served
        at ``GET /slo`` and scraped as ``keto_slo_*``."""

        def build():
            from keto_tpu.x.slo import SloEngine

            return SloEngine(
                self.metrics(),
                availability_objective=float(
                    self._config.get("serve.slo_availability_objective", 0.999)
                ),
                latency_objective_ms=float(
                    self._config.get("serve.slo_latency_objective_ms", 250.0)
                ),
                latency_objective_ratio=float(
                    self._config.get("serve.slo_latency_objective_ratio", 0.99)
                ),
            )

        return self._memo("slo", build)

    def flight_recorder(self):
        """The anomaly flight recorder (keto_tpu/x/flightrec.py), or
        None without ``serve.debug_bundle_dir``. ``wire_flight_recorder``
        attaches its triggers to the live components."""
        bundle_dir = str(self._config.get("serve.debug_bundle_dir", "") or "")
        if not bundle_dir:
            return None

        def build():
            from keto_tpu.x.flightrec import FlightRecorder

            return FlightRecorder(
                bundle_dir,
                collect=self._flightrec_collect,
                max_bundles=int(self._config.get("serve.debug_bundle_max", 8)),
                min_interval_s=float(
                    self._config.get("serve.debug_bundle_min_interval_s", 30.0)
                ),
                max_bytes=int(
                    self._config.get("serve.debug_bundle_max_bytes", 4 << 20)
                ),
                version=VERSION,
            )

        return self._memo("flightrec", build)

    def wire_flight_recorder(self) -> None:
        """Attach the flight recorder's anomaly triggers: health
        transitions into DEGRADED/NOT_SERVING (which also covers audit
        mismatches — they surface as a DEGRADED transition), contained
        device OOMs, and lock-watchdog trips. Called by the daemon after
        the serving components exist; a no-op without a bundle dir."""
        fr = self.flight_recorder()
        if fr is None:
            return
        from keto_tpu.driver.health import HealthState

        def on_transition(state, reason):
            if state in (HealthState.DEGRADED, HealthState.NOT_SERVING):
                fr.trigger(f"health-{state.value}", reason)

        self.health_monitor().add_listener(on_transition)
        gov = getattr(self.permission_engine(), "hbm", None)
        if gov is not None:
            # OOMs are detected MID-request: defer briefly so the
            # triggering request's finished timeline is in the bundle
            gov.on_oom = lambda what: fr.trigger("oom", what, defer_s=0.3)
        from keto_tpu.x import lockwatch

        if lockwatch.installed():
            lockwatch.add_trip_listener(
                lambda trip: fr.trigger("watchdog", str(trip.get("lock_site", "")))
            )

    def _flightrec_collect(self) -> dict:
        """The flight recorder's bundle sections, every one gathered
        under its own guard so a broken component cannot suppress the
        evidence from the rest."""
        sections: dict = {}

        def sec(name, fn):
            try:
                sections[name] = fn()
            except Exception as e:
                sections[name] = {"error": repr(e)}

        rec = self.peek("timeline")
        if rec is not None:
            sec("timelines", lambda: rec.snapshot(recent=100, slowest=20))
        monitor = self.peek("health_monitor")
        if monitor is not None:
            sec("health", monitor.snapshot)
        gov = getattr(self.peek("permission_engine"), "hbm", None)
        if gov is not None:
            sec("hbm", gov.snapshot)
        batcher = self.peek("check_batcher")
        if batcher is not None:
            def batcher_state():
                adm = batcher.admission
                return {
                    "queue_depth": batcher.queue_depth,
                    "lane_depths": batcher.lane_depths,
                    "inflight": batcher.inflight,
                    "shed_count": batcher.shed_count,
                    "shed_by_lane": dict(batcher.shed_by_lane),
                    "admission_shed_count": batcher.admission_shed_count,
                    "deadline_drop_count": batcher.deadline_drop_count,
                    "admission": None if adm is None else {
                        "window": getattr(adm, "window", None),
                        "budget_ms": getattr(adm, "budget_ms", None),
                        "last_p99_ms": getattr(adm, "last_p99_ms", None),
                    },
                }

            sec("batcher", batcher_state)
        m = self.peek("metrics")
        if m is not None:
            sec("metrics", m.render)
        from keto_tpu.x import lockwatch

        if lockwatch.installed():
            sec("lockwatch", lockwatch.report)
        hub = self.peek("watch_hub")
        if hub is not None:
            sec("watch", hub.snapshot)
        rep = self.peek("replica")
        if rep is not None:
            sec("replica", rep.snapshot)
        slo = self.peek("slo")
        if slo is not None:
            sec("slo", slo.to_json)
        pool = self.peek("tenants")
        if pool is not None:
            # noisy-neighbor forensics: per-tenant residency, shed
            # totals, spike counts, and degradation reasons — who was
            # storming and who paid, at the moment of anomaly
            sec("tenants", pool.snapshot)
        ex = self.peek("explain_engine")
        if ex is not None and ex.recent_failures:
            # witnesses that failed edge-by-edge verification — each one
            # is a bug in the producing route; the failing path is the
            # evidence triage starts from
            sec("explain", lambda: {
                "verify_failures": ex.verify_failures,
                "recent": list(ex.recent_failures),
            })
        eng = self.peek("permission_engine")
        divs = getattr(eng, "audit_divergences", None)
        if divs:
            # shadow-parity divergences WITH both witnesses (device
            # route's vs the CPU oracle's) — triage starts from the
            # disagreeing edge, not a bare mismatch counter
            sec("audit_divergences", lambda: list(divs))
        sections["config"] = {
            "role": str(self._config.get("serve.role", "primary")),
            "version": VERSION,
        }
        return sections

    # -- observability -------------------------------------------------------

    def metrics(self):
        """The process-wide MetricsRegistry (keto_tpu/x/metrics.py),
        bridged from every existing stat sink: REST/gRPC layers record
        their request counters/histograms directly, while the batcher,
        engine maintenance, health machine, tracer, and persister are
        read through scrape-time callbacks — their hot paths never learn
        about Prometheus. ``metrics.enabled: false`` swaps in the no-op
        registry (and /metrics answers 404)."""

        def build():
            from keto_tpu.x.metrics import MetricsRegistry, NullMetricsRegistry

            if not bool(self._config.get("metrics.enabled", True)):
                return NullMetricsRegistry()
            m = MetricsRegistry()
            m.gauge(
                "keto_build_info",
                "Always 1; the version label identifies the running build.",
                ("version",),
            ).set((VERSION,), 1)

            def device_info():
                d = self._device_info
                if d is None:
                    return [(("none", "none"), 0.0)]
                return [((d["platform"], d["device_kind"]), float(d["count"]))]

            m.register_callback(
                "keto_device_info", "gauge",
                "Devices the check engine runs on as JAX reports them: "
                "platform and device_kind of the first device, valued with "
                "the device count (platform=none, 0 until a device engine "
                "is built — e.g. the oracle backend).",
                device_info, ("platform", "device_kind"),
            )
            # engine slice service times: the SAME numbers the adaptive
            # stream-width controller steers by, mirrored from the
            # engine's DurationStats (attached in permission_engine())
            m.histogram(
                "keto_engine_stream_slice_duration_seconds",
                "Per-slice device service time of the streaming check "
                "pipeline (what StreamSliceController steers by).",
            )
            # streaming-build pipeline phases (declared eagerly so a
            # scrape before the first build exposes the family; the
            # engine attaches the same instrument in permission_engine())
            m.histogram(
                "keto_build_phase_duration_seconds",
                "Wall time per streaming-build pipeline phase "
                "(scan / intern / device_build / labels / "
                "cache_save), one histogram series per phase.",
                ("phase",),
                buckets=(0.01, 0.05, 0.25, 1.0, 5.0, 15.0, 60.0,
                         300.0, 1200.0),
            )
            # request-timeline stage durations (the recorder attaches
            # the same instrument in timeline_recorder()) and the
            # replica-side replication delay — declared eagerly so every
            # role's scrape exposes the documented family set
            m.histogram(
                "keto_timeline_stage_duration_seconds",
                "Per-request time attributed to each pipeline stage "
                "(admit/pack/dispatch/device/land/deliver, from the "
                "request timelines); slowest sample per stage carries "
                "a trace_id exemplar.",
                ("stage",),
            )
            m.histogram(
                "keto_admission_queue_delay_seconds",
                "Queue delay the admission controller estimated at "
                "each evaluated tick (batch-lane backlog over its "
                "EWMA of dispatch throughput): how near the latency "
                "budget the estimate runs.",
            )
            m.histogram(
                "keto_replication_apply_delay_seconds",
                "Replica mode: wall time from the primary's commit to "
                "the change being visible through this replica's 412 "
                "gate (cross-clock; slowest sample carries the "
                "writer's trace_id exemplar).",
                buckets=(0.001, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25,
                         0.5, 1.0, 2.5, 5.0, 15.0, 60.0),
            )
            # request families are declared eagerly (the serving layers
            # re-declare idempotently) so a scrape before first traffic
            # already exposes the full documented family set
            from keto_tpu.servers.grpc_api import _expand_metrics, _request_metrics

            _request_metrics(m)
            _expand_metrics(m)
            self._register_metric_bridges(m)
            return m

        return self._memo("metrics", build)

    def _register_metric_bridges(self, m) -> None:
        """Scrape-time callbacks over already-built components. They read
        through ``peek`` so a scrape never constructs (or starts) a
        component as a side effect; families report zeros until the
        component exists."""

        def batcher_attr(attr):
            def read():
                b = self.peek("check_batcher")
                yield (), float(getattr(b, attr, 0) if b is not None else 0)

            return read

        m.register_callback(
            "keto_check_queue_depth", "gauge",
            "Coalescing check batcher: requests queued, not yet packed.",
            batcher_attr("queue_depth"),
        )
        m.register_callback(
            "keto_check_inflight", "gauge",
            "Accepted check requests whose futures have not resolved.",
            batcher_attr("inflight"),
        )
        m.register_callback(
            "keto_check_shed_total", "counter",
            "Check requests refused at the door with 429/RESOURCE_EXHAUSTED "
            "(queue at capacity).",
            batcher_attr("shed_count"),
        )
        m.register_callback(
            "keto_check_deadline_drops_total", "counter",
            "Check requests dropped before dispatch because their deadline "
            "expired (504/DEADLINE_EXCEEDED).",
            batcher_attr("deadline_drop_count"),
        )

        from keto_tpu.driver.batch import LANES

        def lane_map(attr):
            def read():
                b = self.peek("check_batcher")
                vals = getattr(b, attr, {}) if b is not None else {}
                return [((lane,), float(vals.get(lane, 0))) for lane in LANES]

            return read

        m.register_callback(
            "keto_lane_queue_depth", "gauge",
            "Priority lanes: tuples queued per lane, not yet packed into a "
            "dispatch round.",
            lane_map("lane_depths"), ("lane",),
        )
        m.register_callback(
            "keto_lane_shed_total", "counter",
            "Requests refused at the door per lane (queue full or over the "
            "admission window), 429/RESOURCE_EXHAUSTED + Retry-After.",
            lane_map("shed_by_lane"), ("lane",),
        )
        m.register_callback(
            "keto_admission_shed_total", "counter",
            "Batch-lane requests shed by the AIMD admission window "
            "specifically (subset of keto_check_shed_total).",
            batcher_attr("admission_shed_count"),
        )

        def admission_attr(attr, scale=1.0):
            def read():
                b = self.peek("check_batcher")
                a = getattr(b, "admission", None) if b is not None else None
                v = getattr(a, attr, 0) if a is not None else 0
                yield (), float(v or 0) * scale

            return read

        m.register_callback(
            "keto_admission_window", "gauge",
            "AIMD admission control: currently admitted batch-lane window "
            "(queued tuples); shrinks multiplicatively past the latency "
            "budget, recovers additively.",
            admission_attr("window"),
        )
        m.register_callback(
            "keto_admission_latency_budget_seconds", "gauge",
            "The latency budget the admission controller sheds against "
            "(serve.admission_latency_budget_ms, default 4x the slice "
            "target).",
            admission_attr("budget_ms", 1e-3),
        )
        m.register_callback(
            "keto_admission_observed_p99_seconds", "gauge",
            "Slice service-time p99 the admission controller last judged "
            "(same DurationStats the stream width controller steers by).",
            admission_attr("last_p99_ms", 1e-3),
        )

        from keto_tpu.driver.admission import SIGNALS

        def admission_decreases():
            b = self.peek("check_batcher")
            a = getattr(b, "admission", None) if b is not None else None
            by = getattr(a, "decreases_by_signal", {}) if a is not None else {}
            return [((signal,), float(by.get(signal, 0))) for signal in SIGNALS]

        m.register_callback(
            "keto_admission_decreases_total", "counter",
            "Multiplicative decreases of the admission window, by the "
            "signal that tripped: slice_p99 (slow slices), queue_delay "
            "(backlog over throughput past the budget), stall (a deep "
            "queue and nothing landing).",
            admission_decreases, ("signal",),
        )
        m.register_callback(
            "keto_admission_increases_total", "counter",
            "Additive increases of the admission window (healthy ticks).",
            admission_attr("increases"),
        )
        m.register_callback(
            "keto_admission_rate_tuples_per_second", "gauge",
            "The admission controller's EWMA of dispatch throughput over "
            "rounds — the divisor of its queue-delay estimate.",
            admission_attr("rate_tuples_per_s"),
        )

        from keto_tpu.x.timeline import DISPATCH_STATES

        def batcher_clock():
            b = self.peek("check_batcher")
            return getattr(b, "clock", None) if b is not None else None

        def clock_snapshot():
            clock = batcher_clock()
            if clock is None:
                return [0.0] * len(DISPATCH_STATES), 0
            return clock.snapshot()

        def rounds_by_overlap():
            clock = batcher_clock()
            # overlapped first: a round counted between the two reads
            # shows as one not overlapped, never as a negative count
            over = clock.overlapped if clock is not None else 0
            rounds = clock.rounds if clock is not None else 0
            return [(("true",), float(over)), (("false",), float(rounds - over))]

        m.register_callback(
            "keto_dispatch_thread_seconds_total", "counter",
            "Wall time of the batcher's one dispatch thread by state; the "
            "states are exclusive and sum to the thread's life. "
            "wait_work aside, this is what a round costs the host.",
            lambda: [
                ((state,), seconds)
                for state, seconds in zip(DISPATCH_STATES, clock_snapshot()[0])
            ],
            ("state",),
        )
        m.register_callback(
            "keto_dispatch_rounds_total", "counter",
            "Dispatch rounds the batcher's collector has taken off the "
            "lanes; overlapped=\"true\" for a round launched while another "
            "was still on the device (launched, not landed).",
            rounds_by_overlap, ("overlapped",),
        )

        def round_tuples():
            clock = batcher_clock()
            yield (), float(clock.round_tuples if clock is not None else 0)

        m.register_callback(
            "keto_dispatch_round_tuples_total", "counter",
            "Tuples the dispatch rounds took off the lanes. Over "
            "keto_dispatch_rounds_total (both values) it is the mean "
            "round's width: what the per-round cost of the dispatch "
            "thread is spread over.",
            round_tuples,
        )

        def compile_counts(i):
            def read():
                from keto_tpu.driver.compile_cache import COMPILES

                yield (), float(COMPILES.snapshot()[i])

            return read

        m.register_callback(
            "keto_compile_seconds_total", "counter",
            "Seconds in XLA backend compiles since the device engine was "
            "built (jax.monitoring; loading a program from the persistent "
            "cache counts its retrieval time).",
            compile_counts(0),
        )
        m.register_callback(
            "keto_compiles_total", "counter",
            "XLA backend compile requests (jax.monitoring), programs "
            "loaded from the persistent cache included.",
            compile_counts(1),
        )
        m.register_callback(
            "keto_compile_cache_hits_total", "counter",
            "Compile requests the persistent compilation cache answered.",
            compile_counts(2),
        )

        def device_memory():
            rows = []
            if self._device_info is not None:
                from keto_tpu.driver.hbm import device_memory_rows

                rows = device_memory_rows()
            return rows or [(("none", "in_use"), 0.0)]

        m.register_callback(
            "keto_device_memory_bytes", "gauge",
            "Device memory per local device as the runtime's "
            "memory_stats() reports it: kind is in_use, peak or limit "
            "(device=none, 0 where the backend keeps no stats).",
            device_memory, ("device", "kind"),
        )

        def maintenance_raw():
            engine = self.peek("permission_engine")
            stats = getattr(engine, "maintenance", None)
            if stats is None:
                return {}, {}, {}
            return stats.raw()

        def maintenance_events():
            counters, _, _ = maintenance_raw()
            return [((k,), float(v)) for k, v in counters.items()] or [(("none",), 0.0)]

        m.register_callback(
            "keto_maintenance_events_total", "counter",
            "Snapshot maintenance events (delta applies, compactions, "
            "rebuilds, cache saves/loads, failures), by event.",
            maintenance_events, ("event",),
        )

        def maintenance_durations(field, scale):
            def read():
                _, _, durations = maintenance_raw()
                return [
                    ((op,), float(d[field]) * scale) for op, d in durations.items()
                ] or [(("none",), 0.0)]

            return read

        m.register_callback(
            "keto_maintenance_duration_seconds_total", "counter",
            "Cumulative wall time spent in maintenance operations "
            "(compaction, rebuild, cache save/reload), by op.",
            maintenance_durations("total_ms", 1e-3), ("op",),
        )
        m.register_callback(
            "keto_maintenance_runs_total", "counter",
            "Completed maintenance operations, by op.",
            maintenance_durations("count", 1.0), ("op",),
        )

        def label_paths():
            counters, _, _ = maintenance_raw()
            return [
                (("label",), float(counters.get("label_checks", 0))),
                (("fallback",), float(counters.get("label_fallbacks", 0))),
            ]

        m.register_callback(
            "keto_label_checks_total", "counter",
            "Check queries answered by the 2-hop label fast path (path="
            "label) vs routed to the BFS kernel while labels were live "
            "(path=fallback: seeds x target rows past the pair cap, "
            "coverage gaps, self-queries, wildcard or multi-start subjects; "
            "keto_label_fallbacks_total says which).",
            label_paths, ("path",),
        )

        def label_fallback_reasons():
            counters, _, _ = maintenance_raw()
            return [
                ((reason,), float(counters.get(f"label_fallbacks_{reason}", 0)))
                for reason in ("pair_cap", "uncertifiable", "self_hit", "multi", "whole_slice")
            ]

        m.register_callback(
            "keto_label_fallbacks_total", "counter",
            "Check queries that left the label fast path for the BFS kernel, "
            "by the first cause found: pair_cap (seeds x target rows above "
            "64 pairs), uncertifiable (a pair the index cannot certify), "
            "self_hit (a start row that is the target), multi (wildcard or "
            "multi-start subject), whole_slice (none of its own: the slice's "
            "other queries made a BFS sub-batch as wide as the slice, so the "
            "whole slice rode it and the label kernel was not launched).",
            label_fallback_reasons, ("reason",),
        )

        def bfs_steps(key):
            def read():
                counters, _, _ = maintenance_raw()
                yield (), float(counters.get(key, 0))

            return read

        m.register_callback(
            "keto_check_bfs_steps_total", "counter",
            "Frontier pulls of check_step, summed over the landed BFS and "
            "hybrid slices (each slice's convergence check included); a pull "
            "is one sweep over the buckets (keto_check_sweep_slices_total).",
            bfs_steps("bfs_steps"),
        )
        m.register_callback(
            "keto_check_bfs_slices_total", "counter",
            "Landed slices that ran check_step (BFS and hybrid routes): "
            "keto_check_bfs_steps_total over this is the mean pulls a slice.",
            bfs_steps("bfs_slices"),
        )

        m.register_callback(
            "keto_check_pull_words_total", "counter",
            "Pulls of check_step times the 32-bit words of a bitmap row in "
            "the program that ran them, summed over the slices landed on one "
            "device. Times the slots a pull gathers (keto_snapshot_ell_slots, "
            "both kinds) and 4: the bytes the pulls gathered.",
            bfs_steps("bfs_pull_words"),
        )

        def pack_rows():
            counters, _, _ = maintenance_raw()
            return [
                ((side,), float(counters.get(f"pack_rows_{side}", 0)))
                for side in ("seed", "target")
            ]

        m.register_callback(
            "keto_check_pack_rows_total", "counter",
            "Device rows the host packed for check_step or the label kernel, "
            "by side of the check: seed (start rows, after the host walk) and "
            "target (the interior target, or the rows a sink's answer is "
            "gathered from; a hub sink's relay row counts the rows it holds). "
            "Over keto_check_packed_total: rows a check.",
            pack_rows, ("side",),
        )
        m.register_callback(
            "keto_check_packed_total", "counter",
            "Checks that went through pack_chunk (counted once a chunk, riders "
            "of a hybrid slice included: they come out of the chunk's own pack).",
            bfs_steps("packed_checks"),
        )

        def ell_slots():
            _, gauges, _ = maintenance_raw()
            return [
                ((kind,), float(gauges.get(f"ell_slots_{kind}", 0)))
                for kind in ("edge", "pad")
            ]

        m.register_callback(
            "keto_snapshot_ell_slots", "gauge",
            "Slots of the serving snapshot's bucketed ELL (the in-neighbour "
            "lists check_step pulls over), at its upload: edge (a real "
            "in-neighbour) and pad (a valid row's padding up to its bucket's "
            "power-of-two degree). Their sum is what one pull gathers on one "
            "device; the rows a bucket is padded with are not counted.",
            ell_slots, ("kind",),
        )

        def max_in_degree():
            _, gauges, _ = maintenance_raw()
            yield (), float(gauges.get("max_in_degree", 0))

        m.register_callback(
            "keto_snapshot_max_in_degree", "gauge",
            "Most in-neighbours any row of the serving snapshot's ELL has; "
            "above 1,024 the pull gathers that bucket in chunks.",
            max_in_degree,
        )

        def kernel_geometries():
            engine = self.peek("permission_engine")
            counts = getattr(engine, "kernel_geometry_counts", dict)()
            return [
                ((kernel, met), float(counts.get((kernel, met), 0)))
                for kernel in ("check", "label")
                for met in ("compiled", "padded_up", "inline_compile")
            ]

        m.register_callback(
            "keto_kernel_geometry_total", "counter",
            "Slices launched on the single-device path, by kernel and by how "
            "each found its program: compiled (its own sizes), padded_up "
            "(rode a larger compiled program; its own compiles behind it), "
            "inline_compile (the launch compiled it, on the dispatch thread).",
            kernel_geometries, ("kernel", "met"),
        )

        def label_coverage():
            _, gauges, _ = maintenance_raw()
            v = gauges.get("label_coverage", 0.0)
            yield (), float(v) if isinstance(v, (int, float)) else 0.0

        m.register_callback(
            "keto_label_coverage_ratio", "gauge",
            "Fraction of interior rows the 2-hop label index can certify "
            "on both sides (processed landmark, untruncated labels) — "
            "label build/patch/invalidation events ride "
            "keto_maintenance_events_total.",
            label_coverage,
        )

        def label_truncations():
            counters, _, _ = maintenance_raw()
            return [
                (("cap",), float(counters.get("label_build_truncated_cap", 0))),
                (
                    ("min_gain",),
                    float(counters.get("label_build_truncated_min_gain", 0)),
                ),
            ]

        m.register_callback(
            "keto_label_build_truncated_total", "counter",
            "Label builds that stopped before processing every interior "
            "landmark, by reason (cap: the host path's 131072 landmark "
            "safety cap; min_gain: the device build's "
            "serve.labels_min_gain early exit). Each one logs the "
            "achieved coverage ratio; uncovered deep checks fall back to "
            "the BFS kernel bit-identically, paying the depth tax the "
            "labels exist to remove.",
            label_truncations, ("reason",),
        )

        def label_patch_aborts():
            counters, _, _ = maintenance_raw()
            yield (), float(counters.get("label_patch_aborts", 0))

        m.register_callback(
            "keto_label_patch_aborts_total", "counter",
            "Incremental label patches (compaction folding overlay "
            "inserts into the index) abandoned on the visit budget — "
            "each abort schedules a full device rebuild in the same "
            "supervised maintenance pass (rides "
            "keto_maintenance_events_total as label_rebuilds). A rising "
            "rate means overlay inserts land in dense regions; raise the "
            "budget or compact more often.",
            label_patch_aborts,
        )

        # streaming slice scheduler: per-route landing counts, the
        # observed tail ratio the service-time controller guards, and
        # which pack path (native C++ vs numpy) built each chunk
        from keto_tpu.check.slice_ctrl import ROUTES as STREAM_ROUTES

        def route_slices():
            engine = self.peek("permission_engine")
            fn = getattr(engine, "route_slice_counts", None)
            counts = fn() if fn is not None else {}
            return [((r,), float(counts.get(r, 0))) for r in STREAM_ROUTES]

        m.register_callback(
            "keto_stream_route_slices_total", "counter",
            "Streaming check slices landed, by answering route: label "
            "(intersection kernel only), hybrid (label + BFS sub-batch), "
            "bfs, host (no device work), cpu (degraded fallback).",
            route_slices, ("route",),
        )

        def stream_tail_ratio():
            engine = self.peek("permission_engine")
            stats = getattr(engine, "stream_slice_stats", None)
            snap = stats.snapshot() if stats is not None else None
            if not snap or not snap.get("p50_ms"):
                yield (), 0.0
            else:
                yield (), float(snap["p99_ms"]) / float(snap["p50_ms"])

        m.register_callback(
            "keto_stream_tail_ratio", "gauge",
            "Observed per-slice service-time p99/p50 ratio over the "
            "engine's sliding window — the number the slice controller's "
            "tail guard (serve.stream_tail_ratio) steers and the "
            "tail-smoke CI gate asserts.",
            stream_tail_ratio,
        )

        def native_pack_paths():
            from keto_tpu.check.native_pack import COUNTERS

            return [((p,), float(COUNTERS.get(p, 0))) for p in ("fused", "native", "numpy")]

        m.register_callback(
            "keto_native_pack_chunks_total", "counter",
            "Check chunks packed per path (native/pack.cpp): fused (the label "
            "route's walk, routing and pairing as one GIL-released pass), "
            "native (pack_chunk with its host walk and sink gather native: "
            "the BFS route, and label-route chunks the fused pass declined "
            "for multi or mesh) vs numpy (library absent/disabled, or the "
            "snapshot carries host-visible overlay state).",
            native_pack_paths, ("path",),
        )

        def pack_declines():
            counters, _, _ = maintenance_raw()
            return [
                ((reason,), float(counters.get(f"pack_declines_{reason}", 0)))
                for reason in ("no_library", "mesh", "overlay", "multi")
            ]

        m.register_callback(
            "keto_check_pack_declines_total", "counter",
            "Label-route chunks the fused native pass did not pack, once a "
            "chunk by the first cause found: no_library (absent, stale or "
            "disabled), mesh (the sharded kernels route entries themselves), "
            "overlay (host-visible overlay state: tombstones, overlay "
            "adjacency or sink in-edges), multi (a wildcard or multi-start "
            "query in the chunk). Such a chunk takes pack_chunk and the numpy "
            "pairing: the same answers.",
            pack_declines, ("reason",),
        )

        from keto_tpu.check.dispatch import RESOLVE_DECLINES, RESOLVE_PATHS

        def resolve_chunks():
            counters, _, _ = maintenance_raw()
            return [
                ((path,), float(counters.get(f"resolve_chunks_{path}", 0)))
                for path in RESOLVE_PATHS
            ]

        m.register_callback(
            "keto_check_resolve_chunks_total", "counter",
            "Chunks of check slices by how the dispatch thread resolved them "
            "(raw node ids to device rows, closure bytes and entry counts): "
            "native (one GIL-released pass, native/pack.cpp "
            "keto_resolve_chunk) vs numpy (the pass declined: "
            "keto_check_resolve_declines_total says why).",
            resolve_chunks, ("path",),
        )

        def resolve_declines():
            counters, _, _ = maintenance_raw()
            return [
                ((reason,), float(counters.get(f"resolve_declines_{reason}", 0)))
                for reason in RESOLVE_DECLINES
            ]

        m.register_callback(
            "keto_check_resolve_declines_total", "counter",
            "Chunks the native resolve pass did not take, once a chunk by the "
            "first cause found: no_library (absent, stale or disabled, or no "
            "native intern tables), special (a wildcard or pattern query in "
            "the chunk, or records whose framing is unsafe), overlay (overlay "
            "or extension nodes in the snapshot and a query that missed a "
            "start or a target), overlay_start (a start row past the rewrite "
            "plan's closure bytes). Such a chunk takes the numpy functions: "
            "the same rows.",
            resolve_declines, ("reason",),
        )

        # what the slice controller, the stream and the dispatch clock
        # decided, declared by the modules that count it
        from keto_tpu.check import gates as check_gates
        from keto_tpu.check.dispatch import check_sweep_metrics, stream_chunk_metrics
        from keto_tpu.check.slice_ctrl import stream_ctrl_metrics
        from keto_tpu.x.timeline import dispatch_clock_metrics

        def stream_ctrl_snapshot():
            ctrl = getattr(self.peek("permission_engine"), "stream_ctrl", None)
            return ctrl.snapshot() if ctrl is not None else None

        stream_ctrl_metrics(m, stream_ctrl_snapshot)
        stream_chunk_metrics(m, lambda: maintenance_raw()[0])
        check_sweep_metrics(m, maintenance_raw)
        dispatch_clock_metrics(m, batcher_clock)

        # /check/batch query frames (keto_tpu/check/frame.py): how often
        # the framed path engages, and how often a frame's tuples had to
        # be turned into objects after all
        from keto_tpu.check import frame as check_frame

        check_frame.check_frame_metrics(m)
        m.register_callback(
            "keto_check_frame_materialized_total", "counter",
            "Times a framed /check/batch body's tuples were decoded into "
            "objects after all (once a call or a slice, never per tuple), "
            "by why: oracle (CPU engine or degraded mode), retry, "
            "truncated (exact re-run), audit, lockstep, special (pattern "
            "queries), overlay (nodes newer than the intern tables), "
            "reload (namespaces changed after framing), wild_ns, "
            "no_native, rejected.",
            lambda: [((why,), float(n)) for why, n in sorted(check_frame.MATERIALIZED.items())],
            ("why",),
        )

        def resolve_tuples():
            counters, _, _ = maintenance_raw()
            return [
                ((where,), float(counters.get(f"resolve_tuples_{where}", 0)))
                for where in ("door", "thread")
            ]

        m.register_callback(
            "keto_check_resolve_tuples_total", "counter",
            "Check queries by where their records were resolved to raw node "
            "ids: door (on the REST pool thread that framed the /check/batch "
            "body, against the intern tables the round's snapshot still has) "
            "or thread (on the dispatch thread: lists of tuples, frames "
            "without door ids, frames whose tables a rebuild, a compaction "
            "fold or a reload has since replaced). Counted once a batch a "
            "round resolves, never per tuple.",
            resolve_tuples, ("where",),
        )

        # userset rewrites (keto_tpu/namespace/rewrites.py): all zero where
        # no namespace carries ``config.relations``
        def maintenance_counts(prefix: str, labels: tuple):
            def read():
                counters, _, _ = maintenance_raw()
                return [((lab,), float(counters.get(prefix + lab, 0))) for lab in labels]
            return read

        m.register_callback(
            "keto_check_rewrite_checks_total", "counter",
            "Checks under a rewrite schema by the closure of their (namespace, "
            "relation): rewritten (a userset rewrite is reachable from it, so "
            "derived edges may carry the answer) or plain. Counted once a "
            "resolved batch from one byte a device row, never per tuple.",
            maintenance_counts("rewrite_checks_", ("rewritten", "plain")), ("closure",),
        )
        m.register_callback(
            "keto_check_rewrite_route_total", "counter",
            "Checks with a rewritten closure by who answered: device (the "
            "union class, compiled into the snapshot's edges, and the gated "
            "checks the expansion served: keto_check_gate_checks_total) or "
            "oracle (a gated check the expansion does not reach, by "
            "keto_check_rewrite_oracle_total's reasons: the CPU oracle "
            "answers, the rest of the batch rides the device). Not a fault "
            "path: keto_maintenance_events_total{event=\"fallback_checks\"} does not move.",
            maintenance_counts("rewrite_route_", ("device", "oracle")), ("route",),
        )
        m.register_callback(
            "keto_check_rewrite_oracle_total", "counter",
            "Checks the rewrite route handed to the CPU oracle, by reason. A "
            "check whose closure reaches an intersection or an exclusion is "
            "expanded into device positions (keto_tpu/check/gates.py) unless: "
            "gated_pattern (a wildcard pattern one of whose starts reaches a "
            "gate), fanout (the start reaches more gates, or would become more "
            "positions, than the caps), cycle (an operand reaches its own "
            "gate), overlay (writes since the last build moved what the gate "
            "tables were made from, or brought the start). gated_closure and "
            "gated_unresolved stay at 0 since the expansion: the first was the "
            "whole gated class, the second a gated relation on an object no "
            "row names, which the snapshot now has a node for.",
            maintenance_counts("rewrite_oracle_", check_gates.ORACLE_REASONS),
            ("reason",),
        )
        check_gates.gate_metrics(m, maintenance_raw)

        def snapshot_gauge(key: str):
            def read():
                _, gauges, _ = maintenance_raw()
                yield (), float(gauges.get(key, 0))
            return read

        m.register_callback(
            "keto_snapshot_edges", "gauge",
            "Edges of the serving snapshot at its upload, derived ones included.",
            snapshot_gauge("snapshot_edges"),
        )

        def rewrite_edges():
            _, gauges, _ = maintenance_raw()
            return [
                ((kind,), float(gauges.get(f"rewrite_edges_{kind}", 0)))
                for kind in ("computed_userset", "tuple_to_userset")
            ]

        m.register_callback(
            "keto_snapshot_rewrite_edges", "gauge",
            "Edges of the serving snapshot that no stored row states: derived "
            "from a computed_userset (one an object a rewrite) or from a "
            "tuple_to_userset (one a tupleset row a rewrite), at its upload.",
            rewrite_edges, ("kind",),
        )

        # streaming snapshot build (keto_tpu/graph/stream_build.py): the
        # live pipeline phase plus cumulative ingest counters, read from
        # the engine's BuildProgress at scrape time — a multi-minute
        # STARTING boot is visibly alive on /metrics too
        from keto_tpu.graph.stream_build import PHASES as BUILD_PHASES

        def build_progress():
            engine = self.peek("permission_engine")
            return getattr(engine, "build_progress", None)

        def build_phase():
            bp = build_progress()
            current = bp.current_phase if bp is not None else "idle"
            return [
                ((p,), 1.0 if p == current else 0.0)
                for p in ("idle",) + BUILD_PHASES
            ]

        m.register_callback(
            "keto_build_phase", "gauge",
            "Streaming-build pipeline phase, one-hot over idle/scan/"
            "intern/device_build/labels/cache_save — nonzero off idle "
            "means a snapshot build is in flight.",
            build_phase, ("phase",),
        )

        def build_attr(attr):
            def read():
                bp = build_progress()
                yield (), float(getattr(bp, attr, 0) if bp is not None else 0)

            return read

        m.register_callback(
            "keto_build_rows_ingested_total", "counter",
            "Store rows scanned+interned by snapshot builds since boot "
            "(cumulative across rebuilds; rate it to watch a cold start "
            "make progress).",
            build_attr("rows_ingested"),
        )
        m.register_callback(
            "keto_build_edges_ingested_total", "counter",
            "Graph edges laid out by snapshot builds since boot "
            "(cumulative across rebuilds).",
            build_attr("edges_ingested"),
        )

        def overlay_gauge(key):
            def read():
                _, gauges, _ = maintenance_raw()
                v = gauges.get(key, 0)
                yield (), float(v) if isinstance(v, (int, float)) else 0.0

            return read

        m.register_callback(
            "keto_overlay_edges", "gauge",
            "Delta-overlay occupancy: pending edges + tombstones not yet "
            "folded into the base layout.",
            overlay_gauge("overlay_edges"),
        )
        m.register_callback(
            "keto_overlay_budget", "gauge",
            "serve.overlay_edge_budget: occupancy past this triggers "
            "compaction.",
            overlay_gauge("overlay_budget"),
        )

        # HBM budget governor (keto_tpu/driver/hbm.py): the ledger, the
        # eviction ladder, and OOM containment — read at scrape time from
        # the governor's own snapshot so the exposed totals reconcile
        # with the ledger by construction
        from keto_tpu.driver.hbm import RUNGS as HBM_RUNGS
        from keto_tpu.driver.hbm import TAGS as HBM_TAGS

        def hbm_snapshot():
            engine = self.peek("permission_engine")
            gov = getattr(engine, "hbm", None)
            return gov.snapshot() if gov is not None else {}

        def hbm_ledger():
            led = hbm_snapshot().get("ledger", {})
            out = [((t,), float(led.get(t, 0))) for t in HBM_TAGS]
            out += [
                ((t,), float(v)) for t, v in sorted(led.items())
                if t not in HBM_TAGS
            ]
            return out

        m.register_callback(
            "keto_hbm_resident_bytes", "gauge",
            "Device bytes resident per governor ledger tag (snapshot "
            "buckets, overlay ELL, label arrays, warm-ladder workspace); "
            "the series sums to the governor's total ledger.",
            hbm_ledger, ("tag",),
        )

        def hbm_scalar(key):
            def read():
                yield (), float(hbm_snapshot().get(key, 0) or 0)

            return read

        m.register_callback(
            "keto_hbm_budget_bytes", "gauge",
            "The enforced device-memory budget: serve.hbm_budget_bytes, "
            "or the auto value (device bytes_limit minus headroom, with "
            "a conservative fallback when the backend has no stats).",
            hbm_scalar("budget_bytes"),
        )
        m.register_callback(
            "keto_hbm_eviction_rung", "gauge",
            "Current eviction-ladder depth: 0 = full service, then "
            "staging pool dropped -> labels dropped -> reverse layouts "
            "dropped -> warm ladder trimmed -> overlay budget shrunk; "
            "refresh refusals ride keto_hbm_refusals_total.",
            hbm_scalar("rung"),
        )

        def hbm_evictions():
            by = hbm_snapshot().get("evictions_by_rung", {})
            return [((r,), float(by.get(r, 0))) for r in HBM_RUNGS]

        m.register_callback(
            "keto_hbm_evictions_total", "counter",
            "Eviction-ladder descents, by rung (labels / warm-ladder / "
            "overlay-budget) — planned pressure and real-OOM containment "
            "both count here.",
            hbm_evictions, ("rung",),
        )
        m.register_callback(
            "keto_hbm_refusals_total", "counter",
            "Snapshot refreshes refused because the plan stayed over "
            "budget with every eviction rung spent — the engine serves "
            "stale and reports DEGRADED(memory_pressure).",
            hbm_scalar("refusals"),
        )

        def warm_skipped():
            _, gauges, _ = maintenance_raw()
            v = gauges.get("warm_widths_skipped", 0)
            yield (), float(v) if isinstance(v, (int, float)) else 0.0

        m.register_callback(
            "keto_hbm_warm_widths_skipped", "gauge",
            "Slice widths the boot warmup skipped because their "
            "compiled-buffer footprint would breach the HBM budget "
            "(warming never evicts; it just stops lower on the ladder).",
            warm_skipped,
        )
        m.register_callback(
            "keto_oom_events_total", "counter",
            "Device allocations/compiled calls that raised a classified "
            "RESOURCE_EXHAUSTED (real XLA or the injected device-alloc "
            "oom fault).",
            hbm_scalar("oom_events"),
        )
        m.register_callback(
            "keto_oom_recoveries_total", "counter",
            "OOMs contained by evicting one ladder rung and retrying "
            "once successfully (the remainder escalate to the CPU "
            "fallback or a supervised refresh retry — never a crash).",
            hbm_scalar("oom_recoveries"),
        )

        # sharded serving (keto_tpu/parallel/sharded.py): the per-shard
        # residency ledger and the halo-exchange / frontier counters the
        # shard_map kernel's stats words feed
        def shard_hbm():
            snap = hbm_snapshot()
            shards = snap.get("shards") or []
            return [
                ((str(s),), float(v)) for s, v in enumerate(shards)
            ] or [(("0",), 0.0)]

        m.register_callback(
            "keto_shard_hbm_resident_bytes", "gauge",
            "Per-shard device bytes resident under the governor's "
            "per-shard ledger (owned bucket/overlay/label rows; "
            "replicated state spreads evenly) — the hottest shard is "
            "the binding constraint of every mesh-wide plan.",
            shard_hbm, ("shard",),
        )

        # multi-tenant serving (keto_tpu/driver/tenants.py): pool-level
        # residency/ledger plus per-tenant traffic and degradation —
        # peek-only like every bridge; the labeled families always emit
        # a default-tenant row so the exposed family set (and its
        # observability.md contract) is stable before the first tenant
        def tenant_pool_peek():
            return self.peek("tenants")

        def tenant_pool_count(method):
            def read():
                p = tenant_pool_peek()
                yield (), float(getattr(p, method)() if p is not None else 0)

            return read

        m.register_callback(
            "keto_tenant_known", "gauge",
            "Tenants this process has served since boot (resident or "
            "evicted); the default tenant is not counted.",
            tenant_pool_count("known_count"),
        )
        m.register_callback(
            "keto_tenant_resident", "gauge",
            "Tenants whose engines are currently materialized (bounded "
            "by serve.tenant_max_resident via the tenant-LRU).",
            tenant_pool_count("resident_count"),
        )

        def tenant_pool_attr(attr):
            def read():
                p = tenant_pool_peek()
                yield (), float(getattr(p, attr, 0) if p is not None else 0)

            return read

        m.register_callback(
            "keto_tenant_evictions_total", "counter",
            "Whole-tenant engine evictions (tenant-LRU capacity + the "
            "governor's tenant-lru HBM rung); state faults back in via "
            "the per-tenant snapcache on next touch.",
            tenant_pool_attr("evictions"),
        )
        m.register_callback(
            "keto_tenant_faultins_total", "counter",
            "Tenant engine fault-ins (first touch + every re-build after "
            "an eviction).",
            tenant_pool_attr("faultins"),
        )
        m.register_callback(
            "keto_tenant_shed_spikes_total", "counter",
            "Per-tenant shed-rate spikes detected (>= "
            "serve.tenant_shed_spike sheds inside the tracking window) — "
            "each one also triggers a flight-recorder bundle.",
            tenant_pool_attr("spike_triggers"),
        )

        def tenant_rows(per_ctx):
            def read():
                p = tenant_pool_peek()
                rows = (
                    [((c.name,), per_ctx(c)) for c in p.tenants()]
                    if p is not None
                    else []
                )
                return rows or [(("default",), 0.0)]

            return read

        m.register_callback(
            "keto_tenant_checks_total", "counter",
            "Check tuples dispatched per tenant (the default tenant's "
            "traffic rides the global keto_check_* families).",
            tenant_rows(lambda c: float(c.checks_total)), ("tenant",),
        )

        def tenant_shed():
            p = tenant_pool_peek()
            totals = dict(p.shed_totals) if p is not None else {}
            rows = [((t,), float(v)) for t, v in sorted(totals.items())]
            return rows or [(("default",), 0.0)]

        m.register_callback(
            "keto_tenant_shed_total", "counter",
            "Requests shed per tenant (429 + Retry-After + "
            "X-Keto-Tenant): one tenant's storm sheds under ITS quota "
            "while every other tenant's lanes stay open.",
            tenant_shed, ("tenant",),
        )
        m.register_callback(
            "keto_tenant_resident_bytes", "gauge",
            "Device-ledger bytes per resident tenant engine (0 while "
            "cold/oracle-backed); sums with keto_hbm_resident_bytes to "
            "the whole process's residency account.",
            tenant_rows(lambda c: float(c.resident_bytes())), ("tenant",),
        )

        def tenant_degraded():
            p = tenant_pool_peek()
            if p is None:
                return [(("default",), 0.0)]
            bad = p.degraded()
            rows = [
                ((c.name,), 1.0 if c.name in bad else 0.0)
                for c in p.tenants()
            ]
            return rows or [(("default",), 0.0)]

        m.register_callback(
            "keto_tenant_degraded", "gauge",
            "1 for tenants currently carrying a DEGRADED(tenant=...) "
            "reason (device fallback, memory pressure, audit mismatch) — "
            "per-tenant only, never the global health machine.",
            tenant_degraded, ("tenant",),
        )

        def maint_counter(key):
            def read():
                counters, _, _ = maintenance_raw()
                yield (), float(counters.get(key, 0))

            return read

        m.register_callback(
            "keto_shard_halo_rounds_total", "counter",
            "Halo-exchange rounds executed by the sharded BFS kernel: "
            "one all-gather of every shard's frontier bitmap slab over "
            "the graph axis per real BFS hop.",
            maint_counter("shard_halo_rounds"),
        )
        m.register_callback(
            "keto_shard_halo_bytes_total", "counter",
            "Frontier-slab bytes received per device across all halo "
            "rounds ((shards-1) x slab bytes per round) — the "
            "interconnect cost of cross-shard reachability.",
            maint_counter("shard_halo_bytes"),
        )
        m.register_callback(
            "keto_shard_frontier_bits_total", "counter",
            "Set bits in the fixpoint frontier bitmaps summed over "
            "shards and dispatches — the reachability work the mesh "
            "actually performed.",
            maint_counter("shard_frontier_bits"),
        )

        # sampled shadow-parity auditor (serve.audit_sample_rate)
        def audit_counter(key):
            def read():
                counters, _, _ = maintenance_raw()
                yield (), float(counters.get(key, 0))

            return read

        m.register_callback(
            "keto_audit_checks_total", "counter",
            "Live check decisions re-verified against the CPU reference "
            "oracle by the background shadow-parity auditor.",
            audit_counter("audit_checks"),
        )
        m.register_callback(
            "keto_audit_mismatches_total", "counter",
            "Audited decisions that DIVERGED from the CPU oracle — any "
            "nonzero value flips health to DEGRADED (continuous proof "
            "that eviction rungs never change answers).",
            audit_counter("audit_mismatches"),
        )

        # decision provenance (keto_tpu/explain): explain requests by the
        # route that decided them, witnesses that failed edge-by-edge
        # verification (each one a bug), and the durable decision log's
        # append totals
        def explain_requests():
            ex = self.peek("explain_engine")
            totals = getattr(ex, "requests_by_route", {}) if ex is not None else {}
            out = [((r,), float(v)) for r, v in sorted(totals.items())]
            return out or [(("bfs",), 0.0)]

        m.register_callback(
            "keto_explain_requests_total", "counter",
            "Check-explain requests served, by the route that decided "
            "them (label / hybrid / bfs / host / cpu — the stream's own "
            "route label, not a re-derivation).",
            explain_requests, ("route",),
        )

        def explain_verify_failures():
            ex = self.peek("explain_engine")
            yield (), float(getattr(ex, "verify_failures", 0) if ex is not None else 0)

        m.register_callback(
            "keto_witness_verify_failures_total", "counter",
            "Witnesses that FAILED edge-by-edge verification against the "
            "Manager before return — each one is a bug in the producing "
            "route; the response fell back to the CPU oracle's witness "
            "and the flight recorder captured the failing path.",
            explain_verify_failures,
        )

        def decision_log_attr(attr):
            def read():
                dl = self.peek("decision_log")
                yield (), float(getattr(dl, attr, 0) if dl is not None else 0)

            return read

        m.register_callback(
            "keto_decision_log_records_total", "counter",
            "Records appended to the durable decision-audit log (sampled "
            "hot-path checks plus every explain request), all tenants.",
            decision_log_attr("records_total"),
        )
        m.register_callback(
            "keto_decision_log_bytes_total", "counter",
            "Bytes appended to the decision-audit log across active and "
            "sealed segments, all tenants.",
            decision_log_attr("bytes_total"),
        )

        # reverse-query subsystem (keto_tpu/list/): request counters per
        # answering path, and the watch hub's stream/event counters
        def list_requests():
            eng = self.peek("list_engine")
            totals = getattr(eng, "requests_total", {}) if eng is not None else {}
            out = [
                ((op, path), float(v)) for (op, path), v in sorted(totals.items())
            ]
            return out or [(("objects", "device"), 0.0)]

        m.register_callback(
            "keto_list_requests_total", "counter",
            "Reverse-query requests served, by op (objects/subjects) and "
            "answering path (device BFS, host = CPU-reference lister, "
            "oracle = Manager-backed wildcard/pattern fallback, empty = "
            "unresolvable query).",
            list_requests, ("op", "path"),
        )

        def list_device_errors():
            eng = self.peek("list_engine")
            yield (), float(getattr(eng, "device_errors", 0) if eng is not None else 0)

        m.register_callback(
            "keto_list_device_errors_total", "counter",
            "Device list-BFS failures that fell back to the "
            "CPU-reference lister (answers unchanged).",
            list_device_errors,
        )

        def watch_stat(key):
            def read():
                hub = self.peek("watch_hub")
                snap = hub.snapshot() if hub is not None else {}
                yield (), float(snap.get(key, 0))

            return read

        m.register_callback(
            "keto_watch_streams", "gauge",
            "Watch changefeed streams currently open (REST chunked + "
            "gRPC server-stream), bounded by serve.watch_max_streams.",
            watch_stat("active_streams"),
        )
        m.register_callback(
            "keto_watch_events_total", "counter",
            "Tuple-change events delivered to watch subscribers (inserts "
            "+ deletes, across all streams).",
            watch_stat("events_total"),
        )
        m.register_callback(
            "keto_watch_expired_total", "counter",
            "Watch resumes refused because the snaptoken predates the "
            "retained change log (410 Gone / OUT_OF_RANGE).",
            watch_stat("expired_total"),
        )

        # replica tier (keto_tpu/replica/): replication lag, feed apply
        # and bootstrap counters, and the Watch-invalidated check cache —
        # read from the controller's snapshot at scrape time; a primary
        # (peek returns None) exposes the families at zero so one scrape
        # config and one dashboard cover both roles
        def replica_snapshot():
            rep = self.peek("replica")
            return rep.snapshot() if rep is not None else {}

        def replica_stat(key):
            def read():
                yield (), float(replica_snapshot().get(key, 0) or 0)

            return read

        m.register_callback(
            "keto_replica_lag_seconds", "gauge",
            "Replica mode: seconds since this replica last confirmed it "
            "was caught up with the primary (feed lagging or primary "
            "unreachable — handled the same); past "
            "serve.replica_staleness_budget_s health reports "
            "DEGRADED(replication_lag). 0 on a primary.",
            replica_stat("lag_s"),
        )
        m.register_callback(
            "keto_replica_applied_commits_total", "counter",
            "Watch commit groups this replica applied at their primary "
            "snaptoken through the delta-overlay path (exactly-once: "
            "re-delivered groups are skipped by the watermark guard).",
            replica_stat("applied_commits"),
        )
        m.register_callback(
            "keto_replica_bootstraps_total", "counter",
            "Full-state installs from the primary's /snapshot/export: "
            "the cold start plus every watch-horizon-loss recovery "
            "(410-triggered automatic re-bootstrap, never silent "
            "divergence).",
            replica_stat("bootstraps"),
        )

        def checkcache_stat(key):
            def read():
                cc = replica_snapshot().get("checkcache") or {}
                yield (), float(cc.get(key, 0) or 0)

            return read

        m.register_callback(
            "keto_checkcache_hits_total", "counter",
            "Replica check-cache hits: decisions served from a (tuple, "
            "snaptoken-window) entry still valid for the requested "
            "freshness.",
            checkcache_stat("hits"),
        )
        m.register_callback(
            "keto_checkcache_misses_total", "counter",
            "Replica check-cache misses (no entry, window closed by an "
            "applied delta, or requested snaptoken above the window).",
            checkcache_stat("misses"),
        )
        m.register_callback(
            "keto_checkcache_invalidations_total", "counter",
            "Check-cache entries whose windows were closed by applied "
            "Watch deltas (global invalidation: reachability is "
            "transitive, so any delta may flip any decision).",
            checkcache_stat("invalidations"),
        )

        def health_states():
            from keto_tpu.driver.health import HealthState

            monitor = self.peek("health_monitor")
            current = monitor.status()[0] if monitor is not None else None
            return [((s.value,), 1.0 if s is current else 0.0) for s in HealthState]

        m.register_callback(
            "keto_health_state", "gauge",
            "Serving health state machine, one-hot over "
            "starting/serving/degraded/not_serving.",
            health_states, ("state",),
        )

        def health_transitions():
            monitor = self.peek("health_monitor")
            yield (), float(monitor.transitions if monitor is not None else 0)

        m.register_callback(
            "keto_health_transitions_total", "counter",
            "Health state transitions since boot.",
            health_transitions,
        )

        # request timelines (keto_tpu/x/timeline.py) + flight recorder
        # (keto_tpu/x/flightrec.py) + SLO engine (keto_tpu/x/slo.py) —
        # peek-only like every other bridge; the daemon primes the SLO
        # engine at boot so scrapes see live burn rates
        def timeline_finished():
            rec = self.peek("timeline")
            by = getattr(rec, "finished_by_surface", {}) if rec is not None else {}
            return [
                ((s,), float(by.get(s, 0))) for s in ("http", "grpc")
            ] + [
                ((s,), float(v)) for s, v in sorted(by.items())
                if s not in ("http", "grpc")
            ]

        m.register_callback(
            "keto_timeline_finished_total", "counter",
            "Request timelines recorded (ring + top-K slowest, queryable "
            "at GET /debug/requests), by serving surface.",
            timeline_finished, ("surface",),
        )

        def flightrec_snapshot():
            fr = self.peek("flightrec")
            return fr.snapshot() if fr is not None else {}

        def flightrec_bundles():
            by = flightrec_snapshot().get("bundles_by_reason", {})
            return [
                ((r,), float(v)) for r, v in sorted(by.items())
            ] or [(("none",), 0.0)]

        m.register_callback(
            "keto_flightrec_bundles_total", "counter",
            "Flight-recorder debug bundles written to "
            "serve.debug_bundle_dir, by trigger reason (health-degraded/"
            "health-not_serving/oom/drain/watchdog).",
            flightrec_bundles, ("reason",),
        )

        def flightrec_suppressed():
            yield (), float(flightrec_snapshot().get("suppressed", 0) or 0)

        m.register_callback(
            "keto_flightrec_suppressed_total", "counter",
            "Flight-recorder triggers refused by the rate limit "
            "(serve.debug_bundle_min_interval_s) — a flapping anomaly "
            "cannot fill the disk.",
            flightrec_suppressed,
        )

        def slo_field(field):
            def read():
                slo = self.peek("slo")
                return slo.metric_rows(field) if slo is not None else []

            return read

        m.register_callback(
            "keto_slo_availability_ratio", "gauge",
            "Fraction of REST+gRPC requests without a server-side "
            "failure (5xx / INTERNAL-class codes) over each trailing "
            "window; 1.0 on an idle window.",
            slo_field("availability_ratio"), ("window",),
        )
        m.register_callback(
            "keto_slo_availability_burn_rate", "gauge",
            "Error-budget burn rate of the availability objective per "
            "window: 1.0 spends the budget exactly at the objective "
            "horizon, >1 is an alertable burn.",
            slo_field("availability_burn_rate"), ("window",),
        )
        m.register_callback(
            "keto_slo_latency_ratio", "gauge",
            "Fraction of REST requests answered within the latency "
            "objective threshold (bucket-quantized), per window.",
            slo_field("latency_ratio"), ("window",),
        )
        m.register_callback(
            "keto_slo_latency_burn_rate", "gauge",
            "Error-budget burn rate of the latency objective per "
            "window (same semantics as the availability burn rate).",
            slo_field("latency_burn_rate"), ("window",),
        )

        def slo_objectives():
            slo = self.peek("slo")
            return slo.objective_rows() if slo is not None else []

        m.register_callback(
            "keto_slo_objective", "gauge",
            "The configured objectives the burn rates are judged "
            "against (availability ratio, latency good-ratio, latency "
            "threshold seconds).",
            slo_objectives, ("objective",),
        )

        def tracer_attr(attr):
            def read():
                t = self.peek("tracer")
                yield (), float(getattr(t, attr, 0) if t is not None else 0)

            return read

        m.register_callback(
            "keto_tracer_spans_exported_total", "counter",
            "Spans handed to the configured trace exporter.",
            tracer_attr("spans_exported"),
        )
        m.register_callback(
            "keto_tracer_spans_dropped_total", "counter",
            "Spans lost (full export queue, collector down, dead file).",
            tracer_attr("spans_dropped"),
        )

        def store_attr(attr):
            def read():
                s = self.peek("manager")
                yield (), float(getattr(s, attr, 0) if s is not None else 0)

            return read

        m.register_callback(
            "keto_persistence_reconnect_retries_total", "counter",
            "Store operations re-run after a dialect-recognized connection "
            "loss (reads always; writes only when idempotency-keyed).",
            store_attr("reconnect_retries"),
        )
        m.register_callback(
            "keto_idempotent_replays_total", "counter",
            "Keyed write retries answered from the dedup table instead of "
            "re-applying.",
            store_attr("idempotent_replays"),
        )

        # group-commit write path (keto_tpu/driver/group_commit.py):
        # flush counter bridged from the coordinator; wait/batch-size
        # histograms are recorded directly by the coordinator (attached
        # in write_coordinator()). Declared eagerly so scrapes expose
        # the documented family before the first write.
        m.histogram(
            "keto_group_commit_wait_seconds",
            "Time a writer spent queued in the group-commit "
            "coordinator before its group's durable transaction "
            "started (the coalescing cost the "
            "serve.group_commit_window_ms knob trades against "
            "fsyncs).",
            buckets=(0.0005, 0.001, 0.0025, 0.005, 0.01, 0.025,
                     0.05, 0.1, 0.25, 1.0),
        )
        m.histogram(
            "keto_group_commit_batch_size",
            "Writers coalesced per durable group transaction "
            "(1 = no batching benefit; the ceiling is "
            "serve.group_commit_max_writers).",
            buckets=(1, 2, 4, 8, 16, 32, 64, 128, 256),
        )

        def group_commit_attr(attr):
            def read():
                co = self.peek("group_commit")
                yield (), float(getattr(co, attr, 0) if co is not None else 0)

            return read

        m.register_callback(
            "keto_group_commit_flush_total", "counter",
            "Durable group transactions committed by the write "
            "coordinator (each covers keto_group_commit_batch_size "
            "writers with one BEGIN/COMMIT).",
            group_commit_attr("flush_total"),
        )
        m.register_callback(
            "keto_group_commit_errors_total", "counter",
            "Group transactions that failed (every writer in the group "
            "observed the same error and retries individually).",
            group_commit_attr("flush_errors"),
        )

        # device-resident overlay apply + log-structured fold
        # (keto_tpu/check/tpu_engine.py): bridged from the engine's
        # maintenance stats like the other maintenance families
        m.register_callback(
            "keto_overlay_device_applies_total", "counter",
            "Delta-overlay installs applied directly to the resident "
            "device ELL via scatter patches (no full host re-pack + "
            "re-upload; the complement re-packs, e.g. on capacity "
            "growth).",
            maint_counter("overlay_device_applies"),
        )
        m.register_callback(
            "keto_fold_runs_total", "counter",
            "Background log-structured fold passes: oldest overlay "
            "segments folded into the base snapshot while new writes "
            "keep landing in the newest (the replacement for the "
            "stop-the-world compaction cliff).",
            maint_counter("fold_runs"),
        )

        def fold_duration():
            _, _, durations = maintenance_raw()
            d = durations.get("fold")
            yield (), float(d["total_ms"]) * 1e-3 if d else 0.0

        m.register_callback(
            "keto_fold_duration_seconds_total", "counter",
            "Cumulative wall time spent in background fold passes "
            "(rate against keto_fold_runs_total for the mean fold "
            "cost; folds run off the serving path).",
            fold_duration,
        )

        # where a build's or a fold's sorts ran and why, and what the
        # serving path waited for or went without while a pass held the
        # engine's lock (keto_tpu/graph/device_build.py GovernedSorter,
        # keto_tpu/check/tpu_engine.py)
        from keto_tpu.graph.device_build import SORT_WHYS

        def build_sorts():
            counters, _, _ = maintenance_raw()
            combos = [("device", "ok")] + [("host", why) for why in SORT_WHYS]
            return [
                ((backend, why), float(counters.get(f"build_sort_{backend}_{why}", 0)))
                for backend, why in combos
            ]

        m.register_callback(
            "keto_build_sort_total", "counter",
            "Batches of stable argsorts a snapshot build, a fold or a cache "
            "load asked for, by where they ran and why: device/ok, or the "
            "host because the batch was small, the HBM plan did not fit "
            "(pressure), a snapshot was serving and the batch's padded "
            "program was not compiled in this process yet (cold: it is "
            "compiled behind, off the engine's lock), or the device sort "
            "failed or met a key equal to the padding sentinel (error). "
            "The same permutations either way.",
            build_sorts, ("backend", "why"),
        )

        def duration_seconds(prefix, values):
            def read():
                _, _, durations = maintenance_raw()
                for value in values:
                    d = durations.get(f"{prefix}_{value}")
                    yield (value,), float(d["total_ms"]) * 1e-3 if d else 0.0

            return read

        m.register_callback(
            "keto_build_sort_seconds_total", "counter",
            "Wall time of those batches by backend: padding, transfers and "
            "the sort on the device; numpy on the host.",
            duration_seconds("build_sort", ("device", "host")), ("backend",),
        )

        def build_sort_rungs():
            _, gauges, _ = maintenance_raw()
            yield (), float(gauges.get("build_sort_rungs", 0))

        m.register_callback(
            "keto_build_sort_rungs", "gauge",
            "Padded sort programs (one a rung and arity) compiled in this "
            "process: what a fold can sort on the device without compiling.",
            build_sort_rungs,
        )

        def stale_serves():
            counters, _, _ = maintenance_raw()
            return [
                ((holder,), float(counters.get(f"stale_serves_{holder}", 0)))
                for holder in ("fold", "delta", "rebuild")
            ]

        m.register_callback(
            "keto_snapshot_stale_serves_total", "counter",
            "Rounds snapshot_serving answered from a snapshot older than "
            "the store's watermark, by what stood in the way. A fold or a "
            "delta apply is waited for (these two stay 0); only a full "
            "rebuild, running or owed, is not.",
            stale_serves, ("holder",),
        )
        m.register_callback(
            "keto_engine_lock_wait_seconds_total", "counter",
            "Seconds serving threads waited for the engine's lock while a "
            "refresh pass, a fold or a rebuild held it, by site: serving "
            "(an unpinned round, waiting out a fold or a delta apply), "
            "pinned (a round whose requests all hold snaptokens the "
            "snapshot does not reach yet), latest. One dispatch thread: "
            "what it waits here, every request behind it waits too.",
            duration_seconds("lock_wait", ("serving", "pinned", "latest")), ("site",),
        )

        # fleet control plane (keto_tpu/fleet/): lease epoch, promotion
        # and membership state, live-reshard state machine, and the
        # lag-aware routing weights — peek-only like every other bridge
        def fleet_snapshot():
            f = self.peek("fleet")
            return f.snapshot() if f is not None else {}

        def fleet_epoch():
            yield (), float(fleet_snapshot().get("epoch", 0) or 0)

        m.register_callback(
            "keto_fleet_epoch", "gauge",
            "The fence epoch this node last observed on the fleet lease "
            "(monotone across promotions; a primary's writes carry it, "
            "a deposed primary's writes 409 against a newer one).",
            fleet_epoch,
        )

        def fleet_promotions():
            by = fleet_snapshot().get("promotions_by_reason", {})
            return [
                ((r,), float(v)) for r, v in sorted(by.items())
            ] or [(("none",), 0.0)]

        m.register_callback(
            "keto_fleet_promotions_total", "counter",
            "Times this node installed itself as primary, by reason "
            "(lease-expired: won the election after primary death; "
            "install-retry: re-ran a promotion that crashed between "
            "winning the lease and finishing the install).",
            fleet_promotions, ("reason",),
        )

        def fleet_replicas():
            states: dict[str, int] = {}
            for mem in fleet_snapshot().get("members", []):
                role = str(mem.get("role", "unknown") or "unknown")
                states[role] = states.get(role, 0) + 1
            return [
                ((s,), float(v)) for s, v in sorted(states.items())
            ] or [(("none",), 0.0)]

        m.register_callback(
            "keto_fleet_replicas", "gauge",
            "Live fleet members by advertised role (primary / replica / "
            "deposed), from the heartbeat membership table — stale "
            "members age out of the count.",
            fleet_replicas, ("state",),
        )

        def reshard_state():
            r = self.peek("reshard")
            yield (), float(r.state_code() if r is not None else 0)

        m.register_callback(
            "keto_reshard_state", "gauge",
            "Live-reshard state machine: 0 idle, 1 preparing (target "
            "engine building while the current one serves), 2 handoff "
            "(atomic install), 3 failed (old geometry kept serving).",
            reshard_state,
        )

        def fleet_route_weights():
            w = fleet_snapshot().get("route_weights", {})
            return [
                ((str(nid),), float(v)) for nid, v in sorted(w.items())
            ] or [(("none",), 0.0)]

        m.register_callback(
            "keto_route_weight", "gauge",
            "Lag-aware routing weight per fleet replica (0 = drained: "
            "lag at/over the staleness budget; otherwise lag headroom "
            "over the latency EWMA) — what SDK read routing steers by.",
            fleet_route_weights, ("replica",),
        )

    def tracer(self):
        from keto_tpu.x.tracing import DEFAULT_OTLP_ENDPOINT, Tracer

        return self._memo(
            "tracer",
            lambda: Tracer(
                self._config.get("tracing.provider", ""),
                self.logger(),
                otlp_file=self._config.get("tracing.otlp.file", ""),
                otlp_endpoint=self._config.get(
                    "tracing.otlp.endpoint", DEFAULT_OTLP_ENDPOINT
                ),
            ),
        )

    def telemetry(self):
        from keto_tpu.x.telemetry import Telemetry

        return self._memo(
            "telemetry", lambda: Telemetry(bool(self._config.get("telemetry.enabled", False)))
        )

    # -- info ----------------------------------------------------------------

    def version(self) -> str:
        return VERSION

    def close(self) -> None:
        # the fleet loops go first: they must not renew (or contend for)
        # the lease, heartbeat membership, spawn replicas, or trigger a
        # promotion while the components under them tear down
        scaler = self._singletons.get("autoscaler")
        if scaler is not None:
            scaler.stop()
        fleet = self._singletons.get("fleet")
        if fleet is not None:
            fleet.stop()
        rep = self._singletons.get("replica")
        if rep is not None:
            rep.stop()
        hub = self._singletons.get("watch_hub")
        if hub is not None:
            hub.close()
        # tenant contexts own batchers/engines/hubs of their own: stop
        # them before the default batcher so no tenant dispatch lands on
        # components mid-teardown
        pool = self._singletons.get("tenants")
        if pool is not None:
            pool.close()
        batcher = self._singletons.get("check_batcher")
        if batcher:
            batcher.stop()
        # the write coordinator must stop before the store closes: a
        # group mid-commit against a closed connection would fail every
        # writer in it
        co = self._singletons.get("group_commit")
        if co is not None:
            co.stop()
        engine = self._singletons.get("permission_engine")
        if engine is not None and hasattr(engine, "close"):
            engine.close()
        tracer = self._singletons.get("tracer")
        if tracer is not None:
            tracer.close()
        store = self._singletons.get("manager")
        if store is not None and hasattr(store, "close"):
            store.close()
        lease_store = self._singletons.get("fleet_lease_store")
        if (
            lease_store is not None
            and lease_store is not store
            and hasattr(lease_store, "close")
        ):
            lease_store.close()
        self._config.close()
