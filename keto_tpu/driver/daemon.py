"""Serve loop: read + write APIs, each multiplexing REST and gRPC on one port.

The analog of the reference's ``ServeAll`` (reference
internal/driver/daemon.go:62-159): the read API (default :4466) serves
check/expand/list over both protocols, the write API (default :4467) serves
tuple mutations, and each public port is a sniffing mux in front of loopback
REST and gRPC backends (keto_tpu/servers/mux.py). Graceful shutdown stops
the muxes first, then drains the backends.

Rolling-restart contract: SIGTERM/SIGINT (install_signal_handlers) pins
the health state to NOT_SERVING — load balancers and readiness probes
stop routing new traffic — then waits up to ``serve.drain_timeout_s`` for
every in-flight check to resolve before tearing the stacks down, so a
rolling restart drops zero accepted requests.
"""

from __future__ import annotations

import signal
import threading
import time
from dataclasses import dataclass
from typing import TYPE_CHECKING, Any, Optional

from keto_tpu.servers.grpc_api import build_grpc_server
from keto_tpu.servers.native_mux import make_port_mux
from keto_tpu.servers.rest import READ, WRITE, RestServer

if TYPE_CHECKING:
    from keto_tpu.driver.registry import Registry


def make_rest_server(
    registry: "Registry", role: str, host: str = "127.0.0.1", port: int = 0
) -> Any:
    """REST backend per ``serve.http_backend``: the asyncio reactor
    (default — one event loop, bounded handler pool) or the stdlib
    thread-per-connection server."""
    backend = registry.config().get("serve.http_backend", "async")
    if backend == "threading":
        return RestServer(registry, role, host=host, port=port)
    from keto_tpu.servers.async_rest import AsyncRestServer

    return AsyncRestServer(registry, role, host=host, port=port)


@dataclass
class _RoleServers:
    rest: Any  # RestServer or AsyncRestServer
    grpc_server: Any
    mux: Any  # NativePortMux or PortMux

    @property
    def port(self) -> int:
        return self.mux.port


class Daemon:
    """Owns both roles' server stacks."""

    def __init__(self, registry: "Registry"):
        self.registry = registry
        self._roles: dict[str, _RoleServers] = {}
        # set by a shutdown signal (or shutdown_soon()); serve_all's
        # blocking loop waits on it and then drains
        self._stop_requested = threading.Event()
        # boot warmup worker (_warm_snapshot); shutdown joins it briefly
        self._warm_thread: Optional[threading.Thread] = None

    def _start_role(self, role: str, host: str, port: int) -> _RoleServers:
        rest = make_rest_server(self.registry, role, host="127.0.0.1", port=0)
        rest.start()
        grpc_server, grpc_port = build_grpc_server(self.registry, role)
        grpc_server.start()
        # native epoll mux when built (make native), Python fallback else
        mux = make_port_mux(host, port, rest_port=rest.port, grpc_port=grpc_port)
        mux.start()
        self.registry.logger().info(
            "serving %s API on :%d (REST+gRPC multiplexed)", role, mux.port
        )
        return _RoleServers(rest=rest, grpc_server=grpc_server, mux=mux)

    def serve_all(self, block: bool = True) -> None:
        cfg = self.registry.config()
        # prime the namespace manager before accepting traffic: a watched
        # source (file/dir/websocket URI) connects and loads at BOOT, the
        # way the reference resolves config during registry Init
        # (reference registry_default.go:240-261) — not on first request
        self.registry.namespace_manager()
        # prime the health state machine before accepting traffic so the
        # very first /health/ready or grpc.health.v1 Watch reads a live
        # state instead of constructing the monitor mid-request
        self.registry.health_monitor()
        # prime the observability companions the scrape-time bridges
        # peek at (timeline recorder, SLO engine), and attach the flight
        # recorder's anomaly triggers now that the components exist
        self.registry.timeline_recorder()
        self.registry.slo_engine()
        self.registry.wire_flight_recorder()
        rep = self.registry.replica_controller()
        if rep is not None:
            # replica mode: the controller's supervised feed bootstraps
            # the store from the primary and builds the first snapshot
            # itself (the boot warm below would only build an EMPTY
            # pre-bootstrap snapshot); reads are gated 503 until the
            # first bootstrap completes
            rep.start()
        else:
            self._warm_snapshot()
        # fleet control plane LAST: by the time this node renews/contends
        # for the lease (and could be asked to promote), the engine,
        # health machine, and replica feed it hands off around all exist
        fleet = self.registry.fleet_controller()
        if fleet is not None:
            fleet.start()
        scaler = self.registry.autoscaler()
        if scaler is not None:
            scaler.start()
        read_host, read_port = cfg.read_api_address()
        write_host, write_port = cfg.write_api_address()
        self._roles[READ] = self._start_role(READ, read_host, read_port)
        self._roles[WRITE] = self._start_role(WRITE, write_host, write_port)
        if block:
            try:
                self.wait_for_shutdown()
            except KeyboardInterrupt:
                pass
            self.drain_and_shutdown()

    def wait_for_shutdown(self, poll_s: float = 1.0) -> None:
        """Block until a shutdown signal (or ``shutdown_soon``). The wait
        is BOUNDED and looped rather than a bare ``Event.wait()``: an
        unbounded wait in the main thread delays signal-handler delivery
        on some platforms (CPython runs handlers between bytecodes, and a
        C-level lock wait can absorb the wakeup), which is exactly the
        shutdown-hang class the KTA204 lint flags — a SIGTERM must always
        terminate this wait within ``poll_s``."""
        while not self._stop_requested.wait(timeout=poll_s):
            pass

    # -- graceful shutdown ---------------------------------------------------

    def install_signal_handlers(self) -> None:
        """SIGTERM/SIGINT → drain-then-shutdown (the k8s preStop /
        rolling-restart path). Only callable from the main thread (a
        CPython constraint on signal.signal); elsewhere it is a no-op so
        embedded daemons can call it unconditionally."""
        if threading.current_thread() is not threading.main_thread():
            return
        for sig in (signal.SIGTERM, signal.SIGINT):
            signal.signal(sig, self._on_signal)

    def _on_signal(self, signum, frame) -> None:
        # the handler itself must stay tiny and async-signal-safe-ish:
        # flag the event; serve_all's blocking loop (or whoever owns the
        # daemon) performs the actual drain
        self._stop_requested.set()

    def shutdown_soon(self) -> None:
        """Programmatic equivalent of a shutdown signal."""
        self._stop_requested.set()

    def drain_and_shutdown(self) -> None:
        """Stop taking NEW traffic (health pinned NOT_SERVING so probes
        and load balancers route away), wait up to
        ``serve.drain_timeout_s`` for in-flight checks to resolve, then
        tear the stacks down. In-flight requests accepted before the
        signal complete normally — the zero-dropped-requests half of the
        rolling-restart contract."""
        if not self._roles:
            return
        self._stop_requested.set()
        drain_s = float(self.registry.config().get("serve.drain_timeout_s", 5.0))
        # flight-recorder drain bundle FIRST, while the state it freezes
        # (queues, timelines, health) still describes live serving
        fr = self.registry.flight_recorder()
        if fr is not None:
            fr.trigger("drain", "SIGTERM/SIGINT drain requested")
        try:
            from keto_tpu.driver.health import HealthState

            self.registry.health_monitor().set_override(
                HealthState.NOT_SERVING, "draining: shutdown requested"
            )
        except Exception:
            # health never blocks shutdown — but the failure is a finding,
            # not a non-event: log it and count it where maintenance
            # counters already surface (keto_maintenance_events_total)
            self._count_shutdown_failure("drain_health_override_failures")
            self.registry.logger().warning(
                "health override failed during drain; continuing shutdown",
                exc_info=True,
            )
        deadline = time.monotonic() + drain_s
        # fleet loops first: a draining node must stop renewing the lease
        # (so a successor can take it promptly), stop heartbeating
        # membership, and must not promote or spawn mid-teardown
        for key in ("autoscaler", "fleet"):
            loop = self.registry.peek(key)
            if loop is not None:
                try:
                    loop.stop()
                except Exception:
                    self._count_shutdown_failure(f"drain_{key}_stop_failures")
                    self.registry.logger().warning(
                        "%s stop failed during drain; continuing shutdown",
                        key, exc_info=True,
                    )
        # replica feed next: stop applying new commit groups before the
        # read plane drains, so in-flight reads resolve against a stable
        # watermark (the durable applied-watermark already covers every
        # applied group — a later restart resumes exactly-once)
        rep = self.registry.peek("replica")
        if rep is not None:
            try:
                rep.stop()
            except Exception:
                self._count_shutdown_failure("drain_replica_stop_failures")
                self.registry.logger().warning(
                    "replica feed stop failed during drain; continuing "
                    "shutdown", exc_info=True,
                )
        # watch streams are long-lived BY DESIGN: close the hub first so
        # every changefeed generator ends at its next poll tick and the
        # REST backends' drains below aren't held open by subscribers
        # (clients reconnect-with-resume through their SDK, exactly-once
        # per commit group)
        hub = self.registry.peek("watch_hub")
        if hub is not None:
            try:
                hub.close()
            except Exception:
                self._count_shutdown_failure("drain_watch_close_failures")
                self.registry.logger().warning(
                    "watch hub close failed during drain; continuing shutdown",
                    exc_info=True,
                )
        batcher = self.registry.peek("check_batcher")
        if batcher is not None and hasattr(batcher, "drain"):
            if not batcher.drain(drain_s):
                self.registry.logger().warning(
                    "drain timed out after %.1fs with %d checks in flight",
                    drain_s, getattr(batcher, "inflight", -1),
                )
        # group-commit coordinator: let queued writers flush durably
        # before teardown (an acked snaptoken must survive this exit;
        # unflushed writers were never acked, so a timeout loses nothing
        # a client could have observed)
        co = self.registry.peek("group_commit")
        if co is not None:
            if not co.drain(max(0.5, deadline - time.monotonic())):
                self._count_shutdown_failure("drain_group_commit_timeouts")
                self.registry.logger().warning(
                    "group-commit drain timed out with %d writers in flight",
                    getattr(co, "inflight", -1),
                )
        # the batcher resolving a future is not the response reaching the
        # wire: wait for the REST backends to flush every accepted
        # exchange before connections are torn down
        for role in self._roles.values():
            drain = getattr(role.rest, "drain", None)
            if drain is not None:
                drain(max(0.5, deadline - time.monotonic()))
        # drain the TRACER too: the otlp-http exporter batches spans on a
        # background thread, and a SIGTERM that tears the stacks down
        # while a batch is queued (or held by the worker) would drop the
        # very spans that explain the final requests. close() flushes and
        # joins the exporter — inside the drain window, before teardown.
        tracer = self.registry.peek("tracer")
        if tracer is not None:
            try:
                tracer.close()
            except Exception:
                # telemetry never blocks shutdown; log + count instead of
                # dropping the one signal that says spans were lost
                self._count_shutdown_failure("drain_tracer_close_failures")
                self.registry.logger().warning(
                    "tracer flush failed during drain; spans may be lost",
                    exc_info=True,
                )
        self.shutdown()

    def _count_shutdown_failure(self, event: str) -> None:
        """Count a swallowed shutdown-path failure into the engine's
        maintenance stats (scraped as keto_maintenance_events_total) —
        best-effort by nature: failing to count must not block shutdown
        either."""
        engine = self.registry.peek("permission_engine")
        stats = getattr(engine, "maintenance", None)
        if stats is not None:
            stats.incr(event)

    def _warm_snapshot(self) -> None:
        """Kick the first snapshot build/reload off the request path: with
        a snapshot cache configured (serve.snapshot_cache_dir) the engine
        mmap-reloads in seconds and catches up from the cached watermark
        through the delta path; without one this merely moves the first
        request's build cost to boot. Failures log and defer to the
        ordinary first-request path."""
        engine = self.registry.permission_engine()
        if not hasattr(engine, "snapshot"):
            return

        # the ladder warm-up answers an explicit request for a persistent
        # cache (serve.compile_cache_dir or JAX_COMPILATION_CACHE_DIR) —
        # not the mere existence of the CLI's default directory
        from keto_tpu.driver import compile_cache

        _, cache_requested = compile_cache.resolve(
            str(self.registry.config().get("serve.compile_cache_dir", "") or "")
        )
        warm_widths = cache_requested and hasattr(engine, "warm_compile")

        def run():
            try:
                engine.snapshot()
                gov = getattr(engine, "hbm", None)
                if gov is not None:
                    # boot-time memory picture: the budget the governor
                    # enforces and where the first snapshot landed it —
                    # an over-budget cold boot logs its ladder walk above
                    snap = gov.snapshot()
                    self.registry.logger().info(
                        "HBM governor: %d / %d bytes resident after boot "
                        "snapshot (eviction rung %d/%d)",
                        snap["resident_bytes"], snap["budget_bytes"],
                        snap["rung"], len(snap["rungs"]),
                    )
                if warm_widths:
                    # ahead-of-time compile of the full slice-width
                    # ladder (BFS + label kernels): with the persistent
                    # compilation cache configured, the first boot pays
                    # the compiles once per binary and every later boot
                    # replays them from disk before traffic arrives
                    n = engine.warm_compile()
                    self.registry.logger().info(
                        "width-ladder warmup compiled/loaded %d kernels "
                        "(block_iters %s, sweep %s)", n,
                        engine.dispatch._block_iters, engine.dispatch._sweep,
                    )
            except Exception:
                stats = getattr(engine, "maintenance", None)
                if stats is not None:
                    stats.incr("warm_failures")
                self.registry.logger().warning(
                    "boot snapshot warm failed; first request will build",
                    exc_info=True,
                )

        self._warm_thread = threading.Thread(
            target=run, name="keto-tpu-snapshot-warm", daemon=True
        )
        self._warm_thread.start()

    @property
    def read_port(self) -> int:
        return self._roles[READ].port

    @property
    def write_port(self) -> int:
        return self._roles[WRITE].port

    def shutdown(self) -> None:
        """Stop muxes, drain backends, close the registry. Idempotent —
        callers (tests, signal handlers) may race a second invocation."""
        if not self._roles:
            return
        # end watch streams first even on the non-drain path (tests,
        # double shutdown): their generators exit at the next poll tick
        # instead of leaving stream tasks pending at loop teardown
        hub = self.registry.peek("watch_hub")
        if hub is not None:
            try:
                hub.close()
            except Exception:
                self.registry.logger().debug(
                    "watch hub close raced shutdown", exc_info=True
                )
        for role in self._roles.values():
            role.mux.stop()
        for role in self._roles.values():
            role.rest.stop()
            role.grpc_server.stop(grace=2)
        self._roles.clear()
        self.registry.close()
        # the warm thread checks the engine's closing flag between
        # kernels; a bounded join here keeps interpreter teardown from
        # racing an in-flight XLA compile (observed as a segfault at
        # exit when a quick boot-shutdown cycle interrupted the
        # width-ladder warmup)
        warm = self._warm_thread
        if warm is not None and warm.is_alive():
            warm.join(timeout=30.0)
