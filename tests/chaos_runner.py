"""Chaos-harness daemon: a real keto-tpu server in its own process.

tests/test_chaos.py spawns this script as a subprocess, arms a crash
point through ``KETO_TPU_FAULTS`` (``<point>:kill:<n>`` — the site calls
``os._exit`` on its n-th pass, the injectable analog of SIGKILL landing
mid-write, mid-compaction, mid-cache-save, …), drives concurrent traffic
at it until it dies, restarts it clean, and verifies the recovery
invariants. This wrapper exists so the DEATH is real: a process exit with
no rollback, no atexit, no flushing — exception-based fault injection
(tests/test_faults.py) can never prove durability, only error handling.

Run: ``python tests/chaos_runner.py --dsn sqlite://<file>
--cache-dir <dir> --port-file <path>`` — serves the read and write APIs
on ephemeral ports, publishes them (atomically) to ``--port-file`` as
JSON ``{"read": .., "write": .., "pid": ..}``, then blocks until
SIGTERM/SIGINT and exits through the graceful drain path (exit 0).
"""

import argparse
import json
import os
import sys
import tempfile
from pathlib import Path

# run as a script (python tests/chaos_runner.py): the repo root, not
# tests/, must be importable
sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

#: namespace config shared with the parent test (it builds the CPU
#: reference oracle over the same store, so the ids must agree)
NAMESPACES = [{"id": 0, "name": "docs"}, {"id": 1, "name": "groups"}]


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--dsn", required=True)
    ap.add_argument("--cache-dir", required=True)
    ap.add_argument("--port-file", required=True)
    ap.add_argument("--overlay-budget", type=int, default=24)
    ap.add_argument("--drain-timeout-s", type=float, default=5.0)
    # replica mode (keto_tpu/replica/): --role replica boots a daemon
    # with NO store of its own — it bootstraps from --primary-url's
    # /snapshot/export, tails its /watch, and keeps the durable
    # applied-watermark under --replica-dir so a SIGKILL resumes
    # exactly-once (tests/test_replica.py, scripts/replica_smoke.py)
    ap.add_argument("--role", default="primary", choices=["primary", "replica"])
    ap.add_argument("--primary-url", default="")
    ap.add_argument("--replica-dir", default="")
    ap.add_argument("--staleness-wait-ms", type=float, default=500.0)
    # pinned ports let a failover test restart a primary at the SAME
    # address its replicas were configured with (0 = ephemeral)
    ap.add_argument("--read-port", type=int, default=0)
    ap.add_argument("--write-port", type=int, default=0)
    # fleet control plane (keto_tpu/fleet/): lease-based election
    # through the shared SQL store — a replica with --fleet-enabled
    # contends for the primary lease when it expires and PROMOTES
    # in-process (tests/test_fleet.py, scripts/fleet_smoke.py)
    ap.add_argument("--fleet-enabled", action="store_true")
    ap.add_argument("--node-id", default="")
    ap.add_argument("--advertise-url", default="")
    ap.add_argument("--fleet-lease-ttl-s", type=float, default=2.0)
    ap.add_argument("--fleet-heartbeat-s", type=float, default=0.5)
    ap.add_argument("--fleet-promotion-grace-s", type=float, default=0.5)
    # live reshard: --reshard-delay-s after boot (and between steps),
    # rebuild the permission engine at each comma-separated --reshard-to
    # target in turn and install it under traffic; --mesh-graph pins the
    # STARTING geometry (0 = single device)
    ap.add_argument("--reshard-to", default="")
    ap.add_argument("--reshard-delay-s", type=float, default=2.0)
    ap.add_argument("--mesh-graph", type=int, default=0)
    # flight recorder (keto_tpu/x/flightrec.py): with a bundle dir the
    # daemon dumps anomaly bundles (scripts/flightrec_smoke.py drives it)
    ap.add_argument("--debug-bundle-dir", default="")
    ap.add_argument("--bundle-min-interval-s", type=float, default=0.5)
    # arm a fault spec only AFTER the first snapshot is built, so the
    # boot path cannot consume a count-limited fault meant for a live
    # request (e.g. device-alloc:oom:1); --armed-file is touched when
    # the faults are live so the parent can sequence its traffic
    ap.add_argument("--arm-after-ready", default="")
    ap.add_argument("--armed-file", default="")
    # parent-sequenced arming: the fault spec loads only once the parent
    # creates --arm-on-file (the fleet failover test boots a primary,
    # waits for its replica to catch up, THEN pulls the trigger)
    ap.add_argument("--arm-on-file", default="")
    ap.add_argument("--arm-on-file-spec", default="")
    args = ap.parse_args()

    # chaos daemons always serve from XLA's CPU backend: parents (tests,
    # bench.py's replica section) may hold a chip, which belongs to one
    # process at a time
    os.environ["JAX_PLATFORMS"] = "cpu"

    from keto_tpu.config.provider import Config
    from keto_tpu.driver.daemon import Daemon
    from keto_tpu.driver.registry import Registry

    overrides = {
        "namespaces": NAMESPACES,
        "dsn": args.dsn,
        "serve.read.port": args.read_port,
        "serve.write.port": args.write_port,
        "serve.snapshot_cache_dir": args.cache_dir,
        # small budget so a few dozen writes already exercise the
        # compaction path (and its crash point)
        "serve.overlay_edge_budget": args.overlay_budget,
        "serve.drain_timeout_s": args.drain_timeout_s,
        "engine.batch_window_ms": 0.5,
        "serve.role": args.role,
    }
    if args.role == "replica":
        overrides.update(
            {
                "serve.primary_url": args.primary_url,
                "serve.replica_dir": args.replica_dir,
                "serve.staleness_wait_ms": args.staleness_wait_ms,
                "serve.watch_poll_ms": 20,
            }
        )
    if args.fleet_enabled:
        overrides.update(
            {
                "serve.fleet_enabled": True,
                "serve.fleet_node_id": args.node_id,
                "serve.fleet_advertise_url": args.advertise_url,
                "serve.fleet_lease_ttl_s": args.fleet_lease_ttl_s,
                "serve.fleet_heartbeat_s": args.fleet_heartbeat_s,
                "serve.fleet_promotion_grace_s": args.fleet_promotion_grace_s,
            }
        )
    if args.mesh_graph > 0:
        overrides["serve.mesh_graph"] = args.mesh_graph
    if args.debug_bundle_dir:
        overrides.update(
            {
                "serve.debug_bundle_dir": args.debug_bundle_dir,
                "serve.debug_bundle_min_interval_s": args.bundle_min_interval_s,
            }
        )
    cfg = Config(overrides=overrides)
    daemon = Daemon(Registry(cfg))
    daemon.install_signal_handlers()
    daemon.serve_all(block=False)

    if args.arm_after_ready:
        import threading
        import time as _time

        def arm():
            from keto_tpu.x import faults

            engine = daemon.registry.permission_engine()
            deadline = _time.monotonic() + 60.0
            while _time.monotonic() < deadline:
                try:
                    if not hasattr(engine, "health") or engine.health().get(
                        "has_snapshot"
                    ):
                        break
                except Exception:
                    pass
                _time.sleep(0.05)
            faults.load_env(args.arm_after_ready)
            if args.armed_file:
                Path(args.armed_file).touch()

        threading.Thread(target=arm, name="chaos-arm", daemon=True).start()

    if args.arm_on_file and args.arm_on_file_spec:
        import threading
        import time as _time

        def arm_on_file():
            from keto_tpu.x import faults

            trigger = Path(args.arm_on_file)
            while not trigger.is_file():
                _time.sleep(0.05)
            faults.load_env(args.arm_on_file_spec)

        threading.Thread(
            target=arm_on_file, name="chaos-arm-on-file", daemon=True
        ).start()

    reshard_targets = [int(t) for t in args.reshard_to.split(",") if t.strip()]
    if reshard_targets:
        import threading
        import time as _time

        def reshard():
            for target in reshard_targets:
                _time.sleep(args.reshard_delay_s)
                try:
                    daemon.registry.reshard_coordinator().reshard(target)
                except Exception:
                    import traceback

                    traceback.print_exc()

        threading.Thread(target=reshard, name="chaos-reshard", daemon=True).start()

    ports = {"read": daemon.read_port, "write": daemon.write_port, "pid": os.getpid()}
    # atomic publish: the parent polls this file and must never read a
    # half-written JSON
    target = Path(args.port_file)
    fd, tmp = tempfile.mkstemp(dir=target.parent, prefix=".ports-")
    with os.fdopen(fd, "w") as f:
        json.dump(ports, f)
    os.replace(tmp, target)

    # block until a shutdown signal (bounded, looped — SIGTERM must
    # always terminate the wait), then leave through the drain path —
    # every clean exit in the chaos loop also regression-tests SIGTERM
    daemon.wait_for_shutdown()
    try:
        daemon.drain_and_shutdown()
    except BaseException:
        # a failed drain is a real finding: leave the traceback in the
        # harness log and exit distinctly from a generic crash
        import traceback

        traceback.print_exc()
        return 3
    return 0


if __name__ == "__main__":
    sys.exit(main())
