"""Mesh construction helpers — single-host and multi-host (DCN plane).

The reference scales out as stateless server replicas over one SQL
database (reference internal/driver/registry_default.go:206-224,
persister.go:94-96). The TPU-native analog is a **multi-controller JAX
runtime**: every host runs the same serving process over the same tuple
store, `init_distributed` joins them into one runtime, and `make_mesh`
then builds a global ``(graph, data)`` mesh spanning every host's chips —
graph rows sharded across the pod, collectives riding ICI within a host
and DCN between hosts. Each process feeds identical host-side arrays
(the store is shared/replicated exactly like the reference's database),
so the SPMD program is the same everywhere; XLA keeps the processes in
lockstep."""

from __future__ import annotations

import os
from typing import Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh

GRAPH_AXIS = "graph"
DATA_AXIS = "data"


def _backend_initialized() -> bool:
    """Has jax already initialized a backend in this process? After that
    point, platform/device-count configuration is dead weight — the
    backend snapshotted the flags — so ``init_distributed`` must fail
    loudly instead of silently no-opping into a mis-provisioned mesh."""
    from jax._src import xla_bridge

    return bool(xla_bridge.backends_are_initialized())


def init_distributed(
    coordinator_address: str,
    num_processes: int,
    process_id: int,
    local_device_count: Optional[int] = None,
    platform: Optional[str] = None,
) -> None:
    """Join this process into a multi-controller JAX runtime.

    Call once per process before any device use; afterwards
    ``jax.devices()`` is global across hosts and ``make_mesh()`` builds a
    pod-wide mesh. ``local_device_count`` forces N virtual CPU devices
    per host (testing without a pod); ``platform`` pins the backend (e.g.
    ``"cpu"``). Both apply via jax's config/flag machinery, which is read
    at BACKEND initialization — they work after ``import jax`` but must
    run before the first device use in the process.

    **Lockstep contract:** a multi-controller engine executes one SPMD
    program across every host. All hosts must issue the same engine calls
    with identical inputs in identical order — same store contents, same
    batches, same write points (see the serving note in README.md). A
    front-end that replicates requests to every host in order provides
    this; independently load-balanced traffic does NOT.
    """
    if (platform or local_device_count is not None) and _backend_initialized():
        # both knobs apply via config/flags read at BACKEND initialization;
        # once a backend exists they are silently inert — which previously
        # produced a mesh over the wrong platform/device count with no
        # error until collectives hung. Fail loudly at the call site.
        raise RuntimeError(
            "init_distributed(platform=..., local_device_count=...) called "
            "after the jax backend was already initialized: the settings "
            "cannot take effect. Call init_distributed before any device "
            "use (jax.devices(), device_put, jit execution) in this "
            "process, or drop the platform/local_device_count overrides."
        )
    if platform:
        # env-var writes are useless here — jax snapshots JAX_PLATFORMS at
        # import — but the config entry is read at backend init
        jax.config.update("jax_platforms", platform)
    if local_device_count is not None:
        flag = "--xla_force_host_platform_device_count"
        flags = os.environ.get("XLA_FLAGS", "")
        if flag in flags:
            import re

            flags = re.sub(rf"{flag}=\d+", f"{flag}={local_device_count}", flags)
        else:
            flags = f"{flags} {flag}={local_device_count}"
        os.environ["XLA_FLAGS"] = flags.strip()
    jax.distributed.initialize(
        coordinator_address=coordinator_address,
        num_processes=num_processes,
        process_id=process_id,
    )


def make_mesh(
    devices: Optional[Sequence] = None,
    graph: int = 1,
    data: Optional[int] = None,
) -> Mesh:
    """A ``(graph, data)`` mesh over ``devices`` (default: all local devices).

    ``graph`` devices shard the graph's node rows; the rest shard query
    words. ``data=None`` uses every remaining device.
    """
    devices = list(jax.devices() if devices is None else devices)
    if data is None:
        if len(devices) % graph:
            raise ValueError(f"{len(devices)} devices not divisible by graph={graph}")
        data = len(devices) // graph
    n = graph * data
    if n > len(devices):
        raise ValueError(f"need {n} devices, have {len(devices)}")
    grid = np.asarray(devices[:n]).reshape(graph, data)
    return Mesh(grid, (GRAPH_AXIS, DATA_AXIS))
