"""The child's entry: ``keto_tpu.cmd``'s ``main`` unchanged, plus a helper
thread that this file owns. The benchmark's parent may not touch jax, so what
only the process that holds the chip can do happens here, on a signal:

- ``SIGUSR1``: start ``jax.profiler`` (host tracer on, Python tracer off)
  into ``$KETO_BENCH_CONTROL_DIR/trace`` and touch ``trace_started``;
- ``SIGUSR2``: stop a running trace, then write ``device.json``: platform,
  device kind, device count and the peak bytes in use on the fullest chip, as
  JAX reports them.
"""

from __future__ import annotations

import json
import os
import queue
import signal
import sys
import threading
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))


def _helper(control: Path, jobs: "queue.Queue[int]") -> None:
    tracing = False
    while True:
        sig = jobs.get()
        import jax  # by now the daemon has long imported and configured it

        if sig == signal.SIGUSR1 and not tracing:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 2
            jax.profiler.start_trace(str(control / "trace"), profiler_options=opts)
            tracing = True
            (control / "trace_started").touch()
        elif sig == signal.SIGUSR2:
            if tracing:
                jax.profiler.stop_trace()
                tracing = False
            devices = jax.local_devices()
            peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in devices]
            tmp = control / "device.json.tmp"
            tmp.write_text(json.dumps({
                "platform": devices[0].platform, "kind": devices[0].device_kind,
                "count": len(devices), "memory_peak_bytes": max(peaks),
            }))
            tmp.rename(control / "device.json")


def main() -> None:
    control = os.environ.get("KETO_BENCH_CONTROL_DIR")
    if control:
        jobs: "queue.Queue[int]" = queue.Queue()
        for sig in (signal.SIGUSR1, signal.SIGUSR2):
            signal.signal(sig, lambda signum, frame: jobs.put(signum))
        threading.Thread(target=_helper, args=(Path(control), jobs),
                         name="bench-helper", daemon=True).start()
    from keto_tpu.cmd import main as keto_main

    keto_main()


if __name__ == "__main__":
    main()
