"""With HEAD_PROBE_OUT set: time `TpuCheckEngine._snapshot_for` (the head of
the dispatch thread's `resolve` state: the store's watermark read once a
round) in whatever process imports the engine, and append the totals to that
file every 2 s. No tree is edited: put this directory on PYTHONPATH of a benchmark run
(`PYTHONPATH=<path to>/scripts/head_probe HEAD_PROBE_OUT=<file> python3 benchmarks/run.py ...`);
the last line of the file holds calls and seconds. Without the variable it does nothing."""
import os
import sys
import threading
import time

out = os.environ.get("HEAD_PROBE_OUT")
if out:
    acc = {"n": 0, "s": 0.0}

    def patch():
        for _ in range(60000):
            mod = sys.modules.get("keto_tpu.check.tpu_engine")
            if mod is not None and hasattr(mod, "TpuCheckEngine"):
                break
            time.sleep(0.01)
        else:
            return
        cls = mod.TpuCheckEngine
        orig = cls._snapshot_for

        def timed(self, at_least, mode):
            t = time.perf_counter()
            try:
                return orig(self, at_least, mode)
            finally:
                acc["n"] += 1
                acc["s"] += time.perf_counter() - t

        cls._snapshot_for = timed
        while True:
            time.sleep(2.0)
            with open(out, "a") as f:
                f.write(f"{time.time():.1f} {acc['n']} {acc['s']:.6f}\n")

    threading.Thread(target=patch, daemon=True).start()
