from benchmarks.layer_clock import COMPILE_S, has


def read(run):
    """Seconds the runtime spent in backend compiles before the window:
    boot, label build, ladder warm-up and the warm pass."""
    return run.before.get(COMPILE_S) if has(run, COMPILE_S) else None
