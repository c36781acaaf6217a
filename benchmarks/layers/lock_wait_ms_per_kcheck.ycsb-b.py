from benchmarks.layer_clock import has
from benchmarks.layer_util import delta

WAIT = "keto_engine_lock_wait_seconds_total"


def read(run):
    """What serving threads waited for the engine's lock inside the window,
    every site (``serving``: an unpinned round behind a fold or a delta
    apply; ``pinned``; ``latest``), per 1,000 correct checks, in ms: who waits
    for a fold, and how long. None on a program without the family."""
    if not has(run, WAIT):
        return None
    r = run.result
    correct = r["attempted"] - r["failed"] - r["wrong"]
    if correct <= 0:
        return None
    return delta(run, WAIT) * 1e3 / (correct / 1e3)
