from benchmarks.layer_clock import DISPATCH, dispatch_busy_s
from benchmarks.layer_util import delta


def read(run):
    """Of the dispatch thread's working time, the share it spent blocked on
    a slice's result."""
    busy = dispatch_busy_s(run)
    if not busy:
        return None
    return 100.0 * delta(run, DISPATCH, state="device_wait") / busy
