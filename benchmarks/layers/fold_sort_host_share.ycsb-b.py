from benchmarks.layer_clock import has
from benchmarks.layer_util import delta

SORTS = "keto_build_sort_total"


def read(run):
    """Sort batches the window's folds and delta splices ran on the host
    although they were large enough for the device (``why`` = ``cold``: the
    padded program was not compiled while a snapshot served; ``pressure``;
    ``error``), of all the batches that large, in percent. ``small`` batches
    (under 65,536 keys: numpy's by design) are on neither side. 0 in a window
    whose large sorts all found their program; None on a program without the
    family (every commit before PR 47)."""
    if not has(run, SORTS):
        return None
    device = delta(run, SORTS, backend="device")
    host = delta(run, SORTS, backend="host") - delta(run, SORTS, backend="host", why="small")
    return 100.0 * host / (host + device) if host + device > 0 else 0.0
