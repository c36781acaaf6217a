from benchmarks.daemon import DEVICE_ROUTES
from benchmarks.layer_util import delta

SLICES = "keto_stream_route_slices_total"


def read(run):
    """Slices landed by the ``hybrid`` route (the label kernel for the queries
    it can certify, a compacted ``check_step`` sub-batch for the rest), of all
    slices a device route landed inside the window. None where none did."""
    total = sum(delta(run, SLICES, route=r) for r in DEVICE_ROUTES)
    if total <= 0:
        return None
    return 100.0 * delta(run, SLICES, route="hybrid") / total
