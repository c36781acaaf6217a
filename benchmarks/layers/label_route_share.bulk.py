from benchmarks.layer_util import delta


def read(run):
    """Slices the label kernel answered, of all slices landed in the window."""
    total = delta(run, "keto_stream_route_slices_total")
    if total <= 0:
        return None
    return 100.0 * delta(run, "keto_stream_route_slices_total", route="label") / total
