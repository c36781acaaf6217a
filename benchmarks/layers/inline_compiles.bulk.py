from benchmarks.layer_clock import has
from benchmarks.layer_util import delta

GEOMETRY = "keto_kernel_geometry_total"


def read(run):
    """Slices launched inside the window whose program was compiled by the
    launch itself, on the dispatch thread: should be none. None on a program
    without the counter."""
    if not has(run, GEOMETRY):
        return None
    return delta(run, GEOMETRY, met="inline_compile")
