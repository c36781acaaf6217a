from benchmarks.layer_clock import has
from benchmarks.layer_ctrl import CTRL_EVENTS
from benchmarks.layer_util import delta


def read(run):
    """Slice readings over ``NARROW_FRAC`` x the slice target inside the
    window, every route: each is what a narrow regime of the slice controller
    starts from. 0 in a steady window; None where the program does not count
    them."""
    if not has(run, CTRL_EVENTS):
        return None
    return delta(run, CTRL_EVENTS, event="narrow")
