from benchmarks.layer_clock import has
from benchmarks.layer_util import delta

FAMILY = "keto_admission_decreases_total"


def read(run):
    """Multiplicative decreases of the admission window inside the window,
    whatever signal tripped them: each starts an episode of refused calls."""
    return delta(run, FAMILY) if has(run, FAMILY) else None
