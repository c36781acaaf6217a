"""What the readers of the slice controller's and the dispatch clock's
decision counters share (PR 41): all from the two ``/metrics`` scrapes around
the window. A program without a family (every commit before PR 41 run under
this benchmark) scrapes nothing under its name: the reader returns None and
the line leaves the metric out."""

from __future__ import annotations

from benchmarks.layer_clock import has
from benchmarks.layer_util import delta

ROUND_CAP = "keto_dispatch_round_cap_total"
CHUNKS = "keto_stream_chunks_total"
CTRL_EVENTS = "keto_stream_ctrl_events_total"
LONG_STAY = "keto_dispatch_long_stay_seconds"


def share(run, family: str, **labels):
    """The window's delta of one label value of a counter over that of all
    its values, in percent; None without the family or without a delta."""
    if not has(run, family):
        return None
    total = delta(run, family)
    if total <= 0:
        return None
    return 100.0 * delta(run, family, **labels) / total


def long_stay_ms(run):
    """Time the dispatch thread lost inside the window to stays of 16 ms or
    more in one state, every state but ``wait_work`` (there it waits for
    callers), in ms: the histogram's ``_sum``. 0 in a window without one."""
    if not has(run, LONG_STAY):
        return None
    name = LONG_STAY + "_sum"
    return 1e3 * (delta(run, name) - delta(run, name, state="wait_work"))
