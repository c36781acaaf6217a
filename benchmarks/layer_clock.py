"""What the readers of the program's own clocks share: the dispatch thread's
state clock, the REST listener's stages, the admission controller's signals
and the runtime's compile counts, all read from the two ``/metrics`` scrapes
around the window. A program that lacks a family (an older commit run under
this benchmark) scrapes nothing under its name: every reader here then
returns None, and the line leaves the metric out."""

from __future__ import annotations

from benchmarks.layer_util import delta, hist_mean_ms

STAGES = "keto_timeline_stage_duration_seconds"
DISPATCH = "keto_dispatch_thread_seconds_total"
COMPILE_S = "keto_compile_seconds_total"


def has(run, family: str) -> bool:
    """Counters and gauges scrape under their own name; a histogram under
    ``_count``."""
    return bool(run.after.series(family) or run.after.series(family + "_count"))


def stage_ms(stage: str):
    """A reader: mean of one label value of the stage histogram inside the
    window, in ms."""
    return lambda run: hist_mean_ms(run, STAGES, stage=stage)


def dispatch_busy_s(run):
    """Seconds of the window the dispatch thread spent in any state but
    ``wait_work``."""
    if not has(run, DISPATCH):
        return None
    return delta(run, DISPATCH) - delta(run, DISPATCH, state="wait_work")


def window_compile_s(run):
    """Seconds the runtime spent in backend compiles inside the window:
    should be none."""
    return delta(run, COMPILE_S) if has(run, COMPILE_S) else None
