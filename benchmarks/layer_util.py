"""What the per-layer readers share. A reader is ``read(run) -> number or
None``; ``run`` carries the two ``/metrics`` scrapes around the window
(``before``, ``after``), the driver's ``result``, the reduced device trace
(``trace``, None without ``--trace 1``), the set-up ``stamps`` and the compile
cache's entry counts. A reader that finds nothing to read returns None and the
harness leaves the metric out of the line."""

from __future__ import annotations


def delta(run, name: str, **labels) -> float:
    return run.after.get(name, **labels) - run.before.get(name, **labels)


def hist_mean_ms(run, name: str, **labels):
    """Mean of a histogram's observations inside the window, in ms."""
    n = delta(run, name + "_count", **labels)
    if n <= 0:
        return None
    return delta(run, name + "_sum", **labels) / n * 1e3


def serve_overhead_ms(run):
    """Client-side median minus the median of ``Server-Timing``'s total: what
    the mux, REST layer, JSON and the loopback add around the timeline."""
    client, server = run.result.get("client_ms_median"), run.result.get("server_ms_median")
    if client is None or server is None:
        return None
    return client - server


def queue_ms(run):
    """Timeline segment admit -> pack: the wait on a lane for a dispatch round."""
    return hist_mean_ms(run, "keto_timeline_stage_duration_seconds", stage="pack")


def slice_ms(run):
    """Slice service time as the host sees it: staging, H2D, kernel, D2H."""
    return hist_mean_ms(run, "keto_engine_stream_slice_duration_seconds")


def window_compiles(run):
    """Compile-cache entries added inside the window: should be none."""
    return run.cache_after - run.cache_before
