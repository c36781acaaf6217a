"""``readwrite``'s traffic, reported by its reads: the same open loop of single
checks beside an open loop of single writes, each write followed at once by a
check pinned to its snaptoken, with ``check_p50_ms`` as the end-to-end metric,
which is the benchmark's own definition (single-check latency from scheduled
arrival, median) over the window's unpinned reads.

``prepare`` and ``warm`` are ``readwrite``'s. ``run`` calls ``readwrite.run``
and returns its result with ``metrics`` = ``{"check_p50_ms": extras
["read_p50_ms"]}`` and what a writer feels kept under keys of the result,
``write_visible_p50_ms`` and ``write_visible_p95_ms``, for the per-layer
readers. A read that failed, was shed, timed out or was answered outside the
analytic expectation is charged ``MISS_MS`` in that median, as ``open_single``
charges a check (``readwrite.reduce``: ``read_ms.append(MISS_MS)`` on both
paths), and so is a write's visibility. No other logic: the schedules, the write log,
the analytic expectation and the judge are the accepted driver's.
"""

from __future__ import annotations

from benchmarks.drivers import readwrite
from benchmarks.drivers.readwrite import _log_of, prepare, warm  # noqa: F401


def _by_its_reads(result: dict) -> dict:
    # the two write_visible_* values: keys of the result for the readers, and
    # among the extras so that an untraced run's ``window:`` line shows them
    result.update(result["metrics"])
    result["extras"].update(result["metrics"])
    result["metrics"] = {"check_p50_ms": result["extras"]["read_p50_ms"]}
    return result


def run(ctx, state, seconds: float) -> dict:
    return _by_its_reads(readwrite.run(ctx, state, seconds))


def reduce(state, phase, reads, writes) -> dict:
    """What the controls call (``control_writes.py`` plays a run against a
    server made of the reference and hands its records here)."""
    return _by_its_reads(readwrite.reduce(state, phase, reads, writes))
