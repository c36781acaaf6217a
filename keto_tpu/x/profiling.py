"""Profiling guard.

The analog of the reference's profilex wiring in main (reference
main.go:25-28; config key ``profiling``, config.schema.json:271-280):
``profiling: cpu`` wraps the process in cProfile, ``profiling: mem`` in
tracemalloc, ``profiling: trace`` captures a jax.profiler device trace
(kernel timeline, viewable in TensorBoard/Perfetto). Stats print to
stderr on clean shutdown.

``SESSION`` says whether a ``jax.profiler`` session is open in this
process. The dispatch thread's state clock (keto_tpu/x/timeline.py
``DispatchClock``) reads it once per round and, only while it is set,
writes its states into the capture as ``keto.dispatch.<state>``
annotations — on the device trace's own clock. The flag is kept by ONE
wrapper over ``jax.profiler.start_trace``/``stop_trace``
(``install_trace_hook``, installed when a device engine is built and by
``profiling: trace``), so every caller of those two functions is seen —
this module's ``trace`` mode, an embedding program, a benchmark — and
nothing polls. Captures that bypass the two functions (the
``jax.profiler.trace`` context manager, a profiler server) are not seen.
"""

from __future__ import annotations

import atexit
import os
import sys
from typing import Optional


class ProfilerSession:
    """Is a ``jax.profiler`` session open? A jax process has at most one,
    so the process has one of these (``SESSION``)."""

    def __init__(self):
        #: read by the dispatch thread once per round, written only by
        #: the wrappers below
        self.open = False
        #: ``jax.profiler.TraceAnnotation`` once the hook is installed
        self.annotation = None


SESSION = ProfilerSession()


def install_trace_hook() -> Optional[ProfilerSession]:
    """Wrap ``jax.profiler.start_trace``/``stop_trace`` so ``SESSION.open``
    follows them; idempotent. None when jax is unavailable."""
    try:
        import jax
    except Exception:
        return None
    if SESSION.annotation is not None:
        return SESSION
    start, stop = jax.profiler.start_trace, jax.profiler.stop_trace

    def start_trace(*args, **kwargs):
        start(*args, **kwargs)
        SESSION.open = True

    def stop_trace(*args, **kwargs):
        # cleared first: an annotation opened after the session closed
        # would be dropped anyway, one opened before it closes is kept
        SESSION.open = False
        return stop(*args, **kwargs)

    start_trace.__wrapped__, stop_trace.__wrapped__ = start, stop
    jax.profiler.start_trace, jax.profiler.stop_trace = start_trace, stop_trace
    SESSION.annotation = jax.profiler.TraceAnnotation
    return SESSION


def attach(mode: str) -> None:
    """Install the requested profiler for the process lifetime."""
    if mode == "cpu":
        import cProfile
        import pstats

        profiler = cProfile.Profile()
        profiler.enable()

        def dump():
            profiler.disable()
            pstats.Stats(profiler, stream=sys.stderr).sort_stats("cumulative").print_stats(40)

        atexit.register(dump)
    elif mode == "mem":
        import tracemalloc

        tracemalloc.start(10)

        def dump():
            snapshot = tracemalloc.take_snapshot()
            print("== top allocations ==", file=sys.stderr)
            for stat in snapshot.statistics("lineno")[:25]:
                print(stat, file=sys.stderr)

        atexit.register(dump)
    elif mode == "trace":
        # device-timeline trace via jax.profiler: TPU kernels, host-device
        # transfers, compilation and the dispatch thread's states all land
        # in the capture. Degrades to a no-op when jax (or its profiler
        # backend) is unavailable — the config stays valid on CPU-only
        # and stripped installs.
        if install_trace_hook() is None:
            print("profiling: trace requested but jax is unavailable; skipping",
                  file=sys.stderr)
            return
        import jax

        trace_dir = os.environ.get("KETO_TPU_TRACE_DIR") or os.path.join(
            os.getcwd(), "keto-tpu-trace"
        )
        try:
            jax.profiler.start_trace(trace_dir)
        except Exception as e:
            print(f"profiling: jax trace unavailable ({e!r}); skipping",
                  file=sys.stderr)
            return

        def dump():
            try:
                jax.profiler.stop_trace()
                print(f"== jax profiler trace written to {trace_dir} ==",
                      file=sys.stderr)
            except Exception as e:
                # mirror the start-path degradation: say WHY the trace is
                # missing instead of exiting with no artifact and no hint
                print(f"profiling: jax trace finalization failed ({e!r})",
                      file=sys.stderr)

        atexit.register(dump)
    elif mode:
        raise ValueError(f"unknown profiling mode {mode!r} (want cpu|mem|trace)")
