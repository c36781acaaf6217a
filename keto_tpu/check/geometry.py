"""Which kernel programs this process has compiled, and what a slice does
when its own is not among them.

A slice's program is fixed by the snapshot's shape (row counts, bucket and
label array shapes), by statics it cannot choose (``it_cap``,
``block_iters``, the kernel variant) and by its **sizes**: the batch width
and the pads of its entry arrays. jax compiles a program on the thread that
first calls it, which for a served slice is the one dispatch thread, for a
second or more on the chip. So the engine keeps the set of sizes it has
compiled and asks it before every launch:

- ``compiled``: the slice's own sizes are there; it launches as packed.
- ``padded_up``: they are not, but a program at least as large in every size
  is (``warm_compile`` leaves one minimum rung per ladder width, so a slice
  whose entries outgrew its width's rung rides a wider width's). The slice is
  padded to it with the sentinels the warm-up already uses and answers
  bit-identically. Where the program it rides is wider than its own, a
  worker thread compiles the slice's own behind it (under a profiler
  session: a ``keto.geometry.compile`` span), which the next slice of
  those sizes launches as packed: what runs in the steady state is what an
  inline compile would have left, so a deployment that never leaves its
  warmed rung runs nothing else than before. A slice that rides a program of
  its own width pads entries only and asks for nothing: ``warm_compile``
  leaves such rungs where the snapshot shows hub rows (a sink gathered from
  rows by the hundred), whose riders bring thousands of entries for a few
  dozen queries.
- ``inline_compile``: nothing compiled fits (an engine nobody warmed, a
  snapshot shape met for the first time, a slice with more entries than the
  widest warmed rung holds): the launch compiles, as it always did, and is
  counted.

A set belongs to one kernel on one snapshot shape; a slice that arrives with
a shape not met before starts an empty one, not warmed. The sets of the last
few shapes are kept, so a deployment that goes back and forth between two
(an overlay there, then folded away) finds each as it left it, and what the
worker compiled for a shape that has gone meanwhile lands in that shape's set.
"""

from __future__ import annotations

import logging
import queue
import threading
from collections import Counter
from typing import Callable, Optional

from keto_tpu.x.profiling import SESSION

_log = logging.getLogger("keto_tpu.check.geometry")

COMPILED, PADDED_UP, INLINE = "compiled", "padded_up", "inline_compile"


class _Family:
    """One kernel's compiled sizes on one snapshot shape."""

    __slots__ = ("sizes", "warmed")

    def __init__(self):
        self.sizes: dict[tuple, set] = {}  # fixed -> {sizes}
        self.warmed = False


class KernelGeometries:
    """The compiled sizes per kernel, snapshot shape and ``fixed`` statics.
    ``compile_fn(kernel, shape, fixed, sizes) -> bool`` runs on the worker
    thread and compiles that program (False: the shape has gone)."""

    #: shapes a kernel's sets are kept for, the most recently met last
    KEEP_SHAPES = 4

    def __init__(self, compile_fn: Callable[[str, tuple, tuple, tuple], bool]):
        self._compile_fn = compile_fn
        self._lock = threading.Lock()  # guards everything below
        self._families: dict[tuple, _Family] = {}  # (kernel, shape) ->, in order of last use
        self._asked: set = set()  # (kernel, shape, fixed, sizes) ever handed to the worker
        self._inflight = 0  # of those, queued or compiling
        self._counts: Counter = Counter()  # (kernel, met) -> launches
        self._queue: queue.SimpleQueue = queue.SimpleQueue()
        self._worker: Optional[threading.Thread] = None
        self._closed = False

    def _family(self, kernel: str, shape: tuple) -> _Family:
        fam = self._families.pop((kernel, shape), None) or _Family()
        self._families[(kernel, shape)] = fam
        mine = [k for k in self._families if k[0] == kernel]
        for k in mine[: -self.KEEP_SHAPES]:
            del self._families[k]
        return fam

    def add(self, kernel: str, shape: tuple, fixed: tuple, sizes: tuple) -> None:
        """The program has been compiled (a warm-up or a launch ran it)."""
        with self._lock:
            self._family(kernel, shape).sizes.setdefault(fixed, set()).add(sizes)

    def mark_warmed(self, kernel: str, shape: tuple) -> None:
        """``warm_compile`` has been over this shape: from here a slice pads
        up before it compiles."""
        with self._lock:
            self._family(kernel, shape).warmed = True

    def meet(self, kernel: str, shape: tuple, fixed: tuple, sizes: tuple) -> tuple[tuple, str]:
        """``(sizes to launch at, how the slice met its program)``; after an
        ``inline_compile`` launch the caller ``add``s the sizes."""
        with self._lock:
            fam = self._family(kernel, shape)
            have = fam.sizes.get(fixed, ())
            met, use = INLINE, sizes
            if sizes in have:
                met = COMPILED
            elif fam.warmed:
                fits = [s for s in have if all(a >= b for a, b in zip(s, sizes))]
                if fits:
                    # the narrowest width first (the bitmaps are what a
                    # wider program costs), then the fewest entries
                    met, use = PADDED_UP, min(fits, key=lambda s: (s[-1], sum(s)))
                    if use[-1] > sizes[-1]:
                        # padded entries are dropped seeds and all-zero
                        # answer rows: only a wider bitmap is worth a
                        # program of the slice's own
                        self._ask(kernel, shape, fixed, sizes)
            self._counts[(kernel, met)] += 1
            return use, met

    def _ask(self, kernel: str, shape: tuple, fixed: tuple, sizes: tuple) -> None:
        job = (kernel, shape, fixed, sizes)
        if job in self._asked or self._closed:
            return
        # asked once: a compile that failed is not tried again a slice later
        self._asked.add(job)
        self._inflight += 1
        self._queue.put(job)
        if self._worker is None:
            self._worker = threading.Thread(
                target=self._work, name="keto-tpu-geometry-compile", daemon=True
            )
            self._worker.start()

    def _work(self) -> None:
        while True:
            job = self._queue.get()
            if job is None:
                return
            try:
                if not self._closed and self._compile(job):
                    self.add(*job)
            except Exception:
                # slices of these sizes keep riding the wider program
                _log.warning("background compile of %s %s failed", job[0], job[3], exc_info=True)
            with self._lock:
                self._inflight -= 1

    def _compile(self, job: tuple) -> bool:
        """The worker's compile; while a profiler session is open, under a
        ``keto.geometry.compile`` annotation on this thread that says which
        program: a compile behind the window is then named in the capture,
        not only seen as compiler internals."""
        if not SESSION.open:
            return self._compile_fn(*job)
        kernel, shape, _fixed, sizes = job
        with SESSION.annotation(
            "keto.geometry.compile", kernel=kernel, shape=str(shape),
            sizes="x".join(map(str, sizes)),
        ):
            return self._compile_fn(*job)

    def reset(self) -> None:
        """The compiled programs were dropped (warm-ladder eviction)."""
        with self._lock:
            self._families.clear()
            self._asked.clear()

    def close(self, timeout: float = 60.0) -> None:
        """No further compiles; waits for the one in flight."""
        with self._lock:
            self._closed = True
            worker = self._worker
        if worker is not None:
            self._queue.put(None)
            worker.join(timeout)

    def pending(self) -> int:
        """Background compiles queued or running (tests wait for 0)."""
        with self._lock:
            return self._inflight

    def counts(self) -> dict:
        """``{(kernel, met): launches}`` since the engine was built."""
        with self._lock:
            return dict(self._counts)
