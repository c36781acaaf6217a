"""Per-request timelines: where ONE slow check spent its time.

The metrics pipeline (keto_tpu/x/metrics.py) answers aggregate questions
— p99 moved, the shed rate spiked — but not the operator's next one:
*where did this specific request's 80 ms go*? Histograms sum away the
answer. This module records it per request: every stage a check / list /
expand passes through stamps a ``Timeline`` — arrival, the admission
verdict, lane queue wait, pack, dispatch, each device slice it rode
(width, BFS steps, label-vs-BFS route, halo rounds/bytes in sharded
mode), land, deliver — and the finished timeline is

- kept in a bounded ring buffer plus a top-K-slowest set, queryable at
  ``GET /debug/requests`` (filterable by trace id and snaptoken);
- emitted as child spans under the request's existing traceparent, so a
  distributed trace shows the in-process stage breakdown;
- summarized into a ``Server-Timing`` response header (REST) / trailing
  metadata (gRPC), so the CALLER sees the breakdown without any
  server-side query;
- mirrored into the ``keto_timeline_stage_duration_seconds{stage}``
  histogram, whose slowest samples carry trace-id exemplars.

The recorder is cheap enough to leave on (bench.py ``timeline_overhead``
gates the claim): a stamp is one ``perf_counter`` read and one list
append onto a pre-bounded list — no locks, no allocation beyond the
stamp tuple — and the ring/top-K bookkeeping runs once per request at
finish, under a single lock. ``serve.timeline_enabled: false`` turns
``begin`` into a constant ``None`` and every stamp site into a
``None``-check.
"""

from __future__ import annotations

import contextlib
import heapq
import threading
import time
from collections import deque
from contextvars import ContextVar
from typing import Any, Iterator, Optional

#: canonical stage names, in pipeline order (attrs ride the device stage:
#: width / bfs_steps / route / halo_rounds / halo_bytes / service_ms)
STAGES = (
    "arrival",    # correlation ids bound on a handler thread (timeline birth)
    "decode",     # body / query parsed into relation tuples
    "admit",      # passed the admission window / lane-capacity door
    "shed",       # refused at the door instead (terminal with admit)
    "cache_hit",  # answered from the replica check cache (no dispatch)
    "pack",       # taken off its lane into a dispatch round
    "dispatch",   # handed to the engine's streaming pipeline
    "device",     # one device slice landed (repeats per slice; carries attrs)
    "land",       # every tuple of the request has its decision
    "expand",     # expand tree built (host; carries depth / node count)
    "explain",    # witness reconstructed + verified (carries route/verified)
    "deliver",    # response handed back to the serving layer
)

#: listener-side label values of the stage histogram, observed by the
#: REST layer around the timeline (they lie outside its ``total``):
#: ``pool_wait`` is head+body read on the event loop -> the handler
#: entered on a pool thread; ``encode_write`` is the handler returning ->
#: the response encoded and flushed
LISTENER_STAGES = ("pool_wait", "encode_write")

#: the dispatch thread's states. The thread is in exactly one at any
#: time, so their seconds sum to its wall time
DISPATCH_STATES = (
    "wait_work",    # on the batcher's condition: for items, or the window
    "take",         # packing a round off the lanes
    "resolve",      # pulling the round's tuples, tuples -> node ids
    "pack",         # native or Python pack, staging
    "launch",       # H2D + the jitted call returning
    "device_wait",  # blocked on a slice's result
    "fill",         # futures, timelines, audit sample
)
WAIT_WORK, TAKE, RESOLVE, PACK, LAUNCH, DEVICE_WAIT, FILL = range(7)
_SPAN_NAMES = tuple(f"keto.dispatch.{s}" for s in DISPATCH_STATES)

#: what set the room a round had for batch-lane work (driver/batch.py
#: ``_take_locked``): the round's own size, the sub-slice while
#: interactive work is about, or the slice controller's cap
ROUND_CAP_BY = ("batch_size", "sub_slice", "controller")
#: a stay in one state at least this long is a long stay: several times the
#: longest state of a wide round (1-7 ms)
LONG_STAY_S = 0.016

#: cap on stamps one timeline may hold — a 64k-tuple batch riding many
#: sub-slices must not grow an unbounded stamp list (the flag records
#: that the tail was dropped, the ring stays bounded either way)
MAX_STAMPS = 48

_current_tl: ContextVar[Optional["Timeline"]] = ContextVar(
    "keto_tpu_timeline", default=None
)


def current_timeline() -> Optional["Timeline"]:
    """The timeline bound to the current request context, or None — the
    seam the batcher/engine stamp through without threading a recorder
    handle down the call stack."""
    return _current_tl.get()


class DispatchClock:
    """Where the dispatch thread's wall time goes, by state.

    Only the owning thread calls ``idle``/``round``/``enter``; a
    transition is one ``perf_counter`` read and one float add, whatever
    the round's size. ``snapshot`` is for the scraper, lock-free: a
    scrape that lands inside a transition may miss that one interval
    until the next scrape, it never counts one twice.

    While a ``jax.profiler`` session is open (``session.open``, read
    once per loop pass in ``idle``) each state is also a
    ``TraceAnnotation`` named ``keto.dispatch.<state>`` carrying the
    round's ``tuples``, ``slices`` launched so far, ``lane_depth``, and the
    room it had for batch-lane work with what set it, ``cap`` and ``cap_by``
    (a ``launch`` also its slice's ``route``, ``kernel`` and ``geometry``:
    the two launches of a ``hybrid`` slice differ in ``kernel``):
    contiguous spans on the device trace's clock, so an idle gap of the
    device is named by what its one feeder was doing, and a gap under a
    narrowed round says so (``wait_work`` and ``take`` come before the
    round is known: they say the one before's). ``mark`` writes a
    zero-length annotation there (the slice controller's events,
    ``keto.ctrl.<event>``)."""

    __slots__ = (
        "seconds", "rounds", "overlapped", "round_tuples", "round_cap", "long_stays",
        "_state", "_t", "_session", "_tracing", "_ann", "_tuples", "_slices",
        "_lane_depth", "_cap", "_cap_by", "_probes", "_gates",
    )

    def __init__(self, session=None):
        if session is None:
            from keto_tpu.x.profiling import SESSION as session
        self.seconds = [0.0] * len(DISPATCH_STATES)
        self.rounds = 0
        #: the rounds among ``rounds`` launched while another was open
        self.overlapped = 0
        #: the tuples those rounds took off the lanes, all together
        self.round_tuples = 0
        #: those rounds by what set their room, a ``ROUND_CAP_BY``
        self.round_cap = dict.fromkeys(ROUND_CAP_BY, 0)
        #: ``keto_dispatch_long_stay_seconds`` once the registry attached
        #: it (``dispatch_clock_metrics``); None: long stays are not kept
        self.long_stays = None
        self._probes: list = []
        self._state = WAIT_WORK
        self._t = time.perf_counter()
        self._session = session
        self._tracing = False
        self._ann = None
        self._tuples = self._slices = self._lane_depth = self._cap = 0
        self._cap_by = ROUND_CAP_BY[0]
        self._gates = None

    def enter(self, state: int, note=None) -> None:
        """``note``, on a ``launch``: ``(route, kernel, sizes, how the slice
        met its program)``, read only while a profiler session is open."""
        now = time.perf_counter()
        # _t moves first: snapshot() retries when it sees _t change
        t, self._t = self._t, now
        stay = now - t
        self.seconds[self._state] += stay
        if stay >= LONG_STAY_S and self.long_stays is not None:
            self.long_stays.observe((DISPATCH_STATES[self._state],), stay)
        self._state = state
        if self._tracing or self._ann is not None:
            self._annotate(state, note)
        if self._probes:
            self._ask(now)

    def _ask(self, now: float) -> None:
        self._probes = [p for p in self._probes if not p(now)]

    def watch(self, probe) -> None:
        """Ask ``probe(now) -> bool`` at every transition and ``poll``
        from here on, until it answers True. A round left on the device
        while the thread works on the next one has nobody waiting on it:
        this is how its slices get the time they were first SEEN ready,
        one ``is_ready()`` a slice and site while the round is open and
        nothing otherwise."""
        self._probes.append(probe)

    def poll(self) -> None:
        """Inside a long state: ask the probes, if there are any."""
        if self._probes:
            self._ask(time.perf_counter())

    def idle(self) -> None:
        """Top of a loop pass: back to ``wait_work``."""
        self._tracing = self._session.open
        self.enter(WAIT_WORK)

    def mark(self, name: str, **attrs) -> None:
        """A zero-length annotation on this thread, while a session is open."""
        if self._tracing:
            with self._session.annotation(name, **attrs):
                pass

    def gates(self, gated: int, positions: int) -> None:
        """Under a schema with gates (keto_tpu/check/gates.py): the spans
        from here on carry ``gated`` (checks whose closure reaches a gate)
        and ``positions`` (what they and the rest became on the device), of
        the chunk a ``resolve`` works on or of the slice a ``fill`` lands,
        until the next round."""
        if self._tracing:
            self._gates = {"gated": gated, "positions": positions}

    def resolving(self, path: str) -> None:
        """The chunk a ``resolve`` works on is about to be resolved by
        ``path`` (``native``: the one GIL-released pass of native/pack.cpp;
        ``numpy``: ``_resolve_records`` and ``_entry_counts``): the
        ``resolve`` span is opened anew, holds that call and what follows up
        to the chunk's ``pack``, and carries ``path``. A chunk the native
        pass hands back (an overlay miss) has a span of each. No clock is
        read: the state does not change."""
        if self._tracing:
            self._annotate(RESOLVE, extra={"path": path})

    def round(
        self, tuples: int, lane_depth: int, overlapped: bool = False,
        cap: int = 0, cap_by: str = ROUND_CAP_BY[0],
    ) -> None:
        """A round was taken: what its spans will say of it.
        ``overlapped``: another round is open (launched, not landed);
        ``cap``, ``cap_by``: the room it had for batch-lane work and which
        ``ROUND_CAP_BY`` set it."""
        self.rounds += 1
        self.overlapped += overlapped
        self.round_tuples += tuples
        self.round_cap[cap_by] += 1
        # ``idle`` is not passed while rounds follow each other without a
        # gap: a profiler session opened under load is seen here
        self._tracing = self._session.open
        self._tuples, self._slices, self._lane_depth = tuples, 0, lane_depth
        self._cap, self._cap_by = cap, cap_by
        self._gates = None

    def _annotate(self, state: int, note=None, extra=None) -> None:
        if self._ann is not None:
            self._ann.__exit__(None, None, None)
            self._ann = None
        if self._tracing:
            if state == LAUNCH:
                self._slices += 1
            attrs = dict(self._gates) if self._gates is not None else {}
            if extra is not None:
                attrs.update(extra)
            if note is not None:
                route, kernel, sizes, met = note
                attrs.update(
                    route=route, kernel=kernel,
                    geometry=f"{kernel} {'x'.join(map(str, sizes))} {met or 'untracked'}",
                )
            self._ann = self._session.annotation(
                _SPAN_NAMES[state], tuples=self._tuples, slices=self._slices,
                lane_depth=self._lane_depth, cap=self._cap, cap_by=self._cap_by, **attrs,
            )
            self._ann.__enter__()

    def snapshot(self) -> tuple[list[float], int]:
        """``(seconds by state, rounds)`` with the state in progress
        counted up to now."""
        for _ in range(4):
            t, state = self._t, self._state
            seconds = list(self.seconds)
            if self._t == t:
                break
        seconds[state] += max(0.0, time.perf_counter() - t)
        return seconds, self.rounds


class _NoClock:
    """What threads other than a dispatch thread get: the engine's
    transition sites stay unconditional."""

    __slots__ = ()

    def enter(self, state: int, note=None) -> None:
        pass

    def watch(self, probe) -> None:
        pass

    def poll(self) -> None:
        pass

    def mark(self, name: str, **attrs) -> None:
        pass

    def gates(self, gated: int, positions: int) -> None:
        pass

    def resolving(self, path: str) -> None:
        pass


_NO_CLOCK = _NoClock()
_dispatch = threading.local()


def bind_dispatch_clock(clock: Optional[DispatchClock]) -> None:
    """Make ``clock`` the calling thread's (the batcher's collector
    binds its own when it starts)."""
    _dispatch.clock = clock


def dispatch_clock():
    """The calling thread's state clock — a no-op one off the dispatch
    thread (library callers, warm-up, explain)."""
    return getattr(_dispatch, "clock", None) or _NO_CLOCK


def dispatch_clock_metrics(m, clock_of):
    """Declare the families a ``DispatchClock`` keeps beside its states
    (driver/registry.py calls this once; ``clock_of()`` is the serving
    batcher's clock, or None while there is none). Returns the long-stay
    histogram, for that clock's ``long_stays``."""

    def round_cap():
        clock = clock_of()
        counts = clock.round_cap if clock is not None else {}
        return [((by,), float(counts.get(by, 0))) for by in ROUND_CAP_BY]

    m.register_callback(
        "keto_dispatch_round_cap_total", "counter",
        "Dispatch rounds by what set the room they had for batch-lane work: "
        "batch_size (the round's own size), sub_slice (serve.batch_sub_slice, "
        "while interactive work is about), controller (the slice "
        "controller's cap was the least: a narrowed round). The values sum "
        "to keto_dispatch_rounds_total.",
        round_cap, ("by",),
    )
    hist = m.histogram(
        "keto_dispatch_long_stay_seconds",
        "Stays of the dispatch thread in one state of 16 ms or more (several "
        "times the longest state of a wide round), by state: _sum is the time "
        "they took. Outside wait_work a stall of the host, a compile on the "
        "thread or a slow device; in wait_work, callers that sent nothing.",
        ("state",), buckets=(0.064, 0.256, 1.024),
    )
    for state in DISPATCH_STATES:
        hist.seed((state,))
    return hist


class Timeline:
    """One request's stage stamps. ``stamp`` is the hot path: a
    perf_counter read and a list append; attrs allocate only when given."""

    __slots__ = (
        "kind", "surface", "trace_id", "parent_span_id", "request_id",
        "tenant", "status", "snaptoken", "start_unix", "_t0", "stamps",
        "truncated", "total_ms",
    )

    def __init__(
        self,
        kind: str,
        trace_id: str = "",
        request_id: str = "",
        surface: str = "http",
        parent_span_id: str = "",
        tenant: str = "",
    ):
        self.kind = kind
        self.surface = surface
        self.trace_id = trace_id
        self.parent_span_id = parent_span_id
        self.request_id = request_id
        #: the tenant the request addressed (multi-tenant mode) — "" on
        #: pre-tenancy surfaces; forensic bundles attribute blame by it
        self.tenant = tenant
        self.status: Any = None
        self.snaptoken: Optional[str] = None
        self.start_unix = time.time()
        self._t0 = time.perf_counter()
        #: [(stage, seconds-since-arrival, attrs-or-None), ...]
        self.stamps: list[tuple[str, float, Optional[dict]]] = []
        self.truncated = False
        self.total_ms: float = 0.0

    def stamp(self, stage: str, **attrs) -> None:
        if len(self.stamps) >= MAX_STAMPS:
            self.truncated = True
            return
        self.stamps.append(
            (stage, time.perf_counter() - self._t0, attrs or None)
        )

    def elapsed_ms(self) -> float:
        return (time.perf_counter() - self._t0) * 1e3

    def to_json(self) -> dict:
        """The /debug/requests (and flight-recorder bundle) rendering."""
        return {
            "kind": self.kind,
            "surface": self.surface,
            "trace_id": self.trace_id,
            "request_id": self.request_id,
            "tenant": self.tenant,
            "status": self.status,
            "snaptoken": self.snaptoken,
            "start_unix": round(self.start_unix, 6),
            "total_ms": round(self.total_ms, 3),
            "truncated": self.truncated,
            "stages": [
                {
                    "stage": stage,
                    "t_ms": round(t * 1e3, 3),
                    **({"attrs": attrs} if attrs else {}),
                }
                for stage, t, attrs in self.stamps
            ],
        }


class TimelineRecorder:
    """Bounded ring + top-K-slowest of finished request timelines.

    Lock discipline: the per-request hot path (``begin``/``stamp``) takes
    no lock at all — a timeline is owned by its request until ``finish``,
    which does the ring/heap/counter bookkeeping under one lock, once per
    request."""

    def __init__(
        self,
        capacity: int = 512,
        top_k: int = 32,
        enabled: bool = True,
    ):
        self.enabled = bool(enabled)
        self.capacity = max(16, int(capacity))
        self.top_k = max(1, int(top_k))
        self._lock = threading.Lock()  # guards: _ring, _slow, _seq, finished_by_surface
        self._ring: deque[Timeline] = deque(maxlen=self.capacity)
        # min-heap of (total_ms, seq, timeline): the root is the FASTEST
        # of the keep-set, evicted when a slower one arrives
        self._slow: list[tuple[float, int, Timeline]] = []
        self._seq = 0
        #: finished timelines per surface (the /metrics bridge reads this)
        self.finished_by_surface: dict[str, int] = {}
        self._tracer = None
        self._stage_hist = None

    # -- wiring ---------------------------------------------------------------

    def set_tracer(self, tracer) -> None:
        """Finished timelines emit child spans through ``tracer`` (one
        span per stage segment, under the request's traceparent)."""
        self._tracer = tracer

    def attach_stage_histogram(self, histogram) -> None:
        """Mirror per-stage segment durations into ``histogram`` (labels
        ``(stage,)``, seconds, trace-id exemplars)."""
        self._stage_hist = histogram

    # -- request lifecycle ----------------------------------------------------

    def begin(
        self,
        kind: str,
        trace_id: str = "",
        request_id: str = "",
        surface: str = "http",
        tenant: str = "",
    ) -> Optional[Timeline]:
        """A new timeline with its arrival stamp, or None when disabled.
        Called inside the request's server span so the child spans
        emitted at finish parent correctly."""
        if not self.enabled:
            return None
        parent = ""
        from keto_tpu.x.tracing import current_span_ids

        ids = current_span_ids()
        if ids is not None:
            trace_id = trace_id or ids[0]
            parent = ids[1]
        tl = Timeline(
            kind, trace_id=trace_id, request_id=request_id, surface=surface,
            parent_span_id=parent, tenant=tenant,
        )
        tl.stamp("arrival")
        return tl

    @contextlib.contextmanager
    def activate(self, tl: Optional[Timeline]) -> Iterator[None]:
        """Bind ``tl`` as the current request timeline for the block
        (what ``current_timeline()`` — the batcher's stamp seam —
        resolves to)."""
        if tl is None:
            yield
            return
        token = _current_tl.set(tl)
        try:
            yield
        finally:
            _current_tl.reset(token)

    def finish(
        self,
        tl: Optional[Timeline],
        status: Any = None,
        snaptoken: Optional[str] = None,
    ) -> None:
        """Seal ``tl``: deliver stamp, ring + top-K insertion, metric
        mirror, child-span emission. Accepts None so call sites stay
        unconditional."""
        if tl is None:
            return
        tl.stamp("deliver")
        tl.status = status
        tl.snaptoken = str(snaptoken) if snaptoken is not None else None
        tl.total_ms = tl.stamps[-1][1] * 1e3
        with self._lock:
            self._seq += 1
            seq = self._seq
            self._ring.append(tl)
            if len(self._slow) < self.top_k:
                heapq.heappush(self._slow, (tl.total_ms, seq, tl))
            elif tl.total_ms > self._slow[0][0]:
                heapq.heapreplace(self._slow, (tl.total_ms, seq, tl))
            self.finished_by_surface[tl.surface] = (
                self.finished_by_surface.get(tl.surface, 0) + 1
            )
        self._mirror(tl)
        self._emit_spans(tl)

    # -- export ---------------------------------------------------------------

    @staticmethod
    def _segments(tl: Timeline) -> list[tuple[str, float]]:
        """(stage, duration_s) per consecutive stamp pair — the time
        ATTRIBUTED to reaching each stage — with repeated stages (device
        slices of one batch) aggregated."""
        out: dict[str, float] = {}
        for i in range(1, len(tl.stamps)):
            stage = tl.stamps[i][0]
            out[stage] = out.get(stage, 0.0) + (
                tl.stamps[i][1] - tl.stamps[i - 1][1]
            )
        return list(out.items())

    def _mirror(self, tl: Timeline) -> None:
        hist = self._stage_hist
        if hist is None:
            return
        for stage, dur in self._segments(tl):
            hist.observe((stage,), dur, trace_id=tl.trace_id)

    def _emit_spans(self, tl: Timeline) -> None:
        tracer = self._tracer
        if tracer is None or not getattr(tracer, "enabled", False):
            return
        if not tl.trace_id:
            return
        base_ns = int(tl.start_unix * 1e9)
        for i in range(1, len(tl.stamps)):
            stage, t, attrs = tl.stamps[i]
            t_prev = tl.stamps[i - 1][1]
            tags = dict(attrs or {})
            tags["request_id"] = tl.request_id
            tracer.emit(
                f"timeline.{stage}",
                trace_id=tl.trace_id,
                parent_id=tl.parent_span_id or None,
                start_unix_ns=base_ns + int(t_prev * 1e9),
                duration_s=max(0.0, t - t_prev),
                **tags,
            )

    def server_timing(self, tl: Timeline) -> str:
        """The W3C ``Server-Timing`` header value: one ``<stage>;dur=<ms>``
        entry per stage segment plus the total."""
        parts = [
            f"{stage};dur={dur * 1e3:.2f}" for stage, dur in self._segments(tl)
        ]
        parts.append(f"total;dur={tl.total_ms:.2f}")
        return ", ".join(parts)

    def snapshot(
        self,
        recent: int = 50,
        slowest: int = 20,
        trace_id: Optional[str] = None,
        snaptoken: Optional[str] = None,
        tenant: Optional[str] = None,
    ) -> dict:
        """The /debug/requests body: newest-first recent timelines and
        the top-K slowest, filterable by trace id / snaptoken / tenant
        (noisy-neighbor forensics: one tenant's requests, isolated)."""
        with self._lock:
            ring = list(self._ring)
            slow = sorted(self._slow, key=lambda e: -e[0])
            finished = dict(self.finished_by_surface)

        def keep(tl: Timeline) -> bool:
            if trace_id and tl.trace_id != trace_id:
                return False
            if snaptoken and tl.snaptoken != str(snaptoken):
                return False
            if tenant and tl.tenant != tenant:
                return False
            return True

        recent_out = [tl.to_json() for tl in reversed(ring) if keep(tl)]
        slow_out = [tl.to_json() for _, _, tl in slow if keep(tl)]
        return {
            "enabled": self.enabled,
            "capacity": self.capacity,
            "finished": finished,
            "recent": recent_out[: max(0, int(recent))],
            "slowest": slow_out[: max(0, int(slowest))],
        }


#: process-wide disabled recorder (library callers before a registry)
NOOP = TimelineRecorder(enabled=False)

__all__ = [
    "STAGES",
    "LISTENER_STAGES",
    "DISPATCH_STATES",
    "DispatchClock",
    "dispatch_clock_metrics",
    "bind_dispatch_clock",
    "dispatch_clock",
    "MAX_STAMPS",
    "Timeline",
    "TimelineRecorder",
    "current_timeline",
    "NOOP",
]
