"""Request-coalescing check batcher with priority lanes.

The reference serves one goroutine per request, each paying its own
traversal (reference internal/driver/daemon.go:62-69). On TPU the economics
invert: one device program answers thousands of checks, so concurrent
single-check requests are *coalesced* — a caller enqueues its tuple and
blocks on a future; a collector thread drains the queues up to
``batch_size`` or ``window_ms`` (whichever first) and dispatches one
``batch_check``. This is the serving-plane analog of the data-parallel axis
(SURVEY §2.3: request concurrency → batch parallelism).

PRIORITY LANES. A single FIFO convoys: one interactive check behind a
64k-wide batch request waits the whole batch's service time, which is
exactly the p50≈100 ms / p99≈2 s shape every bench round shows. The
batcher therefore keeps TWO lanes:

- ``interactive`` — single checks and small batches (≤
  ``interactive_max_tuples``): packed into the **next** dispatch round
  ahead of all queued batch work.
- ``batch`` — pre-batched chunks. While interactive work is about (an
  item queued at the take, or one that rode this round or the one before
  it, which may still be on the device) they are dispatched in bounded
  **sub-slices** (≤ ``batch_sub_slice`` tuples per round), so a monster
  request interleaves with the interactive lane instead of owning the
  device for its full width. While the interactive lane is quiet there
  is nobody to interleave with, and a round takes batch-lane work up to
  its own cap (``batch_size``): what a round costs the dispatch thread
  is mostly paid per round, not per tuple. A small reserve
  (``batch_reserve_share`` of the round) keeps the batch lane from
  starving when interactive traffic alone can fill every round.

Lane choice: explicit (``lane=``, from the REST ``X-Keto-Priority``
header / gRPC ``x-keto-priority`` metadata) or by size. ADMISSION
CONTROL: when an ``AdmissionController`` (keto_tpu/driver/admission.py)
is attached, batch-lane arrivals beyond its AIMD window shed 429 +
``Retry-After`` at the door — overload converts to explicit backpressure
before it becomes queue delay, and interactive p99 stays flat through
bursts.

Against the TPU engine the dispatch is STREAMING: each round goes
through ``batch_check_stream_with_token(ordered=False)`` — the engine's
latency-adaptive ready-order pipeline — and each caller's future resolves
the moment its slice lands, re-associated by query offset.

ONE ROUND OF LOOK-AHEAD. A round whose slices are all launched is on the
device and needs nothing of this thread until it lands. If batch-lane
work is queued at that point, the collector takes the next round and
resolves, packs and launches it first, then lands and fills the older
one, whose result has arrived meanwhile. If none is queued it lands at
once: a single check is answered sooner by landing than by looking
ahead. Two rounds are open at most, the one on the device and the one
being launched behind it (``_loop`` holds one and launches one: there is
no third to configure): a third would only wait behind two and hold its
riders' decisions back.
"""

from __future__ import annotations

import logging
import threading
import time
from bisect import bisect_right
from collections import deque
from concurrent.futures import Future, InvalidStateError
from concurrent.futures import TimeoutError as FutureTimeout
from typing import TYPE_CHECKING, Optional, Sequence

from keto_tpu.check.frame import QueryBatch, QueryFrame, as_tuples
from keto_tpu.relationtuple.model import RelationTuple
from keto_tpu.x import faults
from keto_tpu.x.errors import ErrDeadlineExceeded, ErrTooManyRequests, KetoError
from keto_tpu.x.timeline import (
    FILL, RESOLVE, TAKE, DispatchClock, bind_dispatch_clock, current_timeline,
)

if TYPE_CHECKING:
    from keto_tpu.driver.admission import AdmissionController

_log = logging.getLogger("keto_tpu.batch")

#: decisions handed to a request per ``_fill`` call: a landed slice is
#: written back in runs of at most this many. ``_fill`` is the one seam
#: where a decision reaches its request, and the benchmark's own suite
#: breaks the served path there on purpose (benchmarks/tests/
#: broken_entry.py alters every 997th call) and has to see it inside a
#: 3 s window — at one call a rider it would not. 16 calls for a round of
#: 1,024 cost ~20 us of a round of ~5 ms.
_FILL_RUN = 64

INTERACTIVE = "interactive"
BATCH = "batch"
LANES = (INTERACTIVE, BATCH)


class _Item:
    """One queued request: a single tuple (the common case) or a
    pre-batched chunk — a list of ``RelationTuple`` or, for a framed
    ``/check/batch`` body, a ``QueryFrame`` (keto_tpu/check/frame.py).
    Chunks are consumed in bounded sub-slices across dispatch rounds; the
    future resolves once every tuple has a result."""

    __slots__ = (
        "tuples", "fut", "at_least", "latest", "deadline", "lane",
        "results", "taken", "remaining", "tl",
    )

    def __init__(self, tuples, fut, at_least, latest, deadline, lane, tl=None):
        self.tuples = tuples
        self.fut = fut
        self.at_least = at_least
        self.latest = latest
        self.deadline = deadline
        self.lane = lane
        self.results: list = [None] * len(tuples)
        self.taken = 0  # tuples already handed to a dispatch round
        self.remaining = len(tuples)  # results not yet filled in
        #: the caller's request timeline (keto_tpu/x/timeline.py), bound
        #: by the serving layer; the batcher stamps queue/pack/dispatch/
        #: device/land stages through it. None when recording is off.
        self.tl = tl

    @property
    def n(self) -> int:
        return len(self.tuples)


class _Round:
    """One dispatch round's segments as the engine consumes them. A
    segment is looked at when the engine first reaches it, not before:
    an item that finished or expired while earlier slices ran never
    occupies a device slice (an expired request in a slice would displace
    a live one), and its caller hears 504 at once. Each segment that goes
    in is noted with the stream offset it starts at, which is how landed
    slices find their riders again: by range, not by tuple.

    ``take(cap)`` is the engine's stream API (ranges of lists and frames,
    no tuple touched); iterating yields ``RelationTuple``s one by one, for
    the CPU-oracle stream and engines that know nothing of ``take``."""

    __slots__ = (
        "_expire", "_segments", "_held", "offs", "live", "n",
        "segments", "n_tuples", "busy_s", "gen", "token", "want_info",
    )

    def __init__(self, segments, expire):
        self._expire = expire
        #: as taken off the lanes: what a failed round retries
        self.segments = segments
        self.n_tuples = sum(count for _, _, count in segments)
        #: seconds the dispatch thread spent on THIS round (its launch,
        #: its landing and fills; not the other open round's launch in
        #: between): the admission controller's rate is tuples over these
        self.busy_s = 0.0
        #: the engine's stream while the round is open, and what it said
        self.gen = None
        self.token = None
        self.want_info = False
        self._segments = iter(segments)
        self._held = None  # the part of a segment a slice had no room for
        #: ``live[k]`` = (item, start, count) entered the stream at ``offs[k]``
        self.offs: list[int] = []
        self.live: list[tuple] = []
        self.n = 0  # queries handed to the engine so far

    def _next_live(self):
        for item, start, count in self._segments:
            if item.fut.done():
                continue
            if item.deadline is not None and time.monotonic() >= item.deadline:
                self._expire(item)
                continue
            if item.tl is not None:
                item.tl.stamp("dispatch")
            self.offs.append(self.n)
            self.live.append((item, start, count))
            self.n += count
            return item.tuples, start, start + count
        return None

    def take(self, cap: int):
        """Up to ``cap`` queries: a list of ``RelationTuple`` while the
        round holds nothing framed, else a ``QueryBatch``. Empty at the
        round's end."""
        parts = []
        room = cap
        while room > 0:
            part = self._held or self._next_live()
            if part is None:
                break
            src, a, b = part
            cut = min(b, a + room)
            self._held = (src, cut, b) if cut < b else None
            parts.append((src, a, cut))
            room -= cut - a
        if any(isinstance(src, QueryFrame) for src, _, _ in parts):
            return QueryBatch(parts)
        if len(parts) == 1:
            src, a, b = parts[0]
            return src if (a, b) == (0, len(src)) else src[a:b]
        return [t for src, a, b in parts for t in src[a:b]]

    def __iter__(self):
        while True:
            part = self._next_live()
            if part is None:
                return
            src, a, b = part
            yield from as_tuples(src, "oracle")[a:b]

    @property
    def items(self) -> list:
        return [item for item, _, _ in self.segments]

    def asked(self) -> tuple[list, list]:
        """The riders' ``at_least`` floors and ``latest`` flags: what the
        round's snapshot has to satisfy (``_consistency_kw``)."""
        return (
            [item.at_least for item, _, _ in self.segments],
            [item.latest for item, _, _ in self.segments],
        )

    def riders(self, off: int, nq: int):
        """The segments a landed slice ``[off, off + nq)`` answers, as
        ``(item, idx, lo, hi)``: ``out[lo:hi]`` are the decisions for
        ``item``'s tuples at ``idx``, a range."""
        k = bisect_right(self.offs, off) - 1
        end = off + nq
        while k < len(self.offs) and self.offs[k] < end:
            item, start, count = self.live[k]
            seg_off = self.offs[k]
            lo, hi = max(off, seg_off), min(end, seg_off + count)
            if lo < hi:
                first = start + lo - seg_off
                yield item, range(first, first + hi - lo), lo - off, hi - off
            k += 1


class CheckBatcher:
    def __init__(
        self,
        engine,
        batch_size: int = 4096,
        window_ms: float = 1.0,
        max_pending: Optional[int] = None,
        shed_on_full: bool = False,
        interactive_max_tuples: int = 16,
        batch_sub_slice: Optional[int] = None,
        batch_reserve_share: float = 0.125,
        admission: Optional["AdmissionController"] = None,
        tenant: Optional[str] = None,
    ):
        """``engine`` needs ``batch_check(list[RelationTuple]) -> list[bool]``.

        ``max_pending`` bounds each lane's queued tuples (default
        8×batch_size): when the device can't keep up, callers block in
        ``check`` up to their own deadline instead of growing an unbounded
        backlog — backpressure propagates to the accepting sockets rather
        than to memory. With ``shed_on_full`` (what the registry
        configures for serving processes), a full lane instead *sheds
        immediately* with ``ErrTooManyRequests`` (REST 429 + Retry-After /
        gRPC RESOURCE_EXHAUSTED) — the client learns it should back off
        *now*, seconds ahead of the future timeout it would otherwise
        burn. ``admission`` (an AdmissionController) additionally sheds
        batch-lane arrivals beyond its adaptive window.

        ``tenant`` names the tenant this batcher serves (multi-tenant
        mode, keto_tpu/driver/tenants.py): every shed error then carries
        it in ``details`` so the serving layers answer with an
        ``X-Keto-Tenant`` header, and ``retry_after_s`` comes from THIS
        batcher's admission controller — one tenant's consecutive
        overloaded ticks never inflate another tenant's backoff."""
        self._engine = engine
        #: tenant identity stamped onto shed errors (None = untagged)
        self.tenant = tenant
        #: optional ``fn(tenant, lane)`` invoked on every shed — the
        #: TenantPool's shed-rate spike tracker. Called under ``_cond``;
        #: the callback must not re-enter this batcher.
        self.on_shed = None
        self._batch_size = batch_size
        self._window_s = window_ms / 1e3
        self._max_pending = max_pending or 8 * batch_size
        self._shed_on_full = shed_on_full
        self._interactive_max_tuples = max(1, interactive_max_tuples)
        self._sub_slice = max(1, batch_sub_slice or max(1, batch_size // 4))
        self._batch_reserve = max(1, int(batch_size * batch_reserve_share))
        #: whether the round taken last carried interactive work (it may
        #: still be on the device); the collector thread's alone
        self._inter_rode = False
        #: the room the round taken last had for batch-lane work and what
        #: set it (``_take_locked``); the collector thread's alone
        self._round_room = (batch_size, "batch_size")
        self.admission = admission
        self._cond = threading.Condition()  # guards: _lanes, _lane_tuples, _current_round, shed_count, shed_by_lane, admission_shed_count
        self._lanes: dict[str, deque] = {lane: deque() for lane in LANES}
        self._lane_tuples: dict[str, int] = {lane: 0 for lane in LANES}
        #: items taken into the open dispatch rounds, both of them when
        #: two are (failed promptly by ``stop`` so no caller ever hangs on
        #: a dead collector)
        self._current_round: list[_Item] = []
        #: requests refused at the door (lane full or admission window)
        self.shed_count = 0
        self.shed_by_lane: dict[str, int] = {lane: 0 for lane in LANES}
        #: the admission-window subset of ``shed_count``
        self.admission_shed_count = 0
        #: requests dropped at dispatch because their deadline had passed
        self.deadline_drop_count = 0
        #: the collector thread's state clock (x/timeline.DispatchClock):
        #: written by that thread alone, read by the /metrics bridge
        self.clock = DispatchClock()
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        # in-flight accounting for graceful drain: accepted requests whose
        # futures have not resolved yet (queued OR dispatched)
        self._inflight = 0
        self._inflight_lock = threading.Lock()  # guards: _inflight
        self._idle = threading.Event()
        self._idle.set()

    def set_engine(self, engine) -> None:
        """Live-reshard handoff: point subsequent dispatch rounds at a
        new engine. The collector reads ``self._engine`` per dispatch,
        so in-flight rounds finish on the old engine (which keeps a
        valid snapshot until released) and the swap needs no quiesce."""
        self._engine = engine

    def peek_snapshot(self):
        """The snapshot the engine behind this batcher is serving right
        now, or None (no device engine, nothing built yet, a cold tenant):
        a plain read for the thread that frames a ``/check/batch`` body
        (``QueryFrame.resolve_at_door``). The round still chooses its own."""
        peek = getattr(self._engine, "peek_snapshot", None)
        return None if peek is None else peek()

    # -- lifecycle -----------------------------------------------------------

    def start(self) -> None:
        if self._thread:
            return
        self._thread = threading.Thread(target=self._loop, name="check-batcher", daemon=True)
        self._thread.start()

    def stop(self) -> None:
        self._stop.set()
        with self._cond:
            self._cond.notify_all()
        if self._thread:
            self._thread.join(timeout=5)
            self._thread = None
        # requests still queued (or stranded in a wedged dispatch round)
        # would otherwise block their callers for the full future timeout
        # — fail them promptly with a definitive error instead
        with self._cond:
            leftovers = []
            for lane in LANES:
                leftovers.extend(self._lanes[lane])
                self._lanes[lane].clear()
                self._lane_tuples[lane] = 0
            leftovers.extend(self._current_round)
            self._cond.notify_all()
        for item in leftovers:
            if not item.fut.done():
                try:
                    item.fut.set_exception(RuntimeError("check batcher stopped"))
                except InvalidStateError:
                    pass

    # -- API -----------------------------------------------------------------

    def check(
        self,
        tuple_: RelationTuple,
        timeout: Optional[float] = 30.0,
        *,
        at_least: Optional[int] = None,
        latest: bool = False,
        deadline: Optional[float] = None,
        lane: Optional[str] = None,
    ) -> bool:
        """Blocking single check, transparently batched with concurrent
        callers. Default consistency is the serving mode (bounded
        staleness, never stalled by a rebuild); ``at_least`` pins a
        caller's snaptoken, ``latest`` forces read-your-writes."""
        return self.check_with_token(
            tuple_, timeout, at_least=at_least, latest=latest, deadline=deadline,
            lane=lane,
        )[0]

    def check_with_token(
        self,
        tuple_: RelationTuple,
        timeout: Optional[float] = 30.0,
        *,
        at_least: Optional[int] = None,
        latest: bool = False,
        deadline: Optional[float] = None,
        lane: Optional[str] = None,
    ) -> tuple[bool, Optional[int]]:
        """``check`` plus the id of the snapshot that decided it (None when
        the engine has no snapshot concept — e.g. the recursive oracle,
        which reads the store directly and is always fresh).

        ``deadline`` is the request's *absolute* ``time.monotonic()``
        deadline (REST/gRPC propagate theirs): it rides with the queued
        request so the collector sheds it *before packing* if it expires
        waiting, and the caller gets ``ErrDeadlineExceeded`` (504 /
        DEADLINE_EXCEEDED) instead of an answer nobody is waiting for.
        ``timeout`` remains the relative cap; the earlier of the two wins.
        ``lane`` pins the priority lane (single checks default to
        interactive)."""
        results, token = self._submit(
            [tuple_], timeout, at_least, latest, deadline, lane or INTERACTIVE
        )
        return bool(results[0]), token

    def check_batch(
        self,
        tuples: Sequence[RelationTuple],
        timeout: Optional[float] = None,
        *,
        at_least: Optional[int] = None,
        latest: bool = False,
        deadline: Optional[float] = None,
        lane: Optional[str] = None,
    ) -> list[bool]:
        """Pre-batched requests ride the lanes like everything else: big
        chunks land in the batch lane and dispatch a round's worth at a
        time, in bounded sub-slices that interleave with interactive work
        while there is any."""
        return self.check_batch_with_token(
            tuples, timeout, at_least=at_least, latest=latest, deadline=deadline,
            lane=lane,
        )[0]

    def check_batch_with_token(
        self,
        tuples: Sequence[RelationTuple],
        timeout: Optional[float] = None,
        *,
        at_least: Optional[int] = None,
        latest: bool = False,
        deadline: Optional[float] = None,
        lane: Optional[str] = None,
    ) -> tuple[list[bool], Optional[int]]:
        """``tuples`` may be a ``QueryFrame`` (a framed request body): it
        is queued as it is and never walked here."""
        if not isinstance(tuples, QueryFrame):
            tuples = list(tuples)
        if not len(tuples):
            return [], None
        if lane is None:
            lane = self.classify_lane(len(tuples), None)
        results, token = self._submit(tuples, timeout, at_least, latest, deadline, lane)
        return [bool(r) for r in results], token

    def classify_lane(self, n_tuples: int, hint: Optional[str]) -> str:
        """The lane a request belongs to: an explicit hint wins, else
        size decides (≤ ``interactive_max_tuples`` → interactive)."""
        if hint in LANES:
            return hint
        return INTERACTIVE if n_tuples <= self._interactive_max_tuples else BATCH

    def admission_precheck(self, lane: str = BATCH) -> None:
        """Cheap early shed: raise ``ErrTooManyRequests`` when the batch
        lane is already over its admitted window. Serving layers call
        this BEFORE decoding a batch payload — during a brownout the
        refusals must cost microseconds, not a 64k-tuple JSON parse, or
        the parse work itself becomes the overload."""
        if lane != BATCH or self.admission is None:
            return
        with self._cond:
            self.admission.tick(backlog=self._lane_tuples[BATCH])
            if self._lane_tuples[BATCH] >= self.admission.window:
                raise self._shed(
                    lane, True,
                    "batch lane over the admitted window (server near its "
                    "latency budget); retry after the advised backoff",
                )

    # -- enqueue -------------------------------------------------------------

    def _submit(self, tuples, timeout, at_least, latest, deadline, lane):
        if self._stop.is_set():
            raise RuntimeError("check batcher stopped")
        if lane not in LANES:
            raise ValueError(f"unknown priority lane {lane!r} (expected {LANES})")
        if timeout is not None:
            t_deadline = time.monotonic() + timeout
            deadline = t_deadline if deadline is None else min(deadline, t_deadline)
        if deadline is not None and time.monotonic() >= deadline:
            raise ErrDeadlineExceeded("deadline expired before the check was queued")
        item = _Item(
            tuples, Future(), at_least, latest, deadline, lane,
            tl=current_timeline(),
        )
        self._enqueue(item)
        remaining = None
        if deadline is not None:
            remaining = max(0.0, deadline - time.monotonic())
        try:
            return item.fut.result(timeout=remaining)
        except FutureTimeout:
            raise ErrDeadlineExceeded(
                "deadline expired waiting for the check result"
            ) from None

    def _shed(self, lane: str, admission: bool, message: str) -> ErrTooManyRequests:  # holds: _cond
        self.shed_count += 1
        self.shed_by_lane[lane] += 1
        if admission:
            self.admission_shed_count += 1
        retry_after = (
            self.admission.retry_after_s() if self.admission is not None else 1.0
        )
        cb, tenant = self.on_shed, self.tenant
        if cb is not None:
            try:
                cb(tenant or "", lane)
            except Exception:
                _log.warning("on_shed callback failed", exc_info=True)
        return ErrTooManyRequests(
            message,
            retry_after_s=retry_after,
            details={"tenant": tenant} if tenant else None,
        )

    def _enqueue(self, item: _Item) -> None:
        lane, n = item.lane, item.n
        with self._cond:
            if self._stop.is_set():
                raise RuntimeError("check batcher stopped")
            if lane == BATCH and self.admission is not None:
                self.admission.tick(backlog=self._lane_tuples[BATCH])
                if self._lane_tuples[BATCH] + n > self.admission.window:
                    if item.tl is not None:
                        item.tl.stamp("shed", lane=lane, why="admission")
                    raise self._shed(
                        lane, True,
                        "batch lane over the admitted window (server near its "
                        "latency budget); retry after the advised backoff",
                    )
            cap = self._max_pending
            if self._shed_on_full:
                # serving mode: a full lane answers 429 NOW — the device
                # is backlogged and queueing deeper only converts the
                # client's timeout budget into server memory. An
                # oversized chunk is still admitted into an EMPTY lane
                # (the sub-slice split serves it in bounded rounds).
                if self._lane_tuples[lane] + n > cap and self._lane_tuples[lane] > 0:
                    if item.tl is not None:
                        item.tl.stamp("shed", lane=lane, why="queue-full")
                    raise self._shed(
                        lane, False,
                        "check queue full (device backlogged); retry with backoff",
                    )
            else:
                # library mode: a full lane blocks the caller — the
                # backpressure seam between accepts and the device —
                # against the SAME deadline the result wait uses. A
                # deadline that expires while blocked here is a 504
                # (ErrDeadlineExceeded), NOT a queue-full error: the
                # caller ran out of time, the server did not refuse it.
                while (
                    self._lane_tuples[lane] + n > cap and self._lane_tuples[lane] > 0
                ):
                    if self._stop.is_set():
                        raise RuntimeError("check batcher stopped")
                    if item.deadline is not None:
                        remaining = item.deadline - time.monotonic()
                        if remaining <= 0:
                            raise ErrDeadlineExceeded(
                                "deadline expired while blocked on a full check queue"
                            )
                        self._cond.wait(timeout=min(remaining, 0.25))
                    else:
                        self._cond.wait(timeout=0.25)
            self._lanes[lane].append(item)
            self._lane_tuples[lane] += n
            if item.tl is not None:
                item.tl.stamp("admit", lane=lane)
            self._cond.notify_all()
        with self._inflight_lock:
            self._inflight += 1
            self._idle.clear()
        item.fut.add_done_callback(self._note_done)
        if self._stop.is_set() and not item.fut.done():
            # raced with stop()'s drain: nobody will serve the queue
            # anymore — unless the collector's final round got there first
            try:
                item.fut.set_exception(RuntimeError("check batcher stopped"))
            except InvalidStateError:
                pass  # the collector resolved it; return that result

    # -- graceful drain ------------------------------------------------------

    def _note_done(self, _fut) -> None:
        with self._inflight_lock:
            self._inflight -= 1
            if self._inflight <= 0:
                self._idle.set()

    @property
    def inflight(self) -> int:
        """Accepted check requests whose futures have not resolved yet."""
        with self._inflight_lock:
            return self._inflight

    @property
    def queue_depth(self) -> int:
        """Tuples queued across both lanes, not yet packed into a device
        batch (the /metrics pressure gauge; approximate by nature)."""
        with self._cond:
            return sum(self._lane_tuples.values())

    @property
    def lane_depths(self) -> dict[str, int]:
        """Queued tuples per lane (the /metrics per-lane gauge)."""
        with self._cond:
            return dict(self._lane_tuples)

    @property
    def max_pending(self) -> int:
        """Per-lane queue capacity — the denominator of the autoscaler's
        queue_depth_ratio signal (keto_tpu/fleet/autoscale.py)."""
        return self._max_pending

    def drain(self, timeout_s: float) -> bool:
        """Wait until every in-flight request has been answered (the
        SIGTERM drain seam: new traffic is already shed by the health
        override before this runs). True when the batcher went idle
        within ``timeout_s``."""
        return self._idle.wait(timeout=max(0.0, timeout_s))

    # -- dispatch ------------------------------------------------------------

    @staticmethod
    def _consistency_kw(at_leasts, latests) -> dict:
        """The strongest requested consistency wins (freshness is monotone
        — a fresher snapshot satisfies every weaker requirement in the
        batch)."""
        if any(latests):
            # read-your-writes dominates every floor in the batch
            return {"mode": "latest"}
        floors = [a for a in at_leasts if a is not None]
        if floors and len(floors) == len(at_leasts):
            # every request holds a snaptoken: any snapshot that fresh
            # answers at once
            return {"at_least": max(floors), "mode": "pinned"}
        # an unpinned request in the round is owed the serving rules (catch
        # up by delta), whatever floor rides beside it
        return {"at_least": max(floors) if floors else None, "mode": "serving"}

    def _dispatch(self, tuples, at_leasts, latests):
        """One engine call for a coalesced round."""
        if hasattr(self._engine, "batch_check_with_token"):
            return self._engine.batch_check_with_token(
                tuples, **self._consistency_kw(at_leasts, latests)
            )
        # oracle engine: always fresh (reads the store per traversal
        # step), no snapshot concept
        if hasattr(self._engine, "batch_check"):
            return self._engine.batch_check(tuples), None
        return [self._engine.subject_is_allowed(t) for t in tuples], None

    def _expire(self, item: _Item) -> None:
        self.deadline_drop_count += 1
        if not item.fut.done():
            try:
                item.fut.set_exception(
                    ErrDeadlineExceeded("deadline expired before dispatch")
                )
            except InvalidStateError:
                pass

    def _fill(self, item: _Item, idx: range, allowed, token) -> None:
        """Hand the decisions for ``item``'s tuples ``idx`` (a contiguous
        range) to the request: ``allowed`` is one bool each, or one bool
        for them all. An index answered before keeps its first answer and
        is not counted again, so the future resolves exactly once."""
        if not isinstance(allowed, list):
            allowed = [bool(allowed)] * len(idx)
        res = item.results
        a, b = idx.start, idx.stop
        fresh = res[a:b].count(None)
        if fresh == b - a:
            res[a:b] = allowed
        elif fresh:
            for i, ok in zip(idx, allowed):
                if res[i] is None:
                    res[i] = ok
        item.remaining -= fresh
        if item.remaining == 0 and not item.fut.done():
            if item.tl is not None:
                item.tl.stamp("land")  # every tuple has its decision
            try:
                item.fut.set_result((item.results, token))
            except InvalidStateError:
                pass  # expired/failed concurrently; caller already has an answer

    def _launch_stream(self, round_: _Round) -> bool:
        """Streaming dispatch for engines with the ready-order stream API:
        each caller's future resolves the moment ITS slice lands (the
        ``ordered=False`` fast path — re-association is by query offset),
        so early-finishing slices don't wait behind stragglers, and the
        interactive tuples at the head of the round land first.

        Engines advertising ``STREAM_INFO`` additionally yield a
        per-slice info record (width / BFS steps / label-vs-BFS route /
        halo rounds+bytes / service time), which is stamped onto every
        rider's request timeline as its ``device`` stage.

        The engine moves this thread's state clock through resolve / pack
        / launch / device_wait and leaves it in ``fill`` when it yields.

        The round goes to the engine as it is (``_Round``): ranges of the
        items' lists and frames, cut into slices by count, and each landed
        slice is written back by range.

        This half resolves the round's snapshot and runs the stream up to
        the engine's launch mark (``STREAM_LAUNCH_MARK``): True when the
        round is on the device and ``_land_stream`` has the rest to do,
        False when it is over (an engine without the mark, or slices that
        landed as they were launched)."""
        engine = self._engine
        round_.want_info = bool(getattr(engine, "STREAM_INFO", False))
        kw = self._consistency_kw(*round_.asked())
        if round_.want_info:
            kw["with_info"] = True
        if getattr(engine, "STREAM_LAUNCH_MARK", False):
            kw["launch_mark"] = True
        round_.gen, round_.token = engine.batch_check_stream_with_token(
            round_, ordered=False, **kw
        )
        return self._land_stream(round_)

    def _land_stream(self, round_: _Round) -> bool:
        """Fill every slice the stream lands, up to its launch mark (True)
        or its end (False)."""
        token, want_info = round_.token, round_.want_info
        for rec in round_.gen:
            if rec is None:
                return True
            off, out = rec[0], rec[1]
            riders = list(round_.riders(off, len(out)))
            if want_info:
                # stamp the slice's route/cost onto every distinct rider
                # BEFORE filling results, so the device stage precedes
                # land in each timeline (items are contiguous per slice —
                # dedup against the previous one suffices)
                prev = None
                for item, _idx, _lo, _hi in riders:
                    if item is not prev and item.tl is not None:
                        item.tl.stamp("device", **rec[2])
                    prev = item
            allowed = out.tolist()
            for item, idx, lo, hi in riders:
                if hi - lo == 1:
                    # a single check gets its one decision as it always did
                    self._fill(item, idx, allowed[lo], token)
                    continue
                for a in range(0, hi - lo, _FILL_RUN):
                    b = min(a + _FILL_RUN, hi - lo)
                    self._fill(item, idx[a:b], allowed[lo + a : lo + b], token)
        return False

    # -- collector -----------------------------------------------------------

    def _queued(self) -> int:
        return self._lane_tuples[INTERACTIVE] + self._lane_tuples[BATCH]

    def _take_locked(self) -> list:  # holds: _cond
        """Pack one dispatch round (called under ``_cond``): interactive
        items first — every one of them rides the NEXT round — then batch
        lane work, taking *partial* chunks. While interactive work is
        about (queued here, or aboard this round or the one before it)
        that is at most ``batch_sub_slice``, so a monster batch request
        interleaves instead of convoying; while the lane is quiet it is
        the round's own cap, since nobody is there to convoy. A reserve
        keeps the batch lane moving when interactive traffic alone could
        fill every round. Returns ``[(item, start, count), ...]``."""
        segments = []
        n = 0
        cap = self._batch_size
        inter, batchq = self._lanes[INTERACTIVE], self._lanes[BATCH]
        reserve = self._batch_reserve if batchq else 0
        inter_cap = max(1, cap - reserve)
        while inter and n < inter_cap:
            item = inter.popleft()
            self._lane_tuples[INTERACTIVE] -= item.n
            if item.fut.done():
                continue  # expired/failed while queued
            segments.append((item, 0, item.n))
            item.taken = item.n
            if item.tl is not None:
                item.tl.stamp("pack")  # queue wait ended here
            n += item.n
        # the sub-slice is what may stand between an interactive item and
        # its round, so it binds only while there are such items: one
        # aboard this round or still queued behind it, or one aboard the
        # round before, which may be on the device and lands only after
        # this one's launch. A quiet lane lets the round fill: the thread
        # pays most of a round per round, not per tuple
        busy = bool(segments or inter)
        batch_cap, cap_by = cap - n, "batch_size"
        if (busy or self._inter_rode) and self._sub_slice < batch_cap:
            batch_cap, cap_by = self._sub_slice, "sub_slice"
        self._inter_rode = busy
        # service-time-aware sub-slicing: the engine's slice controller
        # predicts how many queries fit one target-latency slice for the
        # routes currently in play — a batch sub-slice wider than that
        # would be split by the engine anyway, so bound the round here
        # and let the freed capacity interleave the NEXT interactive
        # round sooner (host-side sizing only: slice geometry on the
        # device stays the engine's decision, lockstep-safe)
        cap_fn = getattr(
            getattr(self._engine, "stream_ctrl", None), "cap", None
        )
        if cap_fn is not None:
            ctrl_cap = max(1, int(cap_fn()))
            if ctrl_cap < batch_cap:
                batch_cap, cap_by = ctrl_cap, "controller"
        # the round's room and what set it: counted and carried on the
        # round's spans once it is launched (``DispatchClock.round``)
        self._round_room = (batch_cap, cap_by)
        while batchq and batch_cap > 0:
            head = batchq[0]
            if head.fut.done():
                batchq.popleft()
                self._lane_tuples[BATCH] -= head.n - head.taken
                continue
            take = min(batch_cap, head.n - head.taken)
            segments.append((head, head.taken, take))
            if head.tl is not None and head.taken == 0:
                head.tl.stamp("pack")  # first sub-slice: queue wait ended
            head.taken += take
            self._lane_tuples[BATCH] -= take
            batch_cap -= take
            n += take
            if head.taken == head.n:
                batchq.popleft()
        return segments

    def _take(self, ahead: bool) -> tuple[list, int]:
        """The next round's segments and the batch lane's backlog behind
        them. With nothing open: wait for work, then for the coalescing
        window. ``ahead``, while a round is on the device: only if
        batch-lane work is queued, and without a wait — a queue of singles
        alone is served soonest by landing first, and a coalescing window
        would be spent with a round's decisions held back."""
        with self._cond:
            if ahead:
                if not self._lane_tuples[BATCH] or self._stop.is_set():
                    return [], 0
            else:
                if not self._queued():
                    # bounded wait so stop() always terminates the loop
                    self._cond.wait(timeout=0.25)
                    if not self._queued():
                        return [], 0
                # coalescing window: wait for more arrivals up to
                # window_ms or a full round — each wait blocks on the
                # condition for exactly the remaining window, no polling
                window_end = time.monotonic() + self._window_s
                while self._queued() < self._batch_size and not self._stop.is_set():
                    remaining = window_end - time.monotonic()
                    if remaining <= 0:
                        break
                    self._cond.wait(timeout=remaining)
            self.clock.enter(TAKE)
            segments = self._take_locked()
            # beside the items of the round on the device, if one is
            self._current_round = self._current_round + [
                item for item, _, _ in segments
            ]
            backlog = self._lane_tuples[BATCH]
            # space freed: wake producers blocked on a full lane
            self._cond.notify_all()
        return segments, backlog

    def _loop(self) -> None:
        clock = self.clock
        bind_dispatch_clock(clock)  # the engine's transition sites find it
        on_device: Optional[_Round] = None  # launched, not landed
        while not self._stop.is_set():
            if on_device is None:
                clock.idle()
            segments, backlog = self._take(ahead=on_device is not None)
            launched = None
            if segments:
                launched = self._launch(segments, backlog, on_device is not None)
            if on_device is not None:
                self._land(on_device)
            on_device = launched
            with self._cond:
                self._current_round = on_device.items if on_device else []
        if on_device is not None:
            self._land(on_device)  # stopping: what is on the device still lands

    def _launch(self, segments, backlog: int, overlapped: bool) -> Optional[_Round]:
        """One round from its segments to the device. Returns it while it
        is open (launched, not landed: the stream's launch mark), None
        when it is over — landed, expired, failed or retried."""
        clock = self.clock
        if self.admission is not None:
            self.admission.tick(backlog=backlog)
        round_ = _Round(segments, self._expire)
        clock.round(round_.n_tuples, backlog, overlapped, *self._round_room)
        clock.enter(RESOLVE)
        t0 = time.monotonic()
        is_open = False
        try:
            faults.check("check-dispatch")
            if hasattr(self._engine, "batch_check_stream_with_token"):
                is_open = self._launch_stream(round_)
            else:
                # an engine without the stream API: one plain call
                # over the round's live tuples, as objects
                tuples = list(round_)
                if tuples:
                    results, token = self._dispatch(tuples, *round_.asked())
                    clock.enter(FILL)
                    allowed = [bool(r) for r in results]
                    for item, idx, lo, hi in round_.riders(0, len(tuples)):
                        self._fill(item, idx, allowed[lo:hi], token)
        except Exception as e:
            self._fail_or_retry(segments, e)
        round_.busy_s += time.monotonic() - t0
        if is_open:
            return round_
        self._close(round_)
        return None

    def _land(self, round_: _Round) -> None:
        """Land and fill an open round; its failure is its own."""
        t0 = time.monotonic()
        try:
            # to the stream's end: a second mark, should an engine give
            # one, is no second turn
            while self._land_stream(round_):
                pass
        except Exception as e:
            self._fail_or_retry(round_.segments, e)
        round_.busy_s += time.monotonic() - t0
        self._close(round_)

    def _close(self, round_: _Round) -> None:
        round_.gen = None
        if self.admission is not None:
            self.admission.observe_round(round_.n_tuples, round_.busy_s)

    def _fail_or_retry(self, segments, exc: Exception) -> None:
        """A failed dispatch retries its unresolved requests ONCE through
        the engine's plain batch path — a device fault mid-stream flips
        the engine into its CPU degraded mode, so the retry lands on the
        fallback and callers never see the fault. Client errors
        (KetoError) and a failed retry propagate to every waiting
        future."""
        pending = []
        for item, start, count in segments:
            if item.fut.done():
                continue
            idxs = [i for i in range(start, start + count) if item.results[i] is None]
            if idxs:
                pending.append((item, idxs))
        if pending and not isinstance(exc, KetoError):
            n = sum(len(idxs) for _, idxs in pending)
            _log.warning(
                "batch dispatch failed (%s: %s); retrying %d unresolved "
                "checks on the engine's recovery path",
                type(exc).__name__, exc, n,
            )
            tuples: list = []
            for item, idxs in pending:
                src = as_tuples(item.tuples, "retry")
                tuples.extend(src[i] for i in idxs)
            try:
                results, token = self._dispatch(
                    tuples,
                    [item.at_least for item, _ in pending],
                    [item.latest for item, _ in pending],
                )
            except Exception as e2:
                exc = e2
            else:
                k = 0
                for item, idxs in pending:
                    for i in idxs:
                        self._fill(item, range(i, i + 1), bool(results[k]), token)
                        k += 1
                return
        for item, _, _ in segments:
            if not item.fut.done():
                try:
                    item.fut.set_exception(exc)
                except InvalidStateError:
                    pass
