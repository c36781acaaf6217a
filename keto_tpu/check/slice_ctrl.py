"""The slice scheduler of the streaming check pipeline."""

from __future__ import annotations

import collections
import threading
from typing import Optional

from keto_tpu.check.pack import _WORD_WIDTHS
from keto_tpu.x.timeline import dispatch_clock

#: what set the cap a take was offered (``keto_stream_take_cap_total{by}``)
TAKE_CAP_BY = ("reactive", "model", "futile", "bound")
#: what moved the controller (``keto_stream_ctrl_events_total{event}``)
CTRL_EVENTS = ("narrow", "widen", "guard_down")
#: the routes a slice is observed under (``_stream``'s ``land``)
ROUTES = ("label", "hybrid", "bfs", "host", "cpu")


class StreamSliceController:
    """Service-time-aware slice scheduler for the streaming pipeline.

    The memory-derived ``_slice_cap`` optimizes pure throughput — the
    widest bitmap the workspace budget allows — at the price of a long
    service time per slice (how long on a directly attached chip is not
    measured). Per-slice timelines
    (PR 14) showed the residual p99 tail is ROUTE-shaped: label slices
    finish in single-digit ms while a BFS slice of the same width pays
    tens of hops, so one reactive width shared by all routes lets the
    occasional deep slice blow a 10–25× p99/p50 spread. This controller
    therefore keeps a **predicted-service-time model** fit online from
    the per-slice ``(width, route, bfs_steps, entries, service_ms)``
    stats the stream already records, and schedules with it three ways:

    - **width planning** (``cap()``): the widest compiled ladder width
      (``32·_WORD_WIDTHS`` — adapting never compiles a new kernel) whose
      PREDICTED service time stays at or below ``target_ms``, where the
      prediction is pessimistic over the routes seen recently — one slow
      BFS observation immediately narrows the next slices instead of
      waiting for the shared EWMA to catch up. The original reactive
      narrow-fast / re-widen-slow ladder walk is retained underneath as
      a safety net for cost regimes the model has not seen;
    - **pre-dispatch splitting** (``entry_budget()``): the model's
      ms-per-device-entry estimate converts ``target_ms`` into a device
      entry budget, and ``_dispatch_slices`` splits a predicted-slow
      chunk (wildcard fanout, deep host walks) into sub-slices BEFORE
      dispatch — the ready-order window then interleaves them with fast
      slices, so a monster chunk never serializes the stream;
    - **tail guard**: the observed p99/p50 ratio of recent slices is
      checked against ``tail_ratio`` (config ``serve.stream_tail_ratio``)
      and a multiplicative guard scales both the planned width and the
      entry budget down while the tail is blown, recovering gradually —
      the direct control loop for the bench's slice-tail gate.

    ``floor`` bounds narrowing so a latency spike cannot collapse
    throughput (2048 queries/slice keeps > 50k checks/s even at 25
    slices/s). And narrowing has to buy time: a slice that pulls
    (``bfs_steps`` > 0) costs its pulls whatever it carries, which the
    proportional model cannot see. Where such slices of a rung were seen,
    within ``ROUTE_RECENCY`` slices, to take nearly as long as those of the
    rung above it, and that rung's time is within the target, the wider
    rung is served - a quarter of the width for the same wait is four
    times the work (``FUTILE_FRAC``). Slices that do not pull (the label
    route) leave no reading and are scheduled as before.

    Every decision is counted where it is taken, under the lock it is
    taken under: the rung a take was offered and which of the mechanisms
    above set it (``count_take``), the readings that moved the controller
    (``observe``). ``snapshot()`` carries the counts and the model's state;
    ``stream_ctrl_metrics`` puts them on ``/metrics``.
    """

    #: widen when observed ms < WIDEN_FRAC · target, ``patience`` times in a row
    WIDEN_FRAC = 0.5
    #: narrow when observed ms > NARROW_FRAC · target
    NARROW_FRAC = 1.25
    #: a route binds the pessimistic prediction for this many slices
    #: after it was last observed
    ROUTE_RECENCY = 64
    #: recompute the tail guard every this many observations
    TAIL_EVERY = 32
    #: a rung whose pulling slices take at least this share of the next
    #: wider rung's time is not worth narrowing to: a take that half fills
    #: the wider rung is two slices of the narrower, so at a half the two
    #: ways cost the same and above it the narrower costs more (a
    #: proportional cost would read a quarter to a half, a cost of pulls
    #: alone 1)
    FUTILE_FRAC = 0.5

    def __init__(
        self,
        target_ms: float = 40.0,
        floor: int = 2048,
        patience: int = 2,
        tail_ratio: float = 5.0,
    ):
        self._ladder = [32 * w for w in _WORD_WIDTHS]
        self.target_ms = float(target_ms)
        self.tail_ratio = float(tail_ratio)
        self._lo = next(
            (i for i, c in enumerate(self._ladder) if c >= floor),
            len(self._ladder) - 1,
        )
        self._patience = patience
        self._lock = threading.Lock()
        # start two rungs under the top: wide enough that a fast link is
        # near peak throughput from slice one, narrow enough that the
        # first observations on a slow link land near the target
        self._i = max(self._lo, len(self._ladder) - 3)
        self._good = 0
        #: per-route cost model: route → {per_q, per_entry, bfs_steps,
        #: last_seen} (EWMAs; last_seen is a slice counter)
        self._routes: dict[str, dict] = {}
        #: ladder index -> [EWMA service ms, last_seen] of the pulling
        #: slices that filled that rung (the narrowest rung that holds them)
        self._rung_ms: dict[int, list] = {}
        self._slices = 0
        self._ring: collections.deque = collections.deque(maxlen=256)
        self._guard = 1.0
        self._tail_p50 = 0.0
        self._tail_p99 = 0.0
        #: which mechanism set ``cap()``'s last answer (a ``TAKE_CAP_BY``)
        self._cap_by = "reactive"
        #: takes by the rung they were offered, and by what set it
        self._takes = {c: 0 for c in self._ladder[self._lo:]}
        self._take_cap = dict.fromkeys(TAKE_CAP_BY, 0)
        #: event -> route of the observed slice -> readings
        self._events: dict[str, dict] = {e: {} for e in CTRL_EVENTS}

    def _recent_locked(self):
        horizon = self._slices - self.ROUTE_RECENCY
        return [
            st for st in self._routes.values() if st["last_seen"] >= horizon
        ]

    def _model_cap_locked(self) -> Optional[int]:
        """Widest ladder width whose predicted service time (pessimistic
        per-query cost over recently seen routes, scaled by the tail
        guard) fits the target; None before any observation."""
        recent = self._recent_locked()
        per_q = max((st["per_q"] for st in recent), default=None)
        if per_q is None or per_q <= 0:
            return None
        limit = self.target_ms * self._guard / per_q
        want = self._ladder[self._lo]
        for c in self._ladder:
            if c <= limit:
                want = max(want, c)
        return want

    def cap(self) -> int:
        """Per-slice query cap for the NEXT slice: the reactive ladder
        rung bounded by the model's predicted-service-time width, then
        the wider rung for as long as pulling slices showed that this one
        buys no time (``FUTILE_FRAC``). Always a compiled ladder width."""
        with self._lock:
            cap = self._ladder[self._i]
            by = "reactive"
            m = self._model_cap_locked()
            if m is not None:
                if m < cap:
                    by = "model"
                cap = max(self._ladder[self._lo], min(cap, m))
            k = self._ladder.index(cap)
            horizon = self._slices - self.ROUTE_RECENCY
            while k + 1 < len(self._ladder):
                mine, wider = self._rung_ms.get(k), self._rung_ms.get(k + 1)
                if mine is None or wider is None or min(mine[1], wider[1]) < horizon:
                    break
                if wider[0] > self.target_ms or mine[0] < self.FUTILE_FRAC * wider[0]:
                    break
                k += 1  # this rung's slices were no faster: serve the wider
                by = "futile"
            self._cap_by = by
            return self._ladder[k]

    def count_take(self, rung: int, bound: int) -> None:
        """The stream cut a take of ``min(bound, rung)`` off its source,
        ``rung`` being what ``cap()`` just answered: count the rung and what
        set it, ``bound`` where the caller's (the memory-derived slice cap, a
        ``slice_cap``) was lower still. The batcher's own call of ``cap()``
        sizes a round and a take that finds the source dry took nothing:
        neither is counted. The reason is the one ``cap()`` left; a ``cap()``
        of another thread between the two can mislabel a take, never lose it."""
        with self._lock:
            self._takes[rung] = self._takes.get(rung, 0) + 1
            self._take_cap["bound" if bound < rung else self._cap_by] += 1

    def entry_budget(self) -> Optional[int]:
        """Device entries one sub-chunk may carry before its predicted
        service time overshoots the target — the pre-dispatch split
        bound ``_dispatch_slices`` applies. None before the model has an
        entry-cost estimate."""
        with self._lock:
            return self._entry_budget_locked()

    def _entry_budget_locked(self) -> Optional[int]:
        per_e = max(
            (st["per_entry"] for st in self._recent_locked() if st["per_entry"] > 0),
            default=None,
        )
        if per_e is None:
            return None
        return max(256, int(self.target_ms * self._guard / per_e))

    def observe(
        self,
        nq: int,
        ms: float,
        route: str = "bfs",
        bfs_steps: int = 0,
        entries: Optional[int] = None,
        full_take: bool = False,
    ) -> None:
        """Feed one slice's service time: dispatch→ready when the pipeline
        ran dry, ready→ready interval when saturated. ``route``/
        ``bfs_steps``/``entries`` (from the stream's per-slice info) fit
        the per-route model; plain ``observe(nq, ms)`` still steers the
        reactive ladder alone. ``full_take``: the slice is a sub-chunk of
        a take that filled the cap and was cut by the entry budget — it
        speaks for the cap's width like a slice of ``cap`` queries (a
        graph whose takes at one rung are always cut could otherwise
        never widen again after one slow slice)."""
        if nq <= 0:
            return
        per_q = ms / nq
        events = []
        with self._lock:
            before = self._ladder[self._i]
            self._slices += 1
            st = self._routes.get(route)
            if st is None:
                st = {"per_q": per_q, "per_entry": 0.0, "bfs_steps": 0.0,
                      "last_seen": 0, "n": 0}
                self._routes[route] = st
            else:
                # asymmetric EWMA: a slowdown bumps the predicted cost
                # HARD (the very next cap()/entry_budget() narrows —
                # that is the tail control), while a speedup also decays
                # fast so a cleared spike doesn't pin throughput low
                old = st["per_q"]
                st["per_q"] = (
                    0.5 * old + 0.5 * per_q
                    if per_q >= old
                    else 0.3 * old + 0.7 * per_q
                )
            if entries:
                pe = ms / max(1, entries)
                old = st["per_entry"]
                if old <= 0:
                    st["per_entry"] = pe
                else:
                    st["per_entry"] = (
                        0.5 * old + 0.5 * pe
                        if pe >= old
                        else 0.3 * old + 0.7 * pe
                    )
            st["bfs_steps"] = 0.7 * st["bfs_steps"] + 0.3 * float(bfs_steps)
            if bfs_steps > 0:
                rung = next(
                    (k for k, c in enumerate(self._ladder) if c >= nq),
                    len(self._ladder) - 1,
                )
                seen = self._rung_ms.get(rung)
                self._rung_ms[rung] = [
                    ms if seen is None else 0.5 * seen[0] + 0.5 * ms, self._slices
                ]
            st["last_seen"] = self._slices
            st["n"] += 1
            self._ring.append(ms)
            if self._slices % self.TAIL_EVERY == 0:
                guard = self._guard
                self._retune_tail_locked()
                if self._guard < guard:
                    events.append("guard_down")
            if ms > self.NARROW_FRAC * self.target_ms:
                events.append("narrow")  # the reading a narrow regime starts from
                want = self._lo
                for k in range(self._i, self._lo - 1, -1):
                    if self._ladder[k] * per_q <= self.target_ms:
                        want = k
                        break
                self._i = min(self._i, max(self._lo, want))
                self._good = 0
            elif ms < self.WIDEN_FRAC * self.target_ms and (nq >= before or full_take):
                self._good += 1
                if self._good >= self._patience and self._i + 1 < len(self._ladder):
                    self._i += 1
                    self._good = 0
                    events.append("widen")
            else:
                self._good = 0
            for event in events:
                by_route = self._events[event]
                by_route[route] = by_route.get(route, 0) + 1
            after = self._ladder[self._i]
        for event in events:
            # on the device trace's clock while a profiler session is open
            # (the dispatch thread's clock; any other thread's is a no-op)
            dispatch_clock().mark(
                f"keto.ctrl.{event}", route=route, nq=nq, ms=round(ms, 3),
                rung_before=before, rung_after=after,
            )

    def _retune_tail_locked(self) -> None:
        vals = sorted(self._ring)
        if len(vals) < 8:
            return
        self._tail_p50 = vals[len(vals) // 2]
        self._tail_p99 = vals[min(len(vals) - 1, int(len(vals) * 0.99))]
        blown = (
            self._tail_p50 > 0
            and self._tail_p99 > self.tail_ratio * self._tail_p50
            and self._tail_p99 > self.target_ms
        )
        if blown:
            self._guard = max(0.25, self._guard * 0.5)
        else:
            self._guard = min(1.0, self._guard * 1.1)

    def snapshot(self) -> dict:
        """The controller's state and counts: the one source of
        ``stream_ctrl_metrics``' families, of bench.py and of
        scripts/tail_smoke.py. ``cap`` is the reactive rung."""
        with self._lock:
            return {
                "cap": self._ladder[self._i],
                "target_ms": self.target_ms,
                "model_cap": self._model_cap_locked(),
                "entry_budget": self._entry_budget_locked(),
                "takes": dict(self._takes),
                "take_cap": dict(self._take_cap),
                "events": {e: dict(n) for e, n in self._events.items()},
                "tail_ratio": self.tail_ratio,
                "tail_guard": self._guard,
                "tail_p50_ms": round(self._tail_p50, 3),
                "tail_p99_ms": round(self._tail_p99, 3),
                "routes": {
                    r: {
                        "per_q_ms": round(st["per_q"], 6),
                        "per_entry_ms": round(st["per_entry"], 6),
                        "bfs_steps": round(st["bfs_steps"], 2),
                        "slices": st["n"],
                    }
                    for r, st in self._routes.items()
                },
            }


def stream_ctrl_metrics(m, snapshot_of) -> None:
    """Declare the controller's families on ``m`` (driver/registry.py calls
    this once). ``snapshot_of()`` is ``StreamSliceController.snapshot()`` of
    the serving engine's controller, or None while there is none: every
    family then reads 0 over the label set of a controller at its
    defaults, so a window's delta is defined from the first scrape."""
    idle = StreamSliceController().snapshot()

    def rows(read):
        return lambda: read(snapshot_of() or idle)

    m.register_callback(
        "keto_stream_takes_total", "counter",
        "Takes of the check stream (check/dispatch.py _slices) by the ladder "
        "rung the slice controller offered: 32 x the kernel word widths at "
        "or above the controller's floor. With engine.batch_size 4096 a "
        "round is bound by the controller at rung 2048 alone.",
        rows(lambda s: [((str(r),), float(n)) for r, n in s["takes"].items()]),
        ("rung",),
    )
    m.register_callback(
        "keto_stream_take_cap_total", "counter",
        "Takes of the check stream by what set their cap: reactive (the "
        "controller's ladder index), model (the predicted-service-time "
        "width was below it), futile (a wider rung served because pulling "
        "slices of the narrower took half its time or more), bound (the "
        "memory-derived slice cap or the caller's was lower still).",
        rows(lambda s: [((by,), float(n)) for by, n in s["take_cap"].items()]),
        ("by",),
    )
    m.register_callback(
        "keto_stream_ctrl_events_total", "counter",
        "Slice readings that moved the controller, by the observed slice's "
        "route: narrow (over NARROW_FRAC x the target: the reading a narrow "
        "regime starts from), widen (the reactive index went up), guard_down "
        "(the tail guard was halved).",
        rows(lambda s: [
            ((e, r), float(s["events"][e].get(r, 0))) for e in CTRL_EVENTS for r in ROUTES
        ]),
        ("event", "route"),
    )
    def gauge(key):
        return rows(lambda s: [((), float(s[key] or 0))])

    m.register_callback(
        "keto_stream_ctrl_rung", "gauge",
        "The slice controller's reactive ladder rung (queries a slice).",
        gauge("cap"),
    )
    m.register_callback(
        "keto_stream_ctrl_model_cap", "gauge",
        "The widest rung whose predicted service time fits the target, over "
        "the routes seen lately; 0 before any reading.",
        gauge("model_cap"),
    )
    m.register_callback(
        "keto_stream_ctrl_tail_guard", "gauge",
        "The tail guard: 1 at rest, halved (to 0.25 at the least) while the "
        "slices' p99/p50 is over serve.stream_tail_ratio.",
        gauge("tail_guard"),
    )
    m.register_callback(
        "keto_stream_ctrl_entry_budget", "gauge",
        "Device entries a sub-chunk may carry before the model predicts it "
        "over the target; 0 before an entry-cost reading.",
        gauge("entry_budget"),
    )
    m.register_callback(
        "keto_stream_ctrl_route_ms_per_query", "gauge",
        "The model's cost of a query by route (asymmetric EWMA of a slice's "
        "ms over its queries); 0 for a route not seen.",
        rows(lambda s: [
            ((r,), float(s["routes"].get(r, {}).get("per_q_ms", 0.0))) for r in ROUTES
        ]),
        ("route",),
    )
