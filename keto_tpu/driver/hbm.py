"""HBM budget governor: OOM-safe device-state lifecycle.

The engine keeps four device-resident state families — bucket matrices
("snapshot"), the delta-overlay ELL ("overlay"), the 2-hop label arrays
("labels"), and the warm-compiled width ladder ("warmup") — and before
this module nothing accounted for or bounded them: a graph that outgrew
the chip surfaced as an unhandled XLA ``RESOURCE_EXHAUSTED`` mid-refresh,
the one failure family the supervised-maintenance / degraded-mode /
crash-safety work never covered.

``HbmGovernor`` closes that hole with three mechanisms:

1. **A ledger.** Every device allocation site registers its tagged size
   (``register``/``add``/``release``), so ``resident_bytes()`` is an
   honest account of what the engine has placed on device, scraped as
   ``keto_hbm_resident_bytes{tag=...}``. The budget comes from
   ``serve.hbm_budget_bytes`` (0 = auto: ``jax.Device.memory_stats()``
   ``bytes_limit`` minus headroom, with a conservative fallback when the
   backend exposes no stats — e.g. CPU).

2. **Plan-before-upload with a graceful eviction ladder.** Refresh,
   compaction, and label builds call ``plan(nbytes)`` BEFORE uploading
   (old + new state are co-resident during a snapshot swap, so the plan
   is against live residency, not a clean slate). When the plan does not
   fit, the governor walks a deterministic ladder of engine-supplied
   rungs instead of dying — drop the label arrays (coverage loss only:
   the router falls back to BFS), trim the warm compile-width ladder,
   shrink the overlay edge budget to force compaction — and only when
   every rung is spent does ``plan`` return False, which the engine turns
   into "refuse the refresh, serve stale, DEGRADED(memory_pressure)".
   Pressure clearing walks back UP the ladder (``maybe_restore``).

3. **Real-OOM containment.** ``is_resource_exhausted`` classifies an
   exception as device-memory exhaustion (XLA RESOURCE_EXHAUSTED, or the
   injected ``device-alloc`` ``oom`` fault from keto_tpu/x/faults.py);
   the engine's allocation seams evict one rung and retry once, then
   escalate through the existing bit-identical CPU fallback rather than
   crashing.

Lockstep meshes never evict asymmetrically: ladder decisions derive only
from replicated state (configured budget, planned sizes — identical on
every host by the lockstep contract), and the *reactive* paths that could
diverge (auto budget from per-host ``memory_stats``, OOM-triggered
eviction) are disabled in ``deterministic`` mode — multi-controller
engines construct the governor that way and keep their existing
fail-loudly behavior on device errors.
"""

from __future__ import annotations

import logging
import threading
from typing import Callable, Optional

_log = logging.getLogger("keto_tpu.hbm")

#: fraction of the device's reported bytes_limit held back from the auto
#: budget (XLA needs workspace the ledger cannot see: program temporaries,
#: transfer staging, compiled executables)
DEFAULT_HEADROOM_FRAC = 0.08

#: auto-budget fallback when the backend exposes no memory stats (CPU
#: backend, very old runtimes) — conservative, and deterministic across
#: hosts, which is why lockstep meshes pin it
FALLBACK_BUDGET_BYTES = 4 << 30

#: restore a rung only while resident + planned stays under this fraction
#: of the budget — hysteresis so the ladder doesn't oscillate at the edge
RESTORE_FRAC = 0.7

#: the canonical ledger tags, in scrape order ("build" is the snapshot
#: pipeline's transient device footprint — the GovernedSorter's sort
#: workspace (keto_tpu/graph/device_build.py) and the label build's
#: frontier/cover matrices (keto_tpu/graph/label_build.py), registered
#: around the dispatches and released before the result installs;
#: "staging" is the
#: persistent entry-staging pool behind the donated dispatch buffers,
#: keto_tpu/check/pack.py _StagingPool — reconciled against the
#: pool's own accounting at every scrape)
TAGS = ("snapshot", "overlay", "labels", "reverse", "warmup", "build",
        "staging")

#: the eviction ladder rung names, in descent order (the final "refuse
#: the refresh" step is not a rung — it is plan() returning False).
#: "staging" goes first: dropping the entry-staging pool reverts to
#: per-slice allocation + device_put — pure churn cost, never coverage.
#: "reverse" drops the list layouts' device arrays — reverse queries
#: fall back to the CPU-reference lister bit-identically.
#: "tenant-lru" (appended by the registry in multi-tenant mode, AFTER
#: the engine's own rungs) evicts the coldest idle tenant's whole engine
#: — never the tenant currently dispatching — and its state faults back
#: in through the segmented snapcache on next touch
RUNGS = ("staging", "labels", "reverse", "warm-ladder", "overlay-budget",
         "tenant-lru")


#: ``keto_device_memory_bytes{kind}`` <- the key of ``memory_stats()``
_MEMORY_KINDS = (
    ("in_use", "bytes_in_use"), ("peak", "peak_bytes_in_use"), ("limit", "bytes_limit"),
)


def device_memory_rows() -> list[tuple[tuple[str, str], float]]:
    """``((device id, kind), bytes)`` for every local device, straight
    from the runtime's ``memory_stats()`` — what the ledger above cannot
    see (on a mesh, everything not row-sharded sits on device 0). Empty
    where the backend keeps no stats (the CPU backend)."""
    import jax

    rows = []
    for device in jax.local_devices():
        stats = device.memory_stats() or {}
        rows += [
            ((str(device.id), kind), float(stats[key]))
            for kind, key in _MEMORY_KINDS if key in stats
        ]
    return rows


def device_budget_bytes(
    headroom_frac: float = DEFAULT_HEADROOM_FRAC, deterministic: bool = False
) -> int:
    """The auto budget: the first local device's ``memory_stats()``
    ``bytes_limit`` minus headroom, or ``FALLBACK_BUDGET_BYTES`` when the
    backend exposes no stats (the CPU backend; a TPU without stats raises
    instead). ``deterministic`` (lockstep meshes) skips
    the per-host probe entirely — hosts could report different limits,
    and ladder decisions must derive from replicated state only."""
    if deterministic:
        return FALLBACK_BUDGET_BYTES
    platform = "unknown"
    try:
        import jax

        device = jax.local_devices()[0]
        platform = device.platform
        stats = device.memory_stats() or {}
        limit = int(stats.get("bytes_limit") or 0)
        if limit > 0:
            return max(1, int(limit * (1.0 - headroom_frac)))
    except Exception:
        _log.info(
            "device memory stats unavailable; auto budget falls back to "
            "%d bytes", FALLBACK_BUDGET_BYTES, exc_info=True,
        )
    if platform == "tpu":
        # the fallback is sized for backends that keep no HBM (CPU); on a
        # TPU it would be a budget invented for a chip nobody asked
        raise RuntimeError(
            "TPU device reports no memory_stats() bytes_limit: the HBM "
            "budget cannot be derived — set serve.hbm_budget_bytes"
        )
    return FALLBACK_BUDGET_BYTES


def device_measured_bytes() -> Optional[int]:
    """Actual device-memory occupancy (``bytes_in_use``) when the backend
    reports it, else None — bench reports this next to its host-side
    estimate."""
    try:
        import jax

        stats = jax.local_devices()[0].memory_stats() or {}
        v = stats.get("bytes_in_use")
        return int(v) if v is not None else None
    except Exception:
        return None


def is_resource_exhausted(exc: BaseException) -> bool:
    """Classify ``exc`` as device-memory exhaustion. Matches the XLA
    runtime's RESOURCE_EXHAUSTED surface (jaxlib raises XlaRuntimeError
    with the status name in the message), allocator out-of-memory texts,
    and the injected ``device-alloc`` oom fault (keto_tpu/x/faults.py) —
    NEVER plain Python MemoryError, which is a host failure the ladder
    cannot help."""
    from keto_tpu.x import faults

    if isinstance(exc, faults.OomInjected):
        return True
    msg = str(exc)
    return (
        "RESOURCE_EXHAUSTED" in msg
        or "Resource exhausted" in msg
        or ("out of memory" in msg.lower() and "XlaRuntimeError" in type(exc).__name__)
    )


class MemoryPressure(RuntimeError):
    """A planned allocation was refused with every rung spent — the
    engine serves stale and reports DEGRADED(memory_pressure)."""


class _Rung:
    __slots__ = ("name", "evict", "restore", "evicted")

    def __init__(self, name: str, evict: Callable[[], int], restore: Callable[[], None]):
        self.name = name
        self.evict = evict  # returns estimated bytes freed (logging only)
        self.restore = restore
        self.evicted = False


class HbmGovernor:
    """Ledger + budget + eviction-ladder policy (see module docstring).

    Thread-safe; rung callables run under the governor's re-entrant lock
    and may call back into ``release``/``register``. The engine owns the
    rung semantics — the governor only owns the order and the account."""

    def __init__(
        self,
        budget_bytes: int = 0,
        *,
        stats=None,
        deterministic: bool = False,
        headroom_frac: float = DEFAULT_HEADROOM_FRAC,
    ):
        self._lock = threading.RLock()
        self._ledger: dict[str, int] = {}
        # per-shard breakdown of tags the sharded engine mode registers
        # (keto_tpu/parallel/sharded.py): tag → [bytes per shard]. Tags
        # with no breakdown (replicated/transient state) spread evenly
        # over the shards in the per-shard view.
        self._n_shards = 1
        self._shard_ledger: dict[str, list] = {}
        self._rungs: list[_Rung] = []
        self._depth = 0  # rungs currently evicted (prefix of _rungs)
        self._stats = stats  # MaintenanceStats or None
        self.deterministic = bool(deterministic)
        self.configured_budget = int(budget_bytes)
        self.budget_bytes = (
            int(budget_bytes)
            if budget_bytes > 0
            else device_budget_bytes(headroom_frac, deterministic=deterministic)
        )
        self.evictions_by_rung: dict[str, int] = {r: 0 for r in RUNGS}
        self.restores = 0
        self.refusals = 0
        self.forced_allocs = 0  # over-budget allocations allowed (cold boot)
        self.oom_events = 0
        self.oom_recoveries = 0
        self._gauge("hbm_budget_bytes", self.budget_bytes)
        self._gauge("hbm_resident_bytes", 0)
        self._gauge("hbm_rung", 0)

    # -- stats plumbing ------------------------------------------------------

    def _gauge(self, key: str, value) -> None:
        if self._stats is not None:
            self._stats.set_gauge(key, value)

    def _incr(self, key: str) -> None:
        if self._stats is not None:
            self._stats.incr(key)

    def _publish_locked(self) -> None:
        self._gauge("hbm_resident_bytes", sum(self._ledger.values()))
        self._gauge("hbm_rung", self._depth)

    # -- ledger --------------------------------------------------------------

    def register(self, tag: str, nbytes: int) -> None:
        """Record ``tag``'s device residency as exactly ``nbytes``
        (replacing any prior figure — a snapshot swap re-registers its
        family once the old arrays are unreferenced)."""
        with self._lock:
            self._ledger[tag] = max(0, int(nbytes))
            self._publish_locked()

    def add(self, tag: str, nbytes: int) -> None:
        """Additive registration (the warm ladder accumulates per width)."""
        with self._lock:
            self._ledger[tag] = self._ledger.get(tag, 0) + max(0, int(nbytes))
            self._publish_locked()

    def release(self, tag: str) -> int:
        """Drop ``tag`` from the ledger; returns the bytes released."""
        with self._lock:
            freed = self._ledger.pop(tag, 0)
            self._shard_ledger.pop(tag, None)
            self._publish_locked()
            return freed

    # -- per-shard ledger (sharded serving) ----------------------------------

    def set_shard_count(self, n: int) -> None:
        """Declare the graph-axis shard count the per-shard ledger and
        per-shard budget slices divide by. Set once by the sharded
        engine at construction."""
        with self._lock:
            self._n_shards = max(1, int(n))
            self._shard_ledger = {}

    def register_shards(self, tag: str, per_shard) -> None:
        """Record ``tag``'s per-shard owned bytes (the unpadded rows each
        shard actually holds). The global figure for ``tag`` is still
        whatever ``register`` recorded — padding makes the two differ;
        the per-shard view is the honest hot-shard account."""
        with self._lock:
            vals = [max(0, int(v)) for v in per_shard]
            if len(vals) < self._n_shards:
                vals += [0] * (self._n_shards - len(vals))
            self._shard_ledger[tag] = vals[: self._n_shards]

    def shard_resident_bytes(self) -> list:
        """Per-shard resident bytes: tracked tags contribute their owned
        slice, untracked tags spread evenly (replicated / transient
        state is on every shard's devices)."""
        with self._lock:
            return self._shard_resident_locked()

    def _shard_resident_locked(self) -> list:
        n = self._n_shards
        out = [0] * n
        for tag, total in self._ledger.items():
            per = self._shard_ledger.get(tag)
            if per is None:
                for s in range(n):
                    out[s] += total // n
            else:
                for s in range(n):
                    out[s] += per[s]
        return out

    def resident_bytes(self) -> int:
        with self._lock:
            return sum(self._ledger.values())

    def ledger(self) -> dict[str, int]:
        with self._lock:
            return dict(self._ledger)

    def set_budget_bytes(self, nbytes: int) -> None:
        """Operator/test seam: re-pin the budget at runtime (pressure
        rehearsal, live retuning). Restores are NOT applied here — the
        next successful plan walks back up the ladder."""
        with self._lock:
            self.budget_bytes = max(1, int(nbytes))
            self._gauge("hbm_budget_bytes", self.budget_bytes)

    # -- the ladder ----------------------------------------------------------

    def attach_rungs(self, rungs) -> None:
        """``rungs`` is an ordered list of ``(name, evict_fn, restore_fn)``
        — descent order. Attached once by the engine at construction."""
        with self._lock:
            self._rungs = [_Rung(n, e, r) for n, e, r in rungs]
            self._depth = 0

    def append_rung(
        self, name: str, evict: Callable[[], int], restore: Callable[[], None]
    ) -> None:
        """Append one rung BELOW the engine's ladder (``attach_rungs``
        replaces the whole ladder, and the engine attaches its rungs at
        construction — this is the seam for rungs owned by someone else,
        e.g. the registry's cross-tenant ``tenant-lru`` rung). Appended
        rungs run under the same lock discipline and are accounted in
        ``evictions_by_rung`` like any other. Idempotent per name."""
        with self._lock:
            if any(r.name == name for r in self._rungs):
                return
            self._rungs.append(_Rung(name, evict, restore))

    @property
    def rung_depth(self) -> int:
        """How many rungs are currently evicted (0 = full service)."""
        with self._lock:
            return self._depth

    def fits(self, nbytes: int) -> bool:
        with self._lock:
            return sum(self._ledger.values()) + max(0, int(nbytes)) <= self.budget_bytes

    def _evict_next_locked(self, reason: str) -> Optional[str]:
        if self._depth >= len(self._rungs):
            return None
        rung = self._rungs[self._depth]
        self._depth += 1
        rung.evicted = True
        try:
            freed = int(rung.evict() or 0)
        except Exception:
            _log.warning("eviction rung %r failed; continuing down the ladder",
                         rung.name, exc_info=True)
            freed = 0
        self.evictions_by_rung[rung.name] = self.evictions_by_rung.get(rung.name, 0) + 1
        self._incr("hbm_evictions")
        self._publish_locked()
        _log.warning(
            "HBM pressure (%s): evicted rung %r (~%d bytes freed; rung %d/%d, "
            "resident %d / budget %d)",
            reason, rung.name, freed, self._depth, len(self._rungs),
            sum(self._ledger.values()), self.budget_bytes,
        )
        return rung.name

    def evict_one(self, reason: str = "") -> Optional[str]:
        """Descend one rung (the real-OOM containment path). Returns the
        rung name, or None when the ladder is spent. Deterministic mode
        (lockstep meshes) never evicts reactively — per-host OOM timing
        is not replicated state."""
        if self.deterministic:
            return None
        with self._lock:
            return self._evict_next_locked(reason or "oom")

    def plan(
        self,
        nbytes: int,
        *,
        what: str = "",
        evict: bool = True,
        per_shard=None,
    ) -> bool:
        """Will ``nbytes`` more fit? Walks the eviction ladder (in order,
        at most once per rung) until it does; returns False only with
        every rung spent and the plan still over budget — the caller
        refuses the work (or, for optional work like warming one more
        width, simply skips it with ``evict=False``).

        ``per_shard`` (sharded serving) additionally holds each shard's
        incoming bytes against that shard's slice of the budget — the
        HOTTEST shard is the binding constraint, and any rung the walk
        evicts is MESH-WIDE (one ladder for every shard), so a single
        over-full shard can never silently diverge the ladder."""
        need = max(0, int(nbytes))

        def over_locked() -> bool:
            if sum(self._ledger.values()) + need > self.budget_bytes:
                return True
            if per_shard is not None and self._n_shards > 1:
                shard_budget = self.budget_bytes // self._n_shards
                resident = self._shard_resident_locked()
                for s in range(self._n_shards):
                    add = int(per_shard[s]) if s < len(per_shard) else 0
                    if resident[s] + add > shard_budget:
                        return True
            return False

        with self._lock:
            while over_locked():
                if not evict or self._evict_next_locked(f"planning {what or 'allocation'}") is None:
                    return False
            return True

    def note_refused(self) -> None:
        """Count an actual refusal (the engine declined a refresh and is
        serving stale) — distinct from a failed plan the caller then
        force-allows (cold boot) or simply skips (optional warmup)."""
        with self._lock:
            self.refusals += 1
        self._incr("hbm_refusals")

    def note_forced(self, what: str, nbytes: int) -> None:
        """Account an allocation that proceeded over budget (cold boot:
        there is no stale snapshot to serve instead)."""
        with self._lock:
            self.forced_allocs += 1
        self._incr("hbm_forced_allocs")
        _log.warning(
            "HBM budget exceeded but no stale state to serve: allowing %s "
            "(%d bytes) over the %d-byte budget", what, nbytes, self.budget_bytes,
        )

    def maybe_restore(self, planned: int = 0) -> int:
        """Walk back UP the ladder while there is clear headroom
        (resident + planned under RESTORE_FRAC of budget). Called after a
        successful refresh; returns the number of rungs restored."""
        restored = 0
        with self._lock:
            while self._depth > 0:
                if sum(self._ledger.values()) + max(0, int(planned)) > (
                    RESTORE_FRAC * self.budget_bytes
                ):
                    break
                rung = self._rungs[self._depth - 1]
                try:
                    rung.restore()
                except Exception:
                    _log.warning("restore of rung %r failed; staying evicted",
                                 rung.name, exc_info=True)
                    break
                rung.evicted = False
                self._depth -= 1
                restored += 1
                self.restores += 1
                self._incr("hbm_restores")
                _log.info("HBM pressure cleared: restored rung %r (rung %d/%d)",
                          rung.name, self._depth, len(self._rungs))
            if restored:
                self._publish_locked()
        return restored

    # -- OOM accounting ------------------------------------------------------

    #: optional anomaly hook (the flight recorder's OOM trigger seam);
    #: invoked outside the governor lock, exceptions contained
    on_oom: Optional[Callable[[str], None]] = None

    def note_oom(self, what: str = "") -> None:
        with self._lock:
            self.oom_events += 1
        self._incr("oom_events")
        _log.warning("device RESOURCE_EXHAUSTED at %s", what or "unknown site")
        cb = self.on_oom
        if cb is not None:
            try:
                cb(what)
            except Exception:
                _log.warning("on_oom hook failed", exc_info=True)

    def note_oom_recovered(self) -> None:
        with self._lock:
            self.oom_recoveries += 1
        self._incr("oom_recoveries")

    # -- introspection -------------------------------------------------------

    def snapshot(self) -> dict:
        """Operator/metrics view: budget, ledger, ladder position, and
        the counters — ``keto_hbm_*`` / ``keto_oom_*`` read this."""
        with self._lock:
            return {
                "budget_bytes": self.budget_bytes,
                "configured_budget_bytes": self.configured_budget,
                "resident_bytes": sum(self._ledger.values()),
                "ledger": dict(self._ledger),
                "shards": (
                    self._shard_resident_locked() if self._n_shards > 1 else []
                ),
                "shard_count": self._n_shards,
                "rung": self._depth,
                "rungs": [r.name for r in self._rungs],
                "evicted": [r.name for r in self._rungs if r.evicted],
                "evictions_by_rung": dict(self.evictions_by_rung),
                "restores": self.restores,
                "refusals": self.refusals,
                "forced_allocs": self.forced_allocs,
                "oom_events": self.oom_events,
                "oom_recoveries": self.oom_recoveries,
            }
