"""Batched check engine: the device graph's keeper and public face.

Where the reference answers one ``Check`` by a recursive traversal issuing
one SQL query per subject-set node per page (reference
internal/check/engine.go:33-95), this engine answers **thousands of checks
in one device program**. It is split at one seam:

- this module *keeps a snapshot on the chip*: the public API and its
  consistency modes, the snapshot lifecycle (refresh, delta overlay,
  compaction, fold, cache), the uploads, the HBM governor's eviction rungs,
  the labels' lifecycle, the shadow-parity auditor, the CPU fallback;
- ``check/dispatch.py`` *answers a batch from a snapshot it is given*:
  resolve, pack (``check/pack.py``), launch (``check/kernels.py``), land,
  decode. The entry points here pick the snapshot and hand it down.
"""

from __future__ import annotations

import collections
import contextlib
import itertools
import logging
import random
import threading
import time
from typing import Callable, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from keto_tpu import namespace as namespace_pkg
from keto_tpu.namespace.rewrites import RewritePlan, schema_of
from keto_tpu.check.dispatch import CheckDispatch
from keto_tpu.check.frame import pick_tuples
from keto_tpu.check.kernels import _label_witness_kernel
from keto_tpu.check.pack import _WORD_WIDTHS
from keto_tpu.driver.hbm import HbmGovernor, MemoryPressure, is_resource_exhausted
from keto_tpu.graph.gate_tables import tables_of as gate_tables_of
from keto_tpu.graph.snapshot import GraphSnapshot, _ceil_pow2
from keto_tpu.relationtuple.model import RelationTuple
from keto_tpu.x import faults
from keto_tpu.x.errors import KetoError
from keto_tpu.x.profiling import SESSION
from keto_tpu.x.retry import retry_call
from keto_tpu.x.supervise import SupervisedTask
from keto_tpu.x.telemetry import MaintenanceStats

_log = logging.getLogger("keto_tpu.check")


class TpuCheckEngine:
    """Drop-in check engine answering batched queries on the device graph.

    ``store`` must expose ``snapshot_rows() -> (rows, watermark)`` and
    ``watermark()`` (keto_tpu/persistence/memory.py); ``namespaces`` is a
    namespace.Manager or a zero-arg callable returning the current one
    (hot-reload safe). This object is the TPU implementation behind the
    registry's ``PermissionEngine()`` seam (reference
    internal/driver/registry_default.go:158-163).

    **Multi-controller (multi-host mesh) lockstep contract:** when
    ``mesh`` spans more than one process, every host executes one SPMD
    program — so every host must call ``batch_check``/``snapshot`` with
    identical inputs in identical order over identical store contents
    (same batches, same write points). This is ENFORCED, not assumed:
    route traffic through ``parallel.lockstep.LockstepFrontend`` (host 0
    replicates every op to all hosts before execution), and the engine
    itself all-gathers a per-batch (snapshot, batch) fingerprint before
    every multi-process dispatch (``lockstep_verify``, default on),
    failing loudly on divergence instead of hanging mismatched
    collectives or corrupting results.
    """

    #: capability flag: ``batch_check_stream_with_token`` accepts
    #: ``with_info=True`` (ordered=False only) and yields
    #: ``(offset, decisions, slice_info)`` — per-slice width / BFS steps
    #: / label-vs-BFS route / halo rounds+bytes, what the CheckBatcher
    #: stamps onto each rider's request timeline (keto_tpu/x/timeline.py)
    STREAM_INFO = True
    #: capability flag: ``batch_check_stream_with_token`` accepts
    #: ``launch_mark=True`` (ordered=False only) and yields None once,
    #: when every slice of the stream is launched and some have not landed
    #: — the point at which the CheckBatcher may launch its next round
    STREAM_LAUNCH_MARK = True

    def __init__(
        self,
        store,
        namespaces,
        *,
        it_cap: int = 4096,
        max_batch: int = 32 * _WORD_WIDTHS[-1],
        mesh=None,
        shard_rows: bool = False,
        sharded: bool = False,
        mem_budget_bytes: int = 10 << 30,
        compact_after_s: float = 5.0,
        peel_seed_cap: float = 4.0,
        sync_rebuild_budget_s: float = 0.25,
        lockstep_verify: bool = True,
        stream_slice_target_ms: float = 40.0,
        overlay_edge_budget: int = 4096,
        fold_segment_edges: int = 2048,
        snapshot_cache_dir: Optional[str] = None,
        degraded_probe_s: float = 5.0,
        device_error_threshold: int = 3,
        refresh_retry_max_wait_s: float = 2.0,
        labels_enabled: bool = True,
        labels_max_width: int = 64,
        labels_landmarks: int = 0,
        labels_device_build: bool = True,
        labels_min_gain: float = 0.0,
        labels_batch: int = 64,
        labels_device_min_edges: int = 65536,
        hbm_budget_bytes: int = 0,
        audit_sample_rate: float = 0.0,
        device_build_enabled: bool = True,
        build_chunk_rows: int = 262144,
        stream_tail_ratio: float = 5.0,
    ):
        if it_cap < 1:
            raise ValueError("it_cap must be >= 1 (the answer pull needs one step)")
        self._store = store
        if isinstance(namespaces, namespace_pkg.Manager):
            self._nm: Callable[[], namespace_pkg.Manager] = lambda: namespaces
        else:
            self._nm = namespaces
        # 2-hop reachability labels (keto_tpu/graph/labels.py): built at
        # snapshot-build time, served as the O(1)-step fast path for
        # deep checks; BFS stays the fallback for everything the labels
        # can't certify (wildcards, self-queries, overlay-dirtied
        # interior edges, width/landmark coverage gaps)
        self._labels_enabled = bool(labels_enabled)
        self._labels_max_width = int(labels_max_width)
        self._labels_landmarks = int(labels_landmarks)
        # device label construction (keto_tpu/graph/label_build.py):
        # batched frontier sweeps replace the per-landmark host BFS on
        # graphs past labels_device_min_edges interior edge slots —
        # entry-identical by contract, landmark cap LIFTED (the
        # min_gain early exit bounds the build instead), and the build
        # overlaps the snapshot pipeline's host phases (cache_save)
        self._labels_device_build = bool(labels_device_build)
        self._labels_min_gain = float(labels_min_gain)
        self._labels_batch = int(labels_batch)
        self._labels_device_min_edges = int(labels_device_min_edges)
        #: the in-flight background label build (full-rebuild overlap);
        #: snapcache.save_snapshot joins it via labels_wait just before
        #: writing the label segments, so bulk segment writing and the
        #: device sweeps genuinely overlap
        self._label_build_thread: Optional[threading.Thread] = None
        self._mesh = mesh
        # EXPLICIT sharding (keto_tpu/parallel/sharded.py): partition the
        # bucket/bitmap/label rows by interior-row range over the mesh's
        # graph axis and run the BFS step as a shard_map kernel with an
        # explicit per-hop halo exchange, instead of handing GSPMD a
        # globally-addressed program. Queries replicate along the data
        # axis; decisions are bit-identical to the single-device kernels.
        self._sharded = bool(sharded and mesh is not None)
        self._shard_count = (
            int(mesh.shape.get("graph", 1)) if self._sharded else 0
        )
        self._multiprocess = mesh is not None and jax.process_count() > 1
        # per-batch (snapshot, batch) fingerprint agreement across hosts:
        # divergence fails loudly instead of hanging mismatched collectives
        # or corrupting decisions (keto_tpu/parallel/lockstep.py)
        self._lockstep_verify = lockstep_verify and self._multiprocess
        self._shard_stack_sharding = None
        if mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec as P

            from keto_tpu.parallel.mesh import GRAPH_AXIS

            self._bucket_sharding = NamedSharding(mesh, P(GRAPH_AXIS, None))
            self._ov_dst_sharding = NamedSharding(mesh, P(GRAPH_AXIS))
            # sharded mode: stacked [n_shards, ...] arrays split over the
            # graph axis (leading dim), replicated over data
            self._shard_stack_sharding = NamedSharding(mesh, P(GRAPH_AXIS))
        self._lock = threading.Lock()
        # what the lock's holder is doing, for those who find it taken:
        # "delta" (catching up by overlay), "fold", "rebuild", or None.
        # Written under the lock, read without it (a stale read sends a
        # serving thread into one more short wait, no further)
        self._lock_holder: Optional[str] = None
        self._snapshot: Optional[GraphSnapshot] = None
        # delta overlays beyond this edge count trigger COMPACTION — the
        # overlay folds into the base layout by segment
        # (keto_tpu/graph/compaction.py) in seconds instead of the old
        # full-rebuild fallback; only overlays past the hard cap (or
        # shapes compaction can't fold) still rebuild from scratch
        self._max_overlay_edges = int(overlay_edge_budget)
        # an overlay older than this compacts in the background: without
        # it an insert-only workload would keep a small overlay — and
        # everything gated on it, e.g. expand's Manager delegation —
        # alive forever
        self._compact_after_s = compact_after_s
        # persistent snapshot cache (keto_tpu/graph/snapcache.py): reload
        # on cold start, save in the background after every full build
        self._cache_dir = snapshot_cache_dir or None
        #: maintenance counters operators + bench read (overlay occupancy,
        #: compaction/rebuild counts and durations, cache save/reload)
        self.maintenance = MaintenanceStats()
        self.maintenance.set_gauge("overlay_budget", self._max_overlay_edges)
        self.maintenance.set_gauge("overlay_edges", 0)
        self._peel_seed_cap = peel_seed_cap
        self._overlay_born: Optional[float] = None
        # log-structured snapshot maintenance: the engine keeps the last
        # overlay-free snapshot (_fold_base) plus the ordered delta
        # segments applied since ((base_id, wm, ops) triples) — a fold
        # pass replays the OLDEST segments into the base and compacts
        # just those, bounded per pass by fold_segment_edges, while new
        # writes keep landing in the newest segment. Overlay occupancy
        # is bounded by fold rate instead of a hard budget trip, and the
        # serving path never pays a compaction wall.
        self._fold_segment_edges = max(1, int(fold_segment_edges))
        self._fold_base: Optional[GraphSnapshot] = None
        self._seg_log: list = []
        self._pending_seg = None
        # host mirror of the device-resident overlay pack ([K, C] gather
        # matrix + dst vector, slot map, per-row fill): committed delta
        # edges scatter into the resident arrays (functional .at[].set)
        # instead of re-packing and re-uploading the whole matrix; a
        # delta that outgrows the capacity falls back to a full re-pack
        # with pow2 headroom. Single-device path only — mesh/sharded
        # placements re-route and re-upload (their stacked layouts are
        # rebuilt host-side anyway).
        self._ov_pack: Optional[dict] = None
        # supervised maintenance (x/supervise.py): refresh and cache-save
        # run under crash-containing workers with jittered backoff and
        # crash counters instead of ad-hoc threads that die silently;
        # persistence reads inside a pass retry through x/retry.py for up
        # to refresh_retry_max_wait_s before the pass counts as failed
        self._refresh_retry_max_wait_s = refresh_retry_max_wait_s
        self._refresh_force_full = False
        # close() flips this; the background label build checks it
        self._closing = False
        self._refresh_task = SupervisedTask(
            "refresh", self._refresh_pass, stats=self.maintenance
        )
        self._cache_pending: Optional[GraphSnapshot] = None
        self._cache_task = SupervisedTask(
            "cache-save", self._cache_save_pass, stats=self.maintenance,
            base_backoff_s=0.1, max_backoff_s=5.0,
        )
        # degraded mode: repeated device-path failures flip checks to the
        # CPU reference engine (bit-identical decisions, reference
        # throughput); the device path is re-probed every
        # degraded_probe_s and recovery is automatic on success
        self._degraded_probe_s = degraded_probe_s
        self._device_error_threshold = device_error_threshold
        self._consec_device_errors = 0
        self._degraded = False
        self._probe_after = 0.0
        self._fallback_lock = threading.Lock()
        self._fallback_engine_obj = None
        # staleness clock for the health state machine: monotonic instant
        # the serving snapshot was last known current with the store
        self._behind_since: Optional[float] = None
        # serving-mode policy: when the last full rebuild cost more than
        # this, the serving path never rebuilds inline — it serves the
        # current snapshot and catches up in the background (deltas still
        # apply synchronously; they are milliseconds)
        self._sync_rebuild_budget_s = sync_rebuild_budget_s
        self._last_full_build_s = 0.0
        # HBM budget governor (keto_tpu/driver/hbm.py): a ledger of every
        # device allocation this engine makes, plan-before-upload against
        # serve.hbm_budget_bytes, and the graceful eviction ladder —
        # labels → warm compile-width ladder → overlay budget → refuse
        # the refresh and serve stale. Lockstep meshes get deterministic
        # mode: auto-budget probing and reactive (OOM-triggered) eviction
        # are per-host signals and must never diverge the ladder.
        self.hbm = HbmGovernor(
            budget_bytes=int(hbm_budget_bytes),
            stats=self.maintenance,
            deterministic=self._multiprocess,
        )
        if self._sharded:
            # per-shard ledger: the governor tracks each shard's owned
            # residency so the mesh-wide plan binds on the hottest shard
            self.hbm.set_shard_count(self._shard_count)
            self.maintenance.set_gauge("shard_count", self._shard_count)
        # the reverse-query list engine (keto_tpu/list/tpu_engine.py)
        # registers its eviction hooks here once constructed; until then
        # the rung is a no-op (nothing resident to drop)
        self._reverse_evict_cb: Optional[Callable[[], int]] = None
        self._reverse_restore_cb: Optional[Callable[[], None]] = None
        # the half that answers a batch from a snapshot it is given
        # (keto_tpu/check/dispatch.py): it holds the staging pool, the
        # slice controller and the compiled geometries, and asks back for
        # label arrays, the governor's guard, the auditor and maintenance
        self.dispatch = CheckDispatch(
            namespaces=self._nm,
            maintenance=self.maintenance,
            hbm=self.hbm,
            labels_dev=self._labels_dev,
            guard_alloc=self._guard_alloc,
            audit=self._audit_sample,
            current_snapshot=lambda: self._snapshot,
            oracle=self._fallback,
            it_cap=it_cap,
            max_batch=max_batch,
            mem_budget_bytes=mem_budget_bytes,
            labels_enabled=self._labels_enabled,
            mesh=mesh,
            shard_rows=shard_rows,
            shard_count=self._shard_count,
            shard_stack_sharding=self._shard_stack_sharding,
            multiprocess=self._multiprocess,
            lockstep_verify=self._lockstep_verify,
            stream_slice_target_ms=stream_slice_target_ms,
            stream_tail_ratio=stream_tail_ratio,
        )
        self.hbm.attach_rungs([
            ("staging", self.dispatch.suspend_staging, self.dispatch.resume_staging),
            ("labels", self._evict_labels, self._restore_labels),
            ("reverse", self._evict_reverse, self._restore_reverse),
            ("warm-ladder", self._evict_warm_ladder, self._restore_warm_ladder),
            ("overlay-budget", self._evict_overlay_budget,
             self._restore_overlay_budget),
        ])
        # ladder state the rungs flip (all derive from replicated inputs)
        self._labels_suspended = False
        self._configured_overlay_budget = self._max_overlay_edges
        self._memory_pressure = False
        self._last_label_bytes = 0
        self._last_warm_bytes = 0
        # sampled shadow-parity auditor: serve.audit_sample_rate of live
        # check decisions re-verify against the CPU reference oracle in a
        # supervised background worker — continuous proof that eviction
        # rungs (and everything else) never change answers. Divergence
        # counts audit_mismatches and flips health to DEGRADED.
        self._audit_rate = max(0.0, float(audit_sample_rate))
        self._audit_rng = random.Random(0xA0D17)
        self._audit_pending: collections.deque = collections.deque(maxlen=4096)
        self._audit_checks = 0
        self._audit_mismatches = 0
        #: evidence for recent shadow-parity divergences — both witnesses
        #: (the store-closure back-trace vs the CPU oracle's traversal);
        #: read by the flight recorder's ``audit_divergences`` section
        self.audit_divergences: collections.deque = collections.deque(maxlen=8)
        self._audit_task = SupervisedTask(
            "audit", self._audit_pass, stats=self.maintenance
        )
        # True while the supervised refresh worker owns the pass — the
        # seam where ladder restores and deferred label rebuilds run
        # without adding work to inline (serving-thread) refreshes
        self._in_maintenance_pass = False
        # streaming snapshot pipeline (keto_tpu/graph/stream_build.py):
        # build progress feeds health ({phase, pct} while STARTING) and
        # the keto_build_* metric families; the governed sorter runs the
        # build's edge-scale stable sorts on the device when the HBM
        # governor's transient plan fits, host bit-identically otherwise
        from keto_tpu.graph.device_build import GovernedSorter
        from keto_tpu.graph.stream_build import BuildProgress

        self.build_progress = BuildProgress(stats=self.maintenance)
        self._build_chunk_rows = max(1, int(build_chunk_rows))
        self._build_sorter = (
            GovernedSorter(
                hbm=self.hbm, stats=self.maintenance,
                # after the first build every sort is asked under the lock
                # with readers behind it: it may not wait for a compiler
                serving=lambda: self._snapshot is not None,
            )
            if device_build_enabled
            else None
        )

    @property
    def shard_count(self) -> int:
        """Graph-axis shards the explicit sharded mode partitions over
        (0 = not sharded) — bench and the metrics bridge read this."""
        return self._shard_count

    # -- snapshot lifecycle --------------------------------------------------

    def snapshot(self, at_least: Optional[int] = None) -> GraphSnapshot:
        """Device snapshot current with the store's watermark.

        Freshness contract (the real implementation of what the reference
        stubs as "snaptoken", internal/check/handler.go:162):

        - ``at_least=None`` — read-your-writes: blocks until the snapshot
          reflects every acknowledged write. Inserts apply as a delta
          overlay and deletes as tombstones (milliseconds — no re-intern,
          no relayout, keto_tpu/graph/overlay.py); class transitions and
          wildcard-graph deletes rebuild fully.
        - ``at_least=w`` — bounded staleness: any snapshot with id ≥ ``w``
          serves immediately. If the store has moved on, a background
          rebuild is kicked off and *this* call returns the old snapshot —
          checks issued mid-rebuild are served from the old snapshot
          (Zanzibar zookie semantics).
        """
        snap = self._snapshot
        wm = self._store.watermark()
        if snap is not None and self._schema_moved(snap):
            snap = None  # built under another rewrite schema: never served
        if snap is not None and snap.snapshot_id == wm:
            self._maybe_kick_compaction(snap)
            return snap
        if (
            at_least is not None
            and snap is not None
            and snap.snapshot_id >= at_least
        ):
            self._kick_background_refresh()
            return snap
        with self._engine_lock("pinned" if at_least is not None else "latest"):
            return self._refresh_locked()

    @contextlib.contextmanager
    def _engine_lock(self, site: Optional[str] = None):
        """Hold the engine's lock. A thread on the serving path names its
        ``site`` (``latest``, ``pinned``) and what it waits where another
        holds the lock is counted (``_lock_wait``); maintenance passes wait
        uncounted."""
        if site is None:
            self._lock.acquire()
        elif not self._lock.acquire(blocking=False):
            with self._lock_wait(site):
                self._lock.acquire()
        try:
            yield
        finally:
            self._release_engine_lock()

    def _release_engine_lock(self) -> None:
        self._lock_holder = None
        self._lock.release()

    @contextlib.contextmanager
    def _lock_wait(self, site: str):
        """One ``perf_counter`` pair around a serving thread's wait for the
        engine's lock: ``keto_engine_lock_wait_seconds_total{site}``, and a
        ``keto.engine.lock_wait`` span while a profiler session is open,
        so that what the dispatch thread waited for has a name."""
        t0 = time.perf_counter()
        try:
            if not SESSION.open:
                yield
            else:
                with SESSION.annotation(
                    "keto.engine.lock_wait", site=site, holder=str(self._lock_holder)
                ):
                    yield
        finally:
            self.maintenance.observe_ms(
                f"lock_wait_{site}", (time.perf_counter() - t0) * 1e3
            )

    def _serve_stale(self) -> GraphSnapshot:
        """Hand out the snapshot there is although the store has moved on,
        counted by what stands in the way
        (``keto_snapshot_stale_serves_total{holder}``: only ever a full
        rebuild, running or owed; a fold and a delta apply are waited
        for), and have the supervised worker catch up."""
        self.maintenance.incr("stale_serves_rebuild")
        self._kick_background_refresh()
        return self._snapshot

    def snapshot_serving(self) -> GraphSnapshot:
        """Serving-path snapshot: NEVER stalls the read plane on an
        expensive rebuild (VERDICT r4 weak #1 — a single delta-ineligible
        write used to freeze checks for the full rebuild time).

        - the store hasn't moved → current snapshot (plus the usual
          background compaction kick);
        - watermark advanced and a delta applies → synchronous catch-up
          (milliseconds: inserts extend the overlay, deletes tombstone —
          effectively read-your-writes);
        - the lock is held by a delta apply or a fold → wait for it (both
          are bounded work: fractions of a second, and a fold never
          compiles, keto_tpu/graph/device_build.py), then catch up as
          above: a read sent after a write's acknowledgement sees it;
        - only a full rebuild can reach the watermark, or one holds the
          lock → if the last build was cheap (≤ sync_rebuild_budget_s),
          just do it; otherwise serve the current snapshot (bounded
          staleness, Zanzibar default; counted,
          ``keto_snapshot_stale_serves_total``) and let the background
          refresh catch up.

        Callers needing hard read-your-writes use ``snapshot()`` /
        ``mode="latest"``; callers holding a write's snaptoken use
        ``snapshot(at_least=token)``.
        """
        snap = self._snapshot
        if snap is not None and self._schema_moved(snap):
            # the one rebuild the read plane waits for: answers under the
            # old schema are not stale, they are another deployment's
            return self.snapshot()
        if snap is None or self._last_full_build_s <= self._sync_rebuild_budget_s:
            try:
                return self.snapshot()
            except Exception:
                if self._snapshot is None:
                    raise  # nothing to serve stale from — STARTING territory
                # refresh is broken but the read plane holds: serve the
                # last snapshot, count the failure, retry in the
                # supervised background worker (the health state machine
                # flips NOT_SERVING once staleness crosses its budget)
                self.maintenance.incr("refresh_failures")
                _log.warning(
                    "inline refresh failed; serving stale snapshot", exc_info=True
                )
                return self._serve_stale()
        wm = self._store.watermark()
        if snap.snapshot_id >= wm:
            # current — return it directly (NOT via snapshot(): a write
            # landing between the two watermark reads would send that
            # call into an inline rebuild), with the usual compaction kick
            self._maybe_kick_compaction(snap)
            return snap
        if self._lock.acquire(blocking=False) or self._wait_out_bounded_holder():
            try:
                try:
                    got = self._refresh_locked(delta_only=True)
                except Exception:
                    # the serving path NEVER stalls or fails on refresh
                    # trouble: count it, serve the current snapshot
                    # (bounded staleness — the health state machine turns
                    # budget overruns into NOT_SERVING), and let the
                    # supervised background worker retry with backoff
                    self.maintenance.incr("refresh_failures")
                    _log.warning(
                        "inline delta refresh failed; serving stale snapshot",
                        exc_info=True,
                    )
                    got = None
                if got is not None:
                    if self._overlay_edge_count(got) > self._max_overlay_edges:
                        # serve fresh NOW; the supervised worker folds the
                        # oldest overlay segments off the serving path
                        # (bounded per pass — occupancy is governed by
                        # fold rate, not a synchronous compaction wall)
                        self._kick_background_refresh()
                    return got
            finally:
                self._release_engine_lock()
        # rebuild territory (or a rebuild is already holding the lock):
        # serve stale, catch up off the serving path
        return self._serve_stale()

    def _wait_out_bounded_holder(self) -> bool:
        """``snapshot_serving`` found the lock taken. A delta apply and a
        fold end in a fraction of a second and leave a snapshot that holds
        every acknowledged write: wait for them (counted under
        ``site="serving"``). A full rebuild takes as long as a boot: not
        for that one. True with the lock held; False while a rebuild
        holds it."""
        with self._lock_wait("serving"):
            while self._lock_holder != "rebuild":
                if self._lock.acquire(timeout=0.02):
                    return True
        return False

    def _schema_moved(self, snap: GraphSnapshot) -> bool:
        """Was ``snap`` built under another rewrite schema than the current
        namespaces carry? The schema's fingerprint is a build input, as
        ``wild_ns_ids`` is: derived edges stand for it in the adjacency."""
        built = snap.rewrites.fingerprint if snap.rewrites is not None else ""
        return built != schema_of(self._nm()).fingerprint

    def peek_snapshot(self) -> Optional[GraphSnapshot]:
        """The snapshot being served right now, None before the first
        build: a plain read that refreshes nothing and waits for nothing.
        The next round may well choose another one."""
        return self._snapshot

    def _snapshot_for(self, at_least, mode: str) -> GraphSnapshot:
        """The snapshot a round is answered from. ``serving`` with a floor
        is a round that holds unpinned requests beside pinned ones
        (keto_tpu/driver/batch.py ``_consistency_kw``): the unpinned ones
        are owed the serving rules (every acknowledged write that a delta
        reaches), the pinned ones their floor, so the round catches up as
        an unpinned round would and only then looks at the floor."""
        if mode == "serving":
            snap = self.snapshot_serving()
            if at_least is None or snap.snapshot_id >= at_least:
                return snap
        if at_least is not None:
            return self.snapshot(at_least=at_least)
        return self.snapshot()

    def _read_store(self, fn, *args):
        """A persistence read on the refresh path: transient failures
        retry through the shared jittered-backoff policy (x/retry.py) for
        up to ``refresh_retry_max_wait_s`` before the maintenance pass is
        declared failed. ``refresh-read`` is the fault-injection seam
        (x/faults.py) the resilience suite arms to kill refresh."""

        def attempt():
            faults.check("refresh-read")
            return fn(*args)

        return retry_call(
            attempt,
            max_wait_s=self._refresh_retry_max_wait_s,
            base_s=0.05,
            max_s=0.5,
            on_retry=lambda e, d: self.maintenance.incr("refresh_read_retries"),
        )

    # -- health (keto_tpu/driver/health.py reads this surface) ---------------

    def staleness_s(self) -> float:
        """Seconds the serving snapshot has been behind the store
        watermark (0.0 while current, or before the first build — a cold
        engine builds inline on first check, it is not stale). Observing
        a gap also kicks the supervised catch-up, so a health poll is
        itself a self-healing probe."""
        snap = self._snapshot
        if snap is None:
            return 0.0
        try:
            wm = self._store.watermark()
        except Exception:
            wm = None  # store unreadable: keep (or start) the behind clock
        now = time.monotonic()
        if wm is not None and snap.snapshot_id >= wm:
            self._behind_since = None
            return 0.0
        # read once: another poller that finds the snapshot current clears
        # the attribute between a test of it and a use of it
        since = self._behind_since
        if since is None:
            since = self._behind_since = now
        self._kick_background_refresh()
        return now - since

    def health(self) -> dict:
        """Live inputs for the health state machine
        (keto_tpu/driver/health.py): snapshot presence and staleness vs
        the store watermark, maintenance-thread liveness and crash
        counters, and the degraded-mode flag."""
        rt = self._refresh_task
        return {
            "has_snapshot": self._snapshot is not None,
            "staleness_s": self.staleness_s(),
            "maintenance_alive": (
                rt.alive() and self._cache_task.alive() and self._audit_task.alive()
            ),
            "refresh_failures": rt.crashes,
            "refresh_consecutive_failures": rt.consecutive_failures,
            "refresh_last_error": rt.last_error,
            "degraded": self._degraded,
            "consecutive_device_errors": self._consec_device_errors,
            # HBM budget governor (keto_tpu/driver/hbm.py): refusing a
            # refresh for memory reports DEGRADED(memory_pressure)
            "memory_pressure": self._memory_pressure,
            "hbm_resident_bytes": self.hbm.resident_bytes(),
            "hbm_budget_bytes": self.hbm.budget_bytes,
            "hbm_rung": self.hbm.rung_depth,
            # shadow-parity auditor: any divergence flips DEGRADED
            "audit_checks": self._audit_checks,
            "audit_mismatches": self._audit_mismatches,
            # streaming build pipeline: a multi-minute STARTING boot is
            # visibly alive — health surfaces the live phase and a
            # coarse completion estimate (keto_tpu/graph/stream_build.py)
            "build_phase": self.build_progress.current_phase,
            "build_pct": self.build_progress.pct(),
            "build_rows_ingested": self.build_progress.rows_ingested,
        }

    def close(self) -> None:
        """Stop the supervised maintenance workers (daemon threads — this
        is shutdown hygiene, not a liveness requirement) and abort any
        cooperative warmup loop."""
        self._closing = True
        self._refresh_task.stop()
        self._cache_task.stop()
        self._audit_task.stop()
        self._label_build_wait()
        if self._build_sorter is not None:
            self._build_sorter.close()
        self.dispatch.close()

    # -- HBM budget governor (keto_tpu/driver/hbm.py) ------------------------

    def _plan_or_refuse(self, what: str, need: int, per_shard=None) -> None:
        """Plan ``need`` device bytes before an upload. The governor walks
        the eviction ladder until it fits; with every rung spent the
        refresh is REFUSED — unless there is no snapshot at all (cold
        boot: nothing to serve stale from, so the upload proceeds over
        budget and is merely accounted). ``per_shard`` additionally holds
        the plan against each shard's slice of the budget (sharded mode:
        the hottest shard is the binding constraint, and any eviction the
        walk takes is mesh-wide — one ladder, every shard)."""
        if self.hbm.plan(need, what=what, per_shard=per_shard):
            return
        if self._snapshot is None:
            self.hbm.note_forced(what, need)
            return
        self.hbm.note_refused()
        self._memory_pressure = True
        self.maintenance.set_gauge("memory_pressure", 1)
        raise MemoryPressure(
            f"HBM budget refused {what}: need {need} bytes with "
            f"{self.hbm.resident_bytes()} resident of "
            f"{self.hbm.budget_bytes} budgeted and every eviction rung "
            "spent — serving the current snapshot stale"
        )

    def _guard_alloc(self, what: str, fn):
        """Run one device-put / compiled-call seam with OOM containment:
        a classified RESOURCE_EXHAUSTED (real XLA, or the injected
        ``device-alloc`` oom fault) evicts one ladder rung and retries
        ONCE, then escalates to the caller — check paths land on the
        existing bit-identical CPU fallback, refresh paths count a
        supervised failure and serve stale. Never a crash."""

        def attempt():
            faults.check("device-alloc")
            return fn()

        try:
            return attempt()
        except Exception as e:
            if self._multiprocess or not is_resource_exhausted(e):
                raise
            self.hbm.note_oom(what)
            setattr(e, "_keto_oom_handled", True)
            rung = self.hbm.evict_one(reason=f"oom at {what}")
            if rung is None:
                raise
            _log.warning(
                "device OOM at %s: evicted rung %r, retrying once", what, rung
            )
            try:
                out = attempt()
            except Exception as e2:
                if is_resource_exhausted(e2):
                    setattr(e2, "_keto_oom_handled", True)
                raise
            self.hbm.note_oom_recovered()
            return out

    def _restore_plan_bytes(self) -> int:
        """Bytes a full walk back up the ladder would re-place on device
        — the ``planned`` margin ``maybe_restore`` holds against, so the
        ladder doesn't oscillate (restore labels → over budget → evict
        labels → ...)."""
        est = 0
        if self._labels_suspended:
            est += self._last_label_bytes
        if self.dispatch.width_trim:
            est += self._last_warm_bytes
        return est

    def attach_reverse_rung(
        self, evict: Callable[[], int], restore: Callable[[], None]
    ) -> None:
        """The list engine's hooks behind the governor's ``reverse``
        rung (eviction drops the list layouts' device arrays; reverse
        queries fall back to the CPU-reference lister bit-identically).
        Called once at list-engine construction."""
        self._reverse_evict_cb = evict
        self._reverse_restore_cb = restore

    def _evict_reverse(self) -> int:
        cb = self._reverse_evict_cb
        return int(cb()) if cb is not None else 0

    def _restore_reverse(self) -> None:
        cb = self._reverse_restore_cb
        if cb is not None:
            cb()

    def _evict_labels(self) -> int:
        """Rung 1 — drop the 2-hop label arrays: coverage loss only (the
        router falls back to BFS bit-identically), and typically the
        largest discretionary resident family."""
        self._labels_suspended = True
        freed = self.hbm.release("labels")
        self._last_label_bytes = max(self._last_label_bytes, freed)
        snap = self._snapshot
        if snap is not None:
            snap.device_labels = None
            snap.device_shard_labels = None
            snap.labels = None
        self.maintenance.set_gauge("label_coverage", 0.0)
        self.maintenance.set_gauge("label_entries", 0)
        return freed

    def _restore_labels(self) -> None:
        self._labels_suspended = False
        # the next refresh pass rebuilds + re-uploads via _ensure_labels
        self._kick_background_refresh()

    def _evict_warm_ladder(self) -> int:
        """Rung 3 (after labels and the list engine's reverse rung) —
        trim the compile-width ladder to its lower rungs and
        drop the warm-compiled executables: wide-slice throughput falls,
        decisions do not change (the same kernels at narrower widths)."""
        self.dispatch.trim_widths()
        freed = self.hbm.release("warmup")
        self._last_warm_bytes = max(self._last_warm_bytes, freed)
        return freed

    def _restore_warm_ladder(self) -> None:
        self.dispatch.width_trim = 0

    def _evict_overlay_budget(self) -> int:
        """Rung 4 (last) — shrink the overlay edge budget so pending deltas fold
        into the base layout (compaction retires the overlay's device
        arrays and keeps future overlays small)."""
        self._max_overlay_edges = max(64, self._configured_overlay_budget // 8)
        self.maintenance.set_gauge("overlay_budget", self._max_overlay_edges)
        snap = self._snapshot
        freed = 0
        if snap is not None and snap.has_overlay:
            from keto_tpu.graph.overlay import overlay_device_bytes

            freed = overlay_device_bytes(snap)  # retired when the fold lands
            self._kick_background_refresh(force_full=True)
        return freed

    def _restore_overlay_budget(self) -> None:
        self._max_overlay_edges = self._configured_overlay_budget
        self.maintenance.set_gauge("overlay_budget", self._max_overlay_edges)

    # -- sampled shadow-parity auditor ---------------------------------------

    def _audit_sample(self, tuples, decisions, token: Optional[int]) -> None:
        """Queue a random ``audit_sample_rate`` sample of live decisions
        for re-verification against the CPU reference oracle (supervised
        background worker — never on the serving path)."""
        if self._audit_rate <= 0.0 or token is None:
            return
        rng = self._audit_rng
        rate = self._audit_rate
        # the draw comes first: a framed batch builds only the sampled
        # queries as objects
        idx = [i for i in range(len(tuples)) if rng.random() < rate]
        if idx:
            for i, rt in zip(idx, pick_tuples(tuples, idx, "audit")):
                self._audit_pending.append((rt, bool(decisions[i]), token))
            self._audit_task.kick()

    def _audit_pass(self) -> None:
        """One supervised audit pass: drain the sample queue, re-check
        each decision on the CPU oracle. Samples whose snaptoken no
        longer matches the store watermark are skipped (the oracle reads
        the live store — comparing across a write would fabricate
        divergence). A real mismatch is the one alarm that must never be
        rationalized away: count it and flip DEGRADED via health()."""
        while True:
            try:
                rt, decision, token = self._audit_pending.popleft()
            except IndexError:
                return
            try:
                # resilience seam: arming ``audit-flip`` (x/faults.py)
                # corrupts the device's recorded decision, forcing the
                # auditor to see a divergence — how the witness-diff
                # capture below is regression-tested without a real bug
                faults.check("audit-flip")
            except faults.FaultInjected:
                decision = not decision
            try:
                wm = self._store.watermark()
            except Exception:
                continue  # store unreadable: the health machine owns that
            if wm != token:
                self.maintenance.incr("audit_skipped_stale")
                continue
            got = self._fallback().subject_is_allowed(rt)
            self._audit_checks += 1
            self.maintenance.incr("audit_checks")
            if got != decision:
                self._audit_mismatches += 1
                self.maintenance.incr("audit_mismatches")
                self._note_audit_divergence(rt, decision, got, token)
                _log.error(
                    "shadow-parity audit MISMATCH: %r decided %s on device, "
                    "%s on the CPU oracle (snaptoken %d) — flipping DEGRADED",
                    rt, decision, got, token,
                )

    def _note_audit_divergence(
        self, rt: RelationTuple, device: bool, oracle: bool, token: int
    ) -> None:
        """Capture the evidence for one shadow-parity divergence: the
        store-closure back-trace the device route should have witnessed
        (BFS shortest path) next to the CPU oracle's own traversal. The
        deque rides into flight-recorder bundles (driver/registry.py
        ``audit_divergences`` section) — the debugging artifact for the
        one alarm that must never be rationalized away."""
        try:
            from keto_tpu.explain.witness import build_witness, oracle_witness

            _, dev_path, certificate = build_witness(self._store, rt)
            orc_path = oracle_witness(self._store, rt)
            self.audit_divergences.append(
                {
                    "tuple": str(rt),
                    "device_decision": device,
                    "oracle_decision": oracle,
                    "snaptoken": token,
                    "device_witness": (
                        [str(t) for t in dev_path] if dev_path else None
                    ),
                    "oracle_witness": (
                        [str(t) for t in orc_path] if orc_path else None
                    ),
                    "certificate": certificate,
                }
            )
        except Exception:  # keto-analyze: ignore[KTA401] evidence capture is best-effort; the mismatch counter + DEGRADED flip above already raised the alarm
            pass

    # -- degraded mode (CPU fallback) ----------------------------------------

    def _should_fallback(self) -> bool:
        """Route checks to the CPU reference engine? True while degraded,
        except once per ``degraded_probe_s`` — then one batch tries the
        device path again and recovery is automatic on success."""
        if not self._degraded or self._multiprocess:
            return False
        return time.monotonic() < self._probe_after

    def _note_device_error(self, exc: BaseException) -> None:
        # a RESOURCE_EXHAUSTED that escaped the _guard_alloc seams (e.g.
        # raised at transfer time) still counts as memory
        # pressure and descends one rung before the CPU fallback serves
        # the batch — the ladder, not just the fallback, is the answer
        if (
            not self._multiprocess
            and is_resource_exhausted(exc)
            and not getattr(exc, "_keto_oom_handled", False)
        ):
            self.hbm.note_oom("check-path")
            self.hbm.evict_one(reason="oom on the check path")
        self.maintenance.incr("device_errors")
        self._consec_device_errors += 1
        self._probe_after = time.monotonic() + self._degraded_probe_s
        if (
            not self._degraded
            and self._consec_device_errors >= self._device_error_threshold
        ):
            self._degraded = True
            self.maintenance.set_gauge("degraded", 1)
            _log.error(
                "device check path failed %d times in a row (%s); entering "
                "DEGRADED mode — checks served by the CPU reference engine "
                "until the device path heals",
                self._consec_device_errors, exc,
            )
        else:
            _log.warning(
                "device check failed (%s); serving this batch from the CPU "
                "reference engine", exc,
            )

    def _note_device_ok(self) -> None:
        if self._consec_device_errors or self._degraded:
            if self._degraded:
                _log.warning("device check path healthy; leaving DEGRADED mode")
                self.maintenance.set_gauge("degraded", 0)
            self._degraded = False
            self._consec_device_errors = 0

    def _fallback(self):
        with self._fallback_lock:
            if self._fallback_engine_obj is None:
                from keto_tpu.check.engine import CheckEngine

                self._fallback_engine_obj = CheckEngine(self._store, namespaces=self._nm)
            return self._fallback_engine_obj

    def set_store(self, store) -> None:
        """Fleet promotion handoff: swap the backing store WITHOUT
        rebuilding the device snapshot. Valid precisely because the
        durable-watermark handoff guarantees the new store's watermark
        >= the snapshot's id over the same tuple history — the resident
        snapshot stays a correct prefix, and the very next refresh pass
        catches up through the ordinary delta path. Also resets the CPU
        fallback engine (it holds a store reference of its own)."""
        self._store = store
        with self._fallback_lock:
            self._fallback_engine_obj = None

    def _fallback_check(self, tuples) -> tuple[list[bool], Optional[int]]:
        """Answer on the CPU reference engine (keto_tpu/check/engine.py)
        — the differential-testing oracle the device path is fuzz-tested
        against, so decisions are bit-identical by construction. It reads
        the live store (read-your-writes fresh); the returned snaptoken is
        the store watermark when readable."""
        eng = self._fallback()
        out = [eng.subject_is_allowed(t) for t in tuples]
        self.maintenance.incr("fallback_checks", by=len(out))
        try:
            token = self._store.watermark()
        except Exception:
            token = None
        return out, token

    def _fallback_stream(
        self, tuples_iter, *, ordered: bool, chunk: int = 1024,
        with_info: bool = False,
    ):
        """Streaming surface of the CPU fallback — same yield contract as
        ``_stream`` (bool arrays in order, or ``(offset, array)`` pairs
        with ``ordered=False``; ``with_info`` adds the per-slice info
        dict with route ``cpu``). Returns ``(generator, token)``."""
        try:
            token = self._store.watermark()
        except Exception:
            token = None
        eng = self._fallback()

        def gen():
            it = iter(tuples_iter)
            off = 0
            while True:
                t0 = time.perf_counter()
                batch = list(itertools.islice(it, chunk))
                if not batch:
                    return
                out = np.fromiter(
                    (eng.subject_is_allowed(t) for t in batch), dtype=bool,
                    count=len(batch),
                )
                self.maintenance.incr("fallback_checks", by=len(batch))
                ms = (time.perf_counter() - t0) * 1e3
                self.dispatch._note_route("cpu", len(batch), ms)
                if ordered:
                    yield out
                elif with_info:
                    yield off, out, {
                        "width": len(batch),
                        "bfs_steps": 0,
                        "route": "cpu",
                        "service_ms": round(ms, 3),
                    }
                else:
                    yield off, out
                off += len(batch)

        return gen(), token

    def _guard_stream(self, inner, snap: GraphSnapshot):
        """Device-error accounting around a streaming generator: a failed
        stream counts toward degraded mode — the caller (CheckBatcher)
        retries its unresolved futures through ``batch_check_with_token``,
        which then routes to the CPU fallback — and a completed stream
        marks the device path healthy and hands what it saw of the
        traversal depth to ``_after_batch``."""

        def gen():
            try:
                max_iters = yield from inner
            except Exception as e:
                if not self._multiprocess and not isinstance(e, KetoError):
                    self._note_device_error(e)
                raise
            self._note_device_ok()
            self.dispatch._after_batch(max_iters, snap)

        return gen()

    def _maybe_kick_compaction(self, snap: GraphSnapshot) -> None:
        """Fold an overlay that has been quiet for compact_after_s into a
        fresh base layout, off the serving path (one policy, shared by
        snapshot() and snapshot_serving())."""
        if (
            snap.has_overlay
            and self._overlay_born is not None
            and time.monotonic() - self._overlay_born > self._compact_after_s
        ):
            self._kick_background_refresh(force_full=True)

    def _kick_background_refresh(self, force_full: bool = False) -> None:
        """Schedule a supervised background pass bringing the snapshot up
        to the store's watermark — or, with ``force_full``, compacting a
        pending overlay into a fresh base layout — so readers never pay
        the rebuild. Crashes are counted, logged, and retried with
        jittered backoff (x/supervise.py) instead of silently killing the
        maintenance thread."""
        if force_full:
            self._refresh_force_full = True
        self._refresh_task.kick()

    def _refresh_pass(self) -> None:
        """One supervised refresh pass (the SupervisedTask target)."""
        force_full, self._refresh_force_full = self._refresh_force_full, False
        self._in_maintenance_pass = True
        try:
            with self._engine_lock():
                self._refresh_locked(force_full=force_full)
        except Exception:
            if force_full:
                # the failed pass still owes a compaction — retry as one
                self._refresh_force_full = True
            raise
        finally:
            self._in_maintenance_pass = False

    def _refresh_locked(
        self, force_full: bool = False, delta_only: bool = False
    ) -> Optional[GraphSnapshot]:
        """Bring the snapshot to the current watermark (caller holds the
        lock): delta overlay when possible; an overlay past the edge
        budget (or a quiet one, via ``force_full``) folds into the base
        layout by segment (keto_tpu/graph/compaction.py); a full rebuild
        is the fallback for shapes compaction can't express. With
        ``delta_only``, returns None instead of rebuilding (the serving
        path's never-stall contract — snapshot_serving falls back to
        stale; oversized overlays still apply and compact off-path)."""
        snap = self._snapshot
        self._lock_holder = "delta"
        wm = self._store.watermark()
        fold_failed = False
        rewrites = schema_of(self._nm())
        if snap is not None and self._schema_moved(snap):
            if delta_only:
                return None
            # nothing of a graph built under another schema carries over:
            # not its overlay, not its fold history
            snap = None
            self._fold_base, self._seg_log, self._pending_seg = None, [], None
        if snap is None and self._cache_dir is not None and not delta_only:
            self._lock_holder = "rebuild"
            snap = self._load_cache_locked(wm)
            self._lock_holder = "delta"
        # an over-budget overlay owes a fold even when the snapshot is
        # already current: the maintenance pass falls through to the
        # delta path (an empty delta) so the fold below runs — serving
        # callers keep the early return and never pay it
        needs_fold = (
            snap is not None
            and snap.has_overlay
            and self._in_maintenance_pass
            and not delta_only
            and self._overlay_edge_count(snap) > self._max_overlay_edges
        )
        if snap is not None and snap.snapshot_id == wm and not (
            force_full and snap.has_overlay
        ) and not needs_fold:
            self._behind_since = None
            if self._in_maintenance_pass and not delta_only:
                # an already-current engine has no install step, so the
                # supervised pass is where the eviction ladder walks back
                # up once pressure clears — and where labels dropped by
                # the ladder get rebuilt after their rung restores
                self.hbm.maybe_restore(planned=self._restore_plan_bytes())
                if not snap.has_overlay and snap.labels is None:
                    self._ensure_labels(snap)
            return snap
        wild_ns_ids = frozenset(
            n.id for n in self._nm().namespaces() if n.name == ""
        )
        new = None
        if snap is not None:
            new = self._try_delta(snap, wild_ns_ids)
            if new is not None:
                # segment log: record the delta for the background fold
                # (append-at-install would be cleaner, but the fold below
                # needs the newest segment already on the log; a failed
                # install leaves a dangling entry the continuity check in
                # _fold_locked detects and discards)
                seg, self._pending_seg = self._pending_seg, None
                if seg is not None and (seg[2] or seg[0] != seg[1]):
                    self._seg_log.append(seg)
                if len(self._seg_log) > 4096:
                    # runaway log (fold persistently losing to the write
                    # rate): drop the replay history; the next fold runs
                    # as one full compaction
                    self._fold_base, self._seg_log = None, []
                self.maintenance.incr("delta_applies")
                n_ov = self._overlay_edge_count(new)
                self.maintenance.set_gauge("overlay_edges", n_ov)
                over = force_full or n_ov > self._max_overlay_edges
                if over and new.has_overlay and not delta_only:
                    if not self._in_maintenance_pass:
                        # serving caller tripped the budget: NEVER fold on
                        # the caller's thread — install the oversized
                        # overlay (the hard cap in _try_delta still bounds
                        # it) and let the supervised worker fold it
                        self._refresh_task.kick()
                    else:
                        try:
                            self._lock_holder = "fold"
                            folded = self._fold_locked(new, full=force_full)
                        except Exception:
                            # a broken fold must not kill the refresh: log
                            # it and let the full rebuild below re-establish
                            # a clean base layout. The failure counter is
                            # deferred until the rebuild is recorded so an
                            # unlocked reader never observes the failure
                            # without its fallback
                            fold_failed = True
                            _log.warning(
                                "overlay fold failed; falling back to a full rebuild",
                                exc_info=True,
                            )
                            folded = None
                        if folded is not None:
                            new = folded
                        elif force_full or n_ov > self._max_overlay_edges:
                            new = None  # fold requires a real re-layout
        if new is None:
            if delta_only:
                return None
            from keto_tpu.graph.stream_build import full_build

            self._lock_holder = "rebuild"
            t0 = time.monotonic()
            # streaming, overlapped, device-accelerated pipeline: chunked
            # store scan feeds the native intern pool, the layout's
            # stable sorts run on the device when the governor's plan
            # fits, and build_progress narrates phases into health() and
            # the keto_build_* families the whole way
            new = full_build(
                self._store, wild_ns_ids,
                peel_seed_cap=self._peel_seed_cap,
                sorter=self._build_sorter,
                progress=self.build_progress,
                read_retry=self._read_store,
                chunk_rows=self._build_chunk_rows,
                rewrites=rewrites,
            )
            self._upload_buckets(new)
            # labels phase overlaps the rest of the pipeline: the device
            # sweeps run on a background thread while cache_save and the
            # remaining host work proceed; BFS serves the gap
            self._start_label_build(new)
            self._last_full_build_s = time.monotonic() - t0
            self.maintenance.incr("full_rebuilds")
            self.maintenance.observe_ms(
                "full_rebuild", self._last_full_build_s * 1e3
            )
            if fold_failed:
                self.maintenance.incr("compaction_failures")
        self._apply_ell_patch(new)
        self._upload_overlay(new)
        self._snapshot = new
        # freshness clock: reaching the watermark this pass read counts as
        # current even if the store moved again meanwhile (the next pass
        # is kicked by whoever observes the new gap)
        self._behind_since = None
        # the refresh landed within budget: memory pressure (if any) has
        # cleared, and the governor may walk back UP the eviction ladder
        # — holding the restore against what the restored rungs would
        # re-place so the ladder cannot oscillate at the budget edge
        if self._memory_pressure:
            self._memory_pressure = False
            self.maintenance.set_gauge("memory_pressure", 0)
        self.hbm.maybe_restore(planned=self._restore_plan_bytes())
        if new.has_overlay:
            if self._overlay_born is None:
                self._overlay_born = time.monotonic()
            if (
                self._in_maintenance_pass
                and self._overlay_edge_count(new) > self._max_overlay_edges
            ):
                # a bounded fold left the overlay over budget: fold more
                # next pass (each pass retires at least one segment, so
                # this converges whenever writes pause)
                self._refresh_task.kick()
        else:
            # overlay-free install: this snapshot is the new fold base
            # and the segment history behind it is retired
            self._fold_base, self._seg_log = new, []
            self._overlay_born = None
            self.maintenance.set_gauge("overlay_edges", 0)
            self._kick_cache_save(new)
        return new

    def _overlay_edge_count(self, snap: GraphSnapshot) -> int:
        """Overlay occupancy: pending delta edges + tombstones (the number
        the budget gauges)."""
        n = 0
        if snap.ov_ell is not None:
            n += int(snap.ov_ell.shape[0])
        if snap.ov_removed is not None:
            n += int(snap.ov_removed.size)
        if snap.ov_out:
            n += sum(int(np.asarray(v).size) for v in snap.ov_out.values())
        if snap.ov_sink_in:
            n += sum(int(np.asarray(v).size) for v in snap.ov_sink_in.values())
        return n

    def _try_delta(
        self, base: GraphSnapshot, wild_ns_ids
    ) -> Optional[GraphSnapshot]:
        """Apply a watermark advance as an overlay (no re-intern, no
        relayout; inserts extend the overlay, deletes tombstone —
        keto_tpu/graph/overlay.py). None when the store can't produce a
        delta (log overflow, no support), the delta needs a class change,
        or the overlay would exceed the hard cap (budget overflows below
        the cap now COMPACT instead of rebuilding — _refresh_locked)."""
        from keto_tpu.graph.overlay import apply_delta, rows_as_ops

        changes_since = getattr(self._store, "changes_since", None)
        if changes_since is not None:
            got = self._read_store(changes_since, base.snapshot_id)
            if got is None:
                return None
            ops, new_wm = got
        else:
            rows_since = getattr(self._store, "rows_since", None)
            if rows_since is None:
                return None
            got = self._read_store(rows_since, base.snapshot_id)
            if got is None:
                return None
            rows, new_wm = got
            ops = rows_as_ops(rows)
        n_ov = len(ops) + (base.ov_ell.shape[0] if base.ov_ell is not None else 0)
        if base.ov_removed is not None:
            n_ov += int(base.ov_removed.size)
        # hard cap: past this, per-delta overlay merge costs outgrow even
        # a rebuild; the budget itself is a compaction trigger, not a
        # bail. The 64k floor keeps small-budget configs from rebuilding
        # on bursts compaction absorbs in milliseconds.
        if n_ov > max(4 * self._max_overlay_edges, 65536):
            return None
        faults.check("overlay-apply")
        got = apply_delta(base, ops, new_wm, wild_ns_ids)
        if got is not None:
            # stash the raw segment for the log-structured fold: the
            # caller appends it to the segment log with the delta
            self._pending_seg = (int(base.snapshot_id), int(new_wm), list(ops))
        return got

    def _compact_locked(self, snap: GraphSnapshot) -> Optional[GraphSnapshot]:
        """Fold ``snap``'s overlay into its base layout (caller holds the
        lock). Only the touched buckets re-upload; everything else —
        device arrays, interner, kernel geometries — is reused. None when
        the overlay's shape needs the full-rebuild fallback."""
        from keto_tpu.graph.compaction import compact_snapshot

        faults.check("compaction")
        # the compacted snapshot gets a fresh (usually empty) overlay —
        # the resident device pack no longer matches any lineage
        self._ov_pack = None
        t0 = time.monotonic()
        # flush pending device-bucket patches first: compaction reuses
        # untouched device buckets, which is only sound when they agree
        # with the host arrays modulo the tombstones it re-uploads (an
        # unapplied restore patch would otherwise leave a stale sentinel)
        self._apply_ell_patch(snap)
        # device splice: the fold's transposed-CSR / list-layout
        # re-derivation sorts run on the device under the same governed
        # policy as full builds — write-heavy tenants stop paying the
        # host-side rebuild tail (keto_tpu/graph/device_build.py)
        got = compact_snapshot(
            snap, sorter=self._build_sorter, label_patcher=self._label_patcher
        )
        if got is None:
            return None
        new = got.snapshot
        if got.touched_buckets or new.device_buckets is None:
            if new.device_buckets is None:
                self._upload_buckets(new)
            else:
                # old + new copies of every touched bucket are co-resident
                # while in-flight batches still gather the old ones: plan
                # the re-upload like any other swap before placing it
                self._plan_or_refuse("compaction bucket re-upload", got.touched_bytes)
                bufs = list(new.device_buckets)
                for bi in got.touched_buckets:
                    bufs[bi] = self._guard_alloc(
                        "compaction-upload",
                        lambda b=new.buckets[bi]: self._put_bucket(b.nbrs, new.num_int),
                    )
                new.device_buckets = tuple(bufs)
                self.hbm.register("snapshot", new.bucket_device_bytes())
        if new.hub_ptr is None:
            self._upload_hub(new)  # the fold's sinks have lists of their own
        # label index maintenance: compaction patched incrementally,
        # kept the index, or left it for a rebuild here (folded ELL
        # deletions / patch budget) — either way the compacted snapshot
        # serves with labels matching its interior subgraph exactly
        if got.labels == "patched":
            self.maintenance.incr("label_patches")
            self.maintenance.observe_ms("label_patch", new.labels.build_ms)
        elif got.labels == "patch_abort":
            # the incremental patch ran past its visit budget (or the
            # resume sets were truncated) — no longer invisible: counted,
            # bridged to /metrics, and the device rebuild below (this IS
            # the supervised maintenance pass) replaces the stale index
            self.maintenance.incr("label_patch_aborts")
            self.maintenance.incr("label_rebuilds")
        elif got.labels == "rebuild":
            self.maintenance.incr("label_rebuilds")
        self._ensure_labels(new)
        ms = (time.monotonic() - t0) * 1e3
        self.maintenance.incr("compactions")
        self.maintenance.observe_ms("compaction", ms)
        _log.info(
            "overlay compacted in %.1f ms (%d buckets re-uploaded)",
            ms, len(got.touched_buckets),
        )
        return new

    def _fold_locked(
        self, snap: GraphSnapshot, full: bool = False
    ) -> Optional[GraphSnapshot]:
        """Log-structured fold (caller holds the lock): replay the OLDEST
        delta segments onto the last overlay-free base, compact just
        those, then re-apply the remaining segments — so a fold pass
        costs ``fold_segment_edges`` worth of work no matter how large
        the overlay has grown, and new writes keep landing in the newest
        segment meanwhile. With ``full`` (the quiet-overlay timer path)
        every segment folds in one pass. Returns the refreshed snapshot
        (which may still carry the newest segments' overlay), or None
        when the overlay's shape needs the full-rebuild fallback."""
        from keto_tpu.graph.overlay import apply_delta

        fb, log = self._fold_base, self._seg_log
        # continuity: the log must replay fb → snap exactly (a crashed
        # install or a direct _snapshot swap leaves gaps — detect, drop
        # the history, and fold everything at once)
        intact = (
            fb is not None
            and log
            and log[0][0] == fb.snapshot_id
            and log[-1][1] == snap.snapshot_id
            and all(log[i][1] == log[i + 1][0] for i in range(len(log) - 1))
        )
        if not intact:
            got = self._compact_locked(snap)
            if got is not None and not got.has_overlay:
                self._fold_base, self._seg_log = got, []
            return got
        if full:
            take = len(log)
        else:
            take, tot = 0, 0
            while take < len(log) and (
                take == 0 or tot + len(log[take][2]) <= self._fold_segment_edges
            ):
                tot += len(log[take][2])
                take += 1
        prefix, rest = log[:take], log[take:]
        t0 = time.monotonic()
        wild_ns_ids = frozenset(
            n.id for n in self._nm().namespaces() if n.name == ""
        )
        mid = fb
        for _base_id, seg_wm, ops in prefix:
            mid = apply_delta(mid, ops, seg_wm, wild_ns_ids)
            if mid is None:
                return None  # segment needs a re-layout — full rebuild
            # flush each segment's device-bucket patches before stacking
            # the next (apply_delta replaces, not extends, ell_patch)
            self._apply_ell_patch(mid)
        new_base = self._compact_locked(mid) if mid.has_overlay else mid
        if new_base is None or new_base.has_overlay:
            return None
        cur = new_base
        for _base_id, seg_wm, ops in rest:
            cur = apply_delta(cur, ops, seg_wm, wild_ns_ids)
            if cur is None:
                return None
            self._apply_ell_patch(cur)
        self._fold_base, self._seg_log = new_base, rest
        # the replayed overlay is a different lineage than the resident
        # device pack — force a re-pack on the upload below
        self._ov_pack = None
        self.maintenance.incr("fold_runs")
        self.maintenance.observe_ms("fold", (time.monotonic() - t0) * 1e3)
        _log.info(
            "overlay fold: %d/%d segments folded in %.1f ms (%d remain)",
            take, take + len(rest), (time.monotonic() - t0) * 1e3, len(rest),
        )
        return cur

    # -- snapshot cache ------------------------------------------------------

    def _load_cache_locked(self, store_wm: int) -> Optional[GraphSnapshot]:
        """Cold start: reload the newest usable cached snapshot
        (keto_tpu/graph/snapcache.py) and install it; the caller then
        catches up to the store watermark through the ordinary delta
        path. None when no cache fits (wrong watermark range, wildcard
        config drift, unreadable)."""
        from keto_tpu.graph import snapcache

        t0 = time.monotonic()
        # transient read failures (NFS blips, a save racing the reload)
        # retry through the shared backoff before cold start falls back
        # to the full ingest+build path
        snap = retry_call(
            lambda: snapcache.load_latest(
                self._cache_dir, max_watermark=store_wm, stats=self.maintenance,
                sorter=self._build_sorter,
            ),
            max_wait_s=2.0,
            base_s=0.05,
            max_s=0.5,
            on_retry=lambda e, d: self.maintenance.incr("cache_reload_retries"),
        )
        if snap is None:
            return None
        wild_now = frozenset(
            n.id for n in self._nm().namespaces() if n.name == ""
        )
        if snap.wild_ns_ids != wild_now:
            return None  # namespace config changed — expansion differs
        # the rewrite schema is a build input too: a cache saved under
        # another fingerprint holds other derived edges
        saved, schema = snap.rewrites, schema_of(self._nm())
        if (saved or {}).get("fingerprint", "") != schema.fingerprint:
            return None
        snap.rewrites = RewritePlan.from_meta(saved, schema) if saved else None
        self._upload_buckets(snap)
        if snap.labels is not None and not self._labels_enabled:
            snap.labels = None  # cached labels ignored when disabled
        self._ensure_labels(snap)
        self._snapshot = snap
        ms = (time.monotonic() - t0) * 1e3
        self.maintenance.incr("cache_loads")
        self.maintenance.observe_ms("cache_reload", ms)
        _log.info(
            "snapshot cache reloaded (watermark %d) in %.1f ms",
            snap.snapshot_id, ms,
        )
        return snap

    def _kick_cache_save(self, snap: GraphSnapshot) -> None:
        """Persist an overlay-free snapshot via the supervised cache-save
        worker. Failures are no longer a silent drop: the supervisor logs
        them, counts ``cache_save_failures`` into ``maintenance``, and
        retries with jittered backoff; kicks coalesce so only the newest
        pending snapshot is saved. Serving is never affected."""
        if self._cache_dir is None or snap.has_overlay:
            return
        self._cache_pending = snap
        self._cache_task.kick()

    def _cache_save_pass(self) -> None:
        """One supervised cache-save pass (the SupervisedTask target)."""
        snap = self._cache_pending
        if snap is None:
            return
        from keto_tpu.graph import snapcache

        faults.check("cache-save")
        t0 = time.monotonic()
        with self.build_progress.phase("cache_save"):
            path = snapcache.save_snapshot(
                snap, self._cache_dir, shards=max(1, self._shard_count),
                labels_wait=self._label_build_wait,
            )
        if path is not None:
            self.maintenance.incr("cache_saves")
            self.maintenance.observe_ms(
                "cache_save", (time.monotonic() - t0) * 1e3
            )
        self._cache_pending = None

    def save_snapshot_cache(self) -> Optional[str]:
        """Synchronously persist the current snapshot (bench/operator
        seam); returns the cache path or None when not cacheable."""
        if self._cache_dir is None:
            return None
        snap = self.snapshot()
        from keto_tpu.graph import snapcache

        t0 = time.monotonic()
        path = snapcache.save_snapshot(
            snap, self._cache_dir, shards=max(1, self._shard_count),
            labels_wait=self._label_build_wait,
        )
        if path is not None:
            self.maintenance.incr("cache_saves")
            self.maintenance.observe_ms("cache_save", (time.monotonic() - t0) * 1e3)
        return path

    def _apply_ell_patch(self, snap: GraphSnapshot) -> None:
        """Apply a delta's pending device-bucket patches (tombstoned /
        restored iterated edges, keto_tpu/graph/overlay.py) to the device
        buckets inherited from the base snapshot. Functional updates: the
        base snapshot's arrays are untouched, in-flight batches keep
        gathering the old state. The patch is a handful of (row, col)
        slots — one tiny device scatter, no bucket re-upload."""
        patch = snap.ell_patch
        snap.ell_patch = None
        if self._sharded:
            if patch and snap.device_shards is not None:
                self._apply_ell_patch_sharded(snap, patch)
            return
        if not patch or snap.device_buckets is None:
            return
        by_bucket: dict[int, list] = {}
        for bi, row, col, val in patch:
            by_bucket.setdefault(bi, []).append((row, col, val))
        bufs = list(snap.device_buckets)
        for bi, entries in by_bucket.items():
            rows = np.asarray([e[0] for e in entries], np.int32)
            cols = np.asarray([e[1] for e in entries], np.int32)
            vals = np.asarray([e[2] for e in entries], np.int32)

            def patch(buf=bufs[bi], rows=rows, cols=cols, vals=vals):
                # functional update: old + new bucket transiently
                # co-resident — an OOM here evicts a rung and retries
                # through the device-alloc seam like every other site
                out = buf.at[rows, cols].set(jnp.asarray(vals))
                if self._mesh is not None:
                    out = jax.device_put(out, self._bucket_sharding)
                return out

            bufs[bi] = self._guard_alloc("ell-patch", patch)
        snap.device_buckets = tuple(bufs)

    def _apply_ell_patch_sharded(self, snap: GraphSnapshot, patch) -> None:
        """Route pending device-bucket patches to the OWNING SHARD's slot
        of the stacked arrays: each (bucket, row) maps to exactly one
        shard by the spec's row-range assignment, the host stacked array
        updates in place (it is the upload-truth the next re-upload
        reuses), and only touched buckets' stacks re-upload — a handful
        of slots, never a full snapshot."""
        spec = snap.shard_spec
        by_bucket: dict[int, list] = {}
        for bi, row, col, val in patch:
            s, pos = spec.patch_pos(snap.buckets[bi].offset, bi, row)
            by_bucket.setdefault(bi, []).append((s, pos, col, val))
        nbrs_dev = list(snap.device_shards[0])
        for bi, entries in by_bucket.items():
            host = spec.nbrs_sh[bi]
            for s, pos, col, val in entries:
                host[s, pos, col] = val
            nbrs_dev[bi] = self._guard_alloc(
                "ell-patch",
                lambda h=host: jax.device_put(h, self._shard_stack_sharding),
            )
        snap.device_shards = (tuple(nbrs_dev), snap.device_shards[1])

    def _put_bucket(self, nbrs: np.ndarray, num_int: int):
        """Place one bucket matrix on device. On a mesh, rows pad up to a
        multiple of the graph axis with sentinel rows (gathered from the
        all-zero bitmap row, discarded by the _pull valid-row slice) and
        shard over it — replicating instead (the old fallback for
        non-divisible buckets) made SPMD materialize cross-shard gathers
        via select+all-reduce with an "Involuntary full rematerialization"
        on every BFS step."""
        if self._mesh is None:
            return jax.device_put(np.ascontiguousarray(nbrs))
        g = self._mesh.shape.get("graph", 1)
        rem = (-nbrs.shape[0]) % g
        if rem:
            pad = np.full((rem, nbrs.shape[1]), num_int, np.int32)
            nbrs = np.concatenate([nbrs, pad], axis=0)
        return jax.device_put(np.ascontiguousarray(nbrs), self._bucket_sharding)

    def _note_ell(self, snap: GraphSnapshot) -> None:
        """What one pull gathers from the snapshot's ELL, once an upload:
        real edges, the valid rows' padding up to their bucket's degree, the
        widest row (``keto_snapshot_ell_slots{kind}``,
        ``keto_snapshot_max_in_degree``)."""
        degrees = [(b.nbrs[: b.n] != snap.num_int).sum(axis=1) for b in snap.buckets]
        edges = sum(int(d.sum()) for d in degrees)
        slots = sum(int(b.n) * int(b.nbrs.shape[1]) for b in snap.buckets)
        self.maintenance.set_gauge("ell_slots_edge", edges)
        self.maintenance.set_gauge("ell_slots_pad", slots - edges)
        self.maintenance.set_gauge(
            "max_in_degree", max((int(d.max()) for d in degrees if d.size), default=0)
        )
        # all the snapshot's edges, and those of them the rewrite expansion
        # derived (``keto_snapshot_edges``, ``keto_snapshot_rewrite_edges{kind}``)
        plan = snap.rewrites
        self.maintenance.set_gauge("snapshot_edges", snap.n_edges)
        self.maintenance.set_gauge(
            "rewrite_edges_computed_userset", plan.n_computed if plan is not None else 0
        )
        self.maintenance.set_gauge(
            "rewrite_edges_tuple_to_userset", plan.n_ttu if plan is not None else 0
        )
        # the gates among its nodes (``keto_snapshot_gate_nodes{kind}``,
        # ``keto_snapshot_gate_reach_rows``): the build, the cache or the
        # fold left the tables on it
        tables = gate_tables_of(snap, build=False)
        self.maintenance.set_gauge("snapshot_set_nodes", snap.num_sets)
        self.maintenance.set_gauge("gate_nodes_gate", tables.n_gate_nodes if tables else 0)
        self.maintenance.set_gauge("gate_nodes_operand", tables.n_operand_nodes if tables else 0)
        self.maintenance.set_gauge("gate_reach_rows", tables.n_reach_rows if tables else 0)

    def _upload_buckets(self, snap: GraphSnapshot) -> None:
        self._note_ell(snap)
        if self._sharded:
            return self._upload_buckets_sharded(snap)
        # plan BEFORE uploading: during a swap the old snapshot's buckets
        # are still resident (in-flight batches gather them), so the plan
        # runs against live residency; the governor walks the eviction
        # ladder when over, and only a spent ladder refuses the refresh
        need = snap.bucket_device_bytes()
        self._plan_or_refuse("snapshot buckets", need)
        snap.device_buckets = self._guard_alloc(
            "snapshot-upload",
            lambda: tuple(
                self._put_bucket(b.nbrs, snap.num_int) for b in snap.buckets
            ),
        )
        self.hbm.register("snapshot", need)
        self._upload_hub(snap)

    def _upload_hub(self, snap: GraphSnapshot) -> None:
        """The relay rows of the snapshot's hub sinks beside its buckets
        (``GraphSnapshot.hub_relays``; one device, no mesh: the sharded
        kernels gather a sink's rows one by one). A few hundred KB where a
        graph has hubs at all, so it rides the buckets' plan."""
        relays = None if self._mesh is not None else snap.hub_relays()
        if relays is None:
            snap.hub_ptr = snap.hub_rows = snap.device_hub = None
            return
        snap.hub_ptr, snap.hub_rows, nbrs = relays
        snap.device_hub = self._guard_alloc(
            "snapshot-upload", lambda: jax.device_put(nbrs)
        )

    def _upload_buckets_sharded(self, snap: GraphSnapshot) -> None:
        """Sharded mode: partition the buckets into row-range shards
        (keto_tpu/parallel/sharded.py) and place the stacked per-shard
        arrays split over the graph axis. The per-shard owned bytes land
        in the governor's per-shard ledger, so one hot shard is visible
        — and binding — in the mesh-wide plan."""
        from keto_tpu.parallel import sharded as shard_mod

        spec = shard_mod.make_shard_spec(snap, self._shard_count)
        need = spec.padded_bucket_bytes()
        self._plan_or_refuse(
            "snapshot buckets", need, per_shard=spec.owned_bucket_bytes
        )
        snap.shard_spec = spec
        snap.device_shards = self._guard_alloc(
            "snapshot-upload",
            lambda: (
                tuple(
                    jax.device_put(a, self._shard_stack_sharding)
                    for a in spec.nbrs_sh
                ),
                tuple(
                    jax.device_put(a, self._shard_stack_sharding)
                    for a in spec.dst_sh
                ),
            ),
        )
        self.hbm.register("snapshot", need)
        self.hbm.register_shards("snapshot", spec.owned_bucket_bytes)

    def _apply_overlay_delta(self, snap: GraphSnapshot, delta) -> bool:
        """Scatter one delta's added/dropped overlay-ELL edges into the
        device-RESIDENT gather matrix (functional ``.at[].set`` — the
        base snapshot's arrays stay untouched for in-flight batches).
        True when the delta landed; False when it can't (no resident
        pack, lineage mismatch, or capacity outgrown) and the caller
        must re-pack from scratch. Layout invariants the kernel needs:
        one row per destination, holes are the ``num_int`` sentinel
        (all-zero bitmap row, OR-neutral), pad rows scatter-drop via
        ``num_active`` — row order is irrelevant to the OR-gather."""
        pack = self._ov_pack
        if pack is None or delta is None:
            return False
        base_id, added, dropped = delta
        if pack["snap_id"] != base_id:
            return False
        nbrs, dst = pack["nbrs"], pack["dst"]
        K, C = nbrs.shape
        slot, row_of, fill = pack["slot"], pack["row_of"], pack["fill"]
        rows: list = []
        cols: list = []
        vals: list = []
        drows: list = []
        dvals: list = []
        num_int = snap.num_int
        # host mirror mutates as we go: any bail past this point must
        # invalidate the pack (the re-pack rebuilds it from ov_ell)
        for s, d in dropped:
            rc = slot.pop((s, d), None)
            if rc is None:
                self._ov_pack = None
                return False
            r, c = rc
            nbrs[r, c] = num_int
            rows.append(r)
            cols.append(c)
            vals.append(num_int)
        for s, d in added:
            r = row_of.get(d)
            if r is None:
                r = pack["rows_used"]
                if r >= K:
                    self._ov_pack = None
                    return False  # destination rows outgrew capacity
                pack["rows_used"] = r + 1
                row_of[d] = r
                fill[r] = 0
                dst[r] = d
                drows.append(r)
                dvals.append(d)
            c = int(fill[r])
            if c >= C:
                self._ov_pack = None
                return False  # a row outgrew its column capacity
            fill[r] = c + 1
            nbrs[r, c] = s
            slot[(s, d)] = (r, c)
            rows.append(r)
            cols.append(c)
            vals.append(s)
        dev_n, dev_d = pack["dev"]

        def patch():
            out_n, out_d = dev_n, dev_d
            if rows:
                out_n = out_n.at[
                    np.asarray(rows, np.int32), np.asarray(cols, np.int32)
                ].set(jnp.asarray(np.asarray(vals, np.int32)))
            if drows:
                out_d = out_d.at[np.asarray(drows, np.int32)].set(
                    jnp.asarray(np.asarray(dvals, np.int32))
                )
            return out_n, out_d

        try:
            got = self._guard_alloc("overlay-apply", patch)
        except Exception:
            # host mirror already moved — never reuse it
            self._ov_pack = None
            raise
        pack["dev"] = got
        pack["snap_id"] = int(snap.snapshot_id)
        snap.device_overlay = got
        snap.device_shard_overlay = None
        self.hbm.register("overlay", int(nbrs.nbytes + dst.nbytes))
        self.maintenance.incr("overlay_device_applies")
        return True

    def _upload_overlay(self, snap: GraphSnapshot) -> None:
        """Group overlay-ELL edges by destination into a [K, C] gather
        matrix (pow2-padded so repeated small deltas reuse compiled
        geometries) and place it on device. On the single-device path a
        delta whose edges fit the resident matrix's capacity scatters
        into it in place (one tiny ``.at[].set`` — no host re-pack, no
        full re-upload): the group-commit write path applies committed
        edges device-resident instead of mirroring every group through
        host numpy."""
        delta = snap.ov_ell_delta
        snap.ov_ell_delta = None
        if snap.ov_ell is None or snap.ov_ell.shape[0] == 0:
            self._ov_pack = None
            snap.device_overlay = None
            snap.device_shard_overlay = None
            self.hbm.register("overlay", 0)
            if self._sharded:
                self.hbm.register_shards("overlay", [0] * self._shard_count)
            return
        if (
            not self._sharded
            and self._mesh is None
            and self._apply_overlay_delta(snap, delta)
        ):
            return
        from keto_tpu.graph.overlay import overlay_device_bytes

        need = overlay_device_bytes(snap)
        self._plan_or_refuse("overlay ELL", need)
        src = snap.ov_ell[:, 0]
        dst = snap.ov_ell[:, 1]
        order = np.argsort(dst, kind="stable")
        src, dst = src[order], dst[order]
        uniq, starts = np.unique(dst, return_index=True)
        counts = np.diff(np.append(starts, dst.shape[0]))
        K = _ceil_pow2(uniq.shape[0])
        C = _ceil_pow2(int(counts.max()))
        if self._sharded:
            # route overlay rows to the shard owning their destination —
            # the same row-range ownership the buckets partition by, so
            # the kernel's overlay stage is local to each shard's slab
            from keto_tpu.parallel import sharded as shard_mod

            nbrs = np.full((uniq.shape[0], C), snap.num_int, np.int32)
            for i, (s0, c) in enumerate(zip(starts, counts)):
                nbrs[i, :c] = src[s0 : s0 + c]
            ovn, ovd, owned = shard_mod.route_overlay(
                snap.shard_spec, nbrs, uniq, snap.num_active
            )
            snap.device_overlay = None
            snap.device_shard_overlay = self._guard_alloc(
                "overlay-upload",
                lambda: (
                    jax.device_put(ovn, self._shard_stack_sharding),
                    jax.device_put(ovd, self._shard_stack_sharding),
                ),
            )
            self.hbm.register("overlay", int(ovn.nbytes + ovd.nbytes))
            self.hbm.register_shards("overlay", owned)
            return
        if self._mesh is not None:
            # overlay rows shard over the graph axis exactly like buckets
            # (replicated indices into the row-sharded bitmap would trip
            # SPMD's full-rematerialization fallback every BFS step)
            g = self._mesh.shape.get("graph", 1)
            K += (-K) % g
        nbrs = np.full((K, C), snap.num_int, np.int32)  # all-zero bitmap row
        for i, (s0, c) in enumerate(zip(starts, counts)):
            nbrs[i, :c] = src[s0 : s0 + c]
        dst_pad = np.full(K, snap.num_active, np.int32)  # scatter-dropped
        dst_pad[: uniq.shape[0]] = uniq
        if self._mesh is None:
            snap.device_overlay = self._guard_alloc(
                "overlay-upload",
                lambda: (jax.device_put(nbrs), jax.device_put(dst_pad)),
            )
            # host mirror of the resident pack: later deltas scatter into
            # the spare pow2 capacity instead of re-packing (fill is the
            # next free column per row — tombstoned slots become sentinel
            # holes, harmless to the OR-gather, reclaimed at the next
            # re-pack or fold)
            fill = np.zeros(K, np.int64)
            fill[: counts.shape[0]] = counts
            self._ov_pack = {
                "snap_id": int(snap.snapshot_id),
                "nbrs": nbrs,
                "dst": dst_pad,
                "dev": snap.device_overlay,
                "row_of": {int(d): i for i, d in enumerate(uniq)},
                "fill": fill,
                "rows_used": int(uniq.shape[0]),
                "slot": {
                    (int(src[s0 + j]), int(uniq[i])): (i, j)
                    for i, (s0, c) in enumerate(zip(starts, counts))
                    for j in range(int(c))
                },
            }
        else:
            snap.device_overlay = self._guard_alloc(
                "overlay-upload",
                lambda: (
                    jax.device_put(nbrs, self._bucket_sharding),
                    jax.device_put(dst_pad, self._ov_dst_sharding),
                ),
            )
        self.hbm.register("overlay", need)

    # -- 2-hop labels (keto_tpu/graph/labels.py) -----------------------------

    #: landmark auto-cap: with ``labels_landmarks == 0`` the engine
    #: processes min(num_int, this) nodes — full coverage on every graph
    #: the depth tax actually hurts, bounded build time on huge shallow
    #: ones (coverage misses just fall back to BFS, bit-identically)
    LABELS_AUTO_CAP = 131072

    #: the least one batch of the device label build costs: two sweep
    #: dispatches and the transfer back (0.25-0.75 s a batch measured on a
    #: v5e, PERF.md section 6, PR 34 and PR 38). The device build takes
    #: ``landmarks / serve.labels_batch`` of them whatever pruning leaves
    #: of the work; the host builder's time IS what pruning leaves
    LABEL_BATCH_FLOOR_S = 0.1

    def _ensure_labels(self, snap: GraphSnapshot) -> None:
        """Build (or rebuild) the label index for ``snap`` when enabled
        and missing, and place it on device. Called wherever a fresh
        base layout appears: full rebuild, cache load without labels,
        compaction that couldn't patch. Skipped entirely while the HBM
        governor's labels rung is evicted — the index is the FIRST
        pressure valve because dropping it costs coverage, never
        correctness (the router falls back to BFS)."""
        if not self._labels_enabled or self._labels_suspended:
            return
        if snap.labels is None:
            snap.labels = self._build_label_index(snap)
            if snap.labels is None:
                return
            self.maintenance.incr("label_builds")
            self.maintenance.observe_ms("label_build", snap.labels.build_ms)
        if self._labels_dev(snap) is None:
            # plan before uploading; a plan that evicts the labels rung
            # itself (suspension) means the ladder chose to shed this
            # very family — honor it and drop the fresh build
            need = snap.labels.device_bytes()
            self._last_label_bytes = max(self._last_label_bytes, need)
            fits = self.hbm.plan(need, what="label arrays")
            if not fits or self._labels_suspended or snap.labels is None:
                snap.labels = None
                snap.device_labels = None
                snap.device_shard_labels = None
                return
            self._upload_labels(snap)
            if self._labels_suspended:
                # the labels rung evicted during this upload's own OOM
                # retry: the freshly placed arrays are already shed
                snap.labels = None
                snap.device_labels = None
                snap.device_shard_labels = None
                self.hbm.release("labels")
                return
        idx = snap.labels
        self.maintenance.set_gauge("label_coverage", round(idx.coverage, 4))
        self.maintenance.set_gauge("label_entries", idx.n_entries)

    def _labels_dev(self, snap: GraphSnapshot):
        """The device label arrays this engine's dispatch mode reads —
        the row-striped stacks in sharded mode, the replicated pair
        otherwise."""
        return snap.device_shard_labels if self._sharded else snap.device_labels

    def _interior_ell_slots(self, snap: GraphSnapshot) -> int:
        """Padded interior ELL edge slots — the cheap size signal the
        device-build gate compares against labels_device_min_edges
        (below it, dispatch + transfer overhead beats the host BFS)."""
        return sum(
            int(b.n) * int(np.asarray(b.nbrs).shape[1]) for b in snap.buckets
        )

    def _build_label_index(self, snap: GraphSnapshot):
        """Construct the 2-hop index for ``snap`` through the configured
        path. Device (keto_tpu/graph/label_build.py): batched frontier
        sweeps, NO landmark auto-cap — the ``labels_min_gain`` early
        exit bounds the build — with the transient sweep footprint
        planned ``evict=False`` under the governor's ``build`` tag like
        every other device-build transient (a label build must never
        push serving state off the chip). Host: the original
        per-landmark BFS with the 128k auto-cap, the fallback for tiny
        graphs, missing backends, plan refusals, and device errors —
        entry-identical by the builder's contract either way. Any
        truncation (cap or min_gain) is now LOUD: a structured warning
        with the achieved coverage plus the
        ``keto_label_build_truncated_total`` family."""
        from keto_tpu.graph.labels import build_labels

        n = snap.num_int
        landmarks = self._labels_landmarks
        if self._labels_device_build and n > 0:
            from keto_tpu.graph import label_build
            from keto_tpu.graph.device_build import device_available

            eligible = (
                device_available()
                and self._interior_ell_slots(snap) >= self._labels_device_min_edges
            )
            if eligible:
                idx = self._host_labels_first(snap, landmarks)
                if idx is not None:
                    return idx
                need = label_build.estimate_build_bytes(
                    n, self._labels_max_width, self._labels_batch
                )
                if not self.hbm.plan(need, what="label build transient", evict=False):
                    # memory pressure: the build yields, serving state
                    # stays — same policy as GovernedSorter
                    self.maintenance.incr("label_device_build_skipped")
                else:
                    self.hbm.register("build", need)
                    try:
                        idx, info = label_build.device_build_labels(
                            snap,
                            max_width=self._labels_max_width,
                            landmarks=landmarks,
                            min_gain=self._labels_min_gain,
                            batch=self._labels_batch,
                            mesh=self._mesh if self._sharded else None,
                            shard_count=self._shard_count,
                            progress_cb=self._label_build_progress,
                        )
                    except Exception:
                        _log.warning(
                            "device label build failed; falling back to the "
                            "host path (entry-identical)",
                            exc_info=True,
                        )
                        self.maintenance.incr("label_device_build_errors")
                    else:
                        self.maintenance.incr("label_device_builds")
                        self.maintenance.observe_ms(
                            "label_build_device", idx.build_ms
                        )
                        self.maintenance.set_gauge(
                            "label_build_batches", info.batches
                        )
                        if info.truncated:
                            self._note_label_truncation(info.truncated, idx)
                        return idx
                    finally:
                        self.hbm.release("build")
        if landmarks == 0:
            landmarks = min(n, self.LABELS_AUTO_CAP)
        idx = build_labels(
            snap, max_width=self._labels_max_width, landmarks=landmarks
        )
        if landmarks < n:
            self._note_label_truncation("cap", idx)
        return idx

    def _host_labels_first(self, snap: GraphSnapshot, landmarks: int):
        """The other half of the device-build gate. ELL slots say that a
        graph is big enough for the device build to pay its dispatches,
        not that the build is hard: the device sweeps every batch of
        landmarks over the whole interior, the host builder walks what
        pruning leaves, and on a shallow forest (a code host's teams and
        role chains, however many rows) that is seconds against minutes.
        So above the gate the host builder goes first, for as long as the
        device's batches would take at the least (``LABEL_BATCH_FLOOR_S``
        each): a graph it indexes within that never waits for the device,
        and one it does not has cost less than the device build it then
        gets. Entry-identical either way, by the builders' contract. None
        (the device path as it was) when the host ran out of time, where
        the two would not process the same landmarks (the host's auto-cap,
        ``labels_min_gain``), on a multi-controller mesh (every host takes
        the same path, and a clock is not the same on two of them), and
        with ``labels_device_min_edges`` 0, which forces the device."""
        from keto_tpu.graph.labels import build_labels

        n = snap.num_int
        if (
            self._multiprocess
            or self._labels_device_min_edges <= 0
            or self._labels_min_gain > 0.0
            or (landmarks == 0 and n > self.LABELS_AUTO_CAP)
        ):
            return None
        batches = -(-(min(landmarks, n) if landmarks > 0 else n) // max(32, self._labels_batch))
        idx = build_labels(
            snap, max_width=self._labels_max_width, landmarks=landmarks,
            deadline_s=batches * self.LABEL_BATCH_FLOOR_S,
        )
        self.maintenance.incr(
            "label_host_first_builds" if idx is not None else "label_host_first_timeouts"
        )
        return idx

    def _label_build_progress(self, done: int, total: int, entries: int) -> None:
        """Batch-level narration for an in-flight label build: gauges
        BuildProgress/health read while the sweeps run."""
        self.maintenance.set_gauge("label_build_landmarks", done)
        self.maintenance.set_gauge("label_build_landmarks_total", total)
        self.maintenance.set_gauge("label_build_entries", entries)

    def _note_label_truncation(self, reason: str, idx) -> None:
        """Coverage truncation is a serving-quality event, not a silent
        default: count it by reason (``cap`` — the landmark budget, or
        ``min_gain`` — the marginal-coverage early exit) and log the
        achieved coverage so operators can see exactly what the depth
        tax falls back to BFS for."""
        self.maintenance.incr(f"label_build_truncated_{reason}")
        _log.warning(
            "label build truncated (%s): %d/%d landmarks processed, "
            "coverage_ratio=%.4f — uncovered deep checks fall back to the "
            "BFS kernel (bit-identically)",
            reason, idx.n_landmarks, idx.n, idx.coverage,
        )

    def _start_label_build(self, snap: GraphSnapshot) -> None:
        """The full-rebuild pipeline's labels phase, overlapped: kick
        the (device) label construction on a background thread so
        ``cache_save`` and the rest of the refresh's host work proceed
        while the sweeps run; the engine serves the fresh snapshot with
        the BFS fallback until the index installs under the lock.
        Synchronous when the index is already present (cache reload —
        placement is cheap), in multi-controller mode (background
        collectives must not interleave with serving dispatches across
        hosts), or when labels are off."""
        if not self._labels_enabled or self._labels_suspended:
            return
        if snap.labels is not None or self._multiprocess:
            with self.build_progress.phase("labels"):
                self._ensure_labels(snap)
            return

        def work():
            with self.build_progress.phase("labels"):
                try:
                    idx = self._build_label_index(snap)
                except Exception:
                    self.maintenance.incr("label_build_failures")
                    _log.warning(
                        "background label build failed; serving stays on "
                        "the BFS path",
                        exc_info=True,
                    )
                    return
            with self._lock:
                if (
                    self._closing
                    or self._labels_suspended
                    or not self._labels_enabled
                ):
                    return
                self._install_labels_locked(snap, idx)

        t = threading.Thread(target=work, name="label-build", daemon=True)
        self._label_build_thread = t
        t.start()

    def _install_labels_locked(self, snap: GraphSnapshot, idx) -> None:
        """Land a background-built index (caller holds the lock) — ONLY
        onto the exact snapshot it was built for. A later snapshot that
        merely matches on num_int is not safe: a fold or rebuild can
        change the interior edge set at the same node count, and a stale
        index would serve wrong denies. Deltas that extend ``snap``'s
        overlay in place are fine (the label path already gates on
        lab_dirty); if serving moved to a different snapshot object, the
        index is dropped and the next rebuild's build starts fresh."""
        if idx is None:
            return
        if snap.labels is None and snap.num_int == idx.n:
            snap.labels = idx
            self.maintenance.incr("label_builds")
            self.maintenance.observe_ms("label_build", idx.build_ms)
        if snap.labels is idx and self._snapshot is snap:
            self._ensure_labels(snap)

    def _label_build_wait(self) -> None:
        """Join the in-flight background label build (the
        ``labels_wait`` seam snapcache.save_snapshot invokes just before
        writing the label segments — everything before them overlaps
        the sweeps, and the saved cache still carries the index)."""
        t = self._label_build_thread
        if t is not None and t.is_alive():
            t.join()

    def labels_settled(self) -> bool:
        """Force the lazy snapshot refresh and block until the overlapped
        label build (if any) has installed. Serving never needs this —
        checks fall back to the BFS kernel bit-identically while the
        build is in flight — but deterministic consumers (tests, benches,
        warm-up hooks) use it to pin down the moment the label fast path
        is live. Returns whether the serving snapshot carries an index."""
        self.snapshot()
        self._label_build_wait()
        snap = self._snapshot
        return snap is not None and snap.labels is not None

    def _label_patcher(self, idx, snap, added_edges, visit_budget: int = 65536):
        """Compaction's incremental label patch, routed through the
        device sweep path when eligible (``device_patch_labels`` — the
        exact ``patch_labels`` semantics, including the abort outcome,
        as bit-packed lane sweeps) and through the host walk otherwise.
        None means the patch aborted and the caller must rebuild."""
        if self._labels_device_build:
            from keto_tpu.graph.device_build import device_available

            if (
                device_available()
                and self._interior_ell_slots(snap) >= self._labels_device_min_edges
            ):
                from keto_tpu.graph import label_build

                try:
                    return label_build.device_patch_labels(
                        idx, snap, added_edges, visit_budget=visit_budget,
                        batch=self._labels_batch,
                        mesh=self._mesh if self._sharded else None,
                        shard_count=self._shard_count,
                    )
                except Exception:
                    _log.warning(
                        "device label patch failed; retrying on the host "
                        "path (entry-identical)",
                        exc_info=True,
                    )
                    self.maintenance.incr("label_device_build_errors")
        from keto_tpu.graph.labels import patch_labels

        return patch_labels(idx, snap, added_edges, visit_budget=visit_budget)

    def _upload_labels(self, snap: GraphSnapshot) -> None:
        idx = snap.labels
        if idx is None:
            snap.device_labels = None
            snap.device_shard_labels = None
            return
        out_lab = np.ascontiguousarray(idx.out_lab)
        in_lab = np.ascontiguousarray(idx.in_lab)
        if self._sharded:
            # row-striped over the graph axis: the sharded intersection
            # kernel reconstructs each pair's two rows with a one-shot
            # psum exchange (keto_tpu/parallel/sharded.py)
            from keto_tpu.parallel import sharded as shard_mod

            out_sh, in_sh, rl, owned = shard_mod.route_labels(
                out_lab, in_lab, self._shard_count
            )
            snap.device_labels = None
            snap.device_shard_labels = self._guard_alloc(
                "labels-upload",
                lambda: (
                    jax.device_put(out_sh, self._shard_stack_sharding),
                    jax.device_put(in_sh, self._shard_stack_sharding),
                    rl,
                ),
            )
            self.hbm.register("labels", idx.device_bytes())
            self.hbm.register_shards("labels", owned)
            return
        if self._mesh is None:
            snap.device_labels = self._guard_alloc(
                "labels-upload",
                lambda: (jax.device_put(out_lab), jax.device_put(in_lab)),
            )
        else:
            # labels replicate: the rows are narrow (≤ max_width) and the
            # intersection kernel never touches the sharded bitmaps
            from jax.sharding import NamedSharding, PartitionSpec as P

            repl = NamedSharding(self._mesh, P())
            snap.device_labels = self._guard_alloc(
                "labels-upload",
                lambda: (
                    jax.device_put(out_lab, repl), jax.device_put(in_lab, repl)
                ),
            )
        self.hbm.register("labels", idx.device_bytes())

    def warm_compile(self) -> int:
        """Ahead-of-time compile of the slice-width ladder against the
        current snapshot (``CheckDispatch.warm_compile`` runs the programs
        and says how many) — with a persistent compilation cache requested
        (keto_tpu/driver/compile_cache.py), the multi-second compile cost
        is paid once per binary instead of once per boot."""
        snap = self.snapshot()
        # the label kernels warm against the index the overlapped boot
        # build installs onto this snapshot: join it first, or every
        # width's first label slice compiles inside the serving window
        self._label_build_wait()
        if snap.n_nodes == 0 or snap.n_edges == 0:
            return 0
        return self.dispatch.warm_compile(snap)

    # -- what the driver reads of the dispatch by name, on any engine it is
    # -- handed (registry.py's /metrics callbacks and admission, batch.py) ----

    @property
    def stream_ctrl(self):
        return self.dispatch.stream_ctrl

    @property
    def stream_slice_stats(self):
        return self.dispatch.stream_slice_stats

    def route_slice_counts(self) -> dict:
        """route → landed slices (``keto_stream_route_slices_total``)."""
        return dict(self.dispatch.route_slices)

    def kernel_geometry_counts(self) -> dict:
        """``{(kernel, met): launched slices}``: how each slice of the
        single-device path found its program (``keto_kernel_geometry_total``)."""
        return self.dispatch.geoms.counts()

    # -- public API ----------------------------------------------------------

    def batch_check(
        self,
        tuples: Sequence[RelationTuple],
        *,
        at_least: Optional[int] = None,
        mode: str = "latest",
    ) -> list[bool]:
        """Answer every query, by draining the same stream the batcher
        drives (``batch_check_stream``, ordered): slices pipeline
        resolve→pack→dispatch (host work on slice k+1 overlaps device
        execution of slice k — dispatch is async) and land in ready order.

        Consistency (the real semantics of the snaptoken/latest fields the
        reference documents but stubs, proto check_service.proto:39-75):
        ``mode="latest"`` (default) is read-your-writes; ``at_least=w``
        serves any snapshot ≥ w (the caller's snaptoken); ``mode="serving"``
        never stalls — see ``snapshot_serving``."""
        return self.batch_check_with_token(tuples, at_least=at_least, mode=mode)[0]

    def batch_check_with_token(
        self,
        tuples: Sequence[RelationTuple],
        *,
        at_least: Optional[int] = None,
        mode: str = "latest",
    ) -> tuple[list[bool], int]:
        """``batch_check`` plus the id of the snapshot that produced the
        decisions — the snaptoken the API returns to callers.

        Degraded mode: when the device path has failed repeatedly, checks
        transparently fall back to the CPU reference engine (bit-identical
        decisions, reference throughput) and the health state machine
        reports DEGRADED; the device path is re-probed periodically and
        recovery is automatic. Multi-controller meshes never fall back —
        hosts diverging on the execution path is a lockstep violation, so
        device failures there fail loudly instead."""
        if self._should_fallback():
            return self._fallback_check(tuples)
        snap = self._snapshot_for(at_least, mode)
        if self._lockstep_verify:
            from keto_tpu.parallel.lockstep import verify_lockstep

            # BEFORE the empty-graph early-out: hosts disagreeing on
            # whether the graph is empty is exactly the divergence that
            # must fail loudly rather than skew answers silently. The
            # fingerprint covers the shard geometry too: a sharded
            # program dispatched with mismatched shard counts would hang
            # mismatched collectives, the failure lockstep exists to
            # pre-empt.
            verify_lockstep(snap.snapshot_id, tuples, shards=self._shard_count)
        if snap.n_nodes == 0 or snap.n_edges == 0 or not tuples:
            return [False] * len(tuples), snap.snapshot_id
        try:
            out, max_iters = self.dispatch._run_exact(snap, tuples)
        except Exception as e:
            if self._multiprocess or isinstance(e, KetoError):
                raise
            self._note_device_error(e)
            return self._fallback_check(tuples)
        self._note_device_ok()
        self.dispatch._after_batch(max_iters, snap)
        return out.tolist(), snap.snapshot_id

    def batch_check_stream(
        self,
        tuples_iter,
        *,
        depth: Optional[int] = None,
        slice_cap: Optional[int] = None,
        at_least: Optional[int] = None,
        mode: str = "latest",
        ordered: bool = True,
    ):
        """Streaming check: consume an iterable of RelationTuples, yield
        decision slices while keeping at most ``depth`` slices in flight
        (flat memory for arbitrarily long streams — BASELINE config 5's
        1M-check batches never materialize device state for more than
        ``depth`` slices).

        The pipeline is latency-adaptive and lands slices in READY order:

        - slice widths follow ``StreamSliceController``: narrowed toward
          ``stream_slice_target_ms`` when kernels/transfers run slow,
          re-widened when headroom returns — instead of the
          throughput-only memory-derived maximum. ``slice_cap`` still
          bounds them from above. (Multi-controller meshes pin the fixed
          bound: slice geometry must be identical on every host.)
        - the dispatch window is decoupled from landing: host resolve/pack
          of slice k+2 proceeds while k+1 executes and k transfers, and an
          early-finished slice is unpacked the moment its
          ``copy_to_host_async`` completes — no head-of-line blocking on
          a straggler.
        - ``ordered=True`` (default) preserves the yield contract — numpy
          ``bool[slice]`` arrays in request order, via an in-order
          delivery buffer. ``ordered=False`` is the fast path for callers
          that re-associate results by index (e.g. ``CheckBatcher``): it
          yields ``(offset, bool[slice])`` the moment each slice lands,
          where ``offset`` is the stream index of the slice's first query.

        Per-slice service times are recorded in ``stream_slice_stats``
        (x/telemetry.DurationStats): the width controller and bench.py
        read the same numbers.
        """
        gen, _ = self.batch_check_stream_with_token(
            tuples_iter, depth=depth, slice_cap=slice_cap,
            at_least=at_least, mode=mode, ordered=ordered,
        )
        return gen

    def batch_check_stream_with_token(
        self,
        tuples_iter,
        *,
        depth: Optional[int] = None,
        slice_cap: Optional[int] = None,
        at_least: Optional[int] = None,
        mode: str = "latest",
        ordered: bool = True,
        with_info: bool = False,
        launch_mark: bool = False,
    ):
        """``batch_check_stream`` plus the deciding snapshot's id, resolved
        eagerly so serving callers can attach the snaptoken to responses
        they assemble as slices land. Returns ``(generator, token)``.

        ``with_info=True`` (requires ``ordered=False``) widens each yield
        to ``(offset, decisions, info)`` where ``info`` describes the
        slice that landed: ``width`` (queries), ``bfs_steps``, ``route``
        (``label`` | ``hybrid`` | ``bfs`` | ``host`` | ``cpu``),
        ``service_ms``, and — in sharded mode — ``halo_rounds`` /
        ``halo_bytes``. The CheckBatcher stamps this onto every rider's
        request timeline.

        ``launch_mark=True`` (requires ``ordered=False``) adds one yield of
        ``None``: every slice of the stream has been launched and at least
        one has not landed. Whatever the caller does before it resumes the
        generator runs while those slices are on the device — the
        CheckBatcher resolves, packs and launches its next round there.
        Launch order on the device is the order of the calls, as without
        the mark (sharded and lockstep engines see the same sequence of
        programs: only the host's ``device_get`` moves). A stream whose
        slices all landed as they were launched (host-only slices, the CPU
        fallback) gives no mark.

        In degraded mode the stream is served by the CPU reference engine
        with the same yield contract (see ``batch_check_with_token`` for
        the fallback semantics)."""
        if (with_info or launch_mark) and ordered:
            raise ValueError("with_info and launch_mark require ordered=False")
        if self._should_fallback():
            return self._fallback_stream(
                tuples_iter, ordered=ordered, with_info=with_info
            )
        snap = self._snapshot_for(at_least, mode)
        gen = self.dispatch._stream(
            snap, tuples_iter, depth=depth, slice_cap=slice_cap,
            ordered=ordered, with_info=with_info, launch_mark=launch_mark,
        )
        return self._guard_stream(gen, snap), snap.snapshot_id

    def label_witness_info(
        self, rt: RelationTuple, *, at_least: Optional[int] = None,
        mode: str = "latest",
    ) -> Optional[dict]:
        """Explain-path enrichment (keto_tpu/explain): the winning landmark
        of the 2-hop label intersection for ``rt``'s (start, target) pair —
        the hub node the label route's proof went through — or None when
        the pair isn't label-resolvable (labels off/dirty, wildcard query,
        non-interior endpoint). Reads the device arrays through the
        ``label_step_witness`` argmin kernel when they are resident, else
        the host index — entry-identical by construction. Only the explain
        endpoint calls this; the check hot path never does."""
        if not self._labels_enabled:
            return None
        try:
            snap = self._snapshot_for(at_least, mode)
        except Exception:
            return None
        idx = snap.labels
        if idx is None:
            return None
        try:
            sd, tg, multi = self.dispatch._resolve_bulk(snap, [rt])
        except Exception:
            return None
        if 0 in multi:
            return None  # wildcard pattern: no single (start, target) pair
        a, b = int(sd[0]), int(tg[0])
        ni = snap.num_int
        if a < 0 or b < 0 or a >= ni or b >= ni:
            return None
        lm: Optional[int] = None
        dl = self._labels_dev(snap)
        if dl is not None and not self._sharded:
            try:
                got = int(
                    np.asarray(
                        _label_witness_kernel(
                            dl[0], dl[1],
                            jnp.asarray(np.array([a], np.int32)),
                            jnp.asarray(np.array([b], np.int32)),
                        )
                    )[0]
                )
                lm = got if got >= 0 else None
            except Exception:
                lm = None
        if lm is None:
            lm = idx.witness_landmark(a, b)
        if lm is None:
            return None
        info: dict = {"kind": "2-hop-label", "pair": [a, b], "landmark_dev": int(lm)}
        try:
            kind, key = snap.key_of_dev(int(lm))
            if kind == "set":
                ns_id, obj, rel = key
                name = next(
                    (n.name for n in self._nm().namespaces() if n.id == ns_id), ""
                )
                info["landmark"] = f"{name}:{obj}#{rel}"
            else:
                info["landmark"] = str(key)
        except Exception:  # keto-analyze: ignore[KTA401] landmark naming is best-effort enrichment; the numeric id in landmark_dev already carries the answer
            pass
        return info

    def subject_is_allowed(self, requested: RelationTuple) -> bool:
        """Single-query convenience with the oracle engine's signature
        (reference internal/check/engine.go:93-95)."""
        return self.batch_check([requested])[0]
