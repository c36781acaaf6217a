"""Answering a batch of checks from a snapshot it is given.

``CheckDispatch`` is the half of the TPU check engine that never looks at
the store: resolve queries to device rows, cut them into slices, pack each
slice on the host (``pack.py``), launch its kernels (``kernels.py``), land
the outputs in ready order and decode them. It holds what only that path
reads — the staging pool, the slice controller and its stats, the set of
compiled kernel geometries, the route and BFS-step counters. The engine
that owns it (``tpu_engine.TpuCheckEngine``) keeps the snapshot on the
chip; this half never takes the refresh lock and never calls an eviction
rung.

Decision parity with the reference engine:
- unknown namespace → denied, not an error (engine.go:76-77): host
  resolution of a literal unknown namespace contributes no start nodes and
  the query's answer bit can never be set;
- empty namespace/object/relation fields wildcard the expansion exactly like
  the reference's tuple query (relationtuples.go:218-235) — a wildcard
  pattern resolves to *all* matching set nodes as BFS sources
  (GraphSnapshot.resolve_starts);
- pagination transparency: BFS has no pages, and reachability is
  independent of the reference's page-at-a-time visit order;
- the ``...``/empty-relation subtlety (engine_test.go:257-295): an empty
  relation wildcards only the *expansion* of that subject set; it never
  fabricates a transitive grant because matching stays literal.
"""

from __future__ import annotations

import collections
import itertools
import logging
import time
from typing import Callable, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from keto_tpu import namespace as namespace_pkg
from keto_tpu.check.frame import (
    DEAD, NO_TARGET, SPECIAL, QueryBatch, QueryFrame, as_tuples, pick_tuples,
)
from keto_tpu.check.geometry import INLINE, KernelGeometries
from keto_tpu.check import gates, kernels
from keto_tpu.check.kernels import (
    _check_kernel, _check_kernel_donated, _label_kernel, _label_kernel_donated,
)
from keto_tpu.check import native_pack
from keto_tpu.check.pack import (
    LABEL_REASONS, _WORD_WIDTHS, _HybridSlice, _ShardedSlice, _StagingPool, _entry_pad,
    _pad_packed, _padding_packed, device_part, hub_usable, label_pairs, pack_chunk,
    pack_entries, put_pairs, whole_min,
)
from keto_tpu.check.slice_ctrl import StreamSliceController
from keto_tpu.graph.snapshot import WILDCARD, GraphSnapshot, _ceil_pow2
from keto_tpu.namespace.rewrites import REWRITTEN
from keto_tpu.relationtuple.model import RelationTuple, SubjectID, SubjectSet
from keto_tpu.x import faults
from keto_tpu.x.errors import ErrNamespaceUnknown
from keto_tpu.x.telemetry import DurationStats
from keto_tpu.x.timeline import (
    DEVICE_WAIT, FILL, LAUNCH, PACK, RESOLVE, dispatch_clock,
)

_log = logging.getLogger("keto_tpu.check")

#: distinct-from-None cache sentinel for namespace resolution
_UNSET = object()
#: wildcard-namespace marker in the native resolve cache
_WILD = object()
#: native-format record whose result is overwritten on the Python side
_PLACEHOLDER = b"0\x1f\x1f\x1f1\x1f\x1f\x1f\x1e"
_LANES = np.arange(32, dtype=np.uint32)
#: what cut a resolved chunk into sub-chunks (``_dispatch_slices``)
CHUNK_CUTS = ("none", "geometry", "budget")


def _bits(words: np.ndarray, nq: int) -> np.ndarray:
    """A kernel's packed decisions, bit ``q % 32`` of word ``q // 32``, as
    ``bool[nq]``."""
    return ((words[:, None] >> _LANES) & 1).astype(bool).ravel()[:nq]


def check_sweep_metrics(m, stats_of) -> None:
    """Declare the families that say in which order ``check_step`` sweeps
    its buckets (``kernels.SWEEPS``) on ``m`` (driver/registry.py calls this
    once); ``stats_of()`` is the serving engine's maintenance counters and
    gauges (``MaintenanceStats.raw()``), empty while there is no engine."""

    def slices():
        counters = stats_of()[0]
        return [((o,), float(counters.get(f"sweep_slices_{o}", 0))) for o in kernels.SWEEPS]

    def probe():
        gauges = stats_of()[1]
        return [
            ((o,), float(gauges.get(f"sweep_probe_pulls_{o}", 0)))
            for o in kernels.IN_PLACE_SWEEPS
        ]

    m.register_callback(
        "keto_check_sweep_slices_total", "counter",
        "Landed slices that ran check_step (keto_check_bfs_slices_total), by "
        "the order a pull updated the buckets in: up or down (in place, a "
        "bucket's gather reads what the buckets before it in the same sweep "
        "wrote: by ascending or descending device id, whichever the "
        "warm-up's probe of the snapshot converged under in fewer pulls), "
        "whole (every bucket from the same carry: a mesh).",
        slices, ("order",),
    )
    m.register_callback(
        "keto_check_sweep_probe_pulls", "gauge",
        "Pulls the warm-up's probe of the serving snapshot took to its "
        "fixpoint under each in-place order (CheckDispatch."
        "_settle_block_iters): the order of the smaller reading serves, up "
        "on a tie; 0 where no probe ran (no warm-up, no active rows, a mesh).",
        probe, ("order",),
    )


def stream_chunk_metrics(m, counters_of) -> None:
    """Declare ``_dispatch_slices``' families on ``m`` (driver/registry.py
    calls this once); ``counters_of()`` is the serving engine's maintenance
    counters, ``{}`` while there is no engine."""

    def chunks():
        counters = counters_of()
        return [((cut,), float(counters.get(f"stream_chunks_{cut}", 0))) for cut in CHUNK_CUTS]

    m.register_callback(
        "keto_stream_chunks_total", "counter",
        "Resolved chunks of check slices by what cut them into sub-chunks "
        "before dispatch: none, geometry (their device entries pass the "
        "geometric bound: 4 x B, or the pair cap x B on a snapshot of hub "
        "rows), budget (whole under that bound, cut only because the slice "
        "controller's entry budget lowered it).",
        chunks, ("cut",),
    )
    m.register_callback(
        "keto_stream_chunk_pieces_total", "counter",
        "Sub-chunks those chunks were dispatched as, a whole chunk counting "
        "one: over keto_stream_chunks_total, the slices a chunk costs.",
        lambda: [((), float(counters_of().get("stream_chunk_pieces", 0)))],
    )


#: how a stream's chunk was resolved, and why ``resolve``'s one native pass
#: did not take it (``keto_check_resolve_chunks_total{path}``,
#: ``keto_check_resolve_declines_total{reason}``)
RESOLVE_PATHS = ("native", "numpy")
RESOLVE_DECLINES = ("no_library", "special", "overlay", "overlay_start")


class CheckDispatch:
    """The answering half of ``TpuCheckEngine`` (module docstring). Built
    once by the engine with what never changes after — the mesh and what
    derives from it, the iteration cap, the width bounds — and with the four
    things it asks the engine for: ``labels_dev(snap)`` (the label arrays a
    snapshot holds on the device, or None), ``guard_alloc(what, fn)`` (the
    HBM governor's OOM containment round a launch), ``audit(tuples,
    decisions, token)`` (the shadow-parity sampler) and ``maintenance``.
    ``hbm`` plans the staging pool's growth and the warm-up's footprint;
    ``current_snapshot()`` is what the geometry worker compiles against."""

    def __init__(
        self,
        *,
        namespaces: Callable[[], namespace_pkg.Manager],
        maintenance,
        hbm,
        labels_dev: Callable,
        guard_alloc: Callable,
        audit: Callable,
        current_snapshot: Callable[[], Optional[GraphSnapshot]],
        oracle: Callable,
        it_cap: int,
        max_batch: int,
        mem_budget_bytes: int,
        labels_enabled: bool,
        mesh,
        shard_rows: bool,
        shard_count: int,
        shard_stack_sharding,
        multiprocess: bool,
        lockstep_verify: bool,
        stream_slice_target_ms: float,
        stream_tail_ratio: float,
    ):
        self._nm = namespaces
        self.maintenance = maintenance
        self.hbm = hbm
        self._labels_dev = labels_dev
        self._guard_alloc = guard_alloc
        self._audit_sample = audit
        self._current_snapshot = current_snapshot
        #: () -> the CPU oracle (``check/engine.py``): answers the checks
        #: whose closure reaches an intersection or an exclusion
        self._oracle = oracle
        self._it_cap = it_cap
        self._max_batch = max_batch
        # bound on the BFS workspace (~3 W-wide uint32 bitmaps over interior
        # rows); batch width narrows automatically on huge graphs so the
        # default max_batch can never ask for more HBM than this
        self._mem_budget = mem_budget_bytes
        self._labels_enabled = labels_enabled
        # snapshot id last counted as a label invalidation (overlay
        # mutated the interior subgraph) — one count per transition
        self._label_blocked_snap: Optional[int] = None
        #: the snapshot's arrays as the label route's native pass reads them
        #: (``native_pack.PackView``), remade when the snapshot, its label
        #: index or its relay rows change
        self._pack_view: Optional[native_pack.PackView] = None
        #: the same for ``resolve``'s one pass (``native_pack.ResolveView``:
        #: the snapshot alone), remade when the snapshot, its relay rows'
        #: use or its plan's closure bytes change
        self._resolve_view: Optional[native_pack.ResolveView] = None
        self._mesh = mesh
        self._shard_count = shard_count  # 0: not explicitly sharded
        self._sharded = shard_count > 0
        self._shard_stack_sharding = shard_stack_sharding
        self._multiprocess = multiprocess
        self._lockstep_verify = lockstep_verify
        self._bitmap_sharding = None
        if mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec as P

            from keto_tpu.parallel.mesh import DATA_AXIS, GRAPH_AXIS

            row_axis = GRAPH_AXIS if shard_rows else None
            self._bitmap_sharding = NamedSharding(mesh, P(row_axis, DATA_AXIS))
            # narrow batches (W words < data-axis size) can't meaningfully
            # split words: constraining them anyway sends SPMD down its
            # replicate-then-partition fallback on every BFS-step gather
            self._bitmap_sharding_rows_only = NamedSharding(mesh, P(row_axis))
            # per-dispatch label pair entries replicate everywhere
            self._shard_repl_sharding = NamedSharding(mesh, P())
        # pulls per convergence observation, adapted to the workload's
        # traversal depth from the iteration counts kernels report back
        self._block_iters = 8
        #: the order one pull of ``check_step`` updates the buckets in
        #: (``kernels.SWEEPS``): in place on one device, by ascending device
        #: id until ``_settle_block_iters`` has probed the snapshot for the
        #: order that converges in fewer pulls; whole on a mesh, whose
        #: row-sharded buckets cost one all-gather a pull, not one a bucket
        self._sweep = "up" if mesh is None else "whole"
        # which kernel programs are compiled, so that a served slice pads up
        # to one that is before it compiles its own on the dispatch thread
        # (keto_tpu/check/geometry.py); warm_compile settles block_iters, a
        # static of every one of them, from the snapshot
        self.geoms = KernelGeometries(self._compile_geometry)
        self._block_iters_shape: Optional[tuple] = None  # the shape it was settled on
        #: rungs the governor's warm-ladder eviction took off the top of the
        #: compile-width ladder (the engine's rung sets it)
        self.width_trim = 0
        # close() flips this; the warm-up checks it between kernels so
        # teardown never races an in-flight compile
        self._closing = False
        # concurrently in-flight chunks (bounds device bitmap workspaces)
        self._dispatch_window = 16
        # streaming pipeline: the latency-adaptive width controller is
        # shared across streams so a serving process stays converged, and
        # per-slice service times land in stream_slice_stats — the
        # controller, bench.py, and operators all read the same numbers
        self.stream_ctrl = StreamSliceController(
            target_ms=stream_slice_target_ms, tail_ratio=stream_tail_ratio
        )
        self.stream_slice_stats = DurationStats()
        #: per-route slice service times + query/slice counts (route =
        #: label | hybrid | bfs | host | cpu): the stream's landing path
        #: records them, bench's per-route breakdown and the
        #: keto_stream_route_slices_total family read them
        self._route_stats: dict[str, DurationStats] = {}
        self.route_slices: collections.Counter = collections.Counter()
        self._route_queries: collections.Counter = collections.Counter()
        #: device BFS iteration counts per dispatched slice (values are
        #: step counts, not ms) — bench reports bfs_steps_p50/p99 from
        #: here so the label win is attributable to killed frontier hops
        self.bfs_steps_stats = DurationStats()
        # persistent entry staging (donated device buffers' host half):
        # packed entry arrays concatenate into pooled per-geometry
        # buffers leased until their slice LANDS, and — where the
        # backend implements donation — ship through the donated kernel
        # variants so the device-side staging allocation aliases into
        # the output. The pool's bytes ride the governor's "staging"
        # ledger tag; its rung is FIRST on the ladder (dropping it costs
        # per-slice allocation churn, never coverage or correctness).
        self._staging_suspended = False
        self._staging = _StagingPool(
            on_change=lambda b: self.hbm.register("staging", b)
        )
        self._donate_entries = kernels._donation_default()

    def close(self) -> None:
        """Abort a warm-up between kernels; no further background compiles."""
        self._closing = True
        self.geoms.close()

    # -- entry staging --------------------------------------------------------

    def suspend_staging(self) -> int:
        """The governor's rung 0 (``resume_staging`` restores it): drop the
        pool and ship every slice from a buffer of its own — pure churn
        cost, zero coverage or correctness impact, which is why it is the
        first thing pressure sheds. Outstanding leases release into the
        empty pool harmlessly. Returns the bytes the pool held."""
        self._staging_suspended = True
        freed = self._staging.drop()
        self.hbm.release("staging")
        return freed

    def resume_staging(self) -> None:
        # the pool refills lazily as slices dispatch
        self._staging_suspended = False

    def _stage_acquire(self, n: int) -> Optional[np.ndarray]:
        """Lease an ``n``-element int32 staging buffer, planning pool
        growth against the HBM governor (``evict=False`` — staging never
        evicts other families; an unplannable buffer just means this
        slice allocates per-slice). None when staging is off/refused."""
        if self._staging_suspended:
            return None
        return self._staging.acquire(
            n, plan=lambda b: self.hbm.plan(b, what="staging", evict=False)
        )

    def _stage_release(self, leases) -> None:
        """Return a landed slice's staging buffers to the pool. Empties
        the lease list, so releasing a record twice (land() plus a
        stream-teardown sweep) can never hand the same buffer to the
        free list twice."""
        if not leases:
            return
        for buf in leases:
            self._staging.release(buf)
        del leases[:]

    def staging_snapshot(self) -> dict:
        """Pool introspection (bench, /debug, ledger reconciliation)."""
        out = self._staging.snapshot()
        out["suspended"] = self._staging_suspended
        out["donating"] = self._donate_entries
        return out

    def _put(self, entries: np.ndarray):
        """A slice's packed entries on the device. On a multi-controller
        mesh jit inputs must be global arrays: every process holds identical
        host data (the lockstep contract, parallel/mesh.py
        init_distributed), so they replicate."""
        if self._multiprocess:
            return jax.device_put(entries, self._shard_repl_sharding)
        return jnp.asarray(entries)

    def _entry_kernels(self):
        """``(check, label)`` jitted kernels the single-device dispatch
        ships entries through: the donated variants where the backend
        implements donation, the plain ones elsewhere and on every mesh.
        ``warm_compile`` warms exactly these — the two variants are
        distinct executables, so warming the other one leaves the first
        slice of every width compiling inside the serving window."""
        if self._donate_entries and self._mesh is None:
            return _check_kernel_donated, _label_kernel_donated
        return _check_kernel, _label_kernel

    def trim_widths(self) -> None:
        """The governor's warm-ladder rung: serve from the ladder's lower
        rungs (``width_trim``; the rung's restore zeroes it) and drop the
        compiled executables with the record of them."""
        self.width_trim = max(self.width_trim, len(_WORD_WIDTHS) - 4)
        self.geoms.reset()
        kerns: list = [
            _check_kernel, _label_kernel,
            _check_kernel_donated, _label_kernel_donated,
        ]
        if self._sharded:
            from keto_tpu.parallel import sharded as shard_mod

            kerns += [
                shard_mod.check_kernel(self._mesh),
                shard_mod.label_kernel(self._mesh),
            ]
        for kern in kerns:
            clear = getattr(kern, "clear_cache", None)
            if clear is not None:
                try:
                    clear()
                except Exception:
                    # trimming still bounds future widths even when this
                    # jax build can't drop already-compiled executables
                    _log.warning(
                        "compiled-kernel cache clear failed during "
                        "warm-ladder eviction", exc_info=True,
                    )

    def _word_widths(self) -> tuple[int, ...]:
        """The compile-width ladder currently in service: the full
        ``_WORD_WIDTHS`` normally, its lower rungs while the governor's
        warm-ladder rung is evicted (never fewer than two widths)."""
        n = len(_WORD_WIDTHS) - self.width_trim
        return _WORD_WIDTHS[: max(2, n)]

    def _labels_usable(self, snap: GraphSnapshot) -> bool:
        """Route checks through the label index on this snapshot? False
        while a pending overlay has mutated the interior (ELL) subgraph
        — counted ONCE per blocked overlay generation as a
        ``label_invalidations`` maintenance event."""
        if not self._labels_enabled or snap.labels is None:
            return False
        if snap.lab_dirty:
            if self._label_blocked_snap != snap.snapshot_id:
                self._label_blocked_snap = snap.snapshot_id
                self.maintenance.incr("label_invalidations")
                self.maintenance.set_gauge(
                    "label_dirty_nodes", len(snap.lab_dirty)
                )
            return False
        self.maintenance.set_gauge("label_dirty_nodes", 0)
        return self._labels_dev(snap) is not None

    # -- compiled geometries and the warm-up ---------------------------------

    def _warm_width_bytes(self, snap: GraphSnapshot, B: int) -> int:
        """Device bytes one warmed width holds live while its slice runs:
        the BFS workspace (~3 W-wide uint32 bitmaps over interior rows —
        the same formula ``_slice_cap`` budgets with)."""
        return (snap.num_int + 1) * 12 * (B // 32)

    def _check_shape(self, snap: GraphSnapshot) -> tuple:
        """What of the snapshot fixes a ``check_step`` program: row counts
        and the shapes of the arrays it closes over. Kept on the snapshot
        for as long as it holds the same device arrays."""
        bk, ov, hub = snap.device_buckets, snap.device_overlay, snap.device_hub
        kept = getattr(snap, "_check_shape_of", None)
        if kept is not None and kept[0] is bk and kept[1] is ov and kept[2] is hub:
            return kept[3]
        shape = (
            snap.num_active, snap.num_int, tuple(b.n for b in snap.buckets),
            tuple(a.shape for a in bk),
            None if ov is None else (ov[0].shape, ov[1].shape),
            None if hub is None else hub.shape,
        )
        snap._check_shape_of = (bk, ov, hub, shape)
        return shape

    def _check_fixed(self, it_cap: int) -> tuple:
        return (it_cap, self._block_iters, self._donate_entries, self._sweep)

    @staticmethod
    def _label_shape(labs) -> tuple:
        return (labs[0].shape, labs[1].shape)

    def _label_fixed(self) -> tuple:
        return (self._donate_entries,)

    def _bitmap_sharding_for(self, B: int):
        if self._mesh is None:
            return None
        if (B // 32) % self._mesh.shape.get("data", 1):
            return self._bitmap_sharding_rows_only
        return self._bitmap_sharding

    def _run_check_padding(
        self, snap: GraphSnapshot, sizes: tuple, it_cap: int, seeds=None, sweep=None
    ) -> np.ndarray:
        """One ``check_step`` at ``sizes`` = (S1, S2, SA, B) on entries that
        are all padding - dropped seed rows, the all-zero answer row - so
        that the program of these sizes is compiled; with ``seeds``, those
        interior rows start one query each, under ``sweep`` where the probe
        names one. Returns the device output."""
        ni = snap.num_int
        packed = _padding_packed(sizes, ni)
        if seeds is not None:
            packed[0][: seeds.size] = seeds
            packed[1][: seeds.size] = np.arange(seeds.size)
        buf, sizes = pack_entries(packed)
        ov = snap.device_overlay
        kern = self._entry_kernels()[0]
        return self._guard_alloc(
            "warm-compile",
            lambda: kern(
                snap.device_buckets,
                jnp.asarray(buf),
                ov_nbrs=None if ov is None else ov[0],
                ov_dst=None if ov is None else ov[1],
                hub_nbrs=snap.device_hub,
                sizes=sizes,
                n_active=snap.num_active,
                n_int=ni,
                valid_rows=tuple(b.n for b in snap.buckets),
                it_cap=it_cap,
                block_iters=self._block_iters,
                bitmap_sharding=self._bitmap_sharding_for(sizes[3]),
                sweep=sweep or self._sweep,
            ).block_until_ready(),
        )

    def _run_label_padding(self, labs, sizes: tuple) -> None:
        """One ``label_step`` at ``sizes`` = (P, B) on pairs of the all-pad
        row, so that the program of these sizes is compiled."""
        P, B = sizes
        ni = labs[0].shape[0] - 1
        pairs = np.concatenate(
            [np.full(2 * P, ni, np.int32), np.zeros(P, np.int32)]
        )
        kern = self._entry_kernels()[1]
        self._guard_alloc(
            "warm-compile",
            lambda: kern(
                labs[0], labs[1], jnp.asarray(pairs), n_pairs=P, B=B
            ).block_until_ready(),
        )

    def _settle_block_iters(self, snap: GraphSnapshot, B: int) -> None:
        """``block_iters`` and the sweep order are statics of every
        ``check_step`` program, so a change recompiles them all: settle both
        before the ladder is warmed, from how deep the snapshot's own device
        part runs - a BFS from a spread of the interior rows nothing on the
        device points at (the sources of what the pulls walk), once in each
        order: the one that took fewer pulls is kept (device-id order on a
        tie; which one wins is the graph's, no edge count predicts it) - and
        leave them there for as long as snapshots keep this shape
        (``_after_batch``). The order not kept is compiled for this probe
        alone. A mesh keeps the whole-step pull and is not probed."""
        self._block_iters_shape = self._check_shape(snap)
        na, ni = snap.num_active, snap.num_int
        if na == 0 or ni <= na or not snap.buckets:
            return
        seeds = np.unique(
            np.linspace(na, ni - 1, num=min(B, ni - na)).astype(np.int32)
        )
        pulls = {}
        for order in kernels.IN_PLACE_SWEEPS:
            out = np.asarray(
                self._run_check_padding(
                    snap, (B, B, B, B), self._it_cap, seeds=seeds, sweep=order
                )
            )
            pulls[order] = int(out[B // 32])
            self.maintenance.set_gauge(f"sweep_probe_pulls_{order}", pulls[order])
        self._sweep = min(pulls, key=pulls.get)  # "up" comes first: it wins a tie
        self._block_iters = max(
            self._block_iters, min(32, _ceil_pow2(pulls[self._sweep] + 1))
        )

    def _compile_geometry(self, kernel: str, shape: tuple, fixed: tuple, sizes: tuple) -> bool:
        """The geometry worker's compile (keto_tpu/check/geometry.py): run
        the kernel once on padding at ``sizes`` against the current
        snapshot, if that still has the shape the slice saw."""
        snap = self._current_snapshot()
        if self._closing or snap is None:
            return False
        if kernel == "check":
            if self._check_shape(snap) != shape or self._check_fixed(fixed[0]) != fixed:
                return False
            self._run_check_padding(snap, sizes, fixed[0])
            return True
        labs = self._labels_dev(snap)
        if labs is None or self._label_shape(labs) != shape or self._label_fixed() != fixed:
            return False
        self._run_label_padding(labs, sizes)
        return True

    def warm_compile(self, snap: GraphSnapshot) -> int:
        """Run every program of the slice-width ladder (BFS and label
        kernels) once against ``snap``, so the first real slice of every
        width hits the jit cache. Widths whose compiled-buffer footprint
        would breach the HBM budget are SKIPPED (never evicted for —
        warming is optional work) and counted in the
        ``warm_widths_skipped`` gauge / ``keto_hbm_warm_widths_skipped``.
        Returns the number of kernels warmed.

        On the single-device path every warmed program is also entered in
        the geometry set, and from here on a slice whose own program is not
        compiled pads up to one that is (keto_tpu/check/geometry.py): the
        ladder's minimum rungs are what there always is to pad up to."""
        ni = snap.num_int
        warmed = 0
        skipped = 0
        warm_bytes = 0
        plain = self._mesh is None  # the path whose geometries are tracked
        widths = self.stream_widths(snap)
        if plain and widths:
            self._settle_block_iters(snap, widths[0])
        labs = None
        for B in widths:
            if self._closing:
                break  # teardown must never race an in-flight compile
            need = self._warm_width_bytes(snap, B)
            if not self.hbm.plan(need - warm_bytes, what=f"warm width {B}", evict=False):
                skipped += 1
                continue
            # the empty-batch geometry: every entry array at its minimum
            # pad (B), every row a dropped/padded sentinel — the same
            # static shapes a real B-query slice produces
            if self._sharded and snap.device_shards is not None:
                dev = self._dispatch_sharded(
                    snap, _padding_packed((B, B, B, B), ni), self._it_cap
                )
                self._guard_alloc(
                    "warm-compile", lambda d=dev: d.dev.block_until_ready()
                )
            else:
                self._run_check_padding(snap, (B, B, B, B), self._it_cap)
                if plain:
                    self.geoms.add(
                        "check", self._check_shape(snap),
                        self._check_fixed(self._it_cap), (B, B, B, B),
                    )
            warmed += 1
            # one slice runs at a time: the warm family holds the WIDEST
            # warmed width's workspace, not the sum over widths
            warm_bytes = max(warm_bytes, need)
            self.hbm.register("warmup", warm_bytes)
            labs = self._labels_dev(snap)
            if self._labels_enabled and labs is not None:
                if self._sharded:
                    from keto_tpu.parallel import sharded as shard_mod

                    pairs = np.concatenate(
                        [np.full(2 * B, ni, np.int32), np.zeros(B, np.int32)]
                    )
                    self._guard_alloc(
                        "warm-compile",
                        lambda: shard_mod.label_kernel(self._mesh)(
                            labs[0], labs[1],
                            jax.device_put(pairs, self._shard_repl_sharding),
                            n_pairs=B, B=B, rl=labs[2],
                        ).block_until_ready(),
                    )
                else:
                    self._run_label_padding(labs, (B, B))
                    if plain:
                        self.geoms.add(
                            "label", self._label_shape(labs), self._label_fixed(), (B, B)
                        )
                warmed += 1
        if plain and labs is not None and self._riders_expected(snap):
            # hub rows: a chunk's pairs and its riders' entries outgrow the
            # ladder's own rungs by far, at widths the ladder has. Rungs of
            # many entries on those widths, so that no such slice rides a
            # wider bitmap or waits for a program of its own
            for kernel, sizes in self._hub_rungs(widths):
                if self._closing:
                    break
                if kernel == "label":
                    self._run_label_padding(labs, sizes)
                    self.geoms.add(kernel, self._label_shape(labs), self._label_fixed(), sizes)
                else:
                    self._run_check_padding(snap, sizes, self._it_cap)
                    self.geoms.add(
                        kernel, self._check_shape(snap), self._check_fixed(self._it_cap), sizes
                    )
                warmed += 1
        if plain:
            self.geoms.mark_warmed("check", self._check_shape(snap))
            if self._labels_enabled and labs is not None:
                self.geoms.mark_warmed("label", self._label_shape(labs))
        self.maintenance.set_gauge("warm_widths_skipped", skipped)
        return warmed

    @staticmethod
    def _hub_rungs(widths: list[int]) -> list[tuple[str, tuple]]:
        """The programs ``warm_compile`` adds where ``_riders_expected``, as
        ``(kernel, sizes)``: ``(P, B)`` of ``label`` and ``(E, E, E, B)`` of
        ``check``.
        A chunk of B queries brings the label kernel up to the pair cap
        times B pairs (rungs at 8 B and 32 B on the widths a served take
        has), and its riders, a third of it on such a graph, a dozen
        entries each or more (a hub sink's relay rows, the rows of a sink
        just under a hub's, a seed a grant): ``device_part`` pads them
        B' x 2^k (rungs at 16, 32 and 64 B' on the width that holds a take's
        riders, 16,384 and 65,536 entries on the narrower two, which hold
        what is left of a chunk that was cut)."""
        rungs = [("label", (k * B, B)) for B in widths[2:4] for k in (8, 32)]
        rungs += [("check", (E,) * 3 + (B,)) for B in widths[:2] for E in (16384, 65536)]
        rungs += [("check", (k * B,) * 3 + (B,)) for B in widths[2:3] for k in (16, 32, 64)]
        return rungs

    @staticmethod
    def _riders_expected(snap: GraphSnapshot) -> bool:
        """Will hybrid slices carry riders on this snapshot whatever the
        traffic? Yes where it has hub sinks (``GraphSnapshot.hub_relays``:
        an answer gathered from more than twice the rows of the label
        route's pair cap): a check on one passes the cap with a single seed."""
        return snap.hub_ptr is not None

    # -- resolution ----------------------------------------------------------

    def _resolve_bulk(
        self, snap: GraphSnapshot, tuples
    ) -> tuple[np.ndarray, np.ndarray, dict]:
        """Resolve every query to device rows (see ``_resolve_bulk_py`` for
        the result contract). Literal queries go through the C++ intern
        tables in one bulk call when the native library provides it
        (``_raw_ids``); wildcard/pattern/unknown-namespace queries and the
        pure-Python interner use the host loop. The numpy form of the
        ``resolve`` state: what a stream's chunk takes where
        ``_resolve_chunk``'s one native pass declines it, and what that pass
        is held to."""
        tuples, raw = self._raw_ids(snap, tuples)
        if raw is None:
            return self._resolve_bulk_py(snap, tuples)
        return self._resolve_records(snap, tuples, *raw)

    def _raw_ids(self, snap: GraphSnapshot, tuples):
        """``tuples`` (a list of ``RelationTuple`` or a ``QueryBatch``,
        keto_tpu/check/frame.py) as the raw node ids of ``snap.interned``'s
        C++ tables: ``(tuples, (start_raw, sub_raw, special, dead,
        no_target))``, or ``(tuples, None)`` where there are no such tables
        or the records' framing is unsafe.

        Framed ranges bring their records with them and are resolved
        without a loop over tuples. Where the records cannot be trusted
        against this snapshot (see ``_frame_blocker``) the batch is turned
        into objects, handed back as ``tuples``, and takes the list's path."""
        if isinstance(tuples, QueryBatch):
            why = self._frame_blocker(snap, tuples)
            if why is None:
                got = self._records_of(snap, tuples)
                if got is not None:
                    return tuples, got
                why = "rejected"
            tuples = tuples.tuples(why)
        self.maintenance.incr("resolve_tuples_thread", by=len(tuples))
        if hasattr(snap.interned, "resolve_queries"):
            return tuples, self._raw_of_tuples(snap, tuples)
        return tuples, None

    def _resolve_decline(self, snap: GraphSnapshot, raw) -> Optional[str]:
        """Why ``resolve``'s one native pass (``native_pack.resolve_chunk``)
        cannot take a chunk whose raw ids are ``raw``, or None where it can
        try: from what the code observes, never a setting. Two more causes
        show only in what the pass counts (``_resolve_chunk``).
        ``keto_check_resolve_declines_total``."""
        if (
            not native_pack.available()
            or not hasattr(snap.interned, "resolve_queries")
            or snap.fwd_indptr is None
            or snap.sink_indptr is None
        ):
            return "no_library"  # or no native tables to have raw ids from
        if raw is None or raw[2]:
            # records that cannot speak for their queries: wildcards and
            # patterns (they make ``multi``), strings with separator bytes
            return "special"
        return None

    def _resolve_view_of(self, snap: GraphSnapshot) -> native_pack.ResolveView:
        """``snap`` as the native pass reads it, the reach mask
        (``_device_reach``, made once a snapshot) in it: the pass always
        hands over both running sums."""
        hub, reach = hub_usable(snap), self._device_reach(snap)
        plan = snap.rewrites
        flags = None if plan is None else plan.flags_of(snap)
        view = self._resolve_view
        if view is None or not view.of(snap, hub, reach, flags):
            view = self._resolve_view = native_pack.ResolveView(
                snap, hub, reach, flags, REWRITTEN
            )
        return view

    def _resolve_chunk(self, snap: GraphSnapshot, tuples):
        """The ``resolve`` of one chunk of a stream: ``(sd, tg, multi,
        closure, sums, view)``. ``sd`` / ``tg`` / ``multi`` are
        ``_resolve_bulk``'s; ``view`` is the ``ResolveView`` the native pass
        read, None where the numpy path resolved the chunk; ``closure``
        (under a rewrite plan: the closure byte a query and how many hold
        ``REWRITTEN``, ``_rewrite_split``'s) is None then, and the caller
        makes it; ``sums`` (``_entry_sums``) is None then too, and under a
        plan with gates, where what is counted is the positions the expansion
        makes of the chunk.

        One GIL-released call (native/pack.cpp ``keto_resolve_chunk``)
        wherever nothing declines it: no library; ``special`` records; a
        snapshot with overlay or extension nodes and a query that missed a
        start or a target (the tables do not know such nodes: the host path
        re-resolves the misses); a start row past the plan's closure bytes.
        A declined chunk takes ``_resolve_records`` (or the host loop), which
        is also what the pass is fuzzed against. Counted once a chunk; under
        an open profiler session the ``resolve`` span that holds either
        carries its ``path``."""
        tuples, raw = self._raw_ids(snap, tuples)
        incr = self.maintenance.incr
        clk = dispatch_clock()
        declined = self._resolve_decline(snap, raw)
        if declined is None:
            view = self._resolve_view_of(snap)
            plan = snap.rewrites
            clk.resolving("native")
            got = native_pack.resolve_chunk(
                view, raw[0], raw[1], raw[3], raw[4],
                count=plan is None or not plan.has_gated,
            )
            clk.poll()
            if got.bad_inputs:
                # what numpy says of a raw id past ``raw2dev``
                raise IndexError(f"{got.bad_inputs} raw node ids or marks out of range")
            if got.misses and (
                snap.ov_set_ids or snap.ov_leaf_ids or getattr(snap.interned, "has_ext", False)
            ):
                declined = "overlay"
            elif got.overlay_starts:
                declined = "overlay_start"
            else:
                incr("resolve_chunks_native")
                closure = None if got.flags is None else (got.flags, got.rewritten)
                return got.sd, got.tg, {}, closure, got.sums, view
        incr("resolve_chunks_numpy")
        incr(f"resolve_declines_{declined}")
        clk.resolving("numpy")
        if raw is None:
            sd, tg, multi = self._resolve_bulk_py(snap, tuples)
        else:
            sd, tg, multi = self._resolve_records(snap, tuples, *raw)
        return sd, tg, multi, None, None, None

    def _entry_sums(self, snap: GraphSnapshot, sd, tg, multi: dict) -> np.ndarray:
        """``int64[2, n + 1]``: the running sums from 0 of resolved
        positions' entry counts, which is all a cut of the chunk asks:
        ``[0]`` of ``_entry_counts``, ``[1]`` of the same with the positions
        zeroed whose target no pull can change (``_device_reach``; ``[0]``
        again where every target counts). The numpy form of what the native
        pass hands over (``ResolvedChunk.sums``, ``native_pack.entry_sums``)."""
        cnt = self._entry_counts(snap, sd, tg, multi)
        sums = np.zeros((2, cnt.shape[0] + 1), np.int64)
        np.cumsum(cnt, out=sums[0, 1:])
        reach = self._device_reach(snap)
        if reach is None:
            sums[1] = sums[0]
        else:
            known = (tg >= 0) & (tg < snap.num_live)
            cnt[known & ~reach[np.where(known, tg, 0)]] = 0
            np.cumsum(cnt, out=sums[1, 1:])
        return sums

    def _frame_blocker(self, snap: GraphSnapshot, batch: QueryBatch) -> Optional[str]:
        """Why ``batch``'s framed records cannot be resolved as they are
        against ``snap`` (None: they can). Read off the snapshot and the
        frames, never off a setting: an interner without the bulk entry
        point; a namespace named "" (the framer assumes there is none);
        a frame whose namespace ids came from a manager that is no longer
        the current one (hot reload between framing and resolve)."""
        if not hasattr(snap.interned, "resolve_queries"):
            return "no_native"
        if snap.wild_ns_ids:
            return "wild_ns"
        nm = self._nm()
        for src, _a, _b in batch.parts:
            if isinstance(src, QueryFrame) and src.manager is not nm:
                return "reload"
        return None

    def _records_of(self, snap: GraphSnapshot, batch: QueryBatch):
        """``batch`` as raw node ids plus the indices the records cannot
        speak for: ``(start_raw, sub_raw, special, dead, no_target)``, or
        None when a buffer's framing is unsafe.

        A framed part whose body was resolved at the door (``QueryFrame.
        resolve_at_door``) by the very object that ``snap.interned`` is
        contributes slices of those arrays. Any other part (a list, which
        goes through the framing loop first; a frame without door ids; a
        frame whose ids came from tables a rebuild, a compaction fold or a
        snapshot-cache reload has since replaced) contributes its records,
        and a run of such parts is resolved here in one C++ pass. Counts
        ``keto_check_resolve_tuples_total{where}`` once a batch."""
        interned = snap.interned
        starts: list[np.ndarray] = []
        subs: list[np.ndarray] = []
        bufs: list[bytes] = []  # the run of records waiting for this thread's pass
        held = 0
        marked: tuple[list, list, list] = ([], [], [])  # special, dead, no_target
        at_door = 0

        def resolve_held() -> bool:
            nonlocal held
            if held:
                got = self._resolve_buffer(interned, b"".join(bufs), held)
                if got is None:
                    return False
                starts.append(got[0])
                subs.append(got[1])
                bufs.clear()
                held = 0
            return True

        base = 0
        for src, a, b in batch.parts:
            framed = isinstance(src, QueryFrame)
            door = src.door if framed else None
            if door is not None and door[0] is interned:
                if not resolve_held():
                    return None
                starts.append(door[1][a:b])
                subs.append(door[2][a:b])
                at_door += b - a
            elif framed:
                bufs.append(src.buf[int(src.off[a]) : int(src.off[b])])
                held += b - a
            else:
                buf, *lists = self._frame_tuples(snap, src[a:b])
                bufs.append(buf)
                held += b - a
                for k, idxs in enumerate(lists):
                    marked[k].extend(i + base for i in idxs)
            if framed:
                fl = src.flags[a:b]
                if fl.any():
                    for k, flag in enumerate((SPECIAL, DEAD, NO_TARGET)):
                        marked[k].extend((np.flatnonzero(fl == flag) + base).tolist())
            base += b - a
        if not resolve_held():
            return None
        for where, n in (("door", at_door), ("thread", base - at_door)):
            if n:
                self.maintenance.incr(f"resolve_tuples_{where}", by=n)
        one = len(starts) == 1
        return (
            starts[0] if one else np.concatenate(starts),
            subs[0] if one else np.concatenate(subs),
            *marked,
        )

    @staticmethod
    def _resolve_buffer(interned, buf: bytes, n: int):
        """``n`` query records as ``(start_raw, sub_raw)`` in one C++ pass
        over ``interned``'s tables, None when the buffer's framing is unsafe."""
        # separator bytes inside strings corrupt framing — detectable as a
        # field-count mismatch, same check as the ingest path
        if buf.count(b"\x1f") != 6 * n or buf.count(b"\x1e") != n:
            return None
        return interned.resolve_queries(buf, n)

    def _raw_of_tuples(self, snap: GraphSnapshot, tuples: Sequence[RelationTuple]):
        """Pack literal queries into the native wire format and resolve them
        to raw node ids in one C++ pass: ``(start_raw, sub_raw, special,
        dead, no_target)``, the marks being the queries the records cannot
        speak for. None when the buffer framing is unsafe (separator bytes
        in strings): callers fall back to the pure host loop."""
        buf, *marked = self._frame_tuples(snap, tuples)
        raw = self._resolve_buffer(snap.interned, buf, len(tuples))
        return None if raw is None else (*raw, *marked)

    def _frame_tuples(self, snap: GraphSnapshot, tuples: Sequence[RelationTuple]):
        """The framing loop: ``tuples`` as query records, and the indices
        whose record is a placeholder. Returns ``(buf, special, dead,
        no_target)``."""
        wild_ids = snap.wild_ns_ids
        nm = self._nm()
        ns_cache: dict = {}

        def _ns_bytes(name: str):
            """namespace name → decimal-ASCII id bytes, _WILD, or None."""
            hit = ns_cache.get(name, _UNSET)
            if hit is not _UNSET:
                return hit
            if name == "":
                r: object = _WILD
            else:
                try:
                    ns_id = nm.get_namespace_by_name(name).id
                    r = _WILD if ns_id in wild_ids else b"%d" % ns_id
                except ErrNamespaceUnknown:
                    r = None
            ns_cache[name] = r
            return r

        parts: list[bytes] = []
        ap = parts.append
        special: list[int] = []
        dead: list[int] = []  # guaranteed denies; placeholder results ignored
        #: queries whose start resolves normally but whose subject can't
        #: exist (empty-namespace subject set with no "" namespace
        #: configured): the placeholder subject may collide with a real
        #: node, so tg is forced unreachable after the bulk resolve
        no_target: list[int] = []
        for i, rt in enumerate(tuples):
            ns = _ns_bytes(rt.namespace)
            if ns is None:
                dead.append(i)  # unknown namespace → denied
                ap(_PLACEHOLDER)
                continue
            obj, rel = rt.object, rt.relation
            if ns is _WILD or obj == "" or rel == "":
                special.append(i)  # wildcard pattern → host resolver
                ap(_PLACEHOLDER)
                continue
            sub = rt.subject
            if type(sub) is SubjectID:
                ap(b"%b\x1f%b\x1f%b\x1f1\x1f%b\x1f\x1f\x1e"
                   % (ns, obj.encode(), rel.encode(), sub.id.encode()))
            elif isinstance(sub, SubjectSet):
                sns = _ns_bytes(sub.namespace)
                if sns is None:
                    dead.append(i)  # unknown subject namespace → denied
                    ap(_PLACEHOLDER)
                    continue
                if sns is _WILD:
                    # subjects match LITERALLY (host-loop parity:
                    # _subject_target) — an empty subject namespace can
                    # only equal a stored subject in a namespace named
                    # "", so resolve against that namespace's id rather
                    # than routing the whole query to the pattern path
                    # (which the host loop does NOT do when the start is
                    # literal; the divergence was the tier-1
                    # bulk-resolve parity failure)
                    wild_list = list(wild_ids)
                    if not wild_list:
                        # no namespace named "": the target cannot exist
                        # — resolve the start normally, force tg = -1
                        no_target.append(i)
                        ap(b"%b\x1f%b\x1f%b\x1f1\x1f\x1f\x1f\x1e"
                           % (ns, obj.encode(), rel.encode()))
                        continue
                    sns = b"%d" % wild_list[0]
                ap(b"%b\x1f%b\x1f%b\x1f0\x1f%b\x1f%b\x1f%b\x1e"
                   % (ns, obj.encode(), rel.encode(), sns,
                      sub.object.encode(), sub.relation.encode()))
            else:
                dead.append(i)  # nil subject → denied
                ap(_PLACEHOLDER)
        return b"".join(parts), special, dead, no_target

    def _resolve_records(
        self, snap: GraphSnapshot, queries, start_raw: np.ndarray, sub_raw: np.ndarray,
        special: list[int], dead: list[int], no_target: list[int],
    ):
        """The raw node ids of ``len(queries)`` query records (as the C++
        tables of ``snap.interned`` gave them, here or at the door) as
        device rows of ``snap``, with what the records could not say
        patched in. ``queries`` (a list or a ``QueryBatch``) is only asked
        for the tuples at ``special`` and, on a snapshot with nodes the
        C++ tables do not know, at the misses."""
        nl = snap.num_live
        dispatch_clock().poll()
        r2d = snap.raw2dev
        sd = np.where(start_raw >= 0, r2d[np.clip(start_raw, 0, None)], -1)
        t = r2d[np.clip(sub_raw, 0, None)]
        # a target only matters when the query has starts (matches the host
        # loop, which leaves tg at the unreachable row for start-less denies)
        tg = np.where((sub_raw >= 0) & (t < nl) & (sd >= 0), t, -1)
        if dead:
            # placeholder records may coincide with real nodes — force deny
            di = np.asarray(dead)
            sd[di] = -1
            tg[di] = -1
        if no_target:
            tg[np.asarray(no_target)] = -1
        multi: dict = {}
        if special:
            self._resolve_specials(
                snap, pick_tuples(queries, special, "special"), special, sd, tg, multi
            )
        if (
            snap.ov_set_ids
            or snap.ov_leaf_ids
            or getattr(snap.interned, "has_ext", False)
        ):
            # nodes created since the base build — overlay nodes, or
            # compaction-folded extension nodes (interner.ExtendedInterned)
            # — are invisible to the resident C++ tables: re-resolve the
            # queries whose start or target missed through the
            # extension-aware host path, in ONE bulk call (tg == -1
            # includes every guaranteed deny, so deny-heavy workloads
            # would otherwise loop per query)
            done = set(special) | set(dead)
            miss = [
                int(i)
                for i in np.nonzero((sd == -1) | (tg == -1))[0]
                if int(i) not in done
            ]
            if miss:
                s1, t1, m1 = self._resolve_bulk_py(
                    snap, pick_tuples(queries, miss, "overlay")
                )
                for j, i in enumerate(miss):
                    sd[i] = s1[j]
                    tg[i] = t1[j]
                    if j in m1:
                        multi[i] = m1[j]
        return sd, tg, multi

    def _ns_resolver(self):
        """Per-batch namespace-name → id resolver with a cache: ``None`` =
        unknown (→ denied, engine.go:76-77), ``WILDCARD`` = empty name."""
        nm = self._nm()
        cache: dict = {}

        def _ns(name: str):
            hit = cache.get(name, _UNSET)
            if hit is not _UNSET:
                return hit
            if name == "":
                r: object = WILDCARD
            else:
                try:
                    r = nm.get_namespace_by_name(name).id
                except ErrNamespaceUnknown:
                    r = None
            cache[name] = r
            return r

        return _ns

    def _subject_target(self, snap: GraphSnapshot, rt: RelationTuple, ns_of):
        """Resolve a query's subject to its target device row: the id, -1
        when no such node exists (target unreachable), or ``None`` when the
        subject itself forces a deny (nil subject, unknown subject
        namespace)."""
        interned = snap.interned
        raw2dev = snap.raw2dev
        sub = rt.subject
        if type(sub) is SubjectID:
            rawl = interned.resolve_leaf(sub.id)
            if rawl >= 0:
                return int(raw2dev[rawl + snap.num_sets])
            ov_leaf = snap.ov_leaf_ids
            return ov_leaf.get(sub.id, -1) if ov_leaf else -1
        if isinstance(sub, SubjectSet):
            sns_id = ns_of(sub.namespace)
            if sns_id is None:
                return None
            if sns_id == WILDCARD:
                # subjects are matched literally; an empty subject
                # namespace can only equal a stored subject in a
                # namespace named ""
                wild_list = list(snap.wild_ns_ids)
                if not wild_list:
                    return -1
                skey = (wild_list[0], sub.object, sub.relation)
            else:
                skey = (sns_id, sub.object, sub.relation)
            rawt = interned.resolve_set(*skey)
            if rawt >= 0:
                return int(raw2dev[rawt])
            ov_set = snap.ov_set_ids
            return ov_set.get(skey, -1) if ov_set else -1
        return None  # nil subject → denied

    def _resolve_specials(self, snap, picked, indices, sd, tg, multi):
        """Wildcard/pattern queries, resolved in bulk: namespace names go
        through one cache, starts through the snapshot's family-grouped
        sorted indexes (``GraphSnapshot.resolve_starts_bulk`` — one
        vectorized searchsorted pass per pattern family instead of a
        per-query probe), subjects literally. ``picked[k]`` is the tuple
        of query ``indices[k]``; results splice into the caller's bulk
        arrays."""
        _ns = self._ns_resolver()
        live: list[tuple] = []
        pats: list[tuple] = []
        for i, rt in zip(indices, picked):
            ns_id = _ns(rt.namespace)
            if ns_id is None:
                continue  # unknown namespace → denied
            live.append((i, rt))
            pats.append((ns_id, rt.object, rt.relation))
        if not live:
            return
        starts_l = snap.resolve_starts_bulk(pats)
        ni = snap.num_int
        sbase = snap.sink_base
        nl = snap.num_live
        for (i, rt), starts in zip(live, starts_l):
            if starts.size == 0:
                continue  # no matching start node → denied
            t = self._subject_target(snap, rt, _ns)
            if t is None:
                continue  # nil subject / unknown subject namespace → denied
            if 0 <= t < nl or (t >= nl and snap.is_answerable_target(t)):
                tg[i] = t
            sd[i] = -2
            # interior starts seed the bitmap; sink starts (no out-edges)
            # contribute nothing; peeled/static starts are host-propagated
            # at pack time (pack_chunk)
            multi[i] = (
                starts[starts < ni],
                starts[((starts >= ni) & (starts < sbase)) | (starts >= nl)],
            )

    def _resolve_bulk_py(
        self, snap: GraphSnapshot, tuples: Sequence[RelationTuple]
    ) -> tuple[np.ndarray, np.ndarray, dict]:
        """One tight host pass resolving every query to device rows.

        Returns ``(sd, tg, multi)``:

        - ``sd[i]`` — the query's single start row: ``-1`` no start
          (guaranteed deny: unknown namespace per engine.go:76-77, or no
          matching node), ``-2`` multi-start (wildcard pattern, rows in
          ``multi``), else a device id (live or static);
        - ``tg[i]`` — target row, or ``-1`` when unreachable (static row,
          or no such node). -1 — not a node-id sentinel like ``num_live``
          — because every id can be legitimate: in a base graph with zero
          static nodes the first overlay node gets device id num_live,
          and a node-id sentinel would collide with it in the walk's
          target-hit check and the answer-gather key match;
        - ``multi`` — ``{i: (live start rows, host-propagated start rows
          — peeled/static, expanded at pack time)}`` for wildcard-pattern
          queries.

        The common case (literal query, SubjectID) costs two intern-table
        lookups and two ``raw2dev`` reads — no numpy allocation. Pattern
        queries defer to ``_resolve_specials``'s bulk family resolver.
        """
        n = len(tuples)
        nl = snap.num_live
        sd = np.full(n, -1, np.int64)
        tg = np.full(n, -1, np.int64)
        multi: dict = {}
        interned = snap.interned
        resolve_set = interned.resolve_set
        raw2dev = snap.raw2dev
        wild_ids = snap.wild_ns_ids
        ov_set = snap.ov_set_ids or {}
        _ns = self._ns_resolver()

        special: list[int] = []
        for i, rt in enumerate(tuples):
            ns_id = _ns(rt.namespace)
            if ns_id is None:
                continue  # unknown namespace → denied (engine.go:76-77)
            obj, rel = rt.object, rt.relation
            if ns_id == WILDCARD or ns_id in wild_ids or obj == "" or rel == "":
                special.append(i)  # wildcard pattern → bulk family resolver
                continue
            raw = resolve_set(ns_id, obj, rel)
            if raw >= 0:
                start_dev = int(raw2dev[raw])
            else:
                start_dev = ov_set.get((ns_id, obj, rel), -1) if ov_set else -1
                if start_dev < 0:
                    continue
            t = self._subject_target(snap, rt, _ns)
            if t is None:
                continue  # nil subject / unknown subject namespace → denied
            if 0 <= t < nl or (t >= nl and snap.is_answerable_target(t)):
                tg[i] = t
            sd[i] = start_dev
        if special:
            self._resolve_specials(
                snap, [tuples[i] for i in special], special, sd, tg, multi
            )
        return sd, tg, multi

    # -- slices: launch, land, decode ----------------------------------------

    def _run_exact(
        self, snap: GraphSnapshot, tuples: Sequence[RelationTuple], it_cap: Optional[int] = None
    ) -> tuple[np.ndarray, int]:
        """Drain one ordered stream over ``tuples`` at ``it_cap``, with the
        EXACTNESS guarantee the reference's visited-set termination gives
        for free: a truncated kernel (frontier still growing at it_cap)
        never decides a query. The stream's ``land`` re-runs a truncated
        slice through here with an escalating cap, bounded by one that
        cannot truncate, so every decision comes from a true fixpoint.
        Returns ``(bool[len(tuples)], the most pulls any slice took)``;
        ``_after_batch`` is the caller's."""
        gen = self._stream(
            snap, tuples, depth=None, slice_cap=None, ordered=True, it_cap=it_cap
        )
        max_iters = 0

        def drain():
            nonlocal max_iters
            max_iters = yield from gen

        parts = list(drain())
        return (np.concatenate(parts) if parts else np.zeros(0, bool)), max_iters

    @staticmethod
    def _slice_ready(dev) -> bool:
        """Has this slice's async device→host copy completed? Host-only
        slices are always ready. A seam on purpose: skew tests patch it to
        force adversarial landing orders."""
        if dev is None:
            return True
        ready = getattr(dev, "is_ready", None)
        return True if ready is None else bool(ready())

    def stream_widths(self, snap: GraphSnapshot) -> list[int]:
        """The slice-width ladder the adaptive stream can choose from on
        this snapshot (ascending) — callers pre-warm jit geometries by
        running one batch per width."""
        cap = self._slice_cap(snap)
        return [32 * w for w in self._word_widths() if 32 * w <= cap]

    def _slices(self, snap, take, bound, ctrl, it_cap):
        """The launching side of a stream: cut up to the controller's cap
        off the source, resolve, pack and launch it, and yield one
        ``(offset, dev, host_ans, nq, chunk, leases, n_entries, full_take,
        gate)`` a launched slice; ``full_take``: the take it was cut from
        filled the cap (``StreamSliceController.observe``). ``nq`` counts the
        slice's device positions; under a schema with gates ``gate`` (a
        ``gates.GateSlice``) turns their answers into those of the slice's
        checks, which are what ``offset`` and ``chunk`` count."""
        lockstep = self._lockstep_verify
        if lockstep:
            from keto_tpu.parallel.lockstep import verify_lockstep
        clk = dispatch_clock()
        off = 0
        while True:
            rung = ctrl.cap() if ctrl is not None else bound
            cap = min(bound, rung)
            clk.enter(RESOLVE)  # pulling the caller's tuples is part of it
            batch = take(cap)
            if not batch:
                return
            if ctrl is not None:
                ctrl.count_take(rung, bound)
            if lockstep:
                # per stream slice, BEFORE any dispatch (same contract
                # as batch_check_with_token): divergence fails loudly
                batch = as_tuples(batch, "lockstep")
                verify_lockstep(
                    snap.snapshot_id, batch, shards=self._shard_count
                )
            if snap.n_nodes == 0 or snap.n_edges == 0:
                yield (
                    off, None, np.zeros(len(batch), dtype=bool),
                    len(batch), batch, [], 0, False, None,
                )
                off += len(batch)
                continue
            full = len(batch) >= cap
            for dev, host_ans, nq, chunk, leases, n_ent, gate in (
                self._dispatch_slices(snap, batch, it_cap=it_cap)
            ):
                yield off, dev, host_ans, nq, chunk, leases, n_ent, full, gate
                off += nq if gate is None else gate.n_checks

    def _stream(
        self, snap, tuples_iter, *, depth, slice_cap, ordered,
        with_info: bool = False, launch_mark: bool = False,
        it_cap: Optional[int] = None,
    ):
        """The one way a slice is launched and landed: ``_slices`` keeps up
        to ``depth`` of them on the device, ``land`` unpacks each as its
        transfer completes (ready order), and the decisions are yielded in
        request order (``ordered``) or as ``(offset, decisions[, info])``
        the moment they land. The generator returns the most pulls any
        slice took, for the caller's ``_after_batch``."""
        depth = depth or self._dispatch_window
        bound = self._slice_cap(snap)
        if slice_cap:
            bound = min(bound, slice_cap)
        # multi-controller lockstep: every host must dispatch identical
        # slice geometries, and adaptive widths are a per-host latency
        # measurement — pin the deterministic fixed bound instead
        ctrl = None if self._multiprocess else self.stream_ctrl
        stats = self.stream_slice_stats
        # a source that cuts its own slices (the batcher's round: ranges
        # of items, framed or not) hands over up to ``cap`` queries a call,
        # as a list or a QueryBatch; any other iterable is pulled per tuple
        take = getattr(tuples_iter, "take", None)
        if take is None:
            it = iter(tuples_iter)

            def take(cap):
                return list(itertools.islice(it, cap))

        max_iters = 0
        t_prev_ready = time.perf_counter()
        # the calling thread's state clock: the batcher's collector has
        # one, every other caller gets the no-op
        clk = dispatch_clock()

        def land(rec):
            # unpack one slice (blocks iff its transfer hasn't finished);
            # a truncated frontier re-runs exactly, mid-stream
            nonlocal max_iters, t_prev_ready
            _seq, off, dev, host_ans, nq, chunk, leases, n_ent, full, gate, t_disp = rec
            clk.enter(DEVICE_WAIT)
            try:
                out, iters, truncated = self._unpack_slice(dev, host_ans, nq)
            finally:
                # the device output is fetched (or the slice failed and
                # will be re-answered elsewhere): the H2D staging copy is
                # over, the buffers may be re-leased
                self._stage_release(leases)
            if gate is not None:
                clk.gates(gate.n_gated, nq)
            clk.enter(FILL)
            if gate is not None and not truncated:
                # positions to checks; a truncated slice is re-run by its checks
                t_gate = time.perf_counter()
                out = gate.combine(out)
                self.maintenance.incr("gate_seconds_combine", by=time.perf_counter() - t_gate)
            if dev is not None and not (
                isinstance(dev, _HybridSlice) and dev.bfs_dev is None
            ):
                self._note_bfs_steps(iters, getattr(dev, "bfs_words", 0))
            if truncated:
                # the cap that can NEVER truncate: monotone bitmaps reach the
                # fixpoint in at most one pull per active row (each growing
                # pull sets ≥ 1 new bit in some active row), +1 for the
                # convergence observation
                cap, limit = it_cap or self._it_cap, snap.num_active + 1
                if cap >= limit:
                    # mathematically unreachable; fail loudly rather than
                    # return a possibly-wrong deny
                    raise RuntimeError(
                        f"BFS truncated at the fixpoint bound (cap={cap}, "
                        f"active rows={snap.num_active})"
                    )
                new_cap = min(max(cap * 8, 8), limit)
                _log.info(
                    "check BFS hit it_cap=%d; re-running %d queries exactly at cap=%d",
                    cap, nq, new_cap,
                )
                out, redo_iters = self._run_exact(
                    snap, as_tuples(chunk, "truncated"), it_cap=new_cap
                )
                iters = max(iters, redo_iters)
                clk.enter(FILL)  # the re-run moved the clock through a round of its own
            max_iters = max(max_iters, iters)
            # the service time attributable to THIS slice: dispatch→ready
            # when the pipeline ran dry, ready→ready interval when
            # saturated (both equal the caller-visible inter-yield gap).
            # A slice that sat ready while the caller was away on its next
            # round was served by the time the clock's probe first saw it
            # so, not by the time the thread came back for it
            end = seen.pop(_seq, None) or time.perf_counter()
            ms = max(0.0, end - max(t_disp, t_prev_ready)) * 1e3
            t_prev_ready = max(t_prev_ready, end)
            stats.observe(ms)
            if dev is None:
                route = "host"
            elif isinstance(dev, _HybridSlice):
                route = (
                    "label" if dev.bfs_dev is None
                    else "bfs" if dev.label_dev is None else "hybrid"
                )
            else:
                route = "bfs"
            if ctrl is not None:
                ctrl.observe(
                    nq, ms, route=route, bfs_steps=int(iters), entries=n_ent,
                    full_take=full,
                )
            self._note_route(route, nq, ms)
            if not truncated:  # the re-run's own slices were sampled as they landed
                self._audit_sample(chunk, out, snap.snapshot_id)
            if not with_info:
                return off, out
            # per-slice route/cost description for request timelines:
            # which kernel answered and what it did (the stats words the
            # kernels already carry, threaded per request instead of
            # summed into counters)
            info = {
                "width": nq,
                "bfs_steps": int(iters),
                "route": route,
                "service_ms": round(ms, 3),
            }
            halo_src = None
            if isinstance(dev, _ShardedSlice):
                halo_src = dev
            elif isinstance(dev, _HybridSlice) and isinstance(
                dev.bfs_dev, _ShardedSlice
            ):
                halo_src = dev.bfs_dev
            if halo_src is not None:
                # one frontier all-gather per real BFS hop: rounds ==
                # the slice's iteration count, bytes == rounds x the
                # per-round slab cost the dispatch recorded
                info["halo_rounds"] = int(iters)
                info["halo_bytes"] = int(iters) * halo_src.halo_bytes_per_round
            return off, out, info

        #: seq -> when a slice in flight was first seen ready, noted at the
        #: clock's transitions while the caller holds the launch mark
        seen: dict[int, float] = {}
        away = False

        def seen_ready(now):
            if not away:
                return True
            for rec in inflight:
                if rec[0] not in seen and self._slice_ready(rec[2]):
                    seen[rec[0]] = now
            return len(seen) == len(inflight)

        src = self._slices(snap, take, bound, ctrl, it_cap)
        exhausted = False
        marked = not launch_mark
        inflight: list = []
        done: dict[int, tuple[int, np.ndarray]] = {}  # landed, awaiting in-order yield
        seq = 0
        next_seq = 0
        try:
            while True:
                # keep the dispatch window full: resolve/pack/dispatch is host
                # work that overlaps device execution of every in-flight slice
                while not exhausted and len(inflight) < depth:
                    nxt = next(src, None)
                    if nxt is None:
                        exhausted = True
                        break
                    off, dev, host_ans, nq, chunk, leases, n_ent, full, gate = nxt
                    if dev is not None:
                        dev.copy_to_host_async()
                    inflight.append((
                        seq, off, dev, host_ans, nq, chunk, leases, n_ent,
                        full, gate, time.perf_counter(),
                    ))
                    seq += 1
                if not inflight and exhausted:
                    break
                if exhausted and not marked:
                    # launched, not landed: the caller's turn
                    marked = away = True
                    clk.watch(seen_ready)
                    try:
                        yield None
                    finally:
                        away = False
                # ready-order landing: every finished slice unpacks now — an
                # early finisher never waits behind a straggler's transfer
                progressed = False
                still = []
                for rec in inflight:
                    if self._slice_ready(rec[2]):
                        res = land(rec)
                        if ordered:
                            done[rec[0]] = res
                        else:
                            yield res
                        progressed = True
                    else:
                        still.append(rec)
                inflight = still
                if ordered:
                    while next_seq in done:
                        yield done.pop(next_seq)[1]
                        next_seq += 1
                if not progressed and inflight and (exhausted or len(inflight) >= depth):
                    # nothing ready and the window is full (or input is done):
                    # block on the oldest slice — in ordered mode it is the
                    # next to deliver anyway
                    rec = inflight.pop(0)
                    res = land(rec)
                    if ordered:
                        done[rec[0]] = res
                        while next_seq in done:
                            yield done.pop(next_seq)[1]
                            next_seq += 1
                    else:
                        yield res
        finally:
            # a failed or abandoned stream discards its in-flight
            # outputs (the CPU fallback re-answers) — their staging
            # buffers may recycle (_stage_release empties each lease
            # list, so a record whose land() already released is a no-op
            # here)
            for rec in inflight:
                self._stage_release(rec[6])
        return max_iters

    def _slice_cap(self, snap: GraphSnapshot) -> int:
        """Queries per device slice: the widest bitmap the workspace budget
        allows (~3 W-wide uint32 bitmaps over interior rows — huge graphs
        narrow the batch width before the default max_batch could overshoot
        HBM)."""
        widths = self._word_widths()
        w_cap = next(
            (
                w
                for w in reversed(widths)
                if (snap.num_int + 1) * 12 * w <= self._mem_budget
            ),
            widths[0],
        )
        return min(self._max_batch, 32 * w_cap)

    def _entry_counts(
        self, snap: GraphSnapshot, sd: np.ndarray, tg: np.ndarray, multi: dict
    ) -> np.ndarray:
        """Per-query device entry counts (seeds + answer gathers) of a
        resolved slice — the scatter/gather work a query adds to a kernel.
        Host-propagated starts are estimated at one hop of out-degree (the
        peeled closure is not walked here; this only balances sub-chunk
        boundaries)."""
        n = sd.shape[0]
        ni = snap.num_int
        sbase = snap.sink_base
        nl = snap.num_live
        ip = snap.fwd_indptr
        sp_ = snap.sink_indptr
        cnt = np.zeros(n, np.int64)
        m_int = (sd >= 0) & (sd < ni)
        cnt[m_int] = 1
        m_host = ((sd >= ni) & (sd < sbase)) | (sd >= nl)
        if m_host.any():
            s = sd[m_host]
            in_b = s < snap.n_base_nodes
            c = np.ones(s.shape[0], np.int64)  # overlay adjacency ≈ small
            sb_ = s[in_b]
            c[in_b] = ip[sb_ + 1] - ip[sb_]
            cnt[m_host] = c
        has_start = m_int | m_host
        for i, (live, hostp) in multi.items():
            cnt[i] = live.size + hostp.size
            has_start[i] = live.size > 0 or hostp.size > 0
        m_ans = has_start & (tg >= sbase) & (tg < nl)
        if m_ans.any():
            t = tg[m_ans] - sbase
            rows = sp_[t + 1] - sp_[t]
            if hub_usable(snap):
                # a hub sink sends its relay rows, not its rows
                relays = snap.hub_ptr[t + 1] - snap.hub_ptr[t]
                rows = np.where(relays > 0, relays, rows)
            cnt[m_ans] += rows
        return cnt

    @staticmethod
    def _device_reach(snap: GraphSnapshot) -> Optional[np.ndarray]:
        """``bool[num_live]``: can the device add anything to the answer of
        a query with this target - an active interior row, or a sink that
        gathers its answer from one. Worked out once a snapshot; None where
        overlay edges into sinks would have to be counted too."""
        if snap.ov_sink_in or snap.sink_indptr is None:
            return None
        reach = getattr(snap, "_device_reach_of", None)
        if reach is None:
            reach = np.zeros(snap.num_live, bool)
            reach[: snap.num_active] = True
            sink_of = np.repeat(
                np.arange(snap.sink_indptr.shape[0] - 1), np.diff(snap.sink_indptr)
            )
            reach[snap.sink_base + sink_of[snap.sink_indices < snap.num_active]] = True
            snap._device_reach_of = reach
        return reach

    def _dispatch_slices(
        self,
        snap: GraphSnapshot,
        tuples,
        it_cap: Optional[int] = None,
    ):
        """Resolve + pack + dispatch ``tuples`` (a list of
        ``RelationTuple`` or a ``QueryBatch``; both are cut by range, never
        walked) in ``_slice_cap`` query slices, yielding one record as
        each slice is enqueued (the device chews on earlier slices
        meanwhile).

        A slice whose resolved fan-out exceeds the entry budget (wildcard
        patterns, high-out-degree static starts) is sub-chunked so entry
        arrays stay within the {B, 2B, 4B} pad geometries — workload can't
        force unbounded allocations or fresh kernel geometries (a single
        monster query still falls through to ``_entry_pad``'s pow2
        fallback; there is no smaller unit to split). On a snapshot of hub
        rows (``_riders_expected``) with the label route live the bound is
        the pair cap times B: that route cuts a chunk by pairs and hands
        ``check_step`` only the riders, whose entries ``device_part`` packs
        narrow, so a take is one hybrid slice and not one per 4·B entries;
        ``warm_compile`` has left rungs for it. The budget is
        the smaller of that geometric bound and the slice controller's
        PREDICTED-service-time budget (``entry_budget``): a chunk the
        model predicts slow splits BEFORE dispatch, and the stream's
        ready-order window interleaves its sub-slices with fast ones —
        the pre-dispatch half of the slice-tail control loop.

        Yields ``[dev | None, host_ans, nq, chunk_tuples, leases,
        n_entries, gate]``: ``chunk_tuples`` lets a truncated slice re-run,
        ``leases`` are staging buffers released only once the slice has
        landed, ``n_entries`` feeds the controller's entry-cost model.

        Under a schema with gates (keto_tpu/check/gates.py) a resolved
        chunk's checks become positions first: a gated check is its own row
        and one an operand of every gate it reaches, side by side. From
        there on ``nq``, the width and the entry counts are of positions; a
        cut falls between checks, and ``gate`` (None where the chunk has no
        gated check) gives the slice's checks their answers when it lands."""
        cap_q = self._slice_cap(snap)
        n = len(tuples)
        clk = dispatch_clock()
        for s0 in range(0, n, cap_q):
            s1 = min(s0 + cap_q, n)
            clk.enter(RESOLVE)
            chunk = tuples[s0:s1]
            sd, tg, multi, closure, sums, view = self._resolve_chunk(snap, chunk)
            oracle_ans = exp = None
            if snap.rewrites is not None:
                oracle_ans, exp = self._rewrite_split(snap, chunk, sd, tg, multi, cap_q, closure)
            clk.poll()
            if exp is None:
                pieces = [(0, s1 - s0)]
            else:
                # the chunk as positions: the oracle's answers and the
                # patterns' starts move to their checks' own positions
                sd, tg = exp.sd, exp.tg
                multi = {int(exp.self_pos[i]): starts for i, starts in multi.items()}
                if oracle_ans is not None:
                    at = np.flatnonzero(oracle_ans)
                    oracle_ans = np.zeros(sd.shape[0], bool)
                    oracle_ans[exp.self_pos[at]] = True
                ptr, pieces, c0 = exp.pos_ptr, [], 0
                while c0 < s1 - s0:
                    c1 = exp.check_at(int(ptr[c0]) + cap_q, c0)
                    pieces.append((int(ptr[c0]), int(ptr[c1])))
                    c0 = c1
            use_labels = self._labels_usable(snap)
            if sums is None:
                # the numpy path's rows, or the positions under a plan with
                # gates (the split may have unset targets, too)
                if view is None:
                    sums = self._entry_sums(snap, sd, tg, multi)
                else:
                    sums = native_pack.entry_sums(view, sd, tg)
            for q0, q1 in pieces:
                yield from self._dispatch_piece(
                    snap, tuples, s0, sd, tg, multi, sums, q0, q1, exp, oracle_ans,
                    use_labels, it_cap,
                )

    def _dispatch_piece(
        self, snap, tuples, s0, sd, tg, multi, sums, q0, q1, exp, oracle_ans,
        use_labels, it_cap,
    ):
        """Positions ``[q0, q1)`` of a resolved chunk (at most a slice's
        width of them) as one slice, or as several where their entries pass
        the budget (``_dispatch_slices``). ``sums``: the chunk's
        ``_entry_sums``; a cut is a bisect over them."""
        nq = q1 - q0
        W = next(w for w in _WORD_WIDTHS if 32 * w >= nq)
        B = 32 * W
        cap_e = 4 * B
        if use_labels and self._riders_expected(snap):
            # hub rows: the label route cuts a chunk by pairs, at most
            # the pair cap a query, and sends check_step the riders
            # alone, entries packed narrow (``device_part``). A chunk may
            # bring that many entries a query before it is split, or a
            # take would be a dozen hybrid slices of two launches each
            cap_e = self._LABEL_PAIR_CAP * B
        cap_geo = cap_e
        if not self._multiprocess:
            # service-time-aware split bound (never below a quarter of
            # the geometric bound, one B where that is 4·B — the floor
            # keeps slice counts bounded: what a slice costs whatever
            # it carries, its pulls, is paid again by every piece)
            budget = self.stream_ctrl.entry_budget()
            if budget is not None:
                cap_e = min(cap_e, max(cap_e // 4, budget))
        csum = sums[0]
        total = int(csum[q1] - csum[q0])
        if total > cap_e:
            # a query whose target side has no row that a pull changes
            # sends the device nothing (``device_part``): its entries do
            # not count towards a split
            csum = sums[1]
            total = int(csum[q1] - csum[q0])
        if total <= cap_e:
            bounds = [(q0, q1)]
        else:
            seg = csum[q0 : q1 + 1]
            bounds = []
            i0 = 0
            while i0 < nq:
                i1 = int(np.searchsorted(seg, seg[i0] + cap_e, side="right")) - 1
                i1 = max(i0 + 1, min(i1, nq))
                if exp is not None and i1 < nq:
                    # between two checks: a check's positions land together
                    c0 = int(np.searchsorted(exp.pos_ptr, q0 + i0, side="right")) - 1
                    i1 = int(exp.pos_ptr[exp.check_at(q0 + i1, c0)]) - q0
                bounds.append((q0 + i0, q0 + i1))
                i0 = i1
        # what cut the chunk: its entries pass the geometric bound, or
        # only the cap the controller's entry budget lowered
        cut = "none" if len(bounds) == 1 else (
            "geometry" if total > cap_geo else "budget"
        )
        self.maintenance.incr(f"stream_chunks_{cut}")
        self.maintenance.incr("stream_chunk_pieces", by=len(bounds))
        for a, b in bounds:
            # sub-chunks keep the slice width: queries pad, geometry stays
            if use_labels:
                dev, host_ans, leases = self._device_batch_labeled(
                    snap, sd, tg, multi, a, b, W, it_cap=it_cap
                )
            else:
                dev, host_ans, leases = self._device_batch(
                    snap, sd, tg, multi, a, b, W, it_cap=it_cap
                )
            if oracle_ans is not None:
                # the oracle's answers land as the host's own do
                host_ans[: b - a] |= oracle_ans[a:b]
            gate, ca, cb = None, a, b
            if exp is not None:
                ca, cb = (int(np.searchsorted(exp.pos_ptr, x)) for x in (a, b))
                gate = exp.cut(ca, cb)
            yield [
                dev, host_ans, b - a, tuples[s0 + ca : s0 + cb],
                leases, int(csum[b] - csum[a]), gate,
            ]

    def _rewrite_split(
        self, snap: GraphSnapshot, queries, sd, tg, multi, cap_q: Optional[int] = None,
        closure: Optional[tuple] = None,
    ):
        """Under a rewrite schema: count a resolved batch by closure
        (``keto_check_rewrite_checks_total``) from the snapshot's one byte a
        device row and, where the plan has gates, hand it to
        ``gates.split``: the checks whose closure reaches an intersection or
        an exclusion become several device positions each, or are answered
        by the CPU oracle where the expansion does not reach (their rows are
        then unset so that pack sends the device nothing for them). Returns
        ``(the oracle's answers as bool[n] or None, a gates.Expansion or
        None)``. A wildcard pattern (its starts in ``multi``) carries the
        bits of every start and of the relation it names. ``closure``: the
        closure bytes and how many hold ``REWRITTEN``, where ``resolve``'s
        native pass already read them (a chunk it took has no pattern and no
        start past the plan's rows)."""
        plan = snap.rewrites
        n = sd.shape[0]
        if closure is None:
            f = self._closure_bytes(snap, queries, sd, multi)
            closure = f, int(np.count_nonzero(f & REWRITTEN))
        f, rewritten = closure
        incr = self.maintenance.incr
        incr("rewrite_checks_rewritten", by=rewritten)
        incr("rewrite_checks_plain", by=n - rewritten)
        if not plan.has_gated:
            incr("rewrite_route_device", by=rewritten)
            return None, None
        return gates.split(self, snap, queries, sd, tg, multi, f, cap_q or self._slice_cap(snap))

    def _closure_bytes(self, snap: GraphSnapshot, queries, sd, multi) -> np.ndarray:
        """``uint8[n]``: the closure bits of each resolved query's start
        (``RewritePlan.flags_of``), the numpy form."""
        plan = snap.rewrites
        n = sd.shape[0]
        flags = plan.flags_of(snap)

        def bits_of(dev: int) -> int:
            if dev < flags.shape[0]:
                return int(flags[dev])
            kind, key = snap.key_of_dev(dev)  # a start the overlay brought
            return plan.relation_flags(key[0], key[2]) if kind == "set" else 0

        f = np.zeros(n, np.uint8)
        in_base = (sd >= 0) & (sd < flags.shape[0])
        f[in_base] = flags[sd[in_base]]
        for i in np.flatnonzero(sd >= flags.shape[0]).tolist():
            f[i] = bits_of(int(sd[i]))
        if multi:
            ns_of = self._ns_resolver()
            at = sorted(multi)
            for i, rt in zip(at, pick_tuples(queries, at, "rewrite")):
                ns_id = ns_of(rt.namespace)
                bits = plan.relation_flags(ns_id, rt.relation) if isinstance(ns_id, int) else 0
                for starts in multi[i]:
                    for dev in np.asarray(starts).tolist():
                        bits |= bits_of(int(dev))
                f[i] = bits
        return f

    @staticmethod
    def _decode_packed(f: np.ndarray, host_ans: np.ndarray, nq: int):
        """Decode one kernel's packed ``uint32[W+2]`` output (decision
        bits, iteration count, truncation flag — the single place that
        knows the layout check_step emits): device bits ∪ host-decided
        grants. Returns ``(bool[nq], iters, truncated)``."""
        W = f.shape[0] - 2
        return _bits(f[:W], nq) | host_ans[:nq], int(f[W]), bool(f[W + 1])

    def _unpack_slice(self, dev, host_ans, nq):
        """Fetch and decode one slice's decisions. Returns ``(bool[nq],
        iters, truncated)``; a sharded output also feeds the keto_shard_*
        counters (one halo exchange per real hop)."""
        if dev is None:
            return host_ans[:nq], 0, False
        if isinstance(dev, _HybridSlice):
            # label bits for the whole slice, the BFS sub-batch's bits
            # scattered onto its positions; only the BFS part can truncate
            out = (
                np.zeros(nq, bool) if dev.label_dev is None
                else _bits(jax.device_get(dev.label_dev), nq)
            )
            iters, trunc = 0, False
            if dev.bfs_dev is not None:
                pos = dev.bfs_pos
                out[pos], iters, trunc = self._unpack_slice(
                    dev.bfs_dev, host_ans[pos], pos.size
                )
            return out | host_ans[:nq], iters, trunc
        if isinstance(dev, _ShardedSlice):
            # the sharded kernel's packed uint32[W+3]: decision bits,
            # iterations, truncation, frontier-bit population
            # (keto_tpu/parallel/sharded.py)
            f = jax.device_get(dev.dev)
            W = f.shape[0] - 3
            self._note_sharded_stats(int(f[W]), int(f[W + 2]), dev.halo_bytes_per_round)
            return _bits(f[:W], nq) | host_ans[:nq], int(f[W]), bool(f[W + 1])
        return self._decode_packed(jax.device_get(dev), host_ans, nq)

    def _note_bfs_steps(self, iters: int, words: int = 0) -> None:
        """One landed slice that ran ``check_step``: its pulls, for bench's
        percentiles and for ``keto_check_bfs_steps_total`` /
        ``keto_check_bfs_slices_total``, and its pulls times the ``words``
        of a bitmap row in the program that ran them
        (``keto_check_pull_words_total``: times the ELL's slots and 4, the
        bytes its pulls gathered)."""
        self.bfs_steps_stats.observe(float(iters))
        self.maintenance.incr("bfs_slices")
        self.maintenance.incr(f"sweep_slices_{self._sweep}")
        if iters:
            self.maintenance.incr("bfs_steps", by=int(iters))
            if words:
                self.maintenance.incr("bfs_pull_words", by=int(iters) * words)

    def _note_packed(self, snap: GraphSnapshot, packed, nq: int) -> None:
        """One chunk went through ``pack_chunk``: its checks, and the rows
        either side of them (``keto_check_packed_total``,
        ``keto_check_pack_rows_total{side}``). Seed rows are the e1 and e2
        entries; target-side rows are the interior targets and the rows the
        sinks' answers are gathered from."""
        self.maintenance.incr("packed_checks", by=nq)
        if packed is None:
            return
        ni = snap.num_int
        e1r, _, e2r, _, ar, _, targets = packed
        seeds = np.count_nonzero(e1r != ni + 1) + np.count_nonzero(e2r != ni + 1)
        rows = np.count_nonzero(ar < ni) + np.count_nonzero(targets[:nq] < ni)
        if snap.hub_rows is not None:
            # a relay row of a hub sink stands for the rows it holds
            rows += int(snap.hub_rows[ar[ar > ni] - (ni + 1)].sum())
        self.maintenance.incr("pack_rows_seed", by=int(seeds))
        self.maintenance.incr("pack_rows_target", by=int(rows))

    def _note_route(self, route: str, nq: int, ms: float) -> None:
        """Record one landed slice's route (label | hybrid | bfs | host |
        cpu) for the per-route breakdown bench and
        ``keto_stream_route_slices_total`` read."""
        st = self._route_stats.get(route)
        if st is None:
            st = self._route_stats.setdefault(route, DurationStats())
        st.observe(ms)
        self.route_slices[route] += 1
        self._route_queries[route] += nq

    def stream_route_snapshot(self) -> dict:
        """Per-route stream breakdown: slice/query counts and service-
        time percentiles per route since the last ``reset_route_stats``
        (bench's per-route table; the metrics bridge reads the raw
        counters)."""
        out = {}
        for route, st in list(self._route_stats.items()):
            snap = st.snapshot()
            out[route] = {
                "slices": int(self.route_slices.get(route, 0)),
                "queries": int(self._route_queries.get(route, 0)),
                "p50_ms": snap["p50_ms"],
                "p99_ms": snap["p99_ms"],
                "mean_ms": snap["mean_ms"],
            }
        return out

    def reset_route_stats(self) -> None:
        """Zero the per-route breakdown (bench passes start fresh)."""
        self._route_stats.clear()
        self.route_slices.clear()
        self._route_queries.clear()

    def _after_batch(self, max_iters: int, snap: GraphSnapshot) -> None:
        # adapt the pull-block size so deep workloads converge within few
        # convergence observations. Grow-only: block_iters is a static jit
        # argname, so shrinking it would recompile every kernel geometry for
        # a marginal saving (converged pulls inside a block are lax.cond
        # no-ops) — growing pays one recompile to cut while-loop trips.
        # Not on the shape warm_compile settled it for: growing it there
        # would recompile, on the serving thread, every program it warmed.
        # A snapshot of another shape compiles its programs anyway.
        if snap.device_buckets is not None and self._block_iters_shape == self._check_shape(snap):
            return
        want = min(32, _ceil_pow2(max_iters + 1))
        if want > self._block_iters:
            self._block_iters = want

    #: per-query pair-fanout cap on the label path: a query spawning more
    #: pairs than this (huge sink in-degree × wildcardish seed sets)
    #: costs more as intersections than as one more BFS rider
    _LABEL_PAIR_CAP = 64

    def _fused_decline(self, snap: GraphSnapshot, multi: dict, i0: int, i1: int):
        """Why the label route's one native pass (``native_pack.pack_labeled``)
        cannot pack chunk ``[i0, i1)``, or None where it can: from what the
        code observes, never a setting. ``keto_check_pack_declines_total``."""
        if not native_pack.available():
            return "no_library"
        if self._mesh is not None:
            return "mesh"  # the sharded kernels route a chunk's entries themselves
        if not native_pack.walk_eligible(snap):
            return "overlay"
        if multi and any(i0 <= i < i1 for i in multi):
            return "multi"  # wildcard and multi-start queries: several starts a query
        return None

    def _device_batch_labeled(
        self,
        snap: GraphSnapshot,
        sd: np.ndarray,
        tg: np.ndarray,
        multi: dict,
        i0: int,
        i1: int,
        W: int,
        it_cap: Optional[int] = None,
    ):
        """The label fast path for one sub-chunk: resolve the chunk on the
        host as the BFS path does (host walk, sink gathers, host-decided
        grants), then answer every label-certifiable query with ONE
        intersection kernel step and ride the rest on a compacted BFS
        sub-batch, bit-identically.

        The host part is one GIL-released native pass wherever nothing
        declines it (``_fused_decline``): ``native_pack.pack_labeled`` walks,
        routes and pairs query by query and writes the pairs straight into
        the staging lease. A declined chunk takes ``pack_chunk`` and
        ``label_pairs`` (keto_tpu/check/pack.py): the same answers by the
        same routes for the same reasons, and what the pass is fuzzed
        against. The reach0 mapping and the reasons a query leaves the label
        route are ``label_pairs``'s docstring (keto_tpu/graph/labels.py for
        reach0 itself).
        """
        idx = snap.labels
        if idx is None or self._labels_dev(snap) is None:
            # the eviction ladder dropped the labels between routing and
            # dispatch (concurrent OOM containment): BFS answers instead
            return self._device_batch(snap, sd, tg, multi, i0, i1, W, it_cap=it_cap)
        clk = dispatch_clock()
        clk.enter(PACK)
        nq = i1 - i0
        ni = snap.num_int
        B = 32 * W
        leases: list = []
        declined = self._fused_decline(snap, multi, i0, i1)
        if declined is None:
            view, hub = self._pack_view, hub_usable(snap)
            if view is None or not view.of(snap, idx, hub):
                view = self._pack_view = native_pack.PackView(
                    snap, idx, self._LABEL_PAIR_CAP, hub
                )
            host_ans, fallback, counts = native_pack.pack_labeled(
                view, sd, tg, i0, i1, whole_min(W)
            )
            clk.poll()
            self.maintenance.incr("packed_checks", by=nq)
            if not counts.packed:
                return None, host_ans, leases  # nothing reaches any device path
            self.maintenance.incr("pack_rows_seed", by=counts.seed_rows)
            self.maintenance.incr("pack_rows_target", by=counts.target_rows)
            n_pairs, n_fb, whole = counts.pairs, counts.fallbacks, bool(counts.whole)
            reasons = {r: getattr(counts, r) for r in LABEL_REASONS}
            # the riders' entries alone: the label kernel has the others
            packed = native_pack.labeled_riders(counts, B) if n_fb else None
            write_pairs = native_pack.labeled_pairs
        else:
            self.maintenance.incr(f"pack_declines_{declined}")
            packed, host_ans = pack_chunk(snap, sd, tg, multi, i0, i1, W)
            clk.poll()
            self._note_packed(snap, packed, nq)
            if packed is None:
                return None, host_ans, leases  # nothing reaches any device path
            fallback, reasons, whole, pairs = label_pairs(
                snap, idx, packed, multi, i0, i1, W, self._LABEL_PAIR_CAP
            )
            n_pairs, n_fb = int(pairs[0].size), int(np.count_nonzero(fallback))

            def write_pairs(entries, P):
                put_pairs(entries, P, pairs, ni)

        self.maintenance.incr("label_checks", by=nq - n_fb)
        if n_fb:
            self.maintenance.incr("label_fallbacks", by=n_fb)
            for reason, count in reasons.items():
                if count:
                    self.maintenance.incr(f"label_fallbacks_{reason}", by=count)
        if whole:
            faults.check("device-exec")
            dev, leases = self._launch_check(snap, packed, host_ans, it_cap, "bfs")
            return dev, host_ans, leases

        ldev = None
        if n_pairs:
            faults.check("device-exec")
            P = _entry_pad(B, n_pairs)
            dl = self._labels_dev(snap)
            lmet = None
            if self._mesh is None:
                own = (P, B)
                lshape, lfixed = self._label_shape(dl), self._label_fixed()
                (P, B), lmet = self.geoms.meet("label", lshape, lfixed, own)
            stg = self._stage_acquire(3 * P) if self._mesh is None else None
            if stg is not None:
                leases.append(stg)
            entries = np.empty(3 * P, np.int32) if stg is None else stg
            write_pairs(entries, P)
            clk.enter(
                LAUNCH, ("hybrid" if n_fb else "label", "label_step", (P, B), lmet)
            )
            if self._sharded:
                # row-sharded label arrays + replicated pairs: the kernel
                # does the one-shot pair-row exchange internally
                from keto_tpu.parallel import sharded as shard_mod

                ebuf = jax.device_put(entries, self._shard_repl_sharding)
                ldev = self._guard_alloc(
                    "label-kernel",
                    lambda: shard_mod.label_kernel(self._mesh)(
                        dl[0], dl[1], ebuf, n_pairs=P, B=B, rl=dl[2]
                    ),
                )
            else:
                lkern = self._entry_kernels()[1]
                ldev = self._guard_alloc(
                    "label-kernel",
                    lambda: lkern(dl[0], dl[1], self._put(entries), n_pairs=P, B=B),
                )
                if lmet == INLINE:
                    self.geoms.add("label", lshape, lfixed, own)

        sub = None  # the riders' slice
        if n_fb and self._sharded and snap.device_shards is not None:
            # the sharded kernel takes a chunk whole: the riders are packed
            # again as a chunk of their own
            pos = np.nonzero(fallback)[0]
            gidx = pos + i0
            multi2 = {
                j: multi[int(i)] for j, i in enumerate(gidx) if int(i) in multi
            }
            W2 = next(w for w in _WORD_WIDTHS if 32 * w >= n_fb)
            clk.enter(PACK)
            packed2, host2 = pack_chunk(snap, sd[gidx], tg[gidx], multi2, 0, n_fb, W2)
            if packed2 is not None:
                faults.check("device-exec")
                sub, bfs_leases = self._launch_check(
                    snap, packed2, host2, it_cap, "hybrid", sub_of=pos
                )
                leases.extend(bfs_leases)
                host_ans[pos] |= host2  # what the host granted without the device
        elif n_fb:
            # ``device_part`` cuts the chunk's own entries down to the
            # riders that need the device: nothing is walked or gathered twice
            clk.enter(PACK)
            faults.check("device-exec")
            sub, bfs_leases = self._launch_check(
                snap, packed, host_ans, it_cap, "hybrid", only=fallback
            )
            leases.extend(bfs_leases)
        if sub is None:
            return (None if ldev is None else _HybridSlice(ldev)), host_ans, leases
        sub.label_dev = ldev
        return sub, host_ans, leases

    def _device_batch(
        self,
        snap: GraphSnapshot,
        sd: np.ndarray,
        tg: np.ndarray,
        multi: dict,
        i0: int,
        i1: int,
        force_W: Optional[int] = None,
        it_cap: Optional[int] = None,
    ):
        """Pack + dispatch one sub-chunk. Returns ``(dev, host_ans,
        leases)`` — ``leases`` are pooled staging buffers the caller MUST
        release only after the slice lands (``_stage_release``): the H2D
        copy may complete asynchronously, so earlier reuse could corrupt
        an in-flight slice. On the single-device path a chunk whose own
        program is not compiled is padded up to one that is
        (keto_tpu/check/geometry.py)."""
        faults.check("device-exec")
        dispatch_clock().enter(PACK)
        packed, host_ans = pack_chunk(snap, sd, tg, multi, i0, i1, force_W)
        self._note_packed(snap, packed, i1 - i0)
        if packed is None:
            # no query in the chunk reaches the device: host_ans is the
            # whole answer
            return None, host_ans, []
        dev, leases = self._launch_check(snap, packed, host_ans, it_cap, "bfs")
        return dev, host_ans, leases

    def _launch_check(
        self, snap: GraphSnapshot, packed, host_ans: np.ndarray,
        it_cap: Optional[int], route: str, sub_of: Optional[np.ndarray] = None,
        only: Optional[np.ndarray] = None,
    ):
        """Ship what of one packed chunk the device has to see
        (``device_part``: the rest is granted into ``host_ans`` here) to
        ``check_step``. Returns ``(slice, leases)``: a ``_HybridSlice`` of
        no label part whose BFS part answers the chunk's positions that
        needed the device, or None where none does. ``sub_of`` says where
        the chunk's own queries sit in a wider slice; ``only`` (single
        device) keeps the chunk's other queries off the device whatever
        they need: the label kernel has them."""
        clk = dispatch_clock()
        leases: list = []
        it_cap = it_cap or self._it_cap
        if self._sharded and snap.device_shards is not None:
            dev = self._dispatch_sharded(snap, packed, it_cap, leases=leases)
            return (dev if sub_of is None else _HybridSlice(None, dev, sub_of)), leases
        packed, pos = device_part(snap, packed, host_ans, only)
        if packed is None:
            return None, leases
        stg = met = None
        if self._mesh is None:
            own = tuple(packed[i].shape[0] for i in (0, 2, 4, 6))
            shape, fixed = self._check_shape(snap), self._check_fixed(it_cap)
            use, met = self.geoms.meet("check", shape, fixed, own)
            if use != own:
                packed = _pad_packed(packed, use, snap.num_int)
            stg = self._stage_acquire(sum(a.shape[0] for a in packed))
            if stg is not None:
                leases.append(stg)
        buf, sizes = pack_entries(packed, out=stg)
        clk.enter(LAUNCH, (route, "check_step", sizes, met))
        ov = snap.device_overlay
        kern = self._entry_kernels()[0]
        dev = self._guard_alloc(
            "check-kernel",
            # the put is inside the guarded call: the donated path consumes
            # its device buffer, so an OOM retry must re-stage from host
            lambda: kern(
                snap.device_buckets,
                self._put(buf),
                ov_nbrs=None if ov is None else ov[0],
                ov_dst=None if ov is None else ov[1],
                hub_nbrs=snap.device_hub,
                sizes=sizes,
                n_active=snap.num_active,
                n_int=snap.num_int,
                valid_rows=tuple(b.n for b in snap.buckets),
                it_cap=it_cap,
                block_iters=self._block_iters,
                bitmap_sharding=self._bitmap_sharding_for(sizes[3]),
                sweep=self._sweep,
            ),
        )
        if met == INLINE:
            self.geoms.add("check", shape, fixed, own)
        return _HybridSlice(None, dev, pos, sizes[3] // 32), leases

    def _dispatch_sharded(
        self, snap: GraphSnapshot, packed, it_cap: int, leases=None
    ):
        """Route one packed chunk's entries to their owning shards and
        launch the shard_map BFS kernel (keto_tpu/parallel/sharded.py).
        Returns a ``_ShardedSlice`` whose packed ``uint32[W+3]`` output
        ``_unpack_slice`` decodes — decisions bit-identical to the
        single-device kernel, plus the halo/frontier stats words. The
        routed entry stack stages through the same pooled-buffer seam as
        the single-device path (``leases`` collects the buffers for
        release at land time)."""
        from keto_tpu.parallel import sharded as shard_mod

        spec = snap.shard_spec
        B = packed[-1].shape[0]

        def out_alloc(shape):
            if leases is None or self._multiprocess:
                return None
            flat = self._stage_acquire(shape[0] * shape[1])
            if flat is None:
                return None
            leases.append(flat)
            return flat.reshape(shape)

        entries, sizes = shard_mod.route_entries(
            spec, packed, B, out_alloc=out_alloc
        )
        dispatch_clock().enter(LAUNCH)
        ebuf = jax.device_put(entries, self._shard_stack_sharding)
        ov = snap.device_shard_overlay
        dev = self._guard_alloc(
            "check-kernel",
            lambda: shard_mod.check_kernel(self._mesh)(
                snap.device_shards[0],
                snap.device_shards[1],
                ebuf,
                ov_nbrs=None if ov is None else ov[0],
                ov_dst=None if ov is None else ov[1],
                sizes=sizes,
                rps=spec.rows_per_shard,
                B=B,
                it_cap=it_cap,
                block_iters=self._block_iters,
            ),
        )
        return _ShardedSlice(
            dev, shard_mod.halo_bytes_per_round(spec, B // 32)
        )

    def _note_sharded_stats(self, iters: int, frontier_bits: int, halo_bytes_per_round: int) -> None:
        """Turn one sharded slice's tail words into the keto_shard_*
        counters: one halo exchange per real BFS hop."""
        m = self.maintenance
        if iters:
            m.incr("shard_halo_rounds", by=iters)
            m.incr("shard_halo_bytes", by=iters * halo_bytes_per_round)
        if frontier_bits:
            m.incr("shard_frontier_bits", by=frontier_bits)

