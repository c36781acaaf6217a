"""Batched check engine: multi-source bit-packed BFS on TPU.

Where the reference answers one ``Check`` by a recursive traversal issuing
one SQL query per subject-set node per page (reference
internal/check/engine.go:33-95), this engine answers **thousands of checks
in one device program**:

- up to 32·W queries are packed into a ``uint32[n_live+1, W]`` reached
  bitmap ``R`` — bit ``q%32`` of word ``q//32`` in row ``v`` means "query q
  has reached node v". Only nodes *with in-edges* ("live") get bitmap rows;
  zero-in-degree ("static") nodes never change and are handled by
  propagating their start bits one hop on the host at batch setup
  (``pack_chunk``), which both seeds ``R`` and pre-computes their
  contribution to the answer;
- one BFS step is a **pull**: ``P[v] = OR over live in-neighbors s of
  R[s]``, computed per degree bucket as a gather + OR-reduction over
  *live→live* edges only (see keto_tpu/graph/snapshot.py for the layout
  rationale). Rows that can change ("active") form a prefix of the bitmap;
  the loop updates them in place via an aliased carry — nothing the size of
  the full graph is ever copied per step;
- ``lax.while_loop`` iterates to the reachability fixpoint (the analog of
  the reference's visited-set cycle guard — monotone bitmaps make cycles
  terminate for free);
- the answer for query q is the target-row bit of ``pull(fixpoint) ∪
  one-hop-term``, i.e. "reached via ≥ 1 edge", reproducing the reference's
  rule that a subject only matches via an actual tuple, never by being the
  queried set itself. The fixpoint pull is carried out of the loop (the
  converging iteration already computed it) — no extra answer pass.

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
import os
import random
import threading
import time
from functools import partial
from typing import Callable, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from keto_tpu import namespace as namespace_pkg
from keto_tpu.check import native_pack
from keto_tpu.check.geometry import INLINE, KernelGeometries
from keto_tpu.check.frame import (
    DEAD, NO_TARGET, SPECIAL, QueryBatch, QueryFrame, as_tuples, pick_tuples,
)
from keto_tpu.driver.hbm import HbmGovernor, MemoryPressure, is_resource_exhausted
from keto_tpu.graph.snapshot import WILDCARD, GraphSnapshot
from keto_tpu.relationtuple.model import RelationTuple, SubjectID, SubjectSet
from keto_tpu.x import faults
from keto_tpu.x.errors import ErrNamespaceUnknown, KetoError
from keto_tpu.x.retry import retry_call
from keto_tpu.x.supervise import SupervisedTask
from keto_tpu.x.timeline import (
    DEVICE_WAIT, FILL, LAUNCH, PACK, RESOLVE, dispatch_clock,
)
from keto_tpu.x.telemetry import DurationStats, MaintenanceStats

_log = logging.getLogger("keto_tpu.check")

#: distinct-from-None cache sentinel for namespace resolution
_UNSET = object()
#: wildcard-namespace marker in the native resolve cache
_WILD = object()
#: native-format record whose result is overwritten on the Python side
_PLACEHOLDER = b"0\x1f\x1f\x1f1\x1f\x1f\x1f\x1e"

# batch widths (in 32-query words) the engine compiles for; a request is
# padded up to the smallest fitting width so jit caches stay small
_WORD_WIDTHS = (1, 8, 64, 256, 1024, 2048, 4096)
# cap on the [rows, chunk, W] gather intermediate per bucket
_DEGREE_CHUNK = 1024


def _pull(
    bucket_nbrs: Sequence[jnp.ndarray],
    bucket_valid_rows: Sequence[int],
    R: jnp.ndarray,
    row_of=None,
) -> jnp.ndarray:
    """One BFS pull step over the active rows.

    R: uint32[n_live+1, W] → uint32[n_active, W]. Buckets hold live→live
    edges and are contiguous in device-id order — concatenating per-bucket
    OR-reductions yields the active prefix with no scatter. ``row_of``
    maps a neighbor id to the row of ``R`` that holds it, where ``R`` is
    not the whole bitmap.
    """
    outs = []
    for nbrs, n_valid in zip(bucket_nbrs, bucket_valid_rows):
        n_pad, cap = nbrs.shape
        acc = None
        for c0 in range(0, cap, _DEGREE_CHUNK):
            idx = nbrs[:, c0 : c0 + _DEGREE_CHUNK]
            gathered = R[idx if row_of is None else row_of(idx)]  # [n_pad, chunk, W]
            part = lax.reduce(gathered, np.uint32(0), lax.bitwise_or, (1,))
            acc = part if acc is None else lax.bitwise_or(acc, part)
        outs.append(acc[:n_valid])
    return jnp.concatenate(outs, axis=0) if len(outs) > 1 else outs[0]


def check_step(
    bucket_nbrs: tuple[jnp.ndarray, ...],
    entries: jnp.ndarray,  # int32[2·S1+2·S2+2·SA+B] packed entry arrays
    ov_nbrs: Optional[jnp.ndarray] = None,  # int32[K, C] overlay-ELL gather
    ov_dst: Optional[jnp.ndarray] = None,  # int32[K] unique active rows (pad → n_active)
    *,
    sizes: tuple[int, int, int, int],  # (S1, S2, SA, B)
    n_active: int,
    n_int: int,
    valid_rows: tuple[int, ...],
    it_cap: int,
    block_iters: int = 8,
    bitmap_sharding=None,  # NamedSharding for the [rows, words] bitmaps
) -> jnp.ndarray:
    # ``entries`` ships every per-batch host-built array in ONE H2D
    # transfer, and seeds travel as 8-byte (row, query) pairs whose word
    # index / bit mask derive on device: fewer and smaller transfers,
    # bought with a host-side concatenate and a few device shifts per
    # batch. What that is worth on a directly attached chip is not
    # measured. The layout (concatenated int32) is produced by
    # pack_entries(); split points are static per kernel geometry:
    #   e1_rows  int32[S1] interior start rows (padding → n_int+1)
    #   e1_q     int32[S1] owning query index (padding → 0)
    #   e2_*               same pair for host-propagated seeds
    #   a_rows   int32[SA] interior in-neighbors of sink targets
    #   a_q      int32[SA] owning query index (padding → 0 w/ row n_int)
    #   targets  int32[B]  interior target rows, n_int = none
    S1, S2, SA, B = sizes
    o = 0
    e1_rows = entries[o : o + S1]; o += S1
    e1_q = entries[o : o + S1]; o += S1
    e2_rows = entries[o : o + S2]; o += S2
    e2_q = entries[o : o + S2]; o += S2
    a_rows = entries[o : o + SA]; o += SA
    a_q = entries[o : o + SA]; o += SA
    targets = entries[o : o + B]
    e1_words = e1_q >> 5
    e1_masks = jnp.uint32(1) << (e1_q & 31).astype(jnp.uint32)
    e2_words = e2_q >> 5
    e2_masks = jnp.uint32(1) << (e2_q & 31).astype(jnp.uint32)
    W = B // 32
    q = jnp.arange(B)
    words = q // 32
    bits = (q % 32).astype(jnp.uint32)
    # ONE bitmap over the interior rows holds every seed. Per (row, word)
    # slot, masks from distinct queries occupy distinct bits and per-query
    # row lists are deduplicated on host, so scatter-add never carries —
    # add on disjoint bits is bitwise OR. A start row the host walk also
    # reached is in both lists for its query: the second scatter adds only
    # the bits the first left clear.
    R0 = jnp.zeros((n_int + 1, W), jnp.uint32).at[e2_rows, e2_words].add(
        e2_masks, mode="drop"
    )
    have = R0.at[e1_rows, e1_words].get(mode="fill", fill_value=0)
    R0 = R0.at[e1_rows, e1_words].add(e1_masks & ~have, mode="drop")
    if bitmap_sharding is not None:
        # "data" shards words (embarrassingly parallel); "graph" shards rows
        # and lets the SPMD partitioner insert the per-step all-gather the
        # pull's cross-shard row gathers need
        R0 = lax.with_sharding_constraint(R0, bitmap_sharding)
    # the one-hop term: start bits of static (zero-in-degree) nodes
    # propagated to their interior out-neighbors on host. These bits are
    # "reached via ≥ 1 edge" by construction, so one that sits on its
    # query's target answers it; no bitmap is kept for that, the entry
    # list is compared with the targets.
    base_hit = jnp.zeros(B, jnp.uint32).at[e2_q].max(
        (e2_rows == targets[e2_q]).astype(jnp.uint32)
    )

    if n_active == 0 or not bucket_nbrs:
        # no interior→interior edges: the fixpoint is R0 itself
        A_fix = None
        pull_p = jnp.zeros((n_active + 1, W), jnp.uint32)
        iters = jnp.int32(0)
        truncated = jnp.bool_(False)
    else:
        # Only the active prefix R[:n_active] can change, so only it is
        # carried through the loop: A holds it, plus one all-zero row that
        # every passive neighbor id is sent to. What the passive
        # neighbors contribute to a pull is their seed bits, the same in
        # every step, and is gathered from R0 once (row n_int is all-zero:
        # active neighbor ids are sent there).
        def in_A(ids):
            return jnp.minimum(ids, n_active)

        def in_R0(ids):
            return jnp.where(ids >= n_active, ids, n_int)

        p_passive = _pull(bucket_nbrs, valid_rows, R0, in_R0)
        if ov_nbrs is not None:
            # delta-overlay edges (inserts since the base snapshot
            # build, keto_tpu/graph/overlay.py): OR the overlay
            # in-neighbors into their unique destination rows. Inside
            # the loop, so multi-hop paths through delta edges converge
            # exactly like base edges.
            ovo = lax.reduce(R0[in_R0(ov_nbrs)], np.uint32(0), lax.bitwise_or, (1,))
            p_passive = p_passive.at[ov_dst].set(p_passive[ov_dst] | ovo, mode="drop")

        def step(st):
            A, _, _, it = st
            p = _pull(bucket_nbrs, valid_rows, A, in_A) | p_passive
            if ov_nbrs is not None:
                ovo = lax.reduce(A[in_A(ov_nbrs)], np.uint32(0), lax.bitwise_or, (1,))
                p = p.at[ov_dst].set(p[ov_dst] | ovo, mode="drop")
            act = A[:n_active]
            nxt = lax.bitwise_or(p, act)
            return A.at[:n_active].set(nxt), p, jnp.any(nxt != act), it + 1

        # Each while iteration runs a *block* of pulls, each skipped via
        # lax.cond once the fixpoint is reached (monotone bitmaps:
        # converged stays converged), so the loop condition is evaluated
        # once per block instead of once per hop. The trade is up to
        # block_iters − 1 skipped-but-scheduled cond branches per batch
        # against fewer condition evaluations; its value on a directly
        # attached chip is not measured.
        def block(st):
            return lax.fori_loop(
                0, block_iters, lambda _, s: lax.cond(s[2], step, lambda x: x, s), st
            )

        # p0 is shape-placeholder only: changed=True and it_cap ≥ 1 (enforced
        # by the engine) guarantee ≥ 1 real step replaces it. All-zero — not
        # an R0 alias — so even a degenerate caller can't leak start bits
        # (which must never count as "reached via ≥ 1 edge") into answers.
        p0 = jnp.zeros((n_active, W), jnp.uint32)
        A0 = jnp.concatenate([R0[:n_active], jnp.zeros((1, W), jnp.uint32)], axis=0)
        A_fix, p_fix, truncated, iters = lax.while_loop(
            lambda st: st[2] & (st[3] < it_cap),
            block,
            (A0, p0, jnp.bool_(True), jnp.int32(0)),
        )
        pull_p = jnp.concatenate([p_fix, jnp.zeros((1, W), jnp.uint32)], axis=0)

    # interior targets: "reached via ≥ 1 edge" = the pull of the fixpoint —
    # already computed by the converging iteration and carried out of the
    # loop — plus the one-hop term. Passive/absent targets read the padded
    # all-zero row.
    t_act = jnp.where(targets < n_active, targets, n_active)
    hit = ((pull_p[t_act, words] >> bits) & jnp.uint32(1)) | base_hit

    # sink targets: gather each entry's (interior in-neighbor row, query
    # word) from the fixpoint — start bits of the neighbor DO count here
    # (the neighbor is not the target) — and scatter-OR per query. A
    # passive neighbor's fixpoint is its seed bits. Collisions only
    # combine entries of distinct (row, query) pairs: max on {0,1} is
    # exact.
    aw = a_q // 32
    ab = (a_q % 32).astype(jnp.uint32)
    fix = R0[a_rows, aw]
    if A_fix is not None:
        fix = jnp.where(a_rows < n_active, A_fix[jnp.minimum(a_rows, n_active), aw], fix)
    vals = (fix >> ab) & jnp.uint32(1)
    hit = hit.at[a_q].max(vals)

    # Single packed output ``uint32[W+2]``: per-query decision bits, then
    # the iteration count, then the truncation flag (the loop stopped on the
    # cap while the frontier still grew — converging in exactly it_cap steps
    # is NOT truncation). Device-side bit packing trades one small reduce
    # for a D2H fetch of 1 bit per query in one transfer instead of 1 byte
    # in three; how much the fetch costs on a directly attached chip is
    # not measured.
    packed_bits = lax.reduce(
        (hit << bits).reshape(W, 32), np.uint32(0), lax.bitwise_or, (1,)
    )
    tail = jnp.stack([iters.astype(jnp.uint32), truncated.astype(jnp.uint32)])
    out = jnp.concatenate([packed_bits, tail])
    if bitmap_sharding is not None:
        # fully replicate the packed result so every host of a
        # multi-controller mesh can fetch it directly (W+2 words — cheap)
        from jax.sharding import NamedSharding, PartitionSpec

        out = lax.with_sharding_constraint(
            out, NamedSharding(bitmap_sharding.mesh, PartitionSpec())
        )
    return out


#: jitted entrypoint used by the engine; ``check_step`` stays un-jitted for
#: ahead-of-time compile checks (__graft_entry__.py)
_check_kernel = partial(
    jax.jit,
    static_argnames=(
        "sizes", "n_active", "n_int", "valid_rows", "it_cap", "block_iters",
        "bitmap_sharding",
    ),
)(check_step)

#: donated variant: the ``entries`` staging buffer (arg 1) is donated to
#: the computation, so XLA aliases its device memory into the (much
#: smaller) packed output instead of allocating a fresh result buffer —
#: and the staging allocation is released the moment the kernel consumes
#: it, not when Python GC finds the array. Per-slice churn on the hot
#: path drops to: one H2D copy into memory the allocator just got back
#: from slice k-1. The engine only routes here when the backend actually
#: implements donation (``_donation_default``); elsewhere donation is a
#: silent no-op plus a warning, so the plain kernel is used instead.
_check_kernel_donated = partial(
    jax.jit,
    static_argnames=(
        "sizes", "n_active", "n_int", "valid_rows", "it_cap", "block_iters",
        "bitmap_sharding",
    ),
    donate_argnums=(1,),
)(check_step)


def _donation_default() -> bool:
    """Donate entry buffers? ``KETO_TPU_DONATE`` forces (1/0); default is
    platform-derived — XLA implements input-output aliasing for
    device-memory backends (TPU/GPU), while the CPU backend ignores the
    donation and warns."""
    env = os.environ.get("KETO_TPU_DONATE", "")
    if env == "0":
        return False
    if env == "1":
        # forced on (tests exercise the donated call path on CPU, where
        # XLA ignores the donation): suppress the per-geometry warning
        import warnings

        warnings.filterwarnings(
            "ignore", message="Some donated buffers were not usable"
        )
        return True
    try:
        return jax.local_devices()[0].platform in ("tpu", "gpu")
    except Exception:
        return False

#: cap on the [pairs, W_out, W_in] compare intermediate per chunk
_LABEL_PAIR_CHUNK = 2048


def label_step(
    out_lab: jnp.ndarray,  # int32 [n_int+1, Wo], OUT_PAD-padded (row n_int all pad)
    in_lab: jnp.ndarray,  # int32 [n_int+1, Wi], IN_PAD-padded
    entries: jnp.ndarray,  # int32 [3·P]: pair a-rows, pair b-rows, owning query
    *,
    n_pairs: int,
    B: int,
) -> jnp.ndarray:
    """2-hop label-intersection check: ONE device step at any depth.

    Each pair (a, b) asks reach0(a, b) over the interior subgraph — does
    ``OUT(a)`` share a landmark with ``IN(b)``? The two sides pad with
    distinct sentinels (labels.OUT_PAD / IN_PAD), so padded slots (and
    the all-pad row ``n_int`` the pair padding gathers) can never
    witness an intersection. Pair hits OR into their owning query and
    the decisions pack to 1 bit per query, same transfer shape as
    ``check_step`` minus the iteration tail — there is no iteration.

    This is the O(1)-step fast path the BFS kernel's depth tax motivates
    (keto_tpu/graph/labels.py); the engine routes only label-certifiable
    queries here and everything else to ``check_step`` bit-identically.
    """
    P = n_pairs
    pa = entries[:P]
    pb = entries[P : 2 * P]
    pq = entries[2 * P : 3 * P]
    hits = []
    for c0 in range(0, P, _LABEL_PAIR_CHUNK):
        oa = out_lab[pa[c0 : c0 + _LABEL_PAIR_CHUNK]]  # [chunk, Wo]
        ib = in_lab[pb[c0 : c0 + _LABEL_PAIR_CHUNK]]  # [chunk, Wi]
        hits.append(jnp.any(oa[:, :, None] == ib[:, None, :], axis=(1, 2)))
    hit = jnp.concatenate(hits) if len(hits) > 1 else hits[0]
    W = B // 32
    q = jnp.arange(B)
    bits = (q % 32).astype(jnp.uint32)
    # pair hits from one query land on the same bit — max, never add
    ans = jnp.zeros(B, jnp.uint32).at[pq].max(hit.astype(jnp.uint32))
    return lax.reduce(
        (ans << bits).reshape(W, 32), np.uint32(0), lax.bitwise_or, (1,)
    )


def label_step_witness(
    out_lab: jnp.ndarray,  # int32 [n_int+1, Wo], OUT_PAD-padded
    in_lab: jnp.ndarray,  # int32 [n_int+1, Wi], IN_PAD-padded
    pa: jnp.ndarray,  # int32 [P] pair a-rows
    pb: jnp.ndarray,  # int32 [P] pair b-rows
) -> jnp.ndarray:
    """Explain path (keto_tpu/explain): the WINNING entry of each pair's
    label intersection — argmin over the same packed compare ``label_step``
    reduces to one decision bit, one extra output word per pair. The
    distinct pad sentinels keep padded slots out of the argmin exactly as
    they keep them out of the hit reduction. Dispatched only by
    ``label_witness_info`` on explain requests — never on the check hot
    path."""
    oa = out_lab[pa]  # [P, Wo]
    ib = in_lab[pb]  # [P, Wi]
    entry_hit = jnp.any(oa[:, :, None] == ib[:, None, :], axis=2)  # [P, Wo]
    big = jnp.int32(np.iinfo(np.int32).max)
    lm = jnp.min(jnp.where(entry_hit, oa, big), axis=1)
    return jnp.where(jnp.any(entry_hit, axis=1), lm, jnp.int32(-1))


_label_witness_kernel = jax.jit(label_step_witness)

_label_kernel = partial(jax.jit, static_argnames=("n_pairs", "B"))(label_step)

#: donated variant (see _check_kernel_donated): the pair-entry staging
#: buffer (arg 2) aliases into the packed uint32[W] output
_label_kernel_donated = partial(
    jax.jit, static_argnames=("n_pairs", "B"), donate_argnums=(2,)
)(label_step)


class _HybridSlice:
    """Device output(s) of one slice whose BFS part answers only some of
    its positions: the label kernel's packed bits for the whole slice (a
    label-routed slice; None on the BFS route), plus — when some queries
    fell back, or on the BFS route — a BFS sub-batch output and the slice
    positions it answers (``device_part``: the queries the host could not
    answer without the device). Quacks like a device array where the
    streaming pipeline needs it (``copy_to_host_async`` / ``is_ready``)."""

    __slots__ = ("label_dev", "bfs_dev", "bfs_pos")

    def __init__(self, label_dev, bfs_dev=None, bfs_pos=None):
        self.label_dev = label_dev
        self.bfs_dev = bfs_dev
        self.bfs_pos = bfs_pos

    def parts(self) -> list:
        # label_dev is None on the BFS route, and where no certifiable pair
        # survived routing
        out = [] if self.label_dev is None else [self.label_dev]
        if self.bfs_dev is not None:
            out.append(self.bfs_dev)
        return out

    def copy_to_host_async(self) -> None:
        for p in self.parts():
            p.copy_to_host_async()

    def is_ready(self) -> bool:
        return all(
            bool(r()) for p in self.parts()
            for r in (getattr(p, "is_ready", None),) if r is not None
        )


class _ShardedSlice:
    """Device output of one explicitly-sharded dispatch
    (keto_tpu/parallel/sharded.py): the packed ``uint32[W+3]`` kernel
    result (decision bits, iterations, truncation, frontier-bit
    population) plus the halo-exchange byte cost of one round — what the
    engine turns into the ``keto_shard_*`` counters at unpack time.
    Quacks like a device array where the streaming pipeline needs it."""

    __slots__ = ("dev", "halo_bytes_per_round")

    def __init__(self, dev, halo_bytes_per_round: int):
        self.dev = dev
        self.halo_bytes_per_round = int(halo_bytes_per_round)

    def copy_to_host_async(self) -> None:
        self.dev.copy_to_host_async()

    def is_ready(self) -> bool:
        r = getattr(self.dev, "is_ready", None)
        return True if r is None else bool(r())


def pack_entries(
    packed, out: Optional[np.ndarray] = None
) -> tuple[np.ndarray, tuple[int, int, int, int]]:
    """Concatenate pack_chunk's seven arrays into check_step's single
    int32 ``entries`` buffer + static split sizes. ``out`` (a staging
    buffer of exactly the total size, from the engine's ``_StagingPool``)
    receives the concatenation in place — no per-slice host allocation;
    the pool only re-leases it after the slice that shipped it lands."""
    (e1r, e1q, e2r, e2q, ar, aq, targets) = packed
    arrays = [e1r, e1q, e2r, e2q, ar, aq, targets]
    if (
        out is not None
        and out.shape[0] == sum(a.shape[0] for a in arrays)
        and all(a.dtype == np.int32 for a in arrays)
    ):
        buf = np.concatenate(arrays, out=out)
    else:
        buf = np.concatenate(arrays)
    return buf, (e1r.shape[0], e2r.shape[0], ar.shape[0], targets.shape[0])


class _StagingPool:
    """Reusable int32 host staging buffers for the packed entry arrays,
    keyed by exact element count (entry geometries are pow2-padded, so a
    serving process sees a handful of distinct sizes per width rung).

    The aliasing discipline that makes reuse safe: ``acquire`` hands a
    buffer out ON LEASE, and the engine only ``release``s it after the
    slice that shipped it has LANDED (its device output fetched) — the
    H2D copy behind ``jnp.asarray``/``device_put`` may complete
    asynchronously (and on CPU backends may alias the host memory
    outright), so writing the next slice's entries into the buffer any
    earlier could corrupt an in-flight one. tests/test_slice_tail.py
    fuzzes exactly that contract.

    Pool growth is PLANNED: ``on_grow`` (the engine's governor seam)
    may refuse a new buffer, in which case the caller falls back to a
    per-slice allocation — the eviction ladder's "staging" rung drops
    the whole pool the same way. ``bytes()`` is the figure the HBM
    ledger's ``staging`` tag carries, reconciled at scrape."""

    #: free buffers kept per distinct size (beyond the lease depth this
    #: only caches geometry churn, so keep it shallow)
    MAX_FREE_PER_SIZE = 8

    def __init__(self, on_change: Optional[Callable[[int], None]] = None):
        self._lock = threading.Lock()  # guards: _free, _bytes, _leased
        self._free: dict[int, list] = {}
        self._bytes = 0  # free + leased, the ledger figure
        self._leased = 0
        self._on_change = on_change

    def _notify(self, nbytes: int) -> None:
        # ALWAYS outside self._lock: the callback takes the governor's
        # lock, and the governor's staging rung calls back into drop()
        # while holding it — publishing under the pool lock would be a
        # lock-order inversion (the sharded-smoke sanitizer caught
        # exactly that). Concurrent publishes may land out of order; the
        # ledger is reconciled at scrape, not per-update.
        cb = self._on_change
        if cb is not None:
            cb(nbytes)

    def acquire(self, n: int, plan=None) -> Optional[np.ndarray]:
        """An int32 buffer of exactly ``n`` elements, or None when a new
        buffer would be needed and ``plan`` (bytes -> bool) refuses it."""
        with self._lock:
            free = self._free.get(n)
            if free:
                self._leased += 1
                return free.pop()
        if plan is not None and not plan(4 * n):
            return None
        with self._lock:
            self._bytes += 4 * n
            self._leased += 1
            total = self._bytes
        self._notify(total)
        return np.empty(n, np.int32)

    def release(self, buf: np.ndarray) -> None:
        total = None
        with self._lock:
            self._leased = max(0, self._leased - 1)
            free = self._free.setdefault(buf.shape[0], [])
            if len(free) < self.MAX_FREE_PER_SIZE:
                free.append(buf)
            else:
                self._bytes = max(0, self._bytes - 4 * buf.shape[0])
                total = self._bytes
        if total is not None:
            self._notify(total)

    def drop(self) -> int:
        """Evict: clear every free buffer and forget leased accounting
        (outstanding leases release into a fresh pool). Returns the
        bytes freed from the ledger."""
        with self._lock:
            freed = self._bytes
            self._free.clear()
            self._bytes = 0
            self._leased = 0
        self._notify(0)
        return freed

    def bytes(self) -> int:
        with self._lock:
            return self._bytes

    def snapshot(self) -> dict:
        with self._lock:
            return {
                "bytes": self._bytes,
                "leased": self._leased,
                "free_buffers": sum(len(v) for v in self._free.values()),
                "sizes": sorted(self._free),
            }


class _SortedSeen:
    """Sorted-key membership set with amortized O(log n) inserts: keys
    live in a list of sorted runs whose lengths form a (loosely)
    geometric sequence — an insert batch merges equal-or-smaller runs
    (each element participates in O(log n) merges total), replacing the
    ``np.insert``-into-one-array scheme whose per-hop O(n) memmove made
    a long walk quadratic. ``work`` counts elements moved by merges;
    tests/test_native_pack.py asserts the O(n log n) bound."""

    __slots__ = ("_runs", "work")

    def __init__(self):
        self._runs: list[np.ndarray] = []
        self.work = 0

    def contains(self, keys: np.ndarray) -> np.ndarray:
        """bool mask: which of ``keys`` are present (any order)."""
        mask = np.zeros(keys.shape[0], dtype=bool)
        for run in self._runs:
            pos = np.clip(np.searchsorted(run, keys), 0, run.size - 1)
            mask |= run[pos] == keys
        return mask

    def add(self, ks: np.ndarray) -> None:
        """Insert a SORTED batch of keys not currently present."""
        if not ks.size:
            return
        run = ks
        while self._runs and self._runs[-1].size <= run.size:
            prev = self._runs.pop()
            merged = np.concatenate([prev, run])
            merged.sort(kind="stable")
            self.work += merged.size
            run = merged
        self._runs.append(run)


def _ceil_pow2(x: int) -> int:
    return 1 if x <= 1 else 1 << (int(x) - 1).bit_length()


def _csr_gather(indptr: np.ndarray, indices: np.ndarray, nodes: np.ndarray):
    """(all out-neighbors of ``nodes`` concatenated, per-node counts)."""
    cnts = indptr[nodes + 1] - indptr[nodes]
    total = int(cnts.sum())
    if not total:
        return np.zeros(0, indices.dtype), cnts
    base = np.repeat(indptr[nodes], cnts)
    within = np.arange(total) - np.repeat(np.cumsum(cnts) - cnts, cnts)
    return indices[base + within], cnts


def _entry_pad(B: int, size: int) -> int:
    """Scatter/gather entry arrays pad to B·2^k — a couple of geometries per
    batch width, so chunks of one request hit the same jit cache entry."""
    sp = B
    while sp < size:
        sp *= 2
    return sp


def _pad_entries(rows_l, qs_l, B: int, drop_row: int):
    if rows_l:
        rows = np.concatenate(rows_l).astype(np.int32)
        qs = np.concatenate(qs_l).astype(np.int32)
    else:
        rows = np.zeros(0, np.int32)
        qs = np.zeros(0, np.int32)
    pad = _entry_pad(B, rows.size) - rows.size
    rows = np.concatenate([rows, np.full(pad, drop_row, np.int32)])
    qs = np.concatenate([qs, np.zeros(pad, np.int32)])
    return rows, qs


def _pad_packed(packed, sizes: tuple, ni: int):
    """``pack_chunk``'s seven arrays padded up to ``sizes`` = (S1, S2, SA, B)
    with the same sentinels ``pack_chunk`` pads with: seed rows that drop,
    the all-zero answer row, no target."""
    S1, S2, SA, B = sizes

    def up(a, n, fill):
        if a.shape[0] == n:
            return a
        return np.concatenate([a, np.full(n - a.shape[0], fill, np.int32)])

    e1r, e1q, e2r, e2q, ar, aq, targets = packed
    return (
        up(e1r, S1, ni + 1), up(e1q, S1, 0), up(e2r, S2, ni + 1), up(e2q, S2, 0),
        up(ar, SA, ni), up(aq, SA, 0), up(targets, B, ni),
    )


def device_part(snap: GraphSnapshot, packed, host_ans: np.ndarray):
    """What of a packed chunk ``check_step`` has to see, and what the host
    can say without it.

    Only the active rows change under the pulls; every other interior row
    keeps the bits it was seeded with. So a sink target whose answer rows
    hold one of its query's own seed rows is granted here (**direct**: the
    set intersection the kernel would otherwise do as a scatter into, and a
    gather from, a bitmap over all interior rows), and a query gets
    nothing more from the device unless its target side has an active
    row: an answer row below ``num_active``, or an active interior target
    (a passive one is answered by the host walk's own hit alone). The
    others leave the chunk, so on a graph whose device part is small the
    kernel runs a narrow sub-batch of few entries; where most rows are
    active nearly every query stays.

    ORs the direct grants into ``host_ans`` and returns ``(packed, pos)``:
    the seven arrays of the queries that need the device, renumbered 0..
    in their order and padded for the narrowest width that holds them, and
    their positions in the chunk; ``(None, None)`` where none does.
    """
    e1r, e1q, e2r, e2q, ar, aq, targets = packed
    ni, na = snap.num_int, snap.num_active
    nq = host_ans.shape[0]
    v1, v2, va = e1r != ni + 1, e2r != ni + 1, ar != ni
    e1r, e1q, e2r, e2q, ar, aq = e1r[v1], e1q[v1], e2r[v2], e2q[v2], ar[va], aq[va]
    if ar.size:
        direct = native_pack.pairs_member(
            np.concatenate([e1r, e2r]), np.concatenate([e1q, e2q]), ar, aq
        )
        host_ans[aq[direct]] = True
    need = targets[:nq] < na
    need[aq[ar < na]] = True
    need &= ~host_ans
    k1, k2 = need[e1q], need[e2q]
    if not k1.any() and not k2.any():
        return None, None  # no query is left, or nothing seeds those that are
    pos = np.nonzero(need)[0]
    B = 32 * next(w for w in _WORD_WIDTHS if 32 * w >= pos.size)
    place = np.cumsum(need) - 1
    ka = need[aq]
    # one pad for the three entry arrays, B·4^k: how many of its queries a
    # chunk sends here varies from chunk to chunk, and every combination
    # of pads is a program of its own to compile
    E = B
    while E < max(int(k1.sum()), int(k2.sum()), int(ka.sum())):
        E *= 4

    def side(rows, q, keep, pad_row):
        out_r, out_q = np.full(E, pad_row, np.int32), np.zeros(E, np.int32)
        n = int(keep.sum())
        out_r[:n], out_q[:n] = rows[keep], place[q[keep]]
        return out_r, out_q

    sub_targets = np.full(B, ni, np.int32)
    sub_targets[: pos.size] = targets[pos]
    return (
        side(e1r, e1q, k1, ni + 1) + side(e2r, e2q, k2, ni + 1)
        + side(ar, aq, ka, ni) + (sub_targets,),
        pos,
    )


def _padding_packed(sizes: tuple, ni: int):
    """``pack_chunk``'s seven arrays at ``sizes`` = (S1, S2, SA, B) with
    nothing in them: every seed a dropped row, every answer entry the
    all-zero row, no target. What a warm-up runs a program on."""
    S1, S2, SA, B = sizes
    return (
        np.full(S1, ni + 1, np.int32), np.zeros(S1, np.int32),
        np.full(S2, ni + 1, np.int32), np.zeros(S2, np.int32),
        np.full(SA, ni, np.int32), np.zeros(SA, np.int32), np.full(B, ni, np.int32),
    )


def pack_chunk(
    snap: GraphSnapshot,
    sd: np.ndarray,
    tg: np.ndarray,
    multi: dict,
    i0: int,
    i1: int,
    force_W: Optional[int] = None,
    native: bool = True,
):
    """Pack queries ``[i0, i1)`` of a bulk-resolved batch into kernel
    arguments — vectorized numpy throughout (the host side of the hot path,
    replacing the reference's per-traversal-step SQL round trips).

    ``sd``/``tg``/``multi`` come from ``TpuCheckEngine._resolve_bulk``.
    Starts in the host-propagated classes (static, or peeled interior —
    see the peel note in keto_tpu/graph/snapshot.py) expand here through
    the forward CSR, one vectorized gather per hop over the whole chunk's
    frontier: reached bitmap rows become device seeds (e2), reached
    query targets are decided on host, and reached peeled rows continue
    the frontier (the peeled subgraph is a DAG among base nodes; the
    per-(query, row) visited filter also terminates cycles a delta
    overlay may close). Sink targets get answer-gather entries from the
    snapshot's sink reverse CSR.

    Returns ``(packed, host_ans)`` where ``packed`` is ``(e1_rows, e1_q,
    e2_rows, e2_q, a_rows, a_q, targets)`` numpy arrays (None when no
    query has any device entry; pack_entries concatenates them into the
    kernel's single buffer) and ``host_ans`` is a bool[nq] of
    host-decided grants to OR into the device answers.
    """
    nq = i1 - i0
    W = force_W or next(w for w in _WORD_WIDTHS if 32 * w >= nq)
    B = 32 * W
    ni = snap.num_int
    sb = snap.sink_base
    nl = snap.num_live
    qi = np.arange(nq)
    tgc = tg[i0:i1]
    sdc = sd[i0:i1]
    host_ans = np.zeros(nq, dtype=bool)
    targets = np.full(B, ni, dtype=np.int32)
    targets[:nq] = np.where((tgc >= 0) & (tgc < ni), tgc, ni)

    e1: tuple[list, list] = ([], [])
    e2: tuple[list, list] = ([], [])
    m_int = (sdc >= 0) & (sdc < ni)
    if m_int.any():
        e1[0].append(sdc[m_int])
        e1[1].append(qi[m_int])
    # host-propagated starts: peeled interior, static, and overlay nodes
    # (an overlay sink start has no out-edges and yields nothing). Base
    # sink starts [sb, nl) have no out-edges: nothing to seed.
    m_host = ((sdc >= ni) & (sdc < sb)) | (sdc >= nl)
    prop_rows = [sdc[m_host]] if m_host.any() else []
    prop_q = [qi[m_host]] if m_host.any() else []
    for i, (live, hostp) in multi.items():
        if not (i0 <= i < i1):
            continue
        li = i - i0
        if live.size:
            e1[0].append(live)
            e1[1].append(np.full(live.size, li, np.int64))
        if hostp.size:
            prop_rows.append(hostp)
            prop_q.append(np.full(hostp.size, li, np.int64))

    use_native = (
        native and native_pack.available() and native_pack.walk_eligible(snap)
    )
    native_pack.COUNTERS["native" if use_native else "numpy"] += 1
    if prop_rows:
        rows = np.concatenate(prop_rows).astype(np.int64)
        pq = np.concatenate(prop_q).astype(np.int64)
        if use_native:
            # one GIL-released C++ call walks the whole frontier
            # (native/pack.cpp): threaded CSR gathers, hash-set
            # seen/seed dedup, bit-identical output by contract
            # (fuzz-compared in tests/test_native_pack.py)
            srows, sq, hits = native_pack.pack_walk(snap, rows, pq, tgc)
            if hits is not None:
                host_ans |= hits
            if srows.size:
                e2[0].append(srows)
                e2[1].append(sq)
        else:
            # numpy fallback: multi-hop frontier propagation, (query,
            # row)-deduplicated. The visited set lives in merged sorted
            # runs (_SortedSeen) — membership stays one searchsorted pass
            # per run, and inserts amortize to O(log n) instead of the
            # O(n) np.insert memmove that made long walks quadratic.
            seen = _SortedSeen()
            seed_rows: list = []
            seed_q: list = []
            while rows.size:
                key = (pq << 32) | rows
                _, first = np.unique(key, return_index=True)
                keep = np.sort(first)
                rows, pq, key = rows[keep], pq[keep], key[keep]
                fresh = ~seen.contains(key)
                rows, pq, key = rows[fresh], pq[fresh], key[fresh]
                if not rows.size:
                    break
                seen.add(np.sort(key))
                nbrs, cnts = snap.out_neighbors_bulk(rows)
                if not nbrs.size:
                    break
                gq = np.repeat(pq, cnts)
                nbrs = nbrs.astype(np.int64)
                # a traversed edge landing on the query's target decides
                # it ("reached via ≥ 1 edge" — real edges only). The -1
                # no-target sentinel can never match a neighbor id.
                hit = nbrs == tgc[gq]
                if hit.any():
                    host_ans[gq[hit]] = True
                m_seed = nbrs < ni
                if m_seed.any():
                    seed_rows.append(nbrs[m_seed])
                    seed_q.append(gq[m_seed])
                m_next = (nbrs >= ni) & (nbrs < sb)
                rows, pq = nbrs[m_next], gq[m_next]
            if seed_rows:
                # global (query, row) dedup: e2 scatter-adds per-bit, so
                # a row seeded twice for one query would carry into the
                # next bit
                srows = np.concatenate(seed_rows)
                sq = np.concatenate(seed_q)
                skey = (sq << 32) | srows
                _, sfirst = np.unique(skey, return_index=True)
                keep = np.sort(sfirst)
                e2[0].append(srows[keep])
                e2[1].append(sq[keep])

    # answer-gather entries for sink targets of queries that have any start
    has_start = m_int | m_host
    for i in multi:
        if i0 <= i < i1:
            has_start[i - i0] = multi[i][0].size > 0 or multi[i][1].size > 0
    ans: tuple[list, list] = ([], [])
    m_sink_t = (tgc >= sb) & (tgc < nl)
    if snap.ov_sink_in:
        # overlay targets (ids ≥ n_base) and base sinks with overlay
        # in-edges both answer through sink_in_rows_bulk
        m_sink_t = m_sink_t | np.isin(
            tgc, np.fromiter(snap.ov_sink_in.keys(), np.int64)
        )
    m_ans = has_start & m_sink_t
    if m_ans.any():
        if use_native:
            # overlay-free by eligibility: the native gather mirrors
            # sink_in_rows_bulk's plain-CSR arm off the GIL
            rows, cnts = native_pack.sink_gather(snap, tgc[m_ans])
        else:
            rows, cnts = snap.sink_in_rows_bulk(tgc[m_ans])
        if rows.size:
            ans[0].append(rows)
            ans[1].append(np.repeat(qi[m_ans], cnts).astype(np.int32))

    if not e1[0] and not e2[0]:
        return None, host_ans
    if ans[0]:
        a_rows = np.concatenate(ans[0]).astype(np.int32)
        a_q = np.concatenate(ans[1])
    else:
        a_rows = np.zeros(0, np.int32)
        a_q = np.zeros(0, np.int32)
    pad = _entry_pad(B, a_rows.size) - a_rows.size
    # answer padding: in-range all-zero row ni with query 0 — max(0) is a no-op
    a_rows = np.concatenate([a_rows, np.full(pad, ni, np.int32)])
    a_q = np.concatenate([a_q, np.zeros(pad, np.int32)])
    # seed padding row ni+1 is out of range for the [ni+1, W] bitmap → dropped
    return (
        _pad_entries(*e1, B, ni + 1) + _pad_entries(*e2, B, ni + 1)
        + (a_rows, a_q, targets),
        host_ans,
    )


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
    slices/s).
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
        self._ewma_ms_per_q: Optional[float] = None
        #: per-route cost model: route → {per_q, per_entry, bfs_steps,
        #: last_seen} (EWMAs; last_seen is a slice counter)
        self._routes: dict[str, dict] = {}
        self._slices = 0
        self._ring: collections.deque = collections.deque(maxlen=256)
        self._guard = 1.0
        self._tail_p50 = 0.0
        self._tail_p99 = 0.0

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
        rung bounded by the model's predicted-service-time width (always
        a compiled ladder width)."""
        with self._lock:
            cap = self._ladder[self._i]
            m = self._model_cap_locked()
            return cap if m is None else max(self._ladder[self._lo], min(cap, m))

    def entry_budget(self) -> Optional[int]:
        """Device entries one sub-chunk may carry before its predicted
        service time overshoots the target — the pre-dispatch split
        bound ``_dispatch_slices`` applies. None before the model has an
        entry-cost estimate."""
        with self._lock:
            recent = self._recent_locked()
            per_e = max(
                (st["per_entry"] for st in recent if st["per_entry"] > 0),
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
    ) -> None:
        """Feed one slice's service time: dispatch→ready when the pipeline
        ran dry, ready→ready interval when saturated. ``route``/
        ``bfs_steps``/``entries`` (from the stream's per-slice info) fit
        the per-route model; plain ``observe(nq, ms)`` still steers the
        reactive ladder alone."""
        if nq <= 0:
            return
        per_q = ms / nq
        with self._lock:
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
            st["last_seen"] = self._slices
            st["n"] += 1
            self._ring.append(ms)
            if self._slices % self.TAIL_EVERY == 0:
                self._retune_tail_locked()
            e = self._ewma_ms_per_q
            self._ewma_ms_per_q = per_q if e is None else 0.7 * e + 0.3 * per_q
            cap = self._ladder[self._i]
            if ms > self.NARROW_FRAC * self.target_ms:
                want = self._lo
                for k in range(self._i, self._lo - 1, -1):
                    if self._ladder[k] * per_q <= self.target_ms:
                        want = k
                        break
                self._i = min(self._i, max(self._lo, want))
                self._good = 0
            elif ms < self.WIDEN_FRAC * self.target_ms and nq >= cap:
                self._good += 1
                if self._good >= self._patience and self._i + 1 < len(self._ladder):
                    self._i += 1
                    self._good = 0
            else:
                self._good = 0

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
        """Controller state for introspection (bench, /debug)."""
        with self._lock:
            return {
                "cap": self._ladder[self._i],
                "target_ms": self.target_ms,
                "ewma_ms_per_query": self._ewma_ms_per_q,
                "model_cap": self._model_cap_locked(),
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
        native_pack_enabled: bool = True,
        staging_enabled: bool = True,
        stream_tail_ratio: float = 5.0,
    ):
        if it_cap < 1:
            raise ValueError("it_cap must be >= 1 (the answer pull needs one step)")
        self._store = store
        if isinstance(namespaces, namespace_pkg.Manager):
            self._nm: Callable[[], namespace_pkg.Manager] = lambda: namespaces
        else:
            self._nm = namespaces
        self._it_cap = it_cap
        self._max_batch = max_batch
        # bound on the BFS workspace (~3 W-wide uint32 bitmaps over interior
        # rows); batch width narrows automatically on huge graphs so the
        # default max_batch can never ask for more HBM than this
        self._mem_budget = mem_budget_bytes
        # pulls per convergence observation, adapted to the workload's
        # traversal depth from the iteration counts kernels report back
        self._block_iters = 8
        # which kernel programs are compiled, so that a served slice pads up
        # to one that is before it compiles its own on the dispatch thread
        # (keto_tpu/check/geometry.py); warm_compile settles block_iters, a
        # static of every one of them, from the snapshot
        self._geoms = KernelGeometries(self._compile_geometry)
        self._block_iters_shape: Optional[tuple] = None  # the shape it was settled on
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
        self._route_slices: collections.Counter = collections.Counter()
        self._route_queries: collections.Counter = collections.Counter()
        # native pack path (native/pack.cpp via keto_tpu/check/
        # native_pack.py): the host walk runs GIL-released when the
        # library is present and the snapshot is overlay-eligible;
        # False pins the numpy reference path
        self._native_pack = bool(native_pack_enabled)
        #: device BFS iteration counts per dispatched slice (values are
        #: step counts, not ms) — bench reports bfs_steps_p50/p99 from
        #: here so the label win is attributable to killed frontier hops
        self.bfs_steps_stats = DurationStats()
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
        # snapshot id last counted as a label invalidation (overlay
        # mutated the interior subgraph) — one count per transition
        self._label_blocked_snap: Optional[int] = None
        self._mesh = mesh
        self._shard_rows = shard_rows
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
            self._bucket_sharding = NamedSharding(mesh, P(GRAPH_AXIS, None))
            self._ov_dst_sharding = NamedSharding(mesh, P(GRAPH_AXIS))
            # sharded mode: stacked [n_shards, ...] arrays split over the
            # graph axis (leading dim), replicated over data; per-dispatch
            # label pair entries replicate everywhere
            self._shard_stack_sharding = NamedSharding(mesh, P(GRAPH_AXIS))
            self._shard_repl_sharding = NamedSharding(mesh, P())
        self._lock = threading.Lock()
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
        # close() flips this; long cooperative loops (warm_compile) check
        # it between kernels so teardown never races an in-flight compile
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
        # persistent entry staging (donated device buffers' host half):
        # packed entry arrays concatenate into pooled per-geometry
        # buffers leased until their slice LANDS, and — where the
        # backend implements donation — ship through the donated kernel
        # variants so the device-side staging allocation aliases into
        # the output. The pool's bytes ride the governor's "staging"
        # ledger tag; its rung is FIRST on the ladder (dropping it costs
        # per-slice allocation churn, never coverage or correctness).
        self._staging_enabled = bool(staging_enabled)
        self._staging_suspended = False
        self._staging = _StagingPool(
            on_change=lambda b: self.hbm.register("staging", b)
        )
        self._donate_entries = self._staging_enabled and _donation_default()
        self.hbm.attach_rungs([
            ("staging", self._evict_staging, self._restore_staging),
            ("labels", self._evict_labels, self._restore_labels),
            ("reverse", self._evict_reverse, self._restore_reverse),
            ("warm-ladder", self._evict_warm_ladder, self._restore_warm_ladder),
            ("overlay-budget", self._evict_overlay_budget,
             self._restore_overlay_budget),
        ])
        # ladder state the rungs flip (all derive from replicated inputs)
        self._labels_suspended = False
        self._width_trim = 0
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
            GovernedSorter(hbm=self.hbm, stats=self.maintenance)
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
        with self._lock:
            return self._refresh_locked()

    def snapshot_serving(self) -> GraphSnapshot:
        """Serving-path snapshot: NEVER stalls the read plane on an
        expensive rebuild (VERDICT r4 weak #1 — a single delta-ineligible
        write used to freeze checks for the full rebuild time).

        - the store hasn't moved → current snapshot (plus the usual
          background compaction kick);
        - watermark advanced and a delta applies → synchronous catch-up
          (milliseconds: inserts extend the overlay, deletes tombstone —
          effectively read-your-writes);
        - only a full rebuild can reach the watermark → if the last build
          was cheap (≤ sync_rebuild_budget_s), just do it; otherwise serve
          the current snapshot (bounded staleness, Zanzibar default) and
          let the background refresh catch up.

        Callers needing hard read-your-writes use ``snapshot()`` /
        ``mode="latest"``; callers holding a write's snaptoken use
        ``snapshot(at_least=token)``.
        """
        snap = self._snapshot
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
                self._kick_background_refresh()
                return self._snapshot
        wm = self._store.watermark()
        if snap.snapshot_id >= wm:
            # current — return it directly (NOT via snapshot(): a write
            # landing between the two watermark reads would send that
            # call into an inline rebuild), with the usual compaction kick
            self._maybe_kick_compaction(snap)
            return snap
        if self._lock.acquire(blocking=False):
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
                self._lock.release()
        # rebuild territory (or a rebuild is already holding the lock):
        # serve stale, catch up off the serving path
        self._kick_background_refresh()
        return self._snapshot

    def _snapshot_for(self, at_least, mode: str) -> GraphSnapshot:
        if at_least is not None:
            return self.snapshot(at_least=at_least)
        if mode == "serving":
            return self.snapshot_serving()
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
        if self._behind_since is None:
            self._behind_since = now
        self._kick_background_refresh()
        return now - self._behind_since

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
        self._geoms.close()

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
        if self._width_trim:
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

    def _evict_staging(self) -> int:
        """Rung 0 — drop the persistent entry staging pool and fall back
        to per-slice allocation + device_put: pure churn cost, zero
        coverage or correctness impact, which is why it is the first
        thing pressure sheds. Outstanding leases release into the empty
        pool harmlessly."""
        self._staging_suspended = True
        freed = self._staging.drop()
        self.hbm.release("staging")
        return freed

    def _restore_staging(self) -> None:
        # the pool refills lazily as slices dispatch
        self._staging_suspended = False

    def _staging_on(self) -> bool:
        return self._staging_enabled and not self._staging_suspended

    def _stage_acquire(self, n: int) -> Optional[np.ndarray]:
        """Lease an ``n``-element int32 staging buffer, planning pool
        growth against the HBM governor (``evict=False`` — staging never
        evicts other families; an unplannable buffer just means this
        slice allocates per-slice). None when staging is off/refused."""
        if not self._staging_on():
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
        out["enabled"] = self._staging_enabled
        out["suspended"] = self._staging_suspended
        out["donating"] = self._donate_entries
        return out

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
        self._width_trim = max(self._width_trim, len(_WORD_WIDTHS) - 4)
        self._geoms.reset()
        freed = self.hbm.release("warmup")
        self._last_warm_bytes = max(self._last_warm_bytes, freed)
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
        return freed

    def _restore_warm_ladder(self) -> None:
        self._width_trim = 0

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

    def _word_widths(self) -> tuple[int, ...]:
        """The compile-width ladder currently in service: the full
        ``_WORD_WIDTHS`` normally, its lower rungs while the governor's
        warm-ladder rung is evicted (never fewer than two widths)."""
        n = len(_WORD_WIDTHS) - self._width_trim
        return _WORD_WIDTHS[: max(2, n)]

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
        # raised at transfer/collect time) still counts as memory
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

                self._fallback_engine_obj = CheckEngine(self._store)
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
                self._note_route("cpu", len(batch), ms)
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

    def _guard_stream(self, inner):
        """Device-error accounting around a streaming generator: a failed
        stream counts toward degraded mode — the caller (CheckBatcher)
        retries its unresolved futures through ``batch_check_with_token``,
        which then routes to the CPU fallback — and a completed stream
        marks the device path healthy."""

        def gen():
            try:
                yield from inner
            except Exception as e:
                if not self._multiprocess and not isinstance(e, KetoError):
                    self._note_device_error(e)
                raise
            self._note_device_ok()

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
            with self._lock:
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
        wm = self._store.watermark()
        fold_failed = False
        if snap is None and self._cache_dir is not None and not delta_only:
            snap = self._load_cache_locked(wm)
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

    def _upload_buckets(self, snap: GraphSnapshot) -> None:
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

    def _warm_width_bytes(self, snap: GraphSnapshot, B: int) -> int:
        """Device bytes one warmed width holds live while its slice runs:
        the BFS workspace (~3 W-wide uint32 bitmaps over interior rows —
        the same formula ``_slice_cap`` budgets with)."""
        return (snap.num_int + 1) * 12 * (B // 32)

    def _check_shape(self, snap: GraphSnapshot) -> tuple:
        """What of the snapshot fixes a ``check_step`` program: row counts
        and the shapes of the arrays it closes over. Kept on the snapshot
        for as long as it holds the same device arrays."""
        bk, ov = snap.device_buckets, snap.device_overlay
        kept = getattr(snap, "_check_shape_of", None)
        if kept is not None and kept[0] is bk and kept[1] is ov:
            return kept[2]
        shape = (
            snap.num_active, snap.num_int, tuple(b.n for b in snap.buckets),
            tuple(a.shape for a in bk),
            None if ov is None else (ov[0].shape, ov[1].shape),
        )
        snap._check_shape_of = (bk, ov, shape)
        return shape

    def _check_fixed(self, it_cap: int) -> tuple:
        return (it_cap, self._block_iters, self._donate_entries)

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
        self, snap: GraphSnapshot, sizes: tuple, it_cap: int, seeds=None
    ) -> np.ndarray:
        """One ``check_step`` at ``sizes`` = (S1, S2, SA, B) on entries that
        are all padding - dropped seed rows, the all-zero answer row - so
        that the program of these sizes is compiled; with ``seeds``, those
        interior rows start one query each. Returns the device output."""
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
                sizes=sizes,
                n_active=snap.num_active,
                n_int=ni,
                valid_rows=tuple(b.n for b in snap.buckets),
                it_cap=it_cap,
                block_iters=self._block_iters,
                bitmap_sharding=self._bitmap_sharding_for(sizes[3]),
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
        """``block_iters`` is a static of every ``check_step`` program, so a
        change recompiles them all: settle it before the ladder is warmed,
        from how deep the snapshot's own device part runs - a BFS from a
        spread of the interior rows nothing on the device points at (the
        sources of what the pulls walk) - and leave it there for as long as
        snapshots keep this shape (``_after_batch``)."""
        self._block_iters_shape = self._check_shape(snap)
        na, ni = snap.num_active, snap.num_int
        if na == 0 or ni <= na or not snap.buckets:
            return
        seeds = np.unique(
            np.linspace(na, ni - 1, num=min(B, ni - na)).astype(np.int32)
        )
        out = np.asarray(
            self._run_check_padding(snap, (B, B, B, B), self._it_cap, seeds=seeds)
        )
        self._block_iters = max(
            self._block_iters, min(32, _ceil_pow2(int(out[B // 32]) + 1))
        )

    def _compile_geometry(self, kernel: str, shape: tuple, fixed: tuple, sizes: tuple) -> bool:
        """The geometry worker's compile (keto_tpu/check/geometry.py): run
        the kernel once on padding at ``sizes`` against the current
        snapshot, if that still has the shape the slice saw."""
        snap = self._snapshot
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

    def kernel_geometry_counts(self) -> dict:
        """``{(kernel, met): launched slices}``: how each slice of the
        single-device path found its program (the
        ``keto_kernel_geometry_total`` scrape callback)."""
        return self._geoms.counts()

    def warm_compile(self) -> int:
        """Ahead-of-time compile of the slice-width ladder (BFS and
        label kernels) against the current snapshot's geometry, so the
        first real slice of every width hits the jit cache — and, with a
        persistent compilation cache requested
        (keto_tpu/driver/compile_cache.py),
        so the multi-second compile cost is paid once per binary instead
        of once per boot. Widths whose compiled-buffer footprint would
        breach the HBM budget are SKIPPED (never evicted for — warming is
        optional work) and counted in the ``warm_widths_skipped`` gauge /
        ``keto_hbm_warm_widths_skipped``. Returns the number of kernels
        warmed.

        On the single-device path every warmed program is also entered in
        the engine's geometry set, and from here on a slice whose own
        program is not compiled pads up to one that is
        (keto_tpu/check/geometry.py): the ladder's minimum rungs are what
        there always is to pad up to."""
        snap = self.snapshot()
        # the label kernels warm against the index the overlapped boot
        # build installs onto this snapshot: join it first, or every
        # width's first label slice compiles inside the serving window
        self._label_build_wait()
        if snap.n_nodes == 0 or snap.n_edges == 0:
            return 0
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
                    self._geoms.add(
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
                        self._geoms.add(
                            "label", self._label_shape(labs), self._label_fixed(), (B, B)
                        )
                warmed += 1
        if plain:
            self._geoms.mark_warmed("check", self._check_shape(snap))
            if self._labels_enabled and labs is not None:
                self._geoms.mark_warmed("label", self._label_shape(labs))
        self.maintenance.set_gauge("warm_widths_skipped", skipped)
        return warmed

    # -- resolution ----------------------------------------------------------

    def _resolve_bulk(
        self, snap: GraphSnapshot, tuples
    ) -> tuple[np.ndarray, np.ndarray, dict]:
        """Resolve every query to device rows (see ``_resolve_bulk_py`` for
        the result contract). Literal queries go through the C++ intern
        tables in one bulk call when the native library provides it;
        wildcard/pattern/unknown-namespace queries and the pure-Python
        interner use the host loop.

        ``tuples`` is a list of ``RelationTuple`` or a ``QueryBatch``
        (keto_tpu/check/frame.py): framed ranges bring their records with
        them and are resolved without a loop over tuples. Where the
        records cannot be trusted against this snapshot (see
        ``_frame_blocker``) the batch is turned into objects and takes
        the list's path."""
        if isinstance(tuples, QueryBatch):
            why = self._frame_blocker(snap, tuples)
            if why is None:
                got = self._resolve_records(snap, tuples, *self._records_of(snap, tuples))
                if got is not None:
                    return got
                why = "rejected"
            tuples = tuples.tuples(why)
        if hasattr(snap.interned, "resolve_queries"):
            got = self._resolve_bulk_native(snap, tuples)
            if got is not None:
                return got
        return self._resolve_bulk_py(snap, tuples)

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
        """``batch`` as one buffer of query records plus the indices the
        records cannot speak for: ``(buf, special, dead, no_target)``. A
        framed part contributes a slice of its buffer and its flags; a
        part that is a list goes through the framing loop."""
        bufs: list[bytes] = []
        marked: tuple[list, list, list] = ([], [], [])  # special, dead, no_target
        base = 0
        for src, a, b in batch.parts:
            if isinstance(src, QueryFrame):
                off = src.off
                bufs.append(src.buf[int(off[a]) : int(off[b])])
                fl = src.flags[a:b]
                if fl.any():
                    for k, flag in enumerate((SPECIAL, DEAD, NO_TARGET)):
                        marked[k].extend((np.flatnonzero(fl == flag) + base).tolist())
            else:
                buf, *lists = self._frame_tuples(snap, src[a:b])
                bufs.append(buf)
                for k, idxs in enumerate(lists):
                    marked[k].extend(i + base for i in idxs)
            base += b - a
        return (b"".join(bufs), *marked)

    def _resolve_bulk_native(
        self, snap: GraphSnapshot, tuples: Sequence[RelationTuple]
    ):
        """Pack literal queries into the native wire format and resolve them
        in one C++ pass; route the rest through the per-query Python path.
        Returns None when the buffer framing is unsafe (separator bytes in
        strings) — callers fall back to the pure host loop."""
        return self._resolve_records(snap, tuples, *self._frame_tuples(snap, tuples))

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
        self, snap: GraphSnapshot, queries, buf: bytes,
        special: list[int], dead: list[int], no_target: list[int],
    ):
        """Resolve ``len(queries)`` query records in one C++ pass and patch
        in what the records could not say. ``queries`` (a list or a
        ``QueryBatch``) is only asked for the tuples at ``special`` and,
        on a snapshot with nodes the C++ tables do not know, at the
        misses. None when the buffer's framing is unsafe."""
        n = len(queries)
        nl = snap.num_live
        # separator bytes inside strings corrupt framing — detectable as a
        # field-count mismatch, same check as the ingest path
        if buf.count(b"\x1f") != 6 * n or buf.count(b"\x1e") != n:
            return None
        got = snap.interned.resolve_queries(buf, n)
        if got is None:
            return None
        dispatch_clock().poll()
        start_raw, sub_raw = got
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

    # -- public API ----------------------------------------------------------

    def batch_check(
        self,
        tuples: Sequence[RelationTuple],
        *,
        at_least: Optional[int] = None,
        mode: str = "latest",
    ) -> list[bool]:
        """Answer every query: slices pipeline resolve→pack→dispatch (host
        work on slice k+1 overlaps device execution of slice k — dispatch is
        async), then all packed outputs concatenate on device and fetch
        ONCE: the whole request ships 1 bit per query in a single
        transfer, trading a device-side concatenate for fewer D2H fetches
        (the fetch's cost on a directly attached chip is not measured).

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
            out, max_iters = self._run_exact(snap, tuples)
        except Exception as e:
            if self._multiprocess or isinstance(e, KetoError):
                raise
            self._note_device_error(e)
            return self._fallback_check(tuples)
        self._note_device_ok()
        self._after_batch(max_iters, snap)
        self._audit_sample(tuples, out, snap.snapshot_id)
        return out.tolist(), snap.snapshot_id

    def _cap_limit(self, snap: GraphSnapshot) -> int:
        """Iteration count that can NEVER truncate: monotone bitmaps reach
        the fixpoint in at most one pull per active row (each growing pull
        sets ≥ 1 new bit in some active row), +1 for the convergence
        observation."""
        return snap.num_active + 1

    def _run_exact(
        self, snap: GraphSnapshot, tuples: Sequence[RelationTuple], it_cap: Optional[int] = None
    ) -> tuple[np.ndarray, int]:
        """Dispatch + collect with the EXACTNESS guarantee the reference's
        visited-set termination gives for free: a truncated kernel (frontier
        still growing at it_cap) never decides a query. Affected queries
        re-run with an escalating cap, bounded by ``_cap_limit`` — the
        final rung cannot truncate, so every decision comes from a true
        fixpoint."""
        cap = it_cap or self._it_cap
        results = list(self._dispatch_slices(snap, tuples, it_cap=cap))
        out, max_iters, trunc_idx = self._collect(results, len(tuples))
        if trunc_idx:
            limit = self._cap_limit(snap)
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
                cap, len(trunc_idx), new_cap,
            )
            redo, redo_iters = self._run_exact(
                snap, [tuples[i] for i in trunc_idx], it_cap=new_cap
            )
            out[np.asarray(trunc_idx)] = redo
            max_iters = max(max_iters, redo_iters)
        return out, max_iters

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
        gen = self._stream(
            snap, tuples_iter, depth=depth, slice_cap=slice_cap,
            ordered=ordered, with_info=with_info, launch_mark=launch_mark,
        )
        return self._guard_stream(gen), snap.snapshot_id

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
            sd, tg, multi = self._resolve_bulk(snap, [rt])
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

    def _stream(
        self, snap, tuples_iter, *, depth, slice_cap, ordered,
        with_info: bool = False, launch_mark: bool = False,
    ):
        depth = depth or self._dispatch_window
        bound = self._slice_cap(snap)
        if slice_cap:
            bound = min(bound, slice_cap)
        # multi-controller lockstep: every host must dispatch identical
        # slice geometries, and adaptive widths are a per-host latency
        # measurement — pin the deterministic fixed bound instead
        ctrl = None if self._multiprocess else self.stream_ctrl
        stats = self.stream_slice_stats
        lockstep = self._lockstep_verify
        if lockstep:
            from keto_tpu.parallel.lockstep import verify_lockstep
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

        def slices():
            off = 0
            while True:
                cap = min(bound, ctrl.cap()) if ctrl is not None else bound
                clk.enter(RESOLVE)  # pulling the caller's tuples is part of it
                batch = take(cap)
                if not batch:
                    return
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
                        len(batch), batch, [], 0,
                    )
                    off += len(batch)
                    continue
                for dev, host_ans, nq, chunk, leases, n_ent in (
                    self._dispatch_slices(snap, batch)
                ):
                    yield off, dev, host_ans, nq, chunk, leases, n_ent
                    off += nq

        def land(rec):
            # unpack one slice (blocks iff its transfer hasn't finished);
            # a truncated frontier re-runs exactly, mid-stream
            nonlocal max_iters, t_prev_ready
            _seq, off, dev, host_ans, nq, chunk, leases, n_ent, t_disp = rec
            clk.enter(DEVICE_WAIT)
            try:
                out, iters, truncated = self._unpack_slice(dev, host_ans, nq)
            finally:
                # the device output is fetched (or the slice failed and
                # will be re-answered elsewhere): the H2D staging copy is
                # over, the buffers may be re-leased
                self._stage_release(leases)
            clk.enter(FILL)
            if dev is not None and not (
                isinstance(dev, _HybridSlice) and dev.bfs_dev is None
            ):
                self._note_bfs_steps(iters)
            if truncated:
                out, redo_iters = self._run_exact(
                    snap, as_tuples(chunk, "truncated"), it_cap=min(
                        max(self._it_cap * 8, 8), self._cap_limit(snap)
                    ),
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
                    nq, ms, route=route, bfs_steps=int(iters), entries=n_ent
                )
            self._note_route(route, nq, ms)
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

        src = slices()
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
                    off, dev, host_ans, nq, chunk, leases, n_ent = nxt
                    if dev is not None:
                        dev.copy_to_host_async()
                    inflight.append((
                        seq, off, dev, host_ans, nq, chunk, leases, n_ent,
                        time.perf_counter(),
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
            # outputs — their staging buffers may recycle (the same
            # discarded-computation argument as _collect's error path;
            # _stage_release empties each lease list, so a record whose
            # land() already released is a no-op here)
            for rec in inflight:
                self._stage_release(rec[6])
        self._after_batch(max_iters, snap)

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
            cnt[m_ans] += sp_[t + 1] - sp_[t]
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
        walked) in ``_slice_cap`` query slices, yielding
        ``[dev_out | None, host_ans, nq, chunk_tuples]`` records as each
        slice is enqueued (the device chews on earlier slices meanwhile;
        chunk_tuples lets a truncated slice re-run).

        A slice whose resolved fan-out exceeds the entry budget (wildcard
        patterns, high-out-degree static starts) is sub-chunked so entry
        arrays stay within the {B, 2B, 4B} pad geometries — workload can't
        force unbounded allocations or fresh kernel geometries (a single
        monster query still falls through to ``_entry_pad``'s pow2
        fallback; there is no smaller unit to split). The budget is the
        smaller of the geometric 4·B bound and the slice controller's
        PREDICTED-service-time budget (``entry_budget``): a chunk the
        model predicts slow splits BEFORE dispatch, and the stream's
        ready-order window interleaves its sub-slices with fast ones —
        the pre-dispatch half of the slice-tail control loop.

        Yields ``[dev, host_ans, nq, chunk_tuples, leases, n_entries]``;
        ``leases`` are staging buffers released only once the slice has
        landed, ``n_entries`` feeds the controller's entry-cost model."""
        cap_q = self._slice_cap(snap)
        n = len(tuples)
        clk = dispatch_clock()
        for s0 in range(0, n, cap_q):
            s1 = min(s0 + cap_q, n)
            clk.enter(RESOLVE)
            sd, tg, multi = self._resolve_bulk(snap, tuples[s0:s1])
            clk.poll()
            nq = s1 - s0
            W = next(w for w in _WORD_WIDTHS if 32 * w >= nq)
            B = 32 * W
            cap_e = 4 * B
            if not self._multiprocess:
                # service-time-aware split bound (never below one B —
                # the geometric floor keeps slice counts bounded)
                budget = self.stream_ctrl.entry_budget()
                if budget is not None:
                    cap_e = min(cap_e, max(B, budget))
            cnt = self._entry_counts(snap, sd, tg, multi)
            if int(cnt.sum()) > cap_e:
                reach = self._device_reach(snap)
                if reach is not None:
                    # a query whose target side has no row that a pull
                    # changes sends the device nothing (``device_part``):
                    # its entries do not count towards a split
                    known = (tg >= 0) & (tg < snap.num_live)
                    cnt[known & ~reach[np.where(known, tg, 0)]] = 0
            if int(cnt.sum()) <= cap_e:
                bounds = [(0, nq)]
            else:
                csum = np.concatenate([np.zeros(1, np.int64), np.cumsum(cnt)])
                bounds = []
                i0 = 0
                while i0 < nq:
                    i1 = int(np.searchsorted(csum, csum[i0] + cap_e, side="right")) - 1
                    i1 = max(i0 + 1, min(i1, nq))
                    bounds.append((i0, i1))
                    i0 = i1
            use_labels = self._labels_usable(snap)
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
                yield [
                    dev, host_ans, b - a, tuples[s0 + a : s0 + b],
                    leases, int(cnt[a:b].sum()),
                ]

    @staticmethod
    def _decode_packed(f: np.ndarray, host_ans: np.ndarray, nq: int):
        """Decode one kernel's packed ``uint32[W+2]`` output (decision
        bits, iteration count, truncation flag — the single place that
        knows the layout check_step emits): device bits ∪ host-decided
        grants. Returns ``(bool[nq], iters, truncated)``."""
        W = f.shape[0] - 2
        lanes = np.arange(32, dtype=np.uint32)
        bits = ((f[:W, None] >> lanes) & 1).astype(bool).ravel()[:nq]
        return bits | host_ans[:nq], int(f[W]), bool(f[W + 1])

    @staticmethod
    def _decode_label_bits(f: Optional[np.ndarray], nq: int) -> np.ndarray:
        """Label kernel output ``uint32[W]`` → bool[nq] (None → zeros)."""
        if f is None:
            return np.zeros(nq, bool)
        lanes = np.arange(32, dtype=np.uint32)
        return ((f[:, None] >> lanes) & 1).astype(bool).ravel()[:nq]

    @staticmethod
    def _decode_packed_sharded(f: np.ndarray, host_ans: np.ndarray, nq: int):
        """Decode one sharded kernel's packed ``uint32[W+3]`` output
        (decision bits, iterations, truncation, frontier-bit population
        — keto_tpu/parallel/sharded.py). Returns ``(bool[nq], iters,
        truncated, frontier_bits)``."""
        W = f.shape[0] - 3
        lanes = np.arange(32, dtype=np.uint32)
        bits = ((f[:W, None] >> lanes) & 1).astype(bool).ravel()[:nq]
        return bits | host_ans[:nq], int(f[W]), bool(f[W + 1]), int(f[W + 2])

    def _decode_bfs(self, f, host_ans, nq, halo_bytes_per_round=None):
        """Decode one fetched BFS output of either flavor; sharded
        outputs additionally feed the keto_shard_* counters (one halo
        exchange per real hop). Returns ``(bool[nq], iters, trunc)``."""
        if halo_bytes_per_round is not None:
            bits, it, tr, fb = self._decode_packed_sharded(f, host_ans, nq)
            self._note_sharded_stats(it, fb, halo_bytes_per_round)
            return bits, it, tr
        return self._decode_packed(f, host_ans, nq)

    @staticmethod
    def _raw_dev(part):
        """The raw device array behind a slice part (``_ShardedSlice``
        wraps one; everything else IS one)."""
        return part.dev if isinstance(part, _ShardedSlice) else part

    @staticmethod
    def _bfs_halo(part) -> Optional[int]:
        return (
            part.halo_bytes_per_round
            if isinstance(part, _ShardedSlice)
            else None
        )

    def _decode_hybrid(self, lab, bfs, bfs_pos, host_ans, nq, bfs_halo=None):
        """Decode one label-routed slice from fetched arrays: label bits
        for the whole slice, BFS sub-batch bits scattered onto their
        positions. Only the BFS part can truncate."""
        out = self._decode_label_bits(lab, nq)
        iters, trunc = 0, False
        if bfs is not None:
            bits2, iters, trunc = self._decode_bfs(
                bfs, host_ans[bfs_pos], bfs_pos.size, bfs_halo
            )
            out[bfs_pos] = bits2
        return out | host_ans[:nq], iters, trunc

    def _unpack_slice(self, dev, host_ans, nq):
        """One slice's decisions. Returns ``(bool[nq], iters, truncated)``."""
        if dev is None:
            return host_ans[:nq], 0, False
        if isinstance(dev, _HybridSlice):
            lab = (
                jax.device_get(dev.label_dev)
                if dev.label_dev is not None
                else None
            )
            bfs = (
                jax.device_get(self._raw_dev(dev.bfs_dev))
                if dev.bfs_dev is not None
                else None
            )
            return self._decode_hybrid(
                lab, bfs, dev.bfs_pos, host_ans, nq,
                bfs_halo=self._bfs_halo(dev.bfs_dev),
            )
        if isinstance(dev, _ShardedSlice):
            bits, it, tr = self._decode_bfs(
                jax.device_get(dev.dev), host_ans, nq,
                dev.halo_bytes_per_round,
            )
            return bits, it, tr
        return self._decode_packed(jax.device_get(dev), host_ans, nq)

    def _collect(self, results, n: int):
        """Fetch every dispatched slice in ONE device transfer and unpack.
        Returns ``(decisions, max_iters, truncated query indices)`` —
        queries in a truncated slice carry NO decision the caller may use
        (``_run_exact`` re-runs them). Hybrid (label-routed) slices
        contribute their label output and BFS sub-batch to the same
        single transfer."""
        devs: list = []
        for r in results:
            d = r[0]
            if d is None:
                continue
            parts = d.parts() if isinstance(d, _HybridSlice) else [d]
            devs.extend(self._raw_dev(p) for p in parts)
        flat = None
        try:
            if devs:
                cat = jnp.concatenate(devs) if len(devs) > 1 else devs[0]
                cat.copy_to_host_async()
                flat = jax.device_get(cat)
        finally:
            # the single fetch has consumed every slice's staging copy —
            # or the batch failed and its outputs are discarded (the CPU
            # fallback re-answers): either way the leases may recycle
            for rec in results:
                self._stage_release(rec[4])
        out = np.zeros(n, dtype=bool)
        max_iters = 0
        trunc_idx: list[int] = []
        pos = 0
        off = 0

        def take(part):
            nonlocal off
            seg = flat[off : off + part.shape[0]]
            off += part.shape[0]
            return seg

        for dev, host_ans, nq, _, _, _ in results:
            if dev is None:
                out[pos : pos + nq] = host_ans[:nq]
            elif isinstance(dev, _HybridSlice):
                lab = take(dev.label_dev) if dev.label_dev is not None else None
                bfs = (
                    take(self._raw_dev(dev.bfs_dev))
                    if dev.bfs_dev is not None
                    else None
                )
                bits, it, tr = self._decode_hybrid(
                    lab, bfs, dev.bfs_pos, host_ans, nq,
                    bfs_halo=self._bfs_halo(dev.bfs_dev),
                )
                out[pos : pos + nq] = bits
                if bfs is not None:
                    self._note_bfs_steps(it)
                max_iters = max(max_iters, it)
                if tr:
                    trunc_idx.extend(range(pos, pos + nq))
            else:
                bits, it, tr = self._decode_bfs(
                    take(self._raw_dev(dev)), host_ans, nq,
                    self._bfs_halo(dev),
                )
                out[pos : pos + nq] = bits
                self._note_bfs_steps(it)
                max_iters = max(max_iters, it)
                if tr:
                    trunc_idx.extend(range(pos, pos + nq))
            pos += nq
        return out, max_iters, trunc_idx

    def _note_bfs_steps(self, iters: int) -> None:
        """One landed slice that ran ``check_step``: its pulls, for bench's
        percentiles and for ``keto_check_bfs_steps_total`` /
        ``keto_check_bfs_slices_total``."""
        self.bfs_steps_stats.observe(float(iters))
        self.maintenance.incr("bfs_slices")
        if iters:
            self.maintenance.incr("bfs_steps", by=int(iters))

    def _note_route(self, route: str, nq: int, ms: float) -> None:
        """Record one landed slice's route (label | hybrid | bfs | host |
        cpu) for the per-route breakdown bench and
        ``keto_stream_route_slices_total`` read."""
        st = self._route_stats.get(route)
        if st is None:
            st = self._route_stats.setdefault(route, DurationStats())
        st.observe(ms)
        self._route_slices[route] += 1
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
                "slices": int(self._route_slices.get(route, 0)),
                "queries": int(self._route_queries.get(route, 0)),
                "p50_ms": snap["p50_ms"],
                "p99_ms": snap["p99_ms"],
                "mean_ms": snap["mean_ms"],
            }
        return out

    def route_slice_counts(self) -> dict:
        """route → landed slice count (the keto_stream_route_slices_total
        scrape callback)."""
        return dict(self._route_slices)

    def reset_route_stats(self) -> None:
        """Zero the per-route breakdown (bench passes start fresh)."""
        self._route_stats.clear()
        self._route_slices.clear()
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
        """The label fast path for one sub-chunk: resolve the chunk with
        the SAME host machinery as the BFS path (``pack_chunk`` — host
        walk, sink gathers, host-decided grants), then answer every
        label-certifiable query with ONE intersection kernel step and
        ride the rest on a compacted BFS sub-batch, bit-identically.

        The reach0 mapping (see keto_tpu/graph/labels.py):

        - a query's **pairs** are (seed row u) × (target-side row r):
          the interior target itself, or a sink target's interior
          in-neighbor gathers (``a_rows`` — exactly what the BFS kernel
          gathers from the fixpoint);
        - an e1 seed equal to an interior target would conflate reach0
          with the "via ≥ 1 edge" rule — that query falls back (the
          kernel's R0-vs-pull distinction, which labels don't carry);
          an e2 seed equal to the target was reached via a real edge on
          the host walk, so ``host_ans`` already granted it and the pair
          drops;
        - wildcard/multi-start queries, uncertifiable pairs (coverage
          gaps), and over-fanout queries fall back.
        """
        idx = snap.labels
        if idx is None or self._labels_dev(snap) is None:
            # the eviction ladder dropped the labels between routing and
            # dispatch (concurrent OOM containment): BFS answers instead
            return self._device_batch(snap, sd, tg, multi, i0, i1, W, it_cap=it_cap)
        clk = dispatch_clock()
        clk.enter(PACK)
        packed, host_ans = pack_chunk(
            snap, sd, tg, multi, i0, i1, W, native=self._native_pack
        )
        clk.poll()
        nq = i1 - i0
        leases: list = []
        if packed is None:
            return None, host_ans, leases  # nothing reaches any device path
        (e1r, e1q, e2r, e2q, ar, aq, targets) = packed
        ni = snap.num_int
        B = 32 * W
        tq = np.asarray(targets[:nq], np.int64)
        t_int = tq < ni

        fallback = np.zeros(nq, bool)
        for i in multi:
            if i0 <= i < i1:
                fallback[i - i0] = True
        # why each query left the label path, first cause wins
        # (keto_label_fallbacks_total{reason})
        reasons = {"multi": int(np.count_nonzero(fallback))}

        def fall_back(reason: str, where) -> None:
            fresh = np.zeros(nq, bool)
            fresh[where] = True
            fresh &= ~fallback
            reasons[reason] = reasons.get(reason, 0) + int(np.count_nonzero(fresh))
            fallback[where] = True

        # valid (non-padding) entries; e1/e2 pad with row ni+1, a with ni
        m1 = (e1r != ni + 1) & (e1q < nq)
        m2 = (e2r != ni + 1) & (e2q < nq)
        ma = (ar != ni) & (aq < nq)
        s_rows = np.concatenate([e1r[m1], e2r[m2]]).astype(np.int64)
        s_q = np.concatenate([e1q[m1], e2q[m2]]).astype(np.int64)
        # e1 seed == interior target: reach0 would count the 0-edge path
        e1_rows_v = e1r[m1].astype(np.int64)
        e1_q_v = e1q[m1].astype(np.int64)
        self_hit = t_int[e1_q_v] & (e1_rows_v == tq[e1_q_v])
        if self_hit.any():
            fall_back("self_hit", e1_q_v[self_hit])

        # target-side rows per query: the interior target, or the sink
        # answer-gather rows
        b_rows = np.concatenate(
            [tq[t_int], ar[ma].astype(np.int64)]
        )
        b_q = np.concatenate([np.nonzero(t_int)[0], aq[ma].astype(np.int64)])

        # count each side per query first: a query over the pair cap takes
        # neither side into the sort and the cross-join below
        ns = np.bincount(s_q, minlength=nq)
        nr = np.bincount(b_q, minlength=nq)
        over = ns * nr > self._LABEL_PAIR_CAP
        if over.any():
            fall_back("pair_cap", over)

        def rides_whole() -> bool:
            # a sub-batch as wide as the slice holds the slice: the queries
            # the label kernel could take ride it too, and the label kernel
            # is not launched
            n = int(np.count_nonzero(fallback))
            return n > 0 and next(w for w in _WORD_WIDTHS if 32 * w >= n) >= W

        pa = pb = pq = np.zeros(0, np.int64)
        whole = rides_whole()
        if not whole:
            keep_s = ~fallback[s_q]
            keep_b = ~fallback[b_q]
            s_rows, s_q = s_rows[keep_s], s_q[keep_s]
            b_rows, b_q = b_rows[keep_b], b_q[keep_b]
            # group both sides by query, then cross-join per query
            so = np.argsort(s_q, kind="stable")
            s_rows, s_q = s_rows[so], s_q[so]
            bo = np.argsort(b_q, kind="stable")
            b_rows, b_q = b_rows[bo], b_q[bo]
            ns = np.bincount(s_q, minlength=nq) if s_q.size else np.zeros(nq, np.int64)
            nr = np.bincount(b_q, minlength=nq) if b_q.size else np.zeros(nq, np.int64)
            rep_nr = np.repeat(nr, ns)  # aligned to s_rows
            total = int(rep_nr.sum())
            if total:
                b_starts = np.cumsum(nr) - nr
                seed_q = s_q
                base = np.repeat(b_starts[seed_q], rep_nr)
                csum = np.cumsum(rep_nr) - rep_nr
                within = np.arange(total) - np.repeat(csum, rep_nr)
                pa = np.repeat(s_rows, rep_nr)
                pb = b_rows[base + within]
                pq = np.repeat(seed_q, rep_nr)
                # e2-seed == target pairs: already host-granted, reach0 would
                # double-count the 0-edge path — drop (e1 cases fell back)
                drop = t_int[pq] & (pa == pb)
                if drop.any():
                    pa, pb, pq = pa[~drop], pb[~drop], pq[~drop]
                # coverage: a miss on an uncertifiable pair is not a deny
                cert = idx.certifiable(pa, pb)
                if not cert.all():
                    fall_back("uncertifiable", np.unique(pq[~cert]))
                    keep = ~fallback[pq]
                    pa, pb, pq = pa[keep], pb[keep], pq[keep]
                    whole = rides_whole()
        if whole:
            fall_back("whole_slice", ~fallback)

        n_fb = int(np.count_nonzero(fallback))
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
        if pa.size:
            faults.check("device-exec")
            P = _entry_pad(B, pa.size)
            dl = self._labels_dev(snap)
            lmet = None
            if self._mesh is None:
                own = (P, B)
                lshape, lfixed = self._label_shape(dl), self._label_fixed()
                (P, B), lmet = self._geoms.meet("label", lshape, lfixed, own)
            pad = P - pa.size
            stg = self._stage_acquire(3 * P) if self._mesh is None else None
            if stg is not None:
                leases.append(stg)
                entries = stg
                entries[:P] = np.concatenate([pa, np.full(pad, ni, np.int64)])
                entries[P : 2 * P] = np.concatenate(
                    [pb, np.full(pad, ni, np.int64)]
                )
                entries[2 * P :] = np.concatenate(
                    [pq, np.zeros(pad, np.int64)]
                )
            else:
                entries = np.concatenate(
                    [
                        np.concatenate([pa, np.full(pad, ni, np.int64)]),
                        np.concatenate([pb, np.full(pad, ni, np.int64)]),
                        np.concatenate([pq, np.zeros(pad, np.int64)]),
                    ]
                ).astype(np.int32)
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
                if self._multiprocess:
                    from jax.sharding import NamedSharding, PartitionSpec as P_

                    def put_pairs():
                        return jax.device_put(
                            entries, NamedSharding(self._mesh, P_())
                        )

                else:
                    def put_pairs():
                        return jnp.asarray(entries)

                lkern = self._entry_kernels()[1]
                ldev = self._guard_alloc(
                    "label-kernel",
                    lambda: lkern(dl[0], dl[1], put_pairs(), n_pairs=P, B=B),
                )
                if lmet == INLINE:
                    self._geoms.add("label", lshape, lfixed, own)

        bfs_dev = None
        bfs_pos = None
        if n_fb:
            pos = np.nonzero(fallback)[0]
            gidx = pos + i0
            multi2 = {
                j: multi[int(i)] for j, i in enumerate(gidx) if int(i) in multi
            }
            W2 = next(w for w in _WORD_WIDTHS if 32 * w >= n_fb)
            clk.enter(PACK)
            packed2, host2 = pack_chunk(
                snap, sd[gidx], tg[gidx], multi2, 0, n_fb, W2,
                native=self._native_pack,
            )
            if packed2 is not None:
                faults.check("device-exec")
                sub, bfs_leases = self._launch_check(
                    snap, packed2, host2, it_cap, "hybrid", sub_of=pos
                )
                leases.extend(bfs_leases)
                host_ans[pos] |= host2  # what the host granted without the device
                if sub is not None:
                    bfs_dev, bfs_pos = sub.bfs_dev, sub.bfs_pos
        if ldev is None and bfs_dev is None:
            return None, host_ans, leases
        return _HybridSlice(ldev, bfs_dev, bfs_pos), host_ans, leases

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
        route: str = "bfs",
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
        packed, host_ans = pack_chunk(
            snap, sd, tg, multi, i0, i1, force_W, native=self._native_pack
        )
        if packed is None:
            # no query in the chunk reaches the device: host_ans is the
            # whole answer
            return None, host_ans, []
        dev, leases = self._launch_check(snap, packed, host_ans, it_cap, route)
        return dev, host_ans, leases

    def _launch_check(
        self, snap: GraphSnapshot, packed, host_ans: np.ndarray,
        it_cap: Optional[int], route: str, sub_of: Optional[np.ndarray] = None,
    ):
        """Ship what of one packed chunk the device has to see
        (``device_part``: the rest is granted into ``host_ans`` here) to
        ``check_step``. Returns ``(slice, leases)``: a ``_HybridSlice`` of
        no label part whose BFS part answers the chunk's positions that
        needed the device, or None where none does. ``sub_of`` says where
        the chunk's own queries sit in a wider slice."""
        clk = dispatch_clock()
        leases: list = []
        it_cap = it_cap or self._it_cap
        if self._sharded and snap.device_shards is not None:
            dev = self._dispatch_sharded(snap, packed, it_cap, leases=leases)
            return (dev if sub_of is None else _HybridSlice(None, dev, sub_of)), leases
        packed, pos = device_part(snap, packed, host_ans)
        if packed is None:
            return None, leases
        if sub_of is not None:
            pos = sub_of[pos]
        stg = met = None
        if self._mesh is None:
            own = tuple(packed[i].shape[0] for i in (0, 2, 4, 6))
            shape, fixed = self._check_shape(snap), self._check_fixed(it_cap)
            use, met = self._geoms.meet("check", shape, fixed, own)
            if use != own:
                packed = _pad_packed(packed, use, snap.num_int)
            stg = self._stage_acquire(sum(a.shape[0] for a in packed))
            if stg is not None:
                leases.append(stg)
        buf, sizes = pack_entries(packed, out=stg)
        clk.enter(LAUNCH, (route, "check_step", sizes, met))
        ov = snap.device_overlay

        def put_entries():
            # inside the guarded call: the donated path consumes its
            # device buffer, so an OOM retry must re-stage from host
            if self._multiprocess:
                # multi-controller runtime: jit inputs must be global
                # arrays; every process holds identical host data (the
                # lockstep contract, parallel/mesh.py init_distributed)
                from jax.sharding import NamedSharding, PartitionSpec as P

                return jax.device_put(buf, NamedSharding(self._mesh, P()))
            return jnp.asarray(buf)

        kern = self._entry_kernels()[0]
        dev = self._guard_alloc(
            "check-kernel",
            lambda: kern(
                snap.device_buckets,
                put_entries(),
                ov_nbrs=None if ov is None else ov[0],
                ov_dst=None if ov is None else ov[1],
                sizes=sizes,
                n_active=snap.num_active,
                n_int=snap.num_int,
                valid_rows=tuple(b.n for b in snap.buckets),
                it_cap=it_cap,
                block_iters=self._block_iters,
                bitmap_sharding=self._bitmap_sharding_for(sizes[3]),
            ),
        )
        if met == INLINE:
            self._geoms.add("check", shape, fixed, own)
        return _HybridSlice(None, dev, pos), leases

    def _dispatch_sharded(
        self, snap: GraphSnapshot, packed, it_cap: int, leases=None
    ):
        """Route one packed chunk's entries to their owning shards and
        launch the shard_map BFS kernel (keto_tpu/parallel/sharded.py).
        Returns a ``_ShardedSlice`` whose packed ``uint32[W+3]`` output
        the collect paths decode — decisions bit-identical to the
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

    def subject_is_allowed(self, requested: RelationTuple) -> bool:
        """Single-query convenience with the oracle engine's signature
        (reference internal/check/engine.go:93-95)."""
        return self.batch_check([requested])[0]
