"""The device programs of the check path: bit-packed multi-source BFS and
the 2-hop label intersection.

- up to 32·W queries are packed into a ``uint32[n_live+1, W]`` reached
  bitmap ``R`` — bit ``q%32`` of word ``q//32`` in row ``v`` means "query q
  has reached node v". Only nodes *with in-edges* ("live") get bitmap rows;
  zero-in-degree ("static") nodes never change and are handled by
  propagating their start bits one hop on the host at batch setup
  (``pack.pack_chunk``), which both seeds ``R`` and pre-computes their
  contribution to the answer;
- one BFS step is a **pull**: ``P[v] = OR over live in-neighbors s of
  R[s]``, computed per degree bucket as a gather + OR-reduction over
  *live→live* edges only (see keto_tpu/graph/snapshot.py for the layout
  rationale). Rows that can change ("active") form a prefix of the bitmap;
  the loop updates them in place via an aliased carry — nothing the size of
  the full graph is ever copied per step;
- on one device a pull is a **sweep over the buckets in place** (``sweep``
  = ``"up"`` / ``"down"``, by ascending or descending device id): a bucket's
  rows are written into the carry before the next bucket's gather is built,
  so a bit crosses as many edges in one pull as the sweep's order lays end
  to end, and a slice converges in fewer pulls than its longest shortest
  path. The update only ORs bits in, over a finite lattice, so every order
  of applying the bucket updates reaches the same least fixpoint above the
  seeds (chaotic iteration); and the sweep in which no bucket changed
  computed every bucket's pull from a carry that already was the fixpoint,
  so the pull it carries out of the loop is ``pull(fixpoint)``, bit for bit
  what the whole-step form carries. Which order needs fewer pulls is the
  graph's (an edge count does not predict it): ``check/dispatch.py``
  ``_settle_block_iters`` probes the snapshot under both at warm-up and
  keeps the better. On a mesh the buckets are sharded by rows and pulled
  **whole** (``"whole"``: every bucket from the same carry, one all-gather
  a pull where a sweep in place would make it one a bucket);
- ``lax.while_loop`` iterates to the reachability fixpoint (the analog of
  the reference's visited-set cycle guard — monotone bitmaps make cycles
  terminate for free); ``it_cap`` is no depth limit (a slice it cuts is
  re-run to the exact fixpoint), and each sweep that changes something sets
  a new bit, so ``num_active + 1`` sweeps never truncate under any order;
- the answer for query q is the target-row bit of ``pull(fixpoint) ∪
  one-hop-term``, i.e. "reached via ≥ 1 edge", reproducing the reference's
  rule that a subject only matches via an actual tuple, never by being the
  queried set itself. The fixpoint pull is carried out of the loop (the
  converging iteration already computed it) — no extra answer pass.

Nothing here knows of an engine: ``check/dispatch.py`` launches these,
``list/tpu_engine.py`` builds on ``pull``, ``__graft_entry__.py`` compiles
``check_step`` ahead of time.
"""

from __future__ import annotations

import itertools
from functools import partial
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

# cap on the [rows, chunk, W] gather intermediate per bucket
_DEGREE_CHUNK = 1024

#: the order one pull of ``check_step`` updates the buckets in: in place by
#: ascending device id, in place by descending, or all from the same carry
SWEEPS = ("up", "down", "whole")
IN_PLACE_SWEEPS = SWEEPS[:2]


def pull(
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
    hub_nbrs: Optional[jnp.ndarray] = None,  # int32[Kh, C] relay rows of hub sinks (pad → n_int)
    *,
    sizes: tuple[int, int, int, int],  # (S1, S2, SA, B)
    n_active: int,
    n_int: int,
    valid_rows: tuple[int, ...],
    it_cap: int,
    block_iters: int = 8,
    bitmap_sharding=None,  # NamedSharding for the [rows, words] bitmaps
    sweep: str = "whole",  # SWEEPS: the order a pull updates the buckets in
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
    #   a_rows   int32[SA] interior in-neighbors of sink targets; with
    #                      ``hub_nbrs``, n_int+1+k names relay row k
    #   a_q      int32[SA] owning query index (padding → 0 w/ row n_int)
    #   targets  int32[B]  interior target rows, n_int = none
    if sweep not in SWEEPS:
        raise ValueError(f"sweep {sweep!r} is none of {SWEEPS}")
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
        if bitmap_sharding is None:
            # one device: the rows a bucket is padded with (to a power of
            # two) are never read, and gathering them is a third of a pull
            # on a graph whose buckets are half full. On a mesh the
            # buckets are sharded by rows and are pulled whole, as before
            bucket_nbrs = tuple(b[:n] for b, n in zip(bucket_nbrs, valid_rows))

        def in_A(ids):
            return jnp.minimum(ids, n_active)

        def in_R0(ids):
            return jnp.where(ids >= n_active, ids, n_int)

        p_passive = pull(bucket_nbrs, valid_rows, R0, in_R0)
        if ov_nbrs is not None:
            # delta-overlay edges (inserts since the base snapshot
            # build, keto_tpu/graph/overlay.py): OR the overlay
            # in-neighbors into their unique destination rows. Inside
            # the loop, so multi-hop paths through delta edges converge
            # exactly like base edges.
            ovo = lax.reduce(R0[in_R0(ov_nbrs)], np.uint32(0), lax.bitwise_or, (1,))
            p_passive = p_passive.at[ov_dst].set(p_passive[ov_dst] | ovo, mode="drop")

        if bitmap_sharding is not None:
            # row-sharded buckets are pulled whole, one all-gather a pull:
            # a sweep in place would make it one a bucket
            sweep = "whole"

        def ov_pull(A):
            return lax.reduce(A[in_A(ov_nbrs)], np.uint32(0), lax.bitwise_or, (1,))

        def step_whole(st):
            A, _, _, it = st
            p = pull(bucket_nbrs, valid_rows, A, in_A) | p_passive
            if ov_nbrs is not None:
                p = p.at[ov_dst].set(p[ov_dst] | ov_pull(A), mode="drop")
            act = A[:n_active]
            nxt = lax.bitwise_or(p, act)
            return A.at[:n_active].set(nxt), p, jnp.any(nxt != act), it + 1

        offsets = [0, *itertools.accumulate(valid_rows)]  # a bucket's first row
        order = range(len(bucket_nbrs))[:: -1 if sweep == "down" else 1]

        def step_in_place(st):
            # Gauss-Seidel over the monotone OR: bucket b's gather reads the
            # rows that the buckets before it in this sweep have just
            # written, so a bit can cross several edges in one pull
            A, _, _, it = st
            changed = jnp.bool_(False)
            parts = [None] * len(bucket_nbrs)
            for b in order:
                off, n_b = offsets[b], valid_rows[b]
                p_b = pull(bucket_nbrs[b : b + 1], (n_b,), A, in_A) | p_passive[off : off + n_b]
                old = A[off : off + n_b]
                new = lax.bitwise_or(p_b, old)
                changed |= jnp.any(new != old)
                A = lax.dynamic_update_slice(A, new, (off, 0))
                parts[b] = p_b
            p = jnp.concatenate(parts, axis=0) if len(parts) > 1 else parts[0]
            if ov_nbrs is not None:
                # the overlay's rows last, into the carry and the pull both
                # (ov_dst pads with n_active, which p drops and A holds as
                # its all-zero row: nothing may be written there)
                real = (ov_dst < n_active)[:, None]
                ovo = jnp.where(real, ov_pull(A), 0)
                old = A[ov_dst]
                changed |= jnp.any((ovo & ~old) != 0)
                A = A.at[ov_dst].set(old | ovo)
                p = p.at[ov_dst].set(p[ov_dst] | ovo, mode="drop")
            return A, p, changed, it + 1

        step = step_whole if sweep == "whole" else step_in_place

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
    if hub_nbrs is None:
        fix = R0[a_rows, aw]
        if A_fix is not None:
            fix = jnp.where(a_rows < n_active, A_fix[jnp.minimum(a_rows, n_active), aw], fix)
    else:
        # hub sinks (a user in groups by the hundred, a target that every
        # query on it would gather row by row): the snapshot keeps their
        # in-neighbor lists as relay rows of C, OR-reduced here once a
        # slice for all its queries by the row gather a pull is made of,
        # and an entry names a relay row where it would have named C rows
        F = R0 if A_fix is None else R0.at[:n_active].set(A_fix[:n_active])
        block = max(1, _DEGREE_CHUNK * 64 // hub_nbrs.shape[1])  # a bucket's worth of slots
        relay = [
            lax.reduce(F[hub_nbrs[r0 : r0 + block]], np.uint32(0), lax.bitwise_or, (1,))
            for r0 in range(0, hub_nbrs.shape[0], block)
        ]
        fix = jnp.concatenate([F] + relay, axis=0)[a_rows, aw]
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


#: jitted entrypoint the dispatch launches; ``check_step`` stays un-jitted for
#: ahead-of-time compile checks (__graft_entry__.py)
def _jit_check(**kw):
    return jax.jit(
        check_step,
        static_argnames=(
            "sizes", "n_active", "n_int", "valid_rows", "it_cap", "block_iters",
            "bitmap_sharding", "sweep",
        ),
        **kw,
    )


_check_kernel = _jit_check()

#: donated variant: the ``entries`` staging buffer (arg 1) is donated to
#: the computation, so XLA aliases its device memory into the (much
#: smaller) packed output instead of allocating a fresh result buffer —
#: and the staging allocation is released the moment the kernel consumes
#: it, not when Python GC finds the array. Per-slice churn on the hot
#: path drops to: one H2D copy into memory the allocator just got back
#: from slice k-1. The dispatch only routes here when the backend actually
#: implements donation (``_donation_default``); elsewhere donation is a
#: silent no-op plus a warning, so the plain kernel is used instead.
_check_kernel_donated = _jit_check(donate_argnums=(1,))


def _donation_default() -> bool:
    """Donate entry buffers? XLA implements input-output aliasing for
    device-memory backends (TPU/GPU), while the CPU backend ignores the
    donation and warns."""
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
