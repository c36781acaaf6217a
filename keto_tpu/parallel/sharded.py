"""Explicitly sharded multi-chip serving: row-range shards + halo exchange.

The GSPMD path (``TpuCheckEngine(mesh=..., shard_rows=True)``) hands XLA a
globally-addressed program and lets the SPMD partitioner infer the
cross-shard traffic each BFS pull needs. That works, but it hides the one
number that matters at pod scale — how many bytes of frontier bitmap cross
the interconnect per hop — and it gives the partitioner license to fall
back to full rematerialization on shapes it dislikes. This module is the
explicit alternative the sharded engine mode runs:

- the interior bitmap rows ``[0, num_int]`` are partitioned into
  **contiguous row-range shards** along the mesh's ``graph`` axis
  (``graph/device_build.shard_row_ranges`` — the same assignment the
  snapshot cache stripes its segments with). Row-range shards keep the
  bucket/sentinel machinery intact per shard: each shard's slice of a
  degree bucket is still a dense ELL matrix gathered exactly like the
  single-device kernel's, just scattered into shard-local slab rows;
- query slices **replicate along the ``data`` axis** (every data column
  holds the full word range), so the graph axis is the only axis any
  collective crosses;
- one BFS hop inside ``shard_map`` is: **local gather-OR** over the
  shard's bucket rows against the halo-exchanged full bitmap, then the
  **halo exchange** itself — ``lax.all_gather`` of each shard's
  ``[rows_per_shard, W]`` frontier slab over the ``graph`` axis — with no
  host round-trips between hops (the whole fixpoint loop is one device
  program, same ``lax.while_loop``/block structure as ``check_step``);
- the 2-hop label intersection kernel shards the label arrays by the same
  row ownership and resolves each pair's two row reads with a **one-shot
  pair-row exchange**: every shard contributes its owned rows (zeros
  elsewhere) and one ``lax.psum`` over the graph axis reconstructs both
  sides of every pair everywhere — exactly one collective, no iteration.

Decisions are **bit-identical** to the single-device kernels by
construction: the per-hop pull computes the same OR over the same edges
(OR is associative/commutative; bits are bits), so the fixpoint, the
iteration count, and the truncation flag all match —
tests/test_sharded_serving.py fuzz-asserts equality against both the
single-device engine and the CPU oracle across overlay churn, tombstones,
wildcards, and compactions.

The packed output widens by one trailing word: ``uint32[W+3]`` = decision
bits, iteration count, truncation flag, **frontier-bit population** of the
fixpoint bitmap (summed over shards) — the engine turns iterations into
``keto_shard_halo_rounds_total`` (one all-gather per real hop) and the
population into ``keto_shard_frontier_bits_total``.
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import lru_cache, partial
from typing import Optional, Sequence

import numpy as np

from keto_tpu.graph.device_build import shard_row_ranges
from keto_tpu.parallel.mesh import DATA_AXIS, GRAPH_AXIS

#: cap on the [rows, chunk, W] gather intermediate per bucket — matches
#: the single-device kernel's so per-hop peak memory stays comparable
_DEGREE_CHUNK = 1024

#: cap on the [pairs, Wo, Wi] compare intermediate of the label kernel
_LABEL_PAIR_CHUNK = 2048


def _ceil_pow2(x: int) -> int:
    return 1 if x <= 1 else 1 << (int(x) - 1).bit_length()


def _entry_pad(B: int, size: int) -> int:
    """Entry arrays pad to B·2^k (the same geometry rule as the
    single-device path) so repeated dispatches hit the same jit entry."""
    sp = max(1, B)
    while sp < size:
        sp *= 2
    return sp


@dataclass
class ShardSpec:
    """Host-side description of one snapshot's row-range partitioning.

    Built once per uploaded snapshot (``make_shard_spec``); everything a
    dispatch needs to route seeds/targets/answer-gathers to their owning
    shard, and everything a delta needs to route ELL patches
    (``patch_pos``) to the stacked device array slot that owns the
    patched bucket row.
    """

    n_shards: int
    rows_per_shard: int  # bitmap slab rows per shard (covers num_int+1)
    n_int: int
    n_active: int
    #: per bucket: stacked per-shard gather matrices int32[g, rb, cap]
    #: (sentinel n_int = the global all-zero bitmap row) and their local
    #: scatter rows int32[g, rb] (sentinel rows_per_shard = dropped)
    nbrs_sh: tuple
    dst_sh: tuple
    #: per bucket: int64[g] first bucket-local row owned by each shard
    #: (clipped into [0, bucket.n]) — the patch-routing origin
    bucket_lo: tuple
    #: device bytes of each shard's OWNED (unpadded) bucket rows — the
    #: per-shard HBM ledger entry for the ``snapshot`` tag
    owned_bucket_bytes: list

    def patch_pos(self, bucket_offset: int, bi: int, row: int) -> tuple:
        """(shard, stacked-row) owning bucket ``bi``'s local ``row``."""
        g_row = bucket_offset + row
        s = min(g_row // self.rows_per_shard, self.n_shards - 1)
        return s, row - int(self.bucket_lo[bi][s])

    def padded_bucket_bytes(self) -> int:
        """Total device bytes of the stacked bucket arrays as uploaded."""
        return sum(int(a.nbytes) for a in self.nbrs_sh) + sum(
            int(a.nbytes) for a in self.dst_sh
        )


def make_shard_spec(snap, n_shards: int) -> ShardSpec:
    """Partition ``snap``'s buckets into ``n_shards`` row-range shards.

    Shard ``s`` owns bitmap rows ``[s*rps, (s+1)*rps)`` where ``rps``
    covers ``num_int + 1`` rows (the +1 is the all-zero sentinel row).
    Each bucket's member rows are contiguous in device-id order, so a
    shard's slice of a bucket is a contiguous row range — sliced, padded
    to a shared pow2 row count (sentinel gather rows + dropped scatter
    rows), and stacked along a leading shard axis for ``shard_map``.
    """
    g = max(1, int(n_shards))
    ranges = shard_row_ranges(snap.num_int + 1, g)
    rps = ranges[0][1] - ranges[0][0] if ranges[0][1] > ranges[0][0] else 1
    sentinel = np.int32(snap.num_int)
    nbrs_sh: list = []
    dst_sh: list = []
    bucket_lo: list = []
    owned = [0] * g
    for b in snap.buckets:
        nbrs = np.asarray(b.nbrs)
        cap = nbrs.shape[1]
        lo = np.clip([s * rps - b.offset for s in range(g)], 0, b.n)
        hi = np.clip([(s + 1) * rps - b.offset for s in range(g)], 0, b.n)
        rb = _ceil_pow2(int(np.max(hi - lo)) or 1)
        sb = np.full((g, rb, cap), sentinel, np.int32)
        db = np.full((g, rb), rps, np.int32)
        for s in range(g):
            l, h = int(lo[s]), int(hi[s])
            k = h - l
            if k <= 0:
                continue
            sb[s, :k] = nbrs[l:h]
            db[s, :k] = (b.offset + np.arange(l, h)) - s * rps
            owned[s] += k * cap * 4
        nbrs_sh.append(np.ascontiguousarray(sb))
        dst_sh.append(np.ascontiguousarray(db))
        bucket_lo.append(lo.astype(np.int64))
    return ShardSpec(
        n_shards=g,
        rows_per_shard=rps,
        n_int=snap.num_int,
        n_active=snap.num_active,
        nbrs_sh=tuple(nbrs_sh),
        dst_sh=tuple(dst_sh),
        bucket_lo=tuple(bucket_lo),
        owned_bucket_bytes=owned,
    )


def _route_rows(
    rows: np.ndarray, qs: np.ndarray, g: int, rps: int, drop_row: int, B: int
):
    """Route (row, query) entry pairs to their owning shard: stacked
    ``int32[g, P]`` local rows (sentinel ``rps`` = not owned / padding —
    out of the ``[rps, W]`` slab, so scatters drop and gathers mask) and
    their queries. ``drop_row`` marks the input's padding sentinel."""
    rows = np.asarray(rows, np.int64)
    qs = np.asarray(qs, np.int64)
    valid = rows != drop_row
    owner = np.minimum(np.where(valid, rows // rps, 0), g - 1)
    counts = np.bincount(owner[valid], minlength=g)
    P = _entry_pad(B, int(counts.max()) if counts.size else 0)
    out_r = np.full((g, P), rps, np.int32)
    out_q = np.zeros((g, P), np.int32)
    for s in range(g):
        sel = valid & (owner == s)
        k = int(np.count_nonzero(sel))
        if k:
            out_r[s, :k] = rows[sel] - s * rps
            out_q[s, :k] = qs[sel]
    return out_r, out_q, P


def route_entries(spec: ShardSpec, packed, B: int, out=None, out_alloc=None):
    """Split pack_chunk's seven arrays by row ownership into the sharded
    kernel's single stacked ``int32[g, L]`` entry buffer + static sizes.

    Seeds (e1/e2) scatter into the owner's slab; answer gathers (a) read
    the owner's fixpoint rows; targets become per-shard local rows with
    a not-owned sentinel — every shard receives the full query axis (the
    ``data`` replication) but only its own rows.

    ``out`` (an int32 ``[g, L]`` buffer) or ``out_alloc`` (a
    ``shape -> buffer|None`` allocator — the engine's staging-pool seam;
    the stacked width L is only known after routing) receives the
    concatenation in place, so repeated dispatches reuse one host
    staging buffer instead of allocating per slice.
    """
    (e1r, e1q, e2r, e2q, ar, aq, targets) = packed
    g, rps, ni = spec.n_shards, spec.rows_per_shard, spec.n_int
    r1, q1, S1 = _route_rows(e1r, e1q, g, rps, ni + 1, B)
    r2, q2, S2 = _route_rows(e2r, e2q, g, rps, ni + 1, B)
    ra, qa, SA = _route_rows(ar, aq, g, rps, ni, B)
    t = np.asarray(targets, np.int64)
    t_sh = np.full((g, t.shape[0]), rps, np.int32)
    for s in range(g):
        own = (t >= s * rps) & (t < (s + 1) * rps)
        t_sh[s, own] = (t[own] - s * rps).astype(np.int32)
    parts = [r1, q1, r2, q2, ra, qa, t_sh]
    if out is None and out_alloc is not None:
        L = sum(p.shape[1] for p in parts)
        out = out_alloc((g, L))
    if out is not None and out.shape == (g, sum(p.shape[1] for p in parts)):
        entries = np.concatenate(parts, axis=1, out=out)
    else:
        entries = np.concatenate(parts, axis=1)
    return np.ascontiguousarray(entries), (S1, S2, SA, t.shape[0])


def route_overlay(
    spec: ShardSpec, nbrs: np.ndarray, dst: np.ndarray, num_active: int
):
    """Route the overlay-ELL gather matrix by destination-row ownership:
    stacked ``int32[g, K, C]`` neighbor matrices (sentinel n_int) and
    ``int32[g, K]`` local destination rows (sentinel rps = dropped)."""
    g, rps = spec.n_shards, spec.rows_per_shard
    dst = np.asarray(dst, np.int64)
    valid = dst < num_active
    owner = np.minimum(np.where(valid, dst // rps, 0), g - 1)
    counts = np.bincount(owner[valid], minlength=g)
    K = _ceil_pow2(int(counts.max()) if counts.size else 0)
    C = nbrs.shape[1]
    out_n = np.full((g, K, C), spec.n_int, np.int32)
    out_d = np.full((g, K), rps, np.int32)
    owned_bytes = [0] * g
    for s in range(g):
        sel = valid & (owner == s)
        k = int(np.count_nonzero(sel))
        if k:
            out_n[s, :k] = nbrs[sel]
            out_d[s, :k] = (dst[sel] - s * rps).astype(np.int32)
            owned_bytes[s] = k * (C + 1) * 4
    return (
        np.ascontiguousarray(out_n),
        np.ascontiguousarray(out_d),
        owned_bytes,
    )


def route_labels(out_lab: np.ndarray, in_lab: np.ndarray, n_shards: int):
    """Stack the label arrays into per-shard row stripes
    ``int32[g, rl, W]`` padded with each side's own sentinel (padded rows
    can never witness an intersection). Returns ``(out_sh, in_sh, rl,
    owned_bytes)``."""
    from keto_tpu.graph.labels import IN_PAD, OUT_PAD

    g = max(1, int(n_shards))
    n_rows = out_lab.shape[0]
    ranges = shard_row_ranges(n_rows, g)
    rl = ranges[0][1] - ranges[0][0] if ranges[0][1] > ranges[0][0] else 1
    out_sh = np.full((g, rl, out_lab.shape[1]), OUT_PAD, np.int32)
    in_sh = np.full((g, rl, in_lab.shape[1]), IN_PAD, np.int32)
    owned = [0] * g
    for s, (lo, hi) in enumerate(ranges):
        k = hi - lo
        if k <= 0:
            continue
        out_sh[s, :k] = out_lab[lo:hi]
        in_sh[s, :k] = in_lab[lo:hi]
        owned[s] = k * (out_lab.shape[1] + in_lab.shape[1]) * 4
    return (
        np.ascontiguousarray(out_sh),
        np.ascontiguousarray(in_sh),
        rl,
        owned,
    )


def halo_bytes_per_round(spec: ShardSpec, W: int) -> int:
    """Frontier-slab bytes one device RECEIVES per halo exchange: the
    other ``g-1`` shards' ``[rows_per_shard, W]`` uint32 slabs."""
    return (spec.n_shards - 1) * spec.rows_per_shard * W * 4


def route_label_ell(groups, n: int, n_shards: int, rps: int):
    """Route the label builder's pull-ELL groups (``graph/label_build.py
    build_ell_groups`` output: global neighbor ids with gather sentinel
    ``n``, global destination rows) by destination-row ownership — the
    SAME row ranges that stripe the serving label arrays
    (``route_labels``) and bucket slabs (``make_shard_spec``), so the
    rows a sweep writes are the rows the shard will later serve. Returns
    per group ``(int32[g, rb, cap] nbrs, int32[g, rb] local dst)`` with
    scatter sentinel ``rps`` (dropped) and gather ids left GLOBAL: the
    sweep gathers from the halo-exchanged full bitmap."""
    g = max(1, int(n_shards))
    routed = []
    for nbrs, dst in groups:
        dst64 = np.asarray(dst, np.int64)
        owner = np.minimum(dst64 // rps, g - 1)
        counts = np.bincount(owner, minlength=g)
        rb = _ceil_pow2(int(counts.max()) if counts.size else 0) or 1
        cap = nbrs.shape[1]
        sb = np.full((g, rb, cap), np.int32(n), np.int32)
        db = np.full((g, rb), np.int32(rps), np.int32)
        for s in range(g):
            sel = owner == s
            k = int(np.count_nonzero(sel))
            if k:
                sb[s, :k] = nbrs[sel]
                db[s, :k] = (dst64[sel] - s * rps).astype(np.int32)
        routed.append((np.ascontiguousarray(sb), np.ascontiguousarray(db)))
    return routed


# -- kernels -----------------------------------------------------------------


def sharded_check_step(
    mesh,
    bucket_nbrs: tuple,
    bucket_dst: tuple,
    entries,  # int32 [g, 2·S1+2·S2+2·SA+B]
    ov_nbrs=None,  # int32 [g, K, C]
    ov_dst=None,  # int32 [g, K]
    *,
    sizes: tuple,
    rps: int,
    B: int,
    it_cap: int,
    block_iters: int = 8,
):
    """One sharded check dispatch: the BFS fixpoint as a ``shard_map``
    program over the ``graph`` axis. Per hop: halo-exchange the frontier
    slabs (``all_gather``), local gather-OR over this shard's bucket
    rows, scatter into the local slab. Answers reduce per shard and
    OR-combine once at the end. Output ``uint32[W+3]`` replicated (see
    module docstring for the layout)."""
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.sharding import PartitionSpec as P

    S1, S2, SA, _B = sizes
    W = B // 32

    def f(b_nbrs, b_dst, ent, ovn, ovd):
        b_nbrs = tuple(a[0] for a in b_nbrs)
        b_dst = tuple(a[0] for a in b_dst)
        ent = ent[0]
        ovn = None if ovn is None else ovn[0]
        ovd = None if ovd is None else ovd[0]
        o = 0
        e1_rows = ent[o : o + S1]; o += S1
        e1_q = ent[o : o + S1]; o += S1
        e2_rows = ent[o : o + S2]; o += S2
        e2_q = ent[o : o + S2]; o += S2
        a_rows = ent[o : o + SA]; o += SA
        a_q = ent[o : o + SA]; o += SA
        targets = ent[o : o + B]
        e1_words = e1_q >> 5
        e1_masks = jnp.uint32(1) << (e1_q & 31).astype(jnp.uint32)
        e2_words = e2_q >> 5
        e2_masks = jnp.uint32(1) << (e2_q & 31).astype(jnp.uint32)

        zero = jnp.zeros((rps, W), jnp.uint32)
        # row sentinels (rps) are out of the slab range: scatters drop
        ans_base = zero.at[e2_rows, e2_words].add(e2_masks, mode="drop")
        R0 = zero.at[e1_rows, e1_words].add(e1_masks, mode="drop") | ans_base

        def pull(Rfull):
            p = zero
            for nbrs, dst in zip(b_nbrs, b_dst):
                n_pad, cap = nbrs.shape
                acc = None
                for c0 in range(0, cap, _DEGREE_CHUNK):
                    gathered = Rfull[nbrs[:, c0 : c0 + _DEGREE_CHUNK]]
                    part = lax.reduce(
                        gathered, np.uint32(0), lax.bitwise_or, (1,)
                    )
                    acc = part if acc is None else lax.bitwise_or(acc, part)
                p = p.at[dst].set(acc, mode="drop")
            if ovn is not None:
                ovo = lax.reduce(Rfull[ovn], np.uint32(0), lax.bitwise_or, (1,))
                cur = p[jnp.minimum(ovd, rps - 1)]
                p = p.at[ovd].set(cur | ovo, mode="drop")
            return p

        def step(st):
            R, _, _, it = st
            # the halo exchange: every shard's frontier slab crosses the
            # graph axis once per hop — this is the round the paper's
            # communication bound counts
            Rfull = lax.all_gather(R, GRAPH_AXIS, axis=0, tiled=True)
            p = pull(Rfull)
            nxt = R | p
            ch = jnp.any(nxt != R).astype(jnp.int32)
            ch = lax.psum(ch, GRAPH_AXIS) > 0
            return (nxt, p, ch, it + 1)

        def block(st):
            return lax.fori_loop(
                0, block_iters, lambda _, s: lax.cond(s[2], step, lambda x: x, s), st
            )

        p0 = jnp.zeros((rps, W), jnp.uint32)
        R_fix, p_fix, truncated, iters = lax.while_loop(
            lambda st: st[2] & (st[3] < it_cap),
            block,
            (R0, p0, jnp.bool_(True), jnp.int32(0)),
        )

        q = jnp.arange(B)
        words = q // 32
        bits = (q % 32).astype(jnp.uint32)
        own_t = targets < rps
        tc = jnp.minimum(targets, rps - 1)
        a = jnp.where(
            own_t, p_fix[tc, words] | ans_base[tc, words], jnp.uint32(0)
        )
        hit = (a >> bits) & jnp.uint32(1)
        own_a = a_rows < rps
        ac = jnp.minimum(a_rows, rps - 1)
        aw = a_q // 32
        ab = (a_q % 32).astype(jnp.uint32)
        vals = jnp.where(
            own_a, (R_fix[ac, aw] >> ab) & jnp.uint32(1), jnp.uint32(0)
        )
        hit = hit.at[a_q].max(vals)
        packed = lax.reduce(
            (hit << bits).reshape(W, 32), np.uint32(0), lax.bitwise_or, (1,)
        )
        # combine partial answers across shards: [g, W] → OR-reduce. W+3
        # words total cross the axis once per batch — noise next to the
        # per-hop halo slabs.
        packed = lax.reduce(
            lax.all_gather(packed, GRAPH_AXIS, axis=0),
            np.uint32(0), lax.bitwise_or, (0,),
        )
        fb = lax.psum(
            jnp.sum(lax.population_count(R_fix), dtype=jnp.uint32), GRAPH_AXIS
        )
        tail = jnp.stack(
            [iters.astype(jnp.uint32), truncated.astype(jnp.uint32), fb]
        )
        return jnp.concatenate([packed, tail])

    ov_spec = None if ov_nbrs is None else P(GRAPH_AXIS)
    return jax.shard_map(
        f,
        mesh=mesh,
        in_specs=(
            tuple(P(GRAPH_AXIS) for _ in bucket_nbrs),
            tuple(P(GRAPH_AXIS) for _ in bucket_dst),
            P(GRAPH_AXIS),
            ov_spec,
            ov_spec,
        ),
        out_specs=P(),
        check_vma=False,
    )(bucket_nbrs, bucket_dst, entries, ov_nbrs, ov_dst)


def sharded_label_step(
    mesh,
    out_lab,  # int32 [g, rl, Wo] row-striped, OUT_PAD-padded
    in_lab,  # int32 [g, rl, Wi] row-striped, IN_PAD-padded
    entries,  # int32 [3·P] replicated: pair a-rows, b-rows, owning query
    *,
    n_pairs: int,
    B: int,
    rl: int,
):
    """The label-intersection fast path with row-sharded label arrays:
    each shard contributes the pair rows it owns (zeros elsewhere), ONE
    ``psum`` over the graph axis reconstructs every pair's two label rows
    on every shard — the one-shot pair-row exchange — and the compare +
    bit packing run replicated. Output ``uint32[W]`` (no iteration
    tail — there is no iteration), bit-identical to ``label_step``."""
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.sharding import PartitionSpec as P

    Pn = n_pairs
    W = B // 32

    def f(ol, il, ent):
        ol = ol[0]
        il = il[0]
        g0 = lax.axis_index(GRAPH_AXIS) * rl
        pa = ent[:Pn]
        pb = ent[Pn : 2 * Pn]
        pq = ent[2 * Pn : 3 * Pn]
        la = pa - g0
        own_a = (la >= 0) & (la < rl)
        lac = jnp.clip(la, 0, rl - 1)
        # non-owners contribute the additive identity; exactly one shard
        # owns each row, so the psum IS that shard's row (sentinel pads
        # included — they must survive the exchange to stay non-matching)
        oa = lax.psum(jnp.where(own_a[:, None], ol[lac], 0), GRAPH_AXIS)
        lb = pb - g0
        own_b = (lb >= 0) & (lb < rl)
        lbc = jnp.clip(lb, 0, rl - 1)
        ib = lax.psum(jnp.where(own_b[:, None], il[lbc], 0), GRAPH_AXIS)
        hits = []
        for c0 in range(0, Pn, _LABEL_PAIR_CHUNK):
            oc = oa[c0 : c0 + _LABEL_PAIR_CHUNK]
            ic = ib[c0 : c0 + _LABEL_PAIR_CHUNK]
            hits.append(jnp.any(oc[:, :, None] == ic[:, None, :], axis=(1, 2)))
        hit = jnp.concatenate(hits) if len(hits) > 1 else hits[0]
        q = jnp.arange(B)
        bits = (q % 32).astype(jnp.uint32)
        ans = jnp.zeros(B, jnp.uint32).at[pq].max(hit.astype(jnp.uint32))
        return lax.reduce(
            (ans << bits).reshape(W, 32), np.uint32(0), lax.bitwise_or, (1,)
        )

    return jax.shard_map(
        f,
        mesh=mesh,
        in_specs=(P(GRAPH_AXIS), P(GRAPH_AXIS), P()),
        out_specs=P(),
        check_vma=False,
    )(out_lab, in_lab, entries)


@lru_cache(maxsize=8)
def check_kernel(mesh):
    """Jitted ``sharded_check_step`` bound to ``mesh`` (cached per mesh;
    XLA caches per geometry under it, same as the single-device path)."""
    import jax

    return partial(
        jax.jit,
        static_argnames=("sizes", "rps", "B", "it_cap", "block_iters"),
    )(partial(sharded_check_step, mesh))


@lru_cache(maxsize=8)
def label_kernel(mesh):
    """Jitted ``sharded_label_step`` bound to ``mesh``."""
    import jax

    return partial(jax.jit, static_argnames=("n_pairs", "B", "rl"))(
        partial(sharded_label_step, mesh)
    )


def sharded_label_sweep_step(
    mesh,
    nbrs,  # per ELL group: int32 [g, rb, cap], global ids, sentinel n
    dst,  # per ELL group: int32 [g, rb], local rows, sentinel rps
    V,  # uint32 [g, rps, Wt] visited slabs
    X,  # uint32 [g, rps, Wt] frontier slabs
    S,  # uint32 [g, rps, Wt] stored slabs
    cov,  # uint32 [g, rps, Wt] covered slabs (frozen per batch)
    *,
    rps: int,
    prune_expansion: bool = True,
):
    """One wave of the batched label-construction sweep
    (``graph/label_build.py``) as a ``shard_map`` program: the frontier
    slabs halo-exchange over the graph axis exactly like
    ``sharded_check_step``'s BFS hop, then each shard runs the local
    gather-OR pull over its routed ELL rows and applies the PLL pruning
    ANDNOT (``covered``) to its owned rows. OR is OR on any topology, so
    the wave sequence — and therefore the stored entry set — is
    bit-identical to the single-device sweep; the wave loop stays on
    host because the builder meters budgets and transfers per wave."""
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.sharding import PartitionSpec as P

    def f(b_nbrs, b_dst, v, x, s, c):
        b_nbrs = tuple(a[0] for a in b_nbrs)
        b_dst = tuple(a[0] for a in b_dst)
        v = v[0]
        x = x[0]
        s = s[0]
        c = c[0]
        xfull = lax.all_gather(x, GRAPH_AXIS, axis=0, tiled=True)
        p = jnp.zeros_like(v)
        for nb, d in zip(b_nbrs, b_dst):
            cap = nb.shape[1]
            acc = None
            for c0 in range(0, cap, _DEGREE_CHUNK):
                gathered = xfull[nb[:, c0 : c0 + _DEGREE_CHUNK]]
                part = lax.reduce(gathered, np.uint32(0), lax.bitwise_or, (1,))
                acc = part if acc is None else lax.bitwise_or(acc, part)
            p = p.at[d].set(acc, mode="drop")
        newly = p & ~v
        store = newly & ~c
        v2 = v | newly
        x2 = store if prune_expansion else newly
        s2 = s | store
        active = lax.psum(jnp.any(x2 != 0).astype(jnp.int32), GRAPH_AXIS) > 0
        visits = lax.psum(
            jnp.sum(lax.population_count(newly), dtype=jnp.int32), GRAPH_AXIS
        )
        # keep the leading unit shard axis so the global outputs are
        # [g, rps, Wt] — the same layout the next wave feeds back in
        return v2[None], x2[None], s2[None], active, visits

    return jax.shard_map(
        f,
        mesh=mesh,
        in_specs=(
            tuple(P(GRAPH_AXIS) for _ in nbrs),
            tuple(P(GRAPH_AXIS) for _ in dst),
            P(GRAPH_AXIS),
            P(GRAPH_AXIS),
            P(GRAPH_AXIS),
            P(GRAPH_AXIS),
        ),
        out_specs=(P(GRAPH_AXIS), P(GRAPH_AXIS), P(GRAPH_AXIS), P(), P()),
        check_vma=False,
    )(nbrs, dst, V, X, S, cov)


@lru_cache(maxsize=8)
def label_sweep_kernel(mesh):
    """Jitted ``sharded_label_sweep_step`` bound to ``mesh``."""
    import jax

    return partial(jax.jit, static_argnames=("rps", "prune_expansion"))(
        partial(sharded_label_sweep_step, mesh)
    )
