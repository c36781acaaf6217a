"""Host side of a check slice: resolved queries in, kernel entry arrays out.

``pack_chunk`` walks what the host can walk and packs the rest for
``kernels.check_step``; ``label_pairs`` routes a packed chunk's queries
between the label kernel and the BFS riders and pairs the former's rows
(the two together are what ``native_pack.pack_labeled`` does in one native
pass, and what it is fuzzed against); ``device_part`` cuts a packed chunk
down to what the device has to see; ``_StagingPool`` keeps the host buffers
the entries ship from; the slice records carry a launched slice's device
outputs to where it lands. numpy and ``native_pack`` only — nothing here
touches jax.
"""

from __future__ import annotations

import threading
from typing import Callable, Optional

import numpy as np

from keto_tpu.check import native_pack
from keto_tpu.graph.snapshot import GraphSnapshot, _csr_gather_host

# batch widths (in 32-query words) the engine compiles for; a request is
# padded up to the smallest fitting width so jit caches stay small
_WORD_WIDTHS = (1, 8, 64, 256, 1024, 2048, 4096)


class _HybridSlice:
    """Device output(s) of one slice whose BFS part answers only some of
    its positions: the label kernel's packed bits for the whole slice (a
    label-routed slice; None on the BFS route), plus — when some queries
    fell back, or on the BFS route — a BFS sub-batch output and the slice
    positions it answers (``device_part``: the queries the host could not
    answer without the device). Quacks like a device array where the
    streaming pipeline needs it (``copy_to_host_async`` / ``is_ready``)."""

    __slots__ = ("label_dev", "bfs_dev", "bfs_pos", "bfs_words")

    def __init__(self, label_dev, bfs_dev=None, bfs_pos=None, bfs_words=0):
        self.label_dev = label_dev
        self.bfs_dev = bfs_dev
        self.bfs_pos = bfs_pos
        #: words of a bitmap row in the ``check_step`` program that ran the
        #: BFS part (0 where the sharded kernel did)
        self.bfs_words = bfs_words

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


def _entry_pad(B: int, size: int) -> int:
    """Scatter/gather entry arrays pad to B·2^k — a couple of geometries per
    batch width, so chunks of one request hit the same jit cache entry."""
    sp = B
    while sp < size:
        sp *= 2
    return sp


def _pad_entries(rows_l, qs_l, B: int, drop_row: int):
    none = [np.zeros(0, np.int32)]
    rows = np.concatenate(rows_l + none).astype(np.int32)
    qs = np.concatenate(qs_l + none).astype(np.int32)
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


def device_part(
    snap: GraphSnapshot, packed, host_ans: np.ndarray, only: Optional[np.ndarray] = None
):
    """What of a packed chunk ``check_step`` has to see, and what the host
    can say without it.

    Only the active rows change under the pulls; every other interior row
    keeps the bits it was seeded with. So a sink target whose answer rows
    hold one of its query's own seed rows is granted here (**direct**: the
    set intersection the kernel would otherwise do as a scatter into, and a
    gather from, a bitmap over all interior rows), and a query gets
    nothing more from the device unless its target side has an active
    row: an answer row below ``num_active`` (or a relay row of a hub sink,
    which the host does not look into), or an active interior target
    (a passive one is answered by the host walk's own hit alone). The
    others leave the chunk, so on a graph whose device part is small the
    kernel runs a narrow sub-batch of few entries; where most rows are
    active nearly every query stays. ``only`` (bool by query) names the
    queries that may stay: a hybrid slice's riders, the rest being the
    label kernel's.

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
    need[aq[(ar < na) | (ar > ni)]] = True  # an active answer row, or a relay row
    need &= ~host_ans
    if only is not None:
        need &= only
    k1, k2 = need[e1q], need[e2q]
    if not k1.any() and not k2.any():
        return None, None  # no query is left, or nothing seeds those that are
    pos = np.nonzero(need)[0]
    B = 32 * next(w for w in _WORD_WIDTHS if 32 * w >= pos.size)
    place = np.cumsum(need) - 1
    ka = need[aq]
    # one pad for the three entry arrays, B·4^k: how many of its queries a
    # chunk sends here varies from chunk to chunk, and every combination
    # of pads is a program of its own to compile. The riders of a hybrid
    # slice (``only``) pad B·2^k: ``warm_compile`` leaves their rungs where
    # a snapshot has them at all, and at ten thousand entries and more a
    # pad of four times is most of what the kernel scatters
    E, step = B, 4 if only is None else 2
    while E < max(int(k1.sum()), int(k2.sum()), int(ka.sum())):
        E *= step

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


def hub_usable(snap: GraphSnapshot) -> bool:
    """May answer entries name the snapshot's relay rows? Where the engine
    has put them on the device and no overlay edge or tombstone reaches a
    sink: the relay rows are the base's."""
    return (
        snap.device_hub is not None
        and not snap.ov_sink_in
        and (snap.ov_removed is None or snap.ov_removed.size == 0)
    )


def _padding_packed(sizes: tuple, ni: int):
    """``pack_chunk``'s seven arrays at ``sizes`` = (S1, S2, SA, B) with
    nothing in them: every seed a dropped row, every answer entry the
    all-zero row, no target. What a warm-up runs a program on."""
    return _pad_packed((np.zeros(0, np.int32),) * 7, sizes, ni)


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

    ``sd``/``tg``/``multi`` come from ``CheckDispatch._resolve_bulk``.
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
    if m_ans.any() and hub_usable(snap):
        # a hub sink's answer comes from its relay rows (``hub_relays``): an
        # entry a relay row, not one a row, and nothing gathered here
        sink = np.where(m_ans, tgc - sb, 0)
        m_hub = m_ans & (snap.hub_ptr[sink + 1] > snap.hub_ptr[sink])
        if m_hub.any():
            relays, n_relay = _csr_gather_host(
                snap.hub_ptr, np.arange(snap.hub_rows.shape[0]), sink[m_hub]
            )
            ans[0].append((ni + 1 + relays).astype(np.int32))
            ans[1].append(np.repeat(qi[m_hub], n_relay).astype(np.int32))
            m_ans = m_ans & ~m_hub
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
    # seed padding row ni+1 is out of range for the [ni+1, W] bitmap → dropped;
    # answer padding: in-range all-zero row ni with query 0 — max(0) is a no-op
    return (
        _pad_entries(*e1, B, ni + 1) + _pad_entries(*e2, B, ni + 1)
        + _pad_entries(*ans, B, ni) + (targets,),
        host_ans,
    )


#: why a query left the label route, in the order the causes are tried
#: (``keto_label_fallbacks_total{reason}``; ``multi`` before them all)
LABEL_REASONS = ("self_hit", "pair_cap", "uncertifiable", "whole_slice")


def whole_min(W: int) -> int:
    """The fewest fallen-back queries whose sub-batch is as wide as a slice
    of ``W`` words: from there on the slice's other queries ride it too and
    the label kernel is not launched."""
    return 1 + 32 * max((w for w in _WORD_WIDTHS if w < W), default=0)


def label_pairs(snap: GraphSnapshot, idx, packed, multi: dict, i0: int, i1: int,
                W: int, pair_cap: int):
    """Route the ``nq`` queries of a packed chunk between the label kernel
    and the BFS riders, and pair the rows of those it keeps.

    A query's **pairs** are (seed row u) x (target-side row r): the interior
    target itself, or a sink target's answer rows. It falls back, first cause
    wins: ``multi`` (wildcard and multi-start queries, as a class);
    ``self_hit`` (an e1 seed equal to an interior target: reach0 would count
    the 0-edge path, a distinction labels do not carry); ``pair_cap`` (more
    pairs than ``pair_cap``, or a relay row, which stands for more rows than
    the cap); ``uncertifiable`` (a pair whose miss is no sound deny);
    ``whole_slice`` (every other query, once the riders fill a sub-batch as
    wide as the slice, ``whole_min``). An e2 seed equal to the target was
    reached over a real edge on the host walk, so ``host_ans`` granted it
    and the pair drops.

    Returns ``(fallback, reasons, whole, (pa, pb, pq))``: bool[nq], counts
    by reason, whether the whole slice rides, and the pairs as int64 arrays
    (none where ``whole``)."""
    nq = i1 - i0
    (e1r, e1q, e2r, e2q, ar, aq, targets) = packed
    ni = snap.num_int
    tq = np.asarray(targets[:nq], np.int64)
    t_int = tq < ni

    fallback = np.zeros(nq, bool)
    for i in multi:
        if i0 <= i < i1:
            fallback[i - i0] = True
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
    e1_rows_v = e1r[m1].astype(np.int64)
    e1_q_v = e1q[m1].astype(np.int64)
    self_hit = t_int[e1_q_v] & (e1_rows_v == tq[e1_q_v])
    if self_hit.any():
        fall_back("self_hit", e1_q_v[self_hit])

    # target-side rows per query: the interior target, or the sink
    # answer-gather rows. Each side is counted per query first: a query
    # over the pair cap takes neither side into the sort and the
    # cross-join below, and where targets are hub rows its answer rows
    # are most of the chunk's entries
    a_rows_v, a_q_v = ar[ma], aq[ma]
    ns = np.bincount(s_q, minlength=nq)
    nr = np.bincount(a_q_v, minlength=nq) + t_int
    over = ns * nr > pair_cap
    if snap.hub_ptr is not None:
        over[a_q_v[a_rows_v > ni]] = True
    if over.any():
        fall_back("pair_cap", over)

    def rides_whole() -> bool:
        return int(np.count_nonzero(fallback)) >= whole_min(W)

    pa = pb = pq = np.zeros(0, np.int64)
    whole = rides_whole()
    if not whole:
        keep_s = ~fallback[s_q]
        s_rows, s_q = s_rows[keep_s], s_q[keep_s]
        t_keep, keep_a = t_int & ~fallback, ~fallback[a_q_v]
        b_rows = np.concatenate([tq[t_keep], a_rows_v[keep_a].astype(np.int64)])
        b_q = np.concatenate([np.nonzero(t_keep)[0], a_q_v[keep_a].astype(np.int64)])
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
            base = np.repeat(b_starts[s_q], rep_nr)
            csum = np.cumsum(rep_nr) - rep_nr
            within = np.arange(total) - np.repeat(csum, rep_nr)
            pa = np.repeat(s_rows, rep_nr)
            pb = b_rows[base + within]
            pq = np.repeat(s_q, rep_nr)
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
        pa = pb = pq = np.zeros(0, np.int64)
    return fallback, reasons, whole, (pa, pb, pq)


def put_pairs(entries: np.ndarray, P: int, pairs, ni: int) -> None:
    """``label_pairs``'s pairs into ``label_step``'s int32[3 * P] buffer:
    pa, pb, pq at 0, P, 2 * P, padded (ni, ni, 0)."""
    pa, pb, pq = pairs
    pad = P - pa.size
    entries[:P] = np.concatenate([pa, np.full(pad, ni, np.int64)])
    entries[P : 2 * P] = np.concatenate([pb, np.full(pad, ni, np.int64)])
    entries[2 * P :] = np.concatenate([pq, np.zeros(pad, np.int64)])
