"""Device-friendly graph snapshot: bucketed reverse-ELL adjacency.

The TPU check kernel (keto_tpu/check/kernels.py) runs breadth-first
reachability as a **pull**: per step, every node ORs the reached-bitmaps of
its *in*-neighbors. A pull step is gather-only — TPUs gather well but
serialize scatters with colliding indices, so the layout makes the inner
loop pure gathers + OR-reductions:

- nodes are **renumbered** ("device ids") into four classes, sorted in
  this order:

  * **active interior** — has in-edges AND out-edges, with ≥ 1 in-edge
    from another interior node. These are the only rows the BFS loop
    iterates: everything else is provably constant (or irrelevant) during
    propagation.
  * **passive interior** — in/out-edges, but in-edges only from
    zero-in-degree ("static") sources. Constant after initialization (the
    one-hop start propagation computed on host per batch,
    tpu_engine.pack_chunk), yet still gathered as a propagation source.
  * **sink** — in-edges but NO out-edges (typically subject leaves — the
    bulk of most graphs, e.g. every user in an RBAC workload). Sinks
    cannot propagate, so they get **no bitmap row at all**; a sink's
    answer is resolved per batch by gathering its interior in-neighbors
    from the fixpoint bitmap (``sink_indptr``/``sink_indices`` below).
  * **static** — no in-edges. Never materialized on device; their only
    effect is the host-side one-hop propagation.

  Excluding sinks is the big win: the iterated bitmap is
  ``[num_int+1, W]`` over interior nodes only (RBAC example: ~10k groups
  instead of ~110k groups+users), and each pull gathers only
  interior→interior edges — orders of magnitude fewer rows than the raw
  edge count.

- active-interior nodes are grouped into power-of-two **interior-in-degree**
  buckets; each bucket stores a dense ``[rows, degree]`` int32 matrix of
  interior in-neighbor device ids (ELL format), padded with sentinel
  ``num_int`` pointing at an all-zero bitmap row;
- bucket row counts are padded to powers of two so a snapshot rebuild after
  tuple writes usually keeps the same array shapes and hits the jit cache.

Because buckets are contiguous in device-id order, the pull output is the
concatenation of per-bucket OR-reductions — no scatter anywhere.

This layout replaces the reference's covering SQL index as the check hot
path's data structure (reference
internal/persistence/sql/migrations/sql/20210623162417000003_relationtuple.postgres.up.sql:1-9).
"""

from __future__ import annotations

import threading
from dataclasses import dataclass, field
from typing import Any, FrozenSet, Iterable, Optional

import numpy as np

from keto_tpu.graph.interner import InternedGraph, intern_rows

#: namespace sentinel meaning "wildcard" in a resolved query pattern
WILDCARD = -1
#: a sink whose answer is gathered from more rows than this gets relay rows
#: of this many (``GraphSnapshot.hub_relays``): twice the label route's pair
#: cap, so a deployment whose widest sink sits at the cap is not moved in
#: and out of it by its seed
HUB_RELAY_ROWS = 128


def _ceil_pow2(x: int) -> int:
    return 1 if x <= 1 else 1 << (int(x) - 1).bit_length()


def _csr_gather_host(indptr: np.ndarray, indices: np.ndarray, nodes: np.ndarray):
    """(all out-neighbors of ``nodes`` concatenated, per-node counts)."""
    cnts = indptr[nodes + 1] - indptr[nodes]
    return _csr_gather_counts(indptr, indices, nodes, cnts)


def _csr_gather_counts(
    indptr: np.ndarray, indices: np.ndarray, nodes: np.ndarray, cnts: np.ndarray
):
    """CSR gather with caller-supplied per-node counts (callers zero the
    counts of nodes that must contribute nothing, e.g. overlay ids that are
    out of the base CSR's range)."""
    total = int(cnts.sum())
    if not total:
        return np.zeros(0, indices.dtype), cnts
    base = np.repeat(indptr[nodes], cnts)
    within = np.arange(total) - np.repeat(np.cumsum(cnts) - cnts, cnts)
    return indices[base + within], cnts


@dataclass
class Bucket:
    """One live-in-degree bucket: ``nbrs[i, j]`` is the device id of the
    j-th live in-neighbor of device node ``offset + i`` (sentinel
    ``num_int`` — the all-zero bitmap row — when padding)."""

    offset: int  # device id of the first row
    n: int  # valid rows (bucket membership)
    nbrs: np.ndarray  # int32 [n_padded, degree_capacity]


@dataclass
class ListLayout:
    """Bucketed-ELL gather layout for the reverse-query BFS
    (keto_tpu/list/tpu_engine.py), one per orientation.

    Rows cover EVERY interior-class device id ``[0, sink_base)`` —
    unlike the check kernel's buckets there is no peel/passive split,
    because a listing must read the reached flag of every interior node
    rather than a handful of packed targets. Rows are renumbered so
    buckets are contiguous (``order``/``dev2row``); bucket matrices hold
    ROW indices (sentinel ``n_rows`` = the all-zero bitmap row), so a
    pull step is the same gather + OR-reduce + concat the check kernel
    runs — no scatter.

    - ``orient == "fwd"``: row r gathers the interior IN-neighbors of
      its node — forward reachability (ListSubjects) pulls "reached"
      toward edge targets;
    - ``orient == "rev"``: row r gathers the interior OUT-neighbors —
      the TRANSPOSED orientation; backward reachability (ListObjects)
      pulls "reaches the target" toward edge sources.
    """

    orient: str
    n_rows: int  # == sink_base of the owning snapshot
    n_active: int  # rows with ≥ 1 gathered neighbor (bucket-covered prefix)
    order: np.ndarray  # int64 [n_rows]: device id of row r
    dev2row: np.ndarray  # int64 [n_rows]: device id → row
    buckets: list  # [Bucket], nbrs hold row indices, sentinel n_rows

    def device_bytes(self) -> int:
        """Device footprint of the bucket matrices as uploaded — what
        the HBM governor plans under the ``reverse`` ledger tag."""
        return sum(int(np.asarray(b.nbrs).nbytes) for b in self.buckets)


def _host_sorter():
    """Default stable-argsort backend (lazy import — snapshot.py must
    stay importable without the device_build module's jax probing)."""
    from keto_tpu.graph.device_build import host_sorter

    return host_sorter()


def _one_list_layout(
    rows_dev: np.ndarray, nbr_dev: np.ndarray, n_rows: int, orient: str,
    sorter=None,
) -> ListLayout:
    """Bucketize ``rows_dev[i] gathers nbr_dev[i]`` into a ListLayout
    over ``n_rows`` interior-class device ids (same machinery as the
    check buckets: pow2 degree buckets, pow2 row padding, contiguous
    rows per bucket). ``sorter`` is the stable-argsort backend
    (keto_tpu/graph/device_build.py); host and device produce identical
    permutations by the stable-sort contract."""
    S = sorter or _host_sorter()
    deg = np.bincount(rows_dev, minlength=n_rows) if rows_dev.size else np.zeros(n_rows, np.int64)
    with np.errstate(divide="ignore"):
        bkey = np.ceil(np.log2(np.maximum(deg, 1))).astype(np.int64) + 1
    bkey[deg <= 1] = 1
    bkey[deg == 0] = 63  # degree-0 rows sort last, outside every bucket
    # np.lexsort((arange, bkey)) == stable argsort of bkey: the arange
    # tie-break IS stability, so both backends share one primitive
    order = S.argsort(bkey)
    dev2row = np.empty(n_rows, np.int64)
    dev2row[order] = np.arange(n_rows)
    n_active = int(np.count_nonzero(deg > 0))
    buckets: list[Bucket] = []
    if rows_dev.size:
        r = dev2row[rows_dev]
        v = dev2row[nbr_dev].astype(np.int32)
        eorder = S.argsort(r)
        rs = r[eorder]
        vs = v[eorder]
        starts = np.searchsorted(rs, np.arange(n_active))
        cumcount = np.arange(rs.shape[0]) - starts[rs]
        key_by_row = bkey[order][:n_active]
        sentinel = np.int32(n_rows)
        for key in np.unique(key_by_row):
            members = np.nonzero(key_by_row == key)[0]  # contiguous
            offset, n_r = int(members[0]), int(members.shape[0])
            cap = 1 << (int(key) - 1)
            n_pad = _ceil_pow2(n_r)
            nbrs = np.full((n_pad, cap), sentinel, dtype=np.int32)
            emask = (rs >= offset) & (rs < offset + n_r)
            nbrs[rs[emask] - offset, cumcount[emask]] = vs[emask]
            buckets.append(Bucket(offset=offset, n=n_r, nbrs=nbrs))
    return ListLayout(
        orient=orient, n_rows=n_rows, n_active=n_active, order=order,
        dev2row=dev2row, buckets=buckets,
    )


def build_rev_csr(
    fwd_indptr: np.ndarray, fwd_indices: np.ndarray, n_nodes: int,
    sorter=None,
) -> tuple[np.ndarray, np.ndarray]:
    """The transposed CSR over ALL device ids: in-neighbors per node.
    Derived from the forward CSR in one stable sort, persisted by the
    snapshot cache so both orientations survive restarts."""
    S = sorter or _host_sorter()
    src = np.repeat(np.arange(n_nodes, dtype=np.int64), np.diff(fwd_indptr))
    dst = fwd_indices.astype(np.int64)
    rorder = S.argsort(dst)
    rev_indptr = np.searchsorted(dst[rorder], np.arange(n_nodes + 1))
    rev_indices = src[rorder].astype(np.int32)
    return rev_indptr, rev_indices


def build_list_layouts(
    fwd_indptr: np.ndarray, fwd_indices: np.ndarray, n_nodes: int, sink_base: int,
    sorter=None,
) -> tuple[ListLayout, ListLayout]:
    """Both reverse-query orientations over the interior-class subgraph
    (device ids < ``sink_base``), from the forward CSR. Shared by the
    snapshot builder, compaction (which re-derives them after folding),
    and the snapshot-cache load path."""
    src = np.repeat(np.arange(n_nodes, dtype=np.int64), np.diff(fwd_indptr))
    dst = fwd_indices.astype(np.int64)
    m = (src < sink_base) & (dst < sink_base)
    lay_fwd = _one_list_layout(dst[m], src[m], sink_base, "fwd", sorter=sorter)
    lay_rev = _one_list_layout(src[m], dst[m], sink_base, "rev", sorter=sorter)
    return lay_fwd, lay_rev


@dataclass
class GraphSnapshot:
    """An immutable device-layout view of the tuple set at one watermark.

    The watermark doubles as the snapshot id — the real implementation of
    what the reference stubs as "snaptoken" (reference
    internal/check/handler.go:162).
    """

    snapshot_id: int
    num_sets: int
    num_leaves: int
    #: device ids < num_active are iterated by the BFS loop
    num_active: int
    #: device ids < num_int are interior with bitmap rows (active +
    #: passive); the device bitmap has num_int+1 rows (last row all-zero)
    num_int: int
    #: device ids in [num_int, num_live) split into peeled interior
    #: [num_int, sink_base) — init-constant rows folded into host
    #: propagation, see build_snapshot's peel note — and sinks
    #: [sink_base, num_live); ids ≥ num_live are static (no in-edges)
    num_live: int
    #: count of peeled interior nodes (sink_base = num_int + n_peeled)
    n_peeled: int
    buckets: list[Bucket]
    # string→raw-id resolution: an InternedGraph (Python dicts) or a
    # NativeInterned (resident C++ tables) — same interface either way
    interned: Any
    raw2dev: np.ndarray  # int64 [n_nodes]: raw node id → device id
    wild_ns_ids: FrozenSet[int] = frozenset()
    #: the plan of the rewrite schema this snapshot was built under
    #: (keto_tpu/namespace/rewrites.py ``RewritePlan``): its fingerprint is a
    #: build input like ``wild_ns_ids``; None where no namespace has rewrites
    rewrites: Any = None
    # forward CSR over device ids, host-side (expand assist, debugging)
    fwd_indptr: Optional[np.ndarray] = None  # int64 [n_nodes+1]
    fwd_indices: Optional[np.ndarray] = None  # int32 [E]
    #: per sink (indexed by device id - num_int): interior in-neighbor
    #: device ids — the rows gathered to answer a sink-targeted query
    sink_indptr: Optional[np.ndarray] = None  # int64 [num_live-num_int+1]
    sink_indices: Optional[np.ndarray] = None  # int32
    device_buckets: Any = None  # jnp arrays, populated lazily by the engine
    #: relay rows of hub sinks (``hub_relays``), engine-set beside the
    #: buckets on the single-device path: the relay rows of sink ``s`` are
    #: ``hub_ptr[s]..hub_ptr[s+1]`` (int64 [n_sinks+1]; none for a sink of
    #: at most HUB_RELAY_ROWS), ``hub_rows[k]`` is how many of the sink's
    #: rows relay row ``k`` holds, ``device_hub`` the int32 [Kh, C] lists
    hub_ptr: Optional[np.ndarray] = None
    hub_rows: Optional[np.ndarray] = None
    device_hub: Any = None

    # -- delta overlay (keto_tpu/graph/overlay.py) ---------------------------
    # Insert-only writes since the base build live in a small overlay
    # instead of forcing a full re-intern + relayout: new nodes get fresh
    # device ids ≥ ``n_base_nodes`` (they never need bitmap rows — class
    # transitions that would require one trigger a full rebuild), new
    # static→x edges extend the host one-hop adjacency, new edges into
    # sinks extend the answer gathers, and new interior→interior edges form
    # a tiny device-side "overlay ELL" applied as an extra scatter stage in
    # every BFS pull (check/kernels.py check_step).
    ov_set_ids: Optional[dict] = None  # (ns_id, obj, rel) → overlay dev id
    ov_leaf_ids: Optional[dict] = None  # subject str → overlay dev id
    ov_class: Optional[dict] = None  # overlay dev id → "static" | "sink"
    ov_next: int = 0  # next free overlay device id
    ov_out: Optional[dict] = None  # src dev → np.int64[...] out-neighbor devs
    ov_sink_in: Optional[dict] = None  # sink dev → np.int32[...] interior srcs
    #: unified overlay out-adjacency: src dev → [dst devs] for EVERY added
    #: edge regardless of kernel class (ov_out/ov_ell/ov_sink_in are the
    #: class-partitioned device views; this is the expand engine's source)
    ov_fwd: Optional[dict] = None
    ov_ell: Optional[np.ndarray] = None  # int64 [K, 2] (src, dst) edges
    #: tombstoned BASE edges as a sorted int64 key array ((src << 32) | dst)
    #: — deletes applied as deltas (keto_tpu/graph/overlay.py). Host
    #: gathers mask against it; iterated edges are additionally sentinel-
    #: patched out of the device buckets (``ell_patch``).
    ov_removed: Optional[np.ndarray] = None
    #: pending device-bucket patches [(bucket, row, col, value)] relative
    #: to the base's device_buckets; the engine applies + clears them
    ell_patch: Optional[list] = None
    device_overlay: Any = None  # (ov_nbrs, ov_dst) jnp arrays or None
    #: per-delta overlay-ELL change record relative to the base snapshot:
    #: ``(base_snapshot_id, added, dropped)`` where added/dropped are
    #: (src, dst) tuples — the engine's incremental device-overlay apply
    #: consumes (and clears) it like ``ell_patch``; None means "repack"
    ov_ell_delta: Any = None

    # -- reverse-query layouts (keto_tpu/list/) ------------------------------
    #: transposed CSR over ALL device ids (in-neighbors per node) —
    #: backward seeding, static-answer resolution, and the CPU-reference
    #: lister all gather through it (masked by tombstones + overlay)
    rev_indptr: Optional[np.ndarray] = None  # int64 [n_nodes+1]
    rev_indices: Optional[np.ndarray] = None  # int32 [E]
    #: bucketed-ELL list layouts over interior-class rows, both
    #: orientations (ListLayout); None on pre-reverse snapshots
    lay_fwd: Any = None
    lay_rev: Any = None
    #: overlay interior-class edges [(src, dst)] mirrored for the list
    #: kernels' extra gather stage (the transposed twin of ov_ell +
    #: interior-source ov_out entries)
    lst_ov_edges: Optional[list] = None
    #: pending device patches for the list layouts, APPEND-ONLY across
    #: stacked deltas: (orient, bucket, row, col, row-value). The list
    #: engine applies entries past its applied-counter (device arrays
    #: ride dataclasses.replace like device_buckets)
    lst_patch: Optional[list] = None
    #: True when an overlay shape could not be mirrored into the list
    #: layouts — the device list path falls back to the CPU-reference
    #: lister (bit-identical) until compaction folds the overlay
    lst_dirty: bool = False
    device_list: Any = None  # per-orientation jnp arrays, list-engine-set

    # -- sharded serving (keto_tpu/parallel/sharded.py) ----------------------
    #: row-range shard partitioning of the bucket matrices, built at
    #: upload time by the sharded engine mode; None on single-device /
    #: GSPMD engines. Deltas carry it (the base layout is unchanged);
    #: compaction and rebuilds re-derive it with the fresh buckets.
    shard_spec: Any = None
    #: stacked per-shard device arrays: (bucket nbrs tuple, bucket dst
    #: tuple), each [n_shards, ...] sharded over the mesh's graph axis
    device_shards: Any = None
    #: per-shard overlay-ELL gather arrays (nbrs, dst), routed by
    #: destination-row ownership; reset to None by apply_delta exactly
    #: like device_overlay (the engine re-routes + re-uploads)
    device_shard_overlay: Any = None
    #: row-striped label arrays (out, in, rows_per_shard) for the sharded
    #: label-intersection kernel
    device_shard_labels: Any = None

    # -- 2-hop reachability labels (keto_tpu/graph/labels.py) ----------------
    #: pruned-landmark label index over interior rows, built at snapshot
    #: build time; None when disabled or not yet built
    labels: Any = None
    #: interior device ids whose label entries the pending overlay
    #: invalidated (endpoints of inserted/tombstoned ELL edges). While
    #: non-empty the engine routes every check to the BFS kernel; every
    #: other overlay class (new sinks, sink in-edges, host-walk
    #: adjacency, host-masked tombstones) leaves the interior subgraph —
    #: the labels' whole universe — untouched, so labels stay exact.
    lab_dirty: Optional[set] = None
    device_labels: Any = None  # (out_lab, in_lab) jnp arrays, engine-set
    _pattern_cache: dict = field(default_factory=dict)
    _cache_lock: threading.Lock = field(default_factory=threading.Lock)

    @property
    def n_nodes(self) -> int:
        return self.num_sets + self.num_leaves

    @property
    def has_overlay(self) -> bool:
        """True when ANY delta-overlay state is pending (the single
        predicate consumers must use — hand-enumerating the ov_* fields
        is how a newly added one gets missed)."""
        return (
            bool(self.ov_set_ids)
            or bool(self.ov_leaf_ids)
            or bool(self.ov_out)
            or bool(self.ov_sink_in)
            or bool(self.ov_fwd)
            or self.ov_ell is not None
            or (self.ov_removed is not None and self.ov_removed.size > 0)
        )

    @property
    def labels_usable(self) -> bool:
        """True when the 2-hop label index may serve checks on this
        snapshot: an index exists and no pending overlay mutation touched
        the interior (ELL) subgraph it indexes."""
        return self.labels is not None and not self.lab_dirty

    def hub_relays(self):
        """``(hub_ptr, hub_rows, nbrs)`` for the sinks whose answer is
        gathered from more than ``HUB_RELAY_ROWS`` rows (a user in groups
        by the hundred), or None where there is none: each such sink's
        in-neighbor list cut into relay rows of that many (``nbrs`` int32
        [Kh, HUB_RELAY_ROWS], the rows padded to a multiple of 256 and the
        holes filled with ``num_int``, the all-zero bitmap row).
        ``check_step`` OR-reduces every relay row once a slice, and an
        answer entry of a query on a hub sink names relay row ``k`` as
        ``num_int + 1 + k`` where it would have named its rows one by one
        (``check/pack.py`` ``pack_chunk``). The base CSR only: a snapshot
        whose overlay reaches sinks does not use them."""
        sp = self.sink_indptr
        if sp is None or sp.shape[0] < 2:
            return None
        C = HUB_RELAY_ROWS
        deg = np.diff(sp)
        hubs = np.flatnonzero(deg > C)
        if not hubs.size:
            return None
        n_of = np.zeros(deg.shape[0], np.int64)
        n_of[hubs] = -(-deg[hubs] // C)
        hub_ptr = np.zeros(deg.shape[0] + 1, np.int64)
        np.cumsum(n_of, out=hub_ptr[1:])
        kh = int(hub_ptr[-1])
        nbrs = np.full((-(-kh // 256) * 256) * C, self.num_int, np.int32)
        # a hub's rows fill its relay rows end to end: slot j of the sink's
        # list is slot j from the start of its first relay row
        rows, cnts = _csr_gather_host(sp, self.sink_indices, hubs)
        slots, _ = _csr_gather_counts(hub_ptr * C, np.arange(nbrs.size), hubs, cnts)
        nbrs[slots] = rows
        nbrs = nbrs.reshape(-1, C)
        return hub_ptr, (nbrs != self.num_int).sum(axis=1).astype(np.int64), nbrs

    def bucket_device_bytes(self) -> int:
        """Device footprint of the bucket matrices as uploaded — what the
        HBM governor (keto_tpu/driver/hbm.py) plans and registers under
        the ``snapshot`` ledger tag BEFORE ``jax.device_put`` runs (mesh
        row padding adds at most one graph-axis stripe per bucket and is
        ignored here)."""
        return sum(int(np.asarray(b.nbrs).nbytes) for b in self.buckets)

    @property
    def has_wildcards(self) -> bool:
        """True when any set node is wildcard-bearing — fixed per
        snapshot, cached (the raw scan is O(num_sets))."""
        with self._cache_lock:
            v = self._pattern_cache.get("_has_wild")
            if v is None:
                v = bool(np.any(np.asarray(self.interned.key_wild)))
                self._pattern_cache["_has_wild"] = v
            return v

    @property
    def sink_base(self) -> int:
        """First sink device id (peeled interior ids come before)."""
        return self.num_int + self.n_peeled


    @property
    def n_base_nodes(self) -> int:
        """Device ids below this are base nodes (classifiable by range);
        ids in [n_base_nodes, ov_next) are overlay nodes."""
        return self.n_nodes

    @property
    def n_edges(self) -> int:
        base = 0 if self.fwd_indices is None else int(self.fwd_indices.shape[0])
        ov = 0
        if self.ov_out:
            ov = sum(v.size for v in self.ov_out.values())
        if self.ov_ell is not None:
            ov += int(self.ov_ell.shape[0])
        if self.ov_sink_in:
            ov += sum(v.size for v in self.ov_sink_in.values())
        if self.ov_removed is not None:
            ov -= int(self.ov_removed.size)
        return base + ov


    def resolve_set(self, ns_id: int, obj: str, rel: str) -> Optional[int]:
        raw = self.interned.resolve_set(ns_id, obj, rel)
        if raw >= 0:
            return int(self.raw2dev[raw])
        if self.ov_set_ids is not None:
            return self.ov_set_ids.get((ns_id, obj, rel))
        return None

    def resolve_leaf(self, subject_id: str) -> Optional[int]:
        raw = self.interned.resolve_leaf(subject_id)
        if raw >= 0:
            return int(self.raw2dev[raw + self.num_sets])
        if self.ov_leaf_ids is not None:
            return self.ov_leaf_ids.get(subject_id)
        return None

    def is_answerable_target(self, dev: int) -> bool:
        """True when a query targeting device node ``dev`` can be granted:
        the node has in-edges AND either a bitmap row (interior), answer
        gathers (sink), or overlay in-edges (sink-class overlay nodes —
        whose in-edges may live purely in the host one-hop adjacency)."""
        if dev < self.num_live:
            return True
        if self.ov_class is not None and self.ov_class.get(dev) == "sink":
            return True
        if self.ov_sink_in is not None and dev in self.ov_sink_in:
            return True
        return False

    def key_of_dev(self, dev: int):
        """``("set", (ns_id, object, relation))`` or ``("leaf",
        subject_id)`` for any device id, base or overlay — the reverse of
        ``resolve_set``/``resolve_leaf``, used by the expand engine to
        reconstruct tree-node subjects from BFS-captured device ids."""
        nb = self.n_base_nodes
        if dev >= nb:
            with self._cache_lock:
                inv = self._pattern_cache.get("_ov_inv")
                if inv is None:
                    inv = {}
                    for k, d in (self.ov_set_ids or {}).items():
                        inv[d] = ("set", k)
                    for s, d in (self.ov_leaf_ids or {}).items():
                        inv[d] = ("leaf", s)
                    self._pattern_cache["_ov_inv"] = inv
            return inv[dev]
        raw = int(self._dev2raw()[dev])
        if raw < self.num_sets:
            return ("set", self.interned.set_key_of(raw))
        return ("leaf", self.interned.leaf_str(raw - self.num_sets))

    def _dev2raw(self) -> np.ndarray:
        """Lazily cached inverse of the raw2dev permutation."""
        with self._cache_lock:
            d2r = self._pattern_cache.get("_dev2raw")
            if d2r is None:
                nb = self.n_base_nodes
                d2r = np.empty(nb, np.int64)
                d2r[self.raw2dev] = np.arange(nb)
                self._pattern_cache["_dev2raw"] = d2r
            return d2r

    def is_set_dev_bulk(self, devs: np.ndarray) -> np.ndarray:
        """bool[len(devs)] — True where the device id is a set node (base
        or overlay); False for subject-id leaves."""
        devs = np.asarray(devs)
        nb = self.n_base_nodes
        d2r = self._dev2raw()
        in_base = devs < nb
        out = np.zeros(devs.shape[0], bool)
        out[in_base] = d2r[devs[in_base]] < self.num_sets
        if not in_base.all():
            ov_sets = set((self.ov_set_ids or {}).values())
            for i in np.nonzero(~in_base)[0]:
                out[i] = int(devs[i]) in ov_sets
        return out

    def _removed_drop(self, keys: np.ndarray, cnts: np.ndarray):
        """(keep-mask over gathered entries, per-segment adjusted counts)
        for the tombstone filter, or None when nothing matches. ``keys``
        are (endpoint << 32) | endpoint packed like ``ov_removed``."""
        rem = self.ov_removed
        pos = np.clip(np.searchsorted(rem, keys), 0, rem.size - 1)
        hit = rem[pos] == keys
        if not hit.any():
            return None
        seg = np.repeat(np.arange(cnts.shape[0]), cnts)
        return ~hit, cnts - np.bincount(seg[hit], minlength=cnts.shape[0])

    def out_neighbors_bulk(self, nodes: np.ndarray, overlay: bool = True):
        """(concatenated out-neighbor devs of ``nodes``, per-node counts) —
        base forward CSR merged with the delta overlay's host-propagation
        adjacency (``ov_out`` — the class the check engine's batch-setup
        walk needs) and masked by its tombstones (deleted tuples). Node
        order is preserved. Base neighbor order within a node is
        GUARANTEED to be store row order (= the Manager's page order;
        interner dedup keeps first occurrence — the expand engine's
        tree-child parity depends on this, keto_tpu/expand/tpu_engine.py);
        overlay extras append after base neighbors. ``overlay=False``
        skips the ov_out merge (still tombstone-masked) — the expand
        engine merges the COMPLETE overlay adjacency (``ov_fwd``) itself,
        in Manager order."""
        nodes = np.asarray(nodes)
        nb = self.n_base_nodes
        if nodes.size and int(nodes.max()) >= nb:
            # overlay ids are out of the base CSR's range — contribute 0
            # base neighbors (their adjacency, if any, lives in ov_out)
            in_base = nodes < nb
            base_nodes = np.where(in_base, nodes, 0)
            cnts = np.where(
                in_base,
                self.fwd_indptr[base_nodes + 1] - self.fwd_indptr[base_nodes],
                0,
            )
            rows, cnts = _csr_gather_counts(
                self.fwd_indptr, self.fwd_indices, base_nodes, cnts
            )
        else:
            rows, cnts = _csr_gather_host(self.fwd_indptr, self.fwd_indices, nodes)
        if self.ov_removed is not None and self.ov_removed.size and rows.size:
            keys = (np.repeat(nodes.astype(np.int64), cnts) << 32) | rows.astype(
                np.int64
            )
            drop = self._removed_drop(keys, cnts)
            if drop is not None:
                keep, cnts = drop
                rows = rows[keep]
        ov = self.ov_out
        if not overlay or ov is None or not ov:
            return rows, cnts
        # vectorized membership: pack_chunk's multi-hop propagation calls
        # this per hop with frontiers of thousands of rows — a Python
        # per-element `in` loop would serialize the hot path
        with self._cache_lock:
            ov_keys = self._pattern_cache.get("_ov_out_keys")
            if ov_keys is None:
                ov_keys = np.fromiter(ov.keys(), np.int64, len(ov))
                self._pattern_cache["_ov_out_keys"] = ov_keys
        member = np.isin(nodes, ov_keys)
        if not member.any():
            return rows, cnts
        ends = np.cumsum(cnts)
        mi = np.nonzero(member)[0]
        extras = [np.asarray(ov[int(nodes[i])], rows.dtype) for i in mi]
        lens = np.asarray([e.size for e in extras], np.int64)
        rows = np.insert(rows, np.repeat(ends[mi], lens), np.concatenate(extras))
        cnts = cnts.copy()
        cnts[mi] += lens
        return rows, cnts

    def sink_in_rows_bulk(self, sinks: np.ndarray):
        """(concatenated interior in-neighbor rows of sink-class targets,
        per-target counts) — base sink reverse CSR merged with overlay
        in-edges and masked by tombstones. ``sinks`` are device ids (base
        sinks or overlay nodes)."""
        sinks = np.asarray(sinks)
        sb, nl = self.sink_base, self.num_live
        no_ov = self.ov_sink_in is None or not self.ov_sink_in
        if no_ov and (self.ov_removed is None or not self.ov_removed.size):
            return _csr_gather_host(self.sink_indptr, self.sink_indices, sinks - sb)
        in_base = (sinks >= sb) & (sinks < nl)
        base_idx = np.where(in_base, sinks - sb, 0)
        cnts = np.where(
            in_base,
            self.sink_indptr[base_idx + 1] - self.sink_indptr[base_idx],
            0,
        )
        rows, cnts = _csr_gather_counts(self.sink_indptr, self.sink_indices, base_idx, cnts)
        if self.ov_removed is not None and self.ov_removed.size and rows.size:
            keys = (rows.astype(np.int64) << 32) | np.repeat(
                sinks.astype(np.int64), cnts
            )
            drop = self._removed_drop(keys, cnts)
            if drop is not None:
                keep, cnts = drop
                rows = rows[keep]
        if no_ov:
            return rows, cnts
        ov = self.ov_sink_in
        member = np.asarray([int(s) in ov for s in sinks], bool)
        if not member.any():
            return rows, cnts
        ends = np.cumsum(cnts)
        mi = np.nonzero(member)[0]
        extras = [np.asarray(ov[int(sinks[i])], rows.dtype) for i in mi]
        lens = np.asarray([e.size for e in extras], np.int64)
        rows = np.insert(rows, np.repeat(ends[mi], lens), np.concatenate(extras))
        cnts = cnts.copy()
        cnts[mi] += lens
        return rows, cnts

    def _ov_rev(self) -> dict:
        """Lazily cached REVERSE of the unified overlay adjacency:
        dst dev → [src devs] for every overlay-added edge — the seeding
        source for backward listings while a delta overlay pends.
        Rebuilt per snapshot object (apply_delta resets the cache)."""
        with self._cache_lock:
            inv = self._pattern_cache.get("_ov_rev")
            if inv is None:
                inv = {}
                for src, dsts in (self.ov_fwd or {}).items():
                    for dst in dsts:
                        inv.setdefault(int(dst), []).append(int(src))
                self._pattern_cache["_ov_rev"] = inv
            return inv

    def in_neighbors_bulk(self, nodes: np.ndarray):
        """(concatenated in-neighbor devs of ``nodes``, per-node counts)
        — the transposed twin of ``out_neighbors_bulk``: base reverse
        CSR masked by tombstones, merged with the overlay's reverse
        adjacency. Feeds backward-listing seeds and the CPU-reference
        lister (keto_tpu/list/)."""
        nodes = np.asarray(nodes)
        nb = self.n_base_nodes
        if nodes.size and int(nodes.max()) >= nb:
            in_base = nodes < nb
            base_nodes = np.where(in_base, nodes, 0)
            cnts = np.where(
                in_base,
                self.rev_indptr[base_nodes + 1] - self.rev_indptr[base_nodes],
                0,
            )
            rows, cnts = _csr_gather_counts(
                self.rev_indptr, self.rev_indices, base_nodes, cnts
            )
        else:
            rows, cnts = _csr_gather_host(self.rev_indptr, self.rev_indices, nodes)
        if self.ov_removed is not None and self.ov_removed.size and rows.size:
            # tombstone keys pack (src << 32) | dst; here the gathered
            # entry is the SOURCE and the queried node the destination
            keys = (rows.astype(np.int64) << 32) | np.repeat(
                nodes.astype(np.int64), cnts
            )
            drop = self._removed_drop(keys, cnts)
            if drop is not None:
                keep, cnts = drop
                rows = rows[keep]
        ov = self._ov_rev() if self.ov_fwd else None
        if not ov:
            return rows, cnts
        member = np.asarray([int(n) in ov for n in nodes], bool)
        if not member.any():
            return rows, cnts
        ends = np.cumsum(cnts)
        mi = np.nonzero(member)[0]
        extras = [np.asarray(ov[int(nodes[i])], rows.dtype) for i in mi]
        lens = np.asarray([e.size for e in extras], np.int64)
        rows = np.insert(rows, np.repeat(ends[mi], lens), np.concatenate(extras))
        cnts = cnts.copy()
        cnts[mi] += lens
        return rows, cnts

    def _pattern_index(self, kind: str):
        """Lazily built sorted key index for pattern resolution:
        ``(order, sorted primary col, sorted secondary col | None,
        composite (primary<<32 | secondary) col | None)``.
        Kinds: "no" = (ns, obj), "nr" = (ns, rel), "or" = (obj, rel),
        "r" = (rel,). Built once per snapshot; every pattern family then
        resolves with binary searches instead of an O(num_sets) scan —
        the fix for wildcard-heavy batches serializing on the host. The
        composite column is sorted under the same lexsort, so a BULK of
        two-field patterns resolves with one vectorized searchsorted over
        pairs (``resolve_starts_bulk``)."""
        ck = ("_pidx", kind)
        with self._cache_lock:
            hit = self._pattern_cache.get(ck)
        if hit is not None:
            return hit
        i = self.interned
        kn = np.asarray(i.key_ns)
        ko = np.asarray(i.key_obj)
        kr = np.asarray(i.key_rel)
        if kind == "no":
            order = np.lexsort((ko, kn))
            c1, c2 = kn[order], ko[order]
        elif kind == "nr":
            order = np.lexsort((kr, kn))
            c1, c2 = kn[order], kr[order]
        elif kind == "or":
            order = np.lexsort((kr, ko))
            c1, c2 = ko[order], kr[order]
        else:  # "r"
            order = np.argsort(kr, kind="stable")
            c1, c2 = kr[order], None
        comp = None if c2 is None else (c1.astype(np.int64) << 32) | c2.astype(np.int64)
        entry = (order, c1, c2, comp)
        with self._cache_lock:
            self._pattern_cache[ck] = entry
        return entry

    @staticmethod
    def _index_range(entry, v1, v2=None) -> np.ndarray:
        """Raw set ids whose primary key equals ``v1`` (and secondary
        equals ``v2`` when given), via the sorted index."""
        order, c1, c2, _comp = entry
        lo = int(np.searchsorted(c1, v1, "left"))
        hi = int(np.searchsorted(c1, v1, "right"))
        if v2 is None or c2 is None:
            return order[lo:hi]
        seg = c2[lo:hi]
        l2 = int(np.searchsorted(seg, v2, "left"))
        h2 = int(np.searchsorted(seg, v2, "right"))
        return order[lo + l2 : lo + h2]

    def resolve_starts(self, ns_id: int, obj: str, rel: str) -> np.ndarray:
        """Device ids of the set nodes a check starting at ``(ns, obj, rel)``
        expands — the graph analog of the reference's wildcarding tuple query
        (reference internal/persistence/sql/relationtuples.go:218-235).

        ``ns_id == WILDCARD`` (empty namespace name) wildcards the namespace;
        empty ``obj``/``rel`` wildcard those fields. A fully literal pattern
        resolves to at most one node. For wildcard patterns, every node key
        matching the pattern is a start: the union of their out-edges is
        exactly the subjects of the pattern's matching tuples (a matching
        key's query is always a sub-query of the pattern's).
        """
        ns_wild = ns_id == WILDCARD or ns_id in self.wild_ns_ids
        if not ns_wild and obj != "" and rel != "":
            dev = self.resolve_set(ns_id, obj, rel)
            return np.asarray([] if dev is None else [dev], np.int64)

        key = (WILDCARD if ns_wild else ns_id, obj if obj != "" else None, rel if rel != "" else None)
        with self._cache_lock:
            hit = self._pattern_cache.get(key)
        if hit is not None:
            return hit
        oc = self.interned.obj_code(obj) if obj != "" else None
        rc = self.interned.rel_code(rel) if rel != "" else None
        if (obj != "" and oc < 0) or (rel != "" and rc < 0):
            cand = np.zeros(0, np.int64)  # a literal field never interned
        elif not ns_wild:
            if oc is not None:  # (ns, obj, *)
                cand = self._index_range(self._pattern_index("no"), ns_id, oc)
            elif rc is not None:  # (ns, *, rel)
                cand = self._index_range(self._pattern_index("nr"), ns_id, rc)
            else:  # (ns, *, *)
                cand = self._index_range(self._pattern_index("no"), ns_id)
        else:
            if oc is not None and rc is not None:  # (*, obj, rel)
                cand = self._index_range(self._pattern_index("or"), oc, rc)
            elif oc is not None:  # (*, obj, *)
                cand = self._index_range(self._pattern_index("or"), oc)
            elif rc is not None:  # (*, *, rel)
                cand = self._index_range(self._pattern_index("r"), rc)
            else:  # (*, *, *)
                cand = np.arange(self.num_sets, dtype=np.int64)
        return self._starts_from_candidates(key, ns_wild, ns_id, obj, rel, cand)

    def _starts_from_candidates(
        self, key, ns_wild: bool, ns_id, obj: str, rel: str, cand: np.ndarray
    ) -> np.ndarray:
        """Candidate raw set ids → device start rows (+ overlay extras),
        cached under ``key`` — the shared tail of ``resolve_starts`` and
        ``resolve_starts_bulk``."""
        # ascending raw-id order: bitwise-identical to the old full-scan
        # nonzero() result (multi-host lockstep determinism)
        starts = self.raw2dev[np.sort(cand)] if cand.size else np.zeros(0, np.int64)
        if self.ov_set_ids:
            # overlay keys are always fully literal (a new wildcard key
            # forces a full rebuild), so pattern-match them directly
            extra = [
                dev
                for (k_ns, k_obj, k_rel), dev in self.ov_set_ids.items()
                if (ns_wild or k_ns == ns_id)
                and (obj == "" or k_obj == obj)
                and (rel == "" or k_rel == rel)
            ]
            if extra:
                starts = np.concatenate([starts, np.asarray(extra, np.int64)])
        with self._cache_lock:
            self._pattern_cache[key] = starts
        return starts

    def resolve_starts_bulk(self, pats) -> list:
        """``resolve_starts`` for a whole batch of ``(ns_id, obj, rel)``
        patterns in one pass. Duplicate patterns dedupe against the
        pattern cache; uncached patterns group by wildcard family so each
        family costs ONE vectorized searchsorted over its sorted index
        (two-field families probe the composite key column) instead of a
        per-query probe — the fix for wildcard-heavy batches serializing
        on host pattern resolution. Results land in the same cache
        ``resolve_starts`` uses, so follow-up streams stay O(1)."""
        out: list = [None] * len(pats)
        fresh: dict[tuple, list[int]] = {}
        for j, (ns_id, obj, rel) in enumerate(pats):
            ns_wild = ns_id == WILDCARD or ns_id in self.wild_ns_ids
            if not ns_wild and obj != "" and rel != "":
                out[j] = self.resolve_starts(ns_id, obj, rel)  # literal: ≤ 1 node
                continue
            key = (
                WILDCARD if ns_wild else ns_id,
                obj if obj != "" else None,
                rel if rel != "" else None,
            )
            with self._cache_lock:
                hit = self._pattern_cache.get(key)
            if hit is not None:
                out[j] = hit
            else:
                fresh.setdefault(key, []).append(j)
        if not fresh:
            return out
        # one probe spec per distinct uncached pattern, grouped by family
        groups: dict[tuple, list] = {}
        for key, js in fresh.items():
            kns, kobj, krel = key
            ns_wild = kns == WILDCARD
            obj = kobj if kobj is not None else ""
            rel = krel if krel is not None else ""
            oc = self.interned.obj_code(obj) if kobj is not None else None
            rc = self.interned.rel_code(rel) if krel is not None else None
            if (kobj is not None and oc < 0) or (krel is not None and rc < 0):
                # a literal field never interned: no candidates
                starts = self._starts_from_candidates(
                    key, ns_wild, kns, obj, rel, np.zeros(0, np.int64)
                )
                for j in js:
                    out[j] = starts
                continue
            if not ns_wild:
                if oc is not None:  # (ns, obj, *)
                    spec = ("no", kns, oc)
                elif rc is not None:  # (ns, *, rel)
                    spec = ("nr", kns, rc)
                else:  # (ns, *, *)
                    spec = ("no", kns, None)
            else:
                if oc is not None and rc is not None:  # (*, obj, rel)
                    spec = ("or", oc, rc)
                elif oc is not None:  # (*, obj, *)
                    spec = ("or", oc, None)
                elif rc is not None:  # (*, *, rel)
                    spec = ("r", rc, None)
                else:  # (*, *, *): every set node
                    starts = self._starts_from_candidates(
                        key, True, kns, obj, rel,
                        np.arange(self.num_sets, dtype=np.int64),
                    )
                    for j in js:
                        out[j] = starts
                    continue
            kind, v1, v2 = spec
            groups.setdefault((kind, v2 is not None), []).append(
                (key, js, v1, v2, ns_wild, kns, obj, rel)
            )
        for (kind, two), items in groups.items():
            order, c1, _c2, comp = self._pattern_index(kind)
            v1s = np.asarray([it[2] for it in items], np.int64)
            if two:
                probe = (v1s << 32) | np.asarray([it[3] for it in items], np.int64)
                col = comp
            else:
                probe = v1s
                col = c1
            lo = np.searchsorted(col, probe, "left")
            hi = np.searchsorted(col, probe, "right")
            for (key, js, _v1, _v2, ns_wild, kns, obj, rel), l, h in zip(items, lo, hi):
                starts = self._starts_from_candidates(
                    key, ns_wild, kns, obj, rel, order[l:h]
                )
                for j in js:
                    out[j] = starts
        return out


def build_snapshot(
    rows: Iterable,
    watermark: int,
    wild_ns_ids: FrozenSet[int] = frozenset(),
    peel_seed_cap: float = 4.0,
    columns: Optional[dict] = None,
    sorter=None,
    progress=None,
) -> GraphSnapshot:
    """Intern rows and lay out the bucketed reverse-ELL adjacency.

    ``wild_ns_ids``: ids of configured namespaces whose *name* is the empty
    string — their set nodes expand with a wildcarded namespace. Interning
    runs in the native C++ path when ``native/libketoingest.so`` is built
    (``make native``), else in Python. ``columns`` is the store's optional
    sorted column bundle (MemoryPersister.snapshot_columns) — the
    zero-extraction interning input. ``sorter``/``progress`` ride through
    to ``layout_snapshot`` (device-side build + the build-progress
    observability seam); the streaming pipeline
    (keto_tpu/graph/stream_build.py) interns incrementally and calls
    ``layout_snapshot`` directly.
    """
    rows = list(rows)
    from keto_tpu.graph.native import native_intern_rows

    if progress is not None:
        with progress.phase("intern"):
            g = native_intern_rows(rows, wild_ns_ids, columns=columns)
            if g is None:
                g = intern_rows(rows, wild_ns_ids)
            progress.add_rows(len(rows))
    else:
        g = native_intern_rows(rows, wild_ns_ids, columns=columns)
        if g is None:
            g = intern_rows(rows, wild_ns_ids)
    return layout_snapshot(
        g, watermark, wild_ns_ids, peel_seed_cap=peel_seed_cap,
        sorter=sorter, progress=progress,
    )


def layout_snapshot(
    g,
    watermark: int,
    wild_ns_ids: FrozenSet[int] = frozenset(),
    peel_seed_cap: float = 4.0,
    sorter=None,
    progress=None,
) -> GraphSnapshot:
    """Lay out an already-interned graph ``g`` (InternedGraph or
    NativeInterned) into the device snapshot: classify/peel, renumber,
    bucket, and derive the forward CSR, sink reverse CSR, transposed
    CSR, and both list layouts. Every O(E log E) stable sort goes
    through ``sorter`` (keto_tpu/graph/device_build.py) — the device
    backend runs them on the accelerator in fused dispatches, the host
    backend is the legacy numpy path; both are bit-identical by the
    stable-sort contract and fuzz-asserted so
    (tests/test_streaming_build.py)."""
    if progress is not None:
        ctx = progress.phase("device_build")
        ctx.__enter__()
    S = sorter or _host_sorter()
    src_raw, dst_raw = g.src, g.dst
    n = g.num_nodes
    try:
        snap = _layout_snapshot_inner(
            g, watermark, wild_ns_ids, peel_seed_cap, S, src_raw, dst_raw, n
        )
    finally:
        if progress is not None:
            progress.add_edges(int(np.asarray(src_raw).shape[0]))
            ctx.__exit__(None, None, None)
    return snap


def _layout_snapshot_inner(
    g, watermark, wild_ns_ids, peel_seed_cap, S, src_raw, dst_raw, n
) -> GraphSnapshot:
    if n == 0:
        return GraphSnapshot(
            snapshot_id=watermark,
            num_sets=0,
            num_leaves=0,
            num_active=0,
            num_int=0,
            num_live=0,
            n_peeled=0,
            buckets=[],
            interned=g,
            raw2dev=np.zeros(0, np.int64),
            wild_ns_ids=wild_ns_ids,
            fwd_indptr=np.zeros(1, np.int64),
            fwd_indices=np.zeros(0, np.int32),
            sink_indptr=np.zeros(1, np.int64),
            sink_indices=np.zeros(0, np.int32),
            rev_indptr=np.zeros(1, np.int64),
            rev_indices=np.zeros(0, np.int32),
            lay_fwd=_one_list_layout(np.zeros(0, np.int64), np.zeros(0, np.int64), 0, "fwd"),
            lay_rev=_one_list_layout(np.zeros(0, np.int64), np.zeros(0, np.int64), 0, "rev"),
        )

    in_deg = np.bincount(dst_raw, minlength=n)
    out_deg = np.bincount(src_raw, minlength=n)
    has_in = in_deg > 0
    has_out = out_deg > 0
    interior = has_in & has_out
    sink = has_in & ~has_out

    # --- peel ---------------------------------------------------------------
    # An interior node whose in-edges all come from static or
    # already-peeled nodes has an init-CONSTANT bitmap row: its reached
    # bits never change during the BFS loop. If it additionally has no
    # out-edge into a sink (so forward expansion can't fan into the
    # subject-leaf population), it leaves the device entirely — its effect
    # folds into the per-batch host propagation (tpu_engine.pack_chunk),
    # which generalizes the static one-hop term to the peeled DAG. This is
    # the big lever on grant-chain workloads: e.g. the GitHub-shaped
    # BASELINE config 4, where issues→repos→orgs chains peel ~80% of the
    # bitmap rows and ~90% of the gather entries out of the kernel.
    has_sink_out = np.zeros(n, bool)
    m = sink[dst_raw]
    if m.any():
        has_sink_out[np.unique(src_raw[m])] = True
    # Seed-inflation guard: peeling trades device gather work for
    # host-computed seed entries shipped per batch (H2D bytes and host
    # pack time), so a node only peels when the number of bitmap seeds it
    # would expand to (its forward closure through already-peeled nodes)
    # stays small. A high-fanout hub (e.g. an org granting 25 teams)
    # keeps its bitmap row; its fanout stays a device edge gathered per
    # iteration instead of 25 seeds per query. A higher cap
    # (engine.peel_seed_cap) buys smaller kernels with more seed bytes
    # per batch; where the default of 4 sits on that trade for a
    # directly attached chip is not measured.
    SEED_CAP = peel_seed_cap
    peeled = np.zeros(n, bool)
    closure = np.zeros(n)  # seeds a peeled node expands to
    for _ in range(16):  # bounded: adversarial deep chains stay active
        blockers = interior & ~peeled
        deg = np.bincount(dst_raw[blockers[src_raw]], minlength=n)
        cand = interior & ~peeled & (deg == 0) & ~has_sink_out
        if not cand.any():
            break
        # candidates never point at same-round candidates (that would be
        # an unpeeled-interior in-edge), so contributions are well-defined
        contrib = np.where(peeled[dst_raw], closure[dst_raw], 1.0)
        cand_closure = np.bincount(src_raw, weights=contrib, minlength=n)
        newly = cand & (cand_closure <= SEED_CAP)
        if not newly.any():
            break
        peeled |= newly
        closure[newly] = cand_closure[newly]

    live_int = interior & ~peeled  # nodes with bitmap rows
    # iterated ("ELL") edges: unpeeled interior → unpeeled interior. Edges
    # from static/peeled sources are the batch-time host-propagation term;
    # edges into sinks are answer-time gathers — neither is materialized
    # in the loop. (A sink's in-neighbors are never peeled: an edge into a
    # sink is exactly what blocks peeling — the answer gather relies on
    # this.)
    ell_edge = live_int[src_raw] & live_int[dst_raw]
    int_in_deg = np.bincount(dst_raw[ell_edge], minlength=n)

    # bucket key: ceil-log2(interior in-degree) + 1 for active-interior;
    # passive-interior 61, peeled 62, sinks 63, static 64
    with np.errstate(divide="ignore"):
        bucket_key = np.ceil(np.log2(np.maximum(int_in_deg, 1))).astype(np.int64) + 1
    bucket_key[int_in_deg == 1] = 1
    bucket_key[live_int & (int_in_deg == 0)] = 61
    bucket_key[peeled] = 62
    bucket_key[sink] = 63
    bucket_key[~has_in] = 64

    # renumber: device order sorts by (bucket, raw id) — the raw-id
    # tie-break IS stability, so lexsort((arange, key)) == stable
    # argsort(key) and both sorter backends share one primitive
    dev_order = S.argsort(bucket_key)
    raw2dev = np.empty(n, dtype=np.int64)
    raw2dev[dev_order] = np.arange(n)

    num_active = int(np.count_nonzero(bucket_key < 61))
    num_int = int(np.count_nonzero(live_int))
    n_peeled = int(np.count_nonzero(peeled))
    num_live = int(np.count_nonzero(has_in))

    # the three edge-scale groupings below (ELL by destination, forward
    # CSR by source, sink reverse CSR by sink) are independent once
    # raw2dev exists — one fused sorter dispatch covers all of them (on
    # the device backend this is the single round trip over the interned
    # edge array; the host backend just loops)
    dst_dev = raw2dev[dst_raw[ell_edge]]
    src_dev = raw2dev[src_raw[ell_edge]]
    all_src_dev = raw2dev[src_raw]
    all_dst_dev = raw2dev[dst_raw]
    s_edge = has_in[src_raw] & sink[dst_raw]
    sink_base = num_int + n_peeled
    s_dst = raw2dev[dst_raw[s_edge]] - sink_base
    s_src = raw2dev[src_raw[s_edge]].astype(np.int32)
    order, forder, sorder = S.argsort_many([dst_dev, all_src_dev, s_dst])

    # group ELL edges by destination device id; cumcount gives the column
    # slot. Destinations of ELL edges are active-interior by construction.
    dst_sorted = dst_dev[order]
    src_sorted = src_dev[order].astype(np.int32)
    starts = np.searchsorted(dst_sorted, np.arange(num_active))
    cumcount = np.arange(dst_sorted.shape[0]) - starts[dst_sorted]

    key_by_dev = bucket_key[dev_order][:num_active]
    buckets: list[Bucket] = []
    sentinel = np.int32(num_int)  # the bitmap's all-zero row
    for key in np.unique(key_by_dev):
        members = np.nonzero(key_by_dev == key)[0]  # contiguous by construction
        offset, n_rows = int(members[0]), int(members.shape[0])
        cap = 1 << (int(key) - 1)
        n_pad = _ceil_pow2(n_rows)
        nbrs = np.full((n_pad, cap), sentinel, dtype=np.int32)
        edge_mask = (dst_sorted >= offset) & (dst_sorted < offset + n_rows)
        nbrs[dst_sorted[edge_mask] - offset, cumcount[edge_mask]] = src_sorted[edge_mask]
        buckets.append(Bucket(offset=offset, n=n_rows, nbrs=nbrs))

    # host-side forward CSR over ALL edges (device ids) — used by expand
    # and by the batch-setup one-hop propagation from static start nodes
    fsrc = all_src_dev[forder]
    findices = all_dst_dev[forder].astype(np.int32)
    findptr = np.searchsorted(fsrc, np.arange(n + 1))

    # sink reverse CSR: interior in-neighbors per sink, for answer gathers
    # (all unpeeled by construction — see the peel note above)
    n_sink = num_live - sink_base
    sink_indptr = np.searchsorted(s_dst[sorder], np.arange(n_sink + 1))
    sink_indices = s_src[sorder]

    # reverse-query layouts (keto_tpu/list/): the transposed CSR over all
    # device ids plus bucketed-ELL list layouts in BOTH orientations over
    # the interior-class rows — built here so every snapshot can answer
    # ListObjects/ListSubjects without a second interning pass
    rev_indptr, rev_indices = build_rev_csr(findptr, findices, n, sorter=S)
    lay_fwd, lay_rev = build_list_layouts(findptr, findices, n, sink_base, sorter=S)

    return GraphSnapshot(
        snapshot_id=watermark,
        num_sets=g.num_sets,
        num_leaves=g.num_leaves,
        num_active=num_active,
        num_int=num_int,
        n_peeled=n_peeled,
        num_live=num_live,
        buckets=buckets,
        interned=g,
        raw2dev=raw2dev,
        wild_ns_ids=wild_ns_ids,
        fwd_indptr=findptr,
        fwd_indices=findices,
        sink_indptr=sink_indptr,
        sink_indices=sink_indices,
        rev_indptr=rev_indptr,
        rev_indices=rev_indices,
        lay_fwd=lay_fwd,
        lay_rev=lay_rev,
    )
