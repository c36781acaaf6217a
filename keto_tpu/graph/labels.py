"""Pruned-landmark 2-hop reachability labels: O(1)-step checks at any depth.

The BFS check kernel (keto_tpu/check/kernels.py) pays one TPU step per
frontier hop, so deep grant chains (team forests, org hierarchies) tax
every check with their depth: BENCH_r04's depth-8 config runs ~60k
checks/s against ~215k on the shallow graph. This module precomputes a
**2-hop label index** over the interior subgraph at snapshot-build time
("Simple, Fast, and Scalable Reachability Oracle", PAPERS.md) so a
reachability probe becomes ONE sorted-label intersection — a single
gather + compare on device, independent of graph depth.

Scope — the label universe is exactly the BFS kernel's bitmap universe:

- labels cover **interior rows** (device ids < ``num_int``) and the
  **iterated (ELL) edges** between them — the only part of the graph the
  BFS loop walks. Static/peeled starts are host-propagated to interior
  seeds by ``pack_chunk`` (unchanged), and sink targets are answered
  through their interior in-neighbor gathers (unchanged), so the label
  path reuses the engine's existing host resolution end to end and only
  replaces the iterated device loop;
- ``reach0(a, b)`` below means "b reachable from a via ≥ 0 ELL edges"
  (``a == b`` counts). The engine's router maps the check semantics
  ("reached via ≥ 1 real edge") onto reach0 probes exactly — see
  ``TpuCheckEngine._label_route``.

Construction is **pruned landmark labeling** (PLL): process interior
nodes in degree rank order; for node v, a forward pruned BFS appends v to
``IN(u)`` of every node u it reaches (skipping u when an earlier-ranked
hub already certifies v→u), and a backward pruned BFS appends v to
``OUT(u)``. Processing ALL nodes yields an exact oracle; the
``landmarks`` knob caps processing for build-time budgets, and
``max_width`` caps per-row label width for the device layout. Both caps
degrade COVERAGE, never correctness:

- every stored entry witnesses a real path, so a label **hit is always a
  sound grant**;
- a **miss certifies a deny** only for pairs ``(a, b)`` where
  ``out_ok[a] and in_ok[b] and (processed[a] or processed[b])`` — the
  PLL completeness argument needs one endpoint's own BFS to have run,
  and neither endpoint's label truncated. Uncertifiable pairs fall back
  to the BFS kernel, bit-identically.

The index is immutable and shared across snapshots like every other
snapshot array; the mutation path keeps it honest:

- ``overlay.apply_delta`` records inserted/tombstoned ELL edges in
  ``lab_dirty`` — while any are pending, the engine disables the label
  path (every other overlay class — new sinks, sink in-edges, host-walk
  adjacency, host-masked tombstones — leaves the interior subgraph
  untouched, so labels stay EXACT through those);
- ``compaction`` patches labels incrementally for folded ELL inserts
  (``patch_labels`` — resumed pruned BFS per edge, the standard
  incremental-PLL insertion) and falls back to a full label rebuild past
  a visit budget or for folded deletions (2-hop deletion is a rebuild in
  the literature too);
- ``snapcache`` persists the arrays (crc-covered segments) so cold
  starts skip construction.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Optional

import numpy as np

#: padding values for the device rows: the two sides pad differently so a
#: padded slot can never witness an intersection
OUT_PAD = np.int32(-1)
IN_PAD = np.int32(-2)


def _ceil_pow2(x: int) -> int:
    return 1 if x <= 1 else 1 << (int(x) - 1).bit_length()


def interior_adjacency(snap):
    """The iterated (ELL) edge set of ``snap`` as forward + reverse CSRs
    over interior device ids — (out_indptr, out_indices, in_indptr,
    in_indices), all int64/int32. Derived from the bucket matrices (the
    kernel's own edge source), so labels and BFS walk the SAME graph by
    construction."""
    ni = snap.num_int
    srcs: list[np.ndarray] = []
    dsts: list[np.ndarray] = []
    sentinel = np.int32(ni)
    for b in snap.buckets:
        nbrs = np.asarray(b.nbrs[: b.n])
        rows, cols = np.nonzero(nbrs != sentinel)
        if rows.size:
            srcs.append(nbrs[rows, cols].astype(np.int64))
            dsts.append((rows + b.offset).astype(np.int64))
    if srcs:
        src = np.concatenate(srcs)
        dst = np.concatenate(dsts)
    else:
        src = np.zeros(0, np.int64)
        dst = np.zeros(0, np.int64)
    o = np.argsort(src, kind="stable")
    out_indptr = np.searchsorted(src[o], np.arange(ni + 1))
    out_indices = dst[o].astype(np.int32)
    i = np.argsort(dst, kind="stable")
    in_indptr = np.searchsorted(dst[i], np.arange(ni + 1))
    in_indices = src[i].astype(np.int32)
    return out_indptr, out_indices, in_indptr, in_indices


@dataclass
class LabelIndex:
    """Immutable 2-hop label arrays over ``n`` interior rows.

    ``out_lab``/``in_lab`` are padded-ELL int32 ``[n + 1, W]`` matrices
    (row ``n`` is all-padding — the engine's pair padding gathers it);
    valid entries per row are sorted ascending. ``processed[u]`` means
    u's own pruned BFS ran (u was a landmark); ``out_ok``/``in_ok`` mean
    the row never hit the width cap. See the module docstring for the
    exactness rules these flags carry."""

    n: int
    out_lab: np.ndarray  # int32 [n+1, Wo], OUT_PAD-padded
    in_lab: np.ndarray  # int32 [n+1, Wi], IN_PAD-padded
    processed: np.ndarray  # bool [n]
    out_ok: np.ndarray  # bool [n]
    in_ok: np.ndarray  # bool [n]
    max_width: int
    n_landmarks: int
    build_ms: float = 0.0
    #: total stored entries (both sides) — operators size budgets off this
    n_entries: int = 0
    #: which construction path produced the index: "host" (this module's
    #: per-landmark Python BFS) or "device" (the batched frontier sweeps
    #: of keto_tpu/graph/label_build.py — entry-identical by contract)
    backend: str = "host"
    device: object = field(default=None, compare=False)  # jnp arrays, engine-set

    @property
    def coverage(self) -> float:
        """Fraction of interior rows fully certifiable on BOTH sides —
        the ``keto_label_coverage_ratio`` gauge."""
        if self.n == 0:
            return 1.0
        return float(
            np.count_nonzero(self.processed & self.out_ok & self.in_ok) / self.n
        )

    def device_bytes(self) -> int:
        """Device footprint of the uploaded label arrays — what the HBM
        governor (keto_tpu/driver/hbm.py) plans and registers under the
        ``labels`` ledger tag before the engine uploads them."""
        return int(self.out_lab.nbytes + self.in_lab.nbytes)

    def certifiable(self, a: np.ndarray, b: np.ndarray) -> np.ndarray:
        """bool[len(a)] — True where a MISS on pair (a[i], b[i]) is a
        sound deny (see module docstring). Rows == n (the padding row)
        certify trivially: they witness no path and assert none."""
        a = np.asarray(a)
        b = np.asarray(b)
        pad_a = a >= self.n
        pad_b = b >= self.n
        ac = np.where(pad_a, 0, a)
        bc = np.where(pad_b, 0, b)
        out = (
            self.out_ok[ac]
            & self.in_ok[bc]
            & (self.processed[ac] | self.processed[bc])
        )
        return out | pad_a | pad_b

    def query(self, a: int, b: int) -> bool:
        """Host-side reach0 probe (tests, compaction pruning): does
        OUT(a) intersect IN(b)?"""
        if a >= self.n or b >= self.n:
            return False
        oa = self.out_lab[a]
        ib = self.in_lab[b]
        oa = oa[oa != OUT_PAD]
        ib = ib[ib != IN_PAD]
        if not oa.size or not ib.size:
            return False
        return bool(np.isin(oa, ib, assume_unique=True).any())

    def witness_landmark(self, a: int, b: int) -> Optional[int]:
        """The winning entry of the reach0 intersection for ``(a, b)``:
        the minimum common landmark id, or None on miss. Every stored
        entry witnesses a real path, so a returned landmark sits on a
        genuine a→…→landmark→…→b chain — the 2-hop witness the explain
        subsystem surfaces. The device path
        (tpu_engine.label_step_witness) is argmin over the same compare."""
        if a >= self.n or b >= self.n:
            return None
        oa = self.out_lab[a]
        ib = self.in_lab[b]
        oa = oa[oa != OUT_PAD]
        ib = ib[ib != IN_PAD]
        if not oa.size or not ib.size:
            return None
        common = oa[np.isin(oa, ib, assume_unique=True)]
        return int(common.min()) if common.size else None


def _finalize(
    n: int,
    out_sets: list,
    in_sets: list,
    processed: np.ndarray,
    out_ok: np.ndarray,
    in_ok: np.ndarray,
    max_width: int,
    n_landmarks: int,
) -> LabelIndex:
    """Pack per-node label sets into the padded, sorted device layout."""
    wo = max((len(s) for s in out_sets), default=0)
    wi = max((len(s) for s in in_sets), default=0)
    Wo = _ceil_pow2(max(1, wo))
    Wi = _ceil_pow2(max(1, wi))
    out_lab = np.full((n + 1, Wo), OUT_PAD, np.int32)
    in_lab = np.full((n + 1, Wi), IN_PAD, np.int32)
    entries = 0
    for u in range(n):
        s = sorted(out_sets[u])
        if s:
            out_lab[u, : len(s)] = s
            entries += len(s)
        s = sorted(in_sets[u])
        if s:
            in_lab[u, : len(s)] = s
            entries += len(s)
    return LabelIndex(
        n=n,
        out_lab=out_lab,
        in_lab=in_lab,
        processed=processed,
        out_ok=out_ok,
        in_ok=in_ok,
        max_width=max_width,
        n_landmarks=n_landmarks,
        n_entries=entries,
    )


def landmark_order(
    out_indptr: np.ndarray, in_indptr: np.ndarray, n: int
) -> np.ndarray:
    """THE landmark processing order: degree descending, device id
    ascending on ties — deterministic across hosts (the multi-controller
    lockstep contract). Shared by ``build_labels`` and the device
    builder (keto_tpu/graph/label_build.py) so their entry-identity
    contract starts from the identical rank list."""
    out_deg = np.diff(out_indptr)
    in_deg = np.diff(in_indptr)
    return np.lexsort((np.arange(n), -(out_deg + in_deg)))


def _csr_row(indptr, indices, u: int) -> np.ndarray:
    return indices[indptr[u] : indptr[u + 1]]


def _pruned_bfs(
    v: int,
    frontier_adj,  # (indptr, indices) to EXPAND along
    own_label: set,  # OUT(v) for forward, IN(v) for backward
    write_labels: list,  # IN sets for forward, OUT sets for backward
    ok_flags: np.ndarray,
    max_width: int,
    start: Optional[int] = None,
    prune_expansion: bool = True,
    budget: Optional[list] = None,
) -> None:
    """One pruned BFS for landmark ``v``: visit u; when an earlier-ranked
    hub already certifies the pair (``own_label ∩ write_labels[u]`` —
    both sides hold only earlier ranks plus v itself), skip storing v at
    u, else record it (a width-cap overflow trips ``ok_flags[u]``
    instead of lying).

    ``prune_expansion=True`` is static PLL: a certified node's subtree
    is skipped entirely (sound because every label in the index
    witnesses the SAME immutable graph). Incremental patches pass False:
    a hub certificate minted before an edge insertion does not extend to
    the node's new descendants, so the patch must keep expanding and
    apply the hub test per node for storage only. ``start`` resumes the
    walk mid-graph (patching edge a→b resumes at b); ``budget`` (mutable
    ``[remaining_visits]``) makes patches abortable — the caller
    rebuilds when it runs dry."""
    indptr, indices = frontier_adj
    s = v if start is None else start
    visited = {s}
    frontier = [s]
    while frontier:
        nxt: list = []
        for u in frontier:
            for w in _csr_row(indptr, indices, u):
                w = int(w)
                if w in visited:
                    continue
                visited.add(w)
                if budget is not None:
                    budget[0] -= 1
                    if budget[0] < 0:
                        raise _BudgetExceeded
                certified = bool(own_label & write_labels[w])
                if not certified:
                    lab = write_labels[w]
                    if len(lab) < max_width:
                        lab.add(v)
                    else:
                        ok_flags[w] = False
                if certified and prune_expansion:
                    continue
                nxt.append(w)
        frontier = nxt


class _BudgetExceeded(Exception):
    pass


def build_labels(
    snap, max_width: int = 64, landmarks: int = 0, deadline_s: Optional[float] = None
) -> Optional[LabelIndex]:
    """Construct the index for ``snap`` (see module docstring).
    ``landmarks == 0`` processes every interior node (exact oracle);
    a positive cap processes only the top-ranked ones (coverage shrinks,
    soundness holds). Deterministic: rank ties break on device id, BFS
    label content is visit-order independent — the multi-controller
    lockstep contract holds for label-path decisions too. With
    ``deadline_s`` the build gives up (None) once it has run that long:
    the caller has a builder whose time does not depend on what pruning
    leaves (``TpuCheckEngine._build_label_index``)."""
    import time

    t0 = time.monotonic()
    n = snap.num_int
    out_indptr, out_indices, in_indptr, in_indices = interior_adjacency(snap)
    # rank: degree descending, id ascending (deterministic across hosts)
    order = landmark_order(out_indptr, in_indptr, n)
    K = n if landmarks <= 0 else min(int(landmarks), n)

    out_sets: list = [set() for _ in range(n)]
    in_sets: list = [set() for _ in range(n)]
    processed = np.zeros(n, bool)
    out_ok = np.ones(n, bool)
    in_ok = np.ones(n, bool)

    give_up_at = None if deadline_s is None else t0 + deadline_s
    for v in order[:K].tolist():
        if give_up_at is not None and time.monotonic() > give_up_at:
            return None
        # self entries first: reach0(v, v) must hit, and the prune tests
        # below rely on v ∈ own label
        if len(out_sets[v]) < max_width:
            out_sets[v].add(v)
        else:
            out_ok[v] = False
        if len(in_sets[v]) < max_width:
            in_sets[v].add(v)
        else:
            in_ok[v] = False
        _pruned_bfs(
            v, (out_indptr, out_indices), out_sets[v], in_sets, in_ok,
            max_width,
        )
        _pruned_bfs(
            v, (in_indptr, in_indices), in_sets[v], out_sets, out_ok,
            max_width,
        )
        processed[v] = True

    idx = _finalize(
        n, out_sets, in_sets, processed, out_ok, in_ok, max_width, K
    )
    idx.build_ms = (time.monotonic() - t0) * 1e3
    return idx


def patch_labels(
    idx: LabelIndex,
    snap,
    added_edges,
    visit_budget: int = 65536,
) -> Optional[LabelIndex]:
    """Incremental-PLL edge insertion: for each folded ELL edge (a, b),
    every landmark recorded as reaching ``a`` resumes its forward pruned
    BFS from ``b`` (and symmetrically from ``b``'s OUT entries backward
    through ``a``) over the COMPACTED adjacency. Returns the patched
    index, or None when the caller must rebuild: endpoint labels are
    truncated (the resume set is incomplete), the visit budget runs dry,
    or the index universe doesn't match the snapshot."""
    import time

    t0 = time.monotonic()
    n = snap.num_int
    if idx.n != n:
        return None
    added = [(int(a), int(b)) for a, b in added_edges]
    for a, b in added:
        if not (0 <= a < n and 0 <= b < n):
            return None
        if not (idx.in_ok[a] and idx.out_ok[b]):
            # the resume sets IN(a)/OUT(b) are incomplete — a resumed
            # patch would silently miss landmarks
            return None

    out_indptr, out_indices, in_indptr, in_indices = interior_adjacency(snap)
    out_sets = [
        set(int(x) for x in row[row != OUT_PAD]) for row in idx.out_lab[:n]
    ]
    in_sets = [
        set(int(x) for x in row[row != IN_PAD]) for row in idx.in_lab[:n]
    ]
    out_ok = idx.out_ok.copy()
    in_ok = idx.in_ok.copy()
    budget = [int(visit_budget)]

    def _store(lm: int, u: int, own: set, write: list, ok: np.ndarray) -> None:
        if not (own & write[u]):
            lab = write[u]
            if len(lab) < idx.max_width:
                lab.add(lm)
            else:
                ok[u] = False

    try:
        # edges apply one at a time in ascending-rank landmark order: the
        # per-edge invariant restoration ("every landmark's pair set is
        # exact again") is what makes the next edge's resume sound
        for a, b in added:
            # landmarks recorded as reaching a now also reach b's tail:
            # resume each one's forward walk AT b over the new adjacency
            for lm in sorted(in_sets[a]):
                _store(lm, b, out_sets[lm], in_sets, in_ok)
                _pruned_bfs(
                    lm, (out_indptr, out_indices), out_sets[lm], in_sets,
                    in_ok, idx.max_width, start=b, prune_expansion=False,
                    budget=budget,
                )
            # symmetric: landmarks reachable from b now label a's sources
            for lm in sorted(out_sets[b]):
                _store(lm, a, in_sets[lm], out_sets, out_ok)
                _pruned_bfs(
                    lm, (in_indptr, in_indices), in_sets[lm], out_sets,
                    out_ok, idx.max_width, start=a, prune_expansion=False,
                    budget=budget,
                )
    except _BudgetExceeded:
        return None

    new = _finalize(
        n, out_sets, in_sets, idx.processed.copy(), out_ok, in_ok,
        idx.max_width, idx.n_landmarks,
    )
    new.build_ms = (time.monotonic() - t0) * 1e3
    return new
