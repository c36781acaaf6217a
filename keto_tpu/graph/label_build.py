"""Device-side 2-hop label construction: landmark BFS as batched frontier
sweeps on the mesh.

``build_labels`` (keto_tpu/graph/labels.py) runs one Python BFS per
landmark — a serial host wall in the cold-start pipeline, which is why
PR 8 capped landmarks at min(num_int, 128k) and why coverage degrades on
exactly the huge deep graphs where the BFS fallback hurts most. This
module rebuilds construction as **W-landmark-wide bit-packed frontier
waves** through the same dense gather-OR pull the check kernels use
(keto_tpu/check/kernels.py ``check_step``, and the halo-exchange
structure of ``parallel/sharded.py`` in sharded mode):

- the batch's W landmark BFSs run simultaneously as one ``uint32[n+1,
  W/32]`` frontier bitmap; each wave is a dense pull over the interior
  ELL groups (forward orientation walks the in-neighbor lists exactly
  like the check kernel; the transposed orientation — the rev-CSR edge
  set PR 10 derives — gives the backward sweep), and PLL **expansion
  pruning is a per-wave ANDNOT** against the batch's ``covered`` rows: a
  per-(node, landmark) bitmask of pairs the already-built labels certify,
  computed once per batch from the resident label arrays;
- entry-set identity with ``build_labels`` is the contract, not a goal
  (tests/test_label_build.py fuzz-asserts array equality). Pre-batch
  pruning is exact by construction; **intra-batch interference** — an
  earlier-ranked batch member whose fresh labels would have pruned a
  later member's sequential BFS — is detected from the sweep output
  itself (lane i stored at lane j's landmark row means member j's
  sequential run would have seen member i in its own label) and resolved
  by **prefix acceptance**: the longest interference-free rank prefix of
  the batch commits, the rest re-runs in the next batch. Width caps, ok
  flags, and per-row entry order replay on host in rank order, exactly
  as the sequential build would have applied them;
- landmarks stream in degree-rank batches with **no hard coverage cap**:
  an early exit fires when the marginal (non-self) entries per processed
  landmark drop below ``min_gain`` — saturated graphs stop paying for
  fully-pruned landmarks, deep graphs keep going as far as the build
  budget and HBM allow. The caller (``TpuCheckEngine._ensure_labels``)
  plans the transient sweep footprint against the HBM governor
  ``evict=False``, like ``GovernedSorter``: a label build must never
  push serving state off the chip.

``device_patch_labels`` resumes per-landmark sweeps through the same
path for incremental edge insertion (the ``patch_labels`` semantics:
no expansion pruning, per-edge landmark resumption), so overlay churn
no longer forces host rebuilds.

Scale note: sweep state transfers back per batch to extract entries;
batches that store nothing (the saturated tail) skip the transfer. The
per-batch device work is O(edges · depth · W/32) words — independent of
how much pruning shrinks the *entry* count — which is why the
``min_gain`` exit, not a landmark cap, bounds the build.
"""

from __future__ import annotations

import logging
import time
from dataclasses import dataclass, field
from functools import lru_cache, partial
from typing import Callable, Optional

import numpy as np

from keto_tpu.graph.labels import (
    IN_PAD,
    OUT_PAD,
    LabelIndex,
    interior_adjacency,
    landmark_order,
)

_log = logging.getLogger("keto_tpu.label_build")

#: default landmark lanes per sweep batch (one uint32 word pair of
#: frontier state per node); must be a multiple of 32
DEFAULT_BATCH = 64

#: cap on the [rows, chunk] gather intermediate per ELL group — matches
#: the check kernels' per-hop peak-memory bound
_DEGREE_CHUNK = 1024

#: row chunk of the covered-mask kernel (bounds the [rows, W, Wt]
#: compare intermediate)
_COVER_CHUNK = 1 << 16

#: device builds below this interior-edge count lose to dispatch +
#: transfer overhead; callers compare against the snapshot's ELL edges
DEFAULT_MIN_EDGES = 65536


def _ceil_pow2(x: int) -> int:
    return 1 if x <= 1 else 1 << (int(x) - 1).bit_length()


# -- interior ELL groups ------------------------------------------------------


def build_ell_groups(indptr: np.ndarray, indices: np.ndarray, n: int):
    """Degree-bucketed dense gather groups for one pull orientation:
    ``[(nbrs int32[rows, cap], dst int32[rows]), ...]`` with pow2 caps
    and gather sentinel ``n`` (the always-zero bitmap row). Derived from
    the same CSRs as ``interior_adjacency`` so the sweeps and the host
    build walk the identical edge universe."""
    deg = np.diff(indptr)
    groups = []
    if n == 0:
        return groups
    nz = np.nonzero(deg > 0)[0]
    if not nz.size:
        return groups
    bucket_of = np.ceil(np.log2(np.maximum(deg[nz], 1))).astype(np.int64)
    for b in np.unique(bucket_of):
        rows = nz[bucket_of == b]
        cap = 1 << int(b)
        nbrs = np.full((rows.size, cap), np.int32(n), np.int32)
        lens = deg[rows]
        offs = np.arange(int(lens.sum())) - np.repeat(
            np.cumsum(lens) - lens, lens
        )
        nbrs[np.repeat(np.arange(rows.size), lens), offs] = indices[
            np.repeat(indptr[rows], lens) + offs
        ]
        groups.append((np.ascontiguousarray(nbrs), rows.astype(np.int32)))
    return groups


def estimate_build_bytes(n: int, max_width: int, batch: int = DEFAULT_BATCH) -> int:
    """Transient device bytes one sweep batch holds live: frontier /
    visited / stored / covered bitmaps for both orientations plus the
    full-width resident label arrays the covered kernel reads."""
    wt = max(1, batch // 32)
    bitmaps = 6 * (n + 1) * wt * 4
    labels = 2 * (n + 1) * max(1, max_width) * 4
    return bitmaps + labels


# -- jitted kernels -----------------------------------------------------------


@lru_cache(maxsize=1)
def _sweep_step():
    """One frontier wave over every ELL group: dense gather-OR pull of
    the frontier bitmap, newly-visited = pull ANDNOT visited, stores =
    newly-visited ANDNOT covered. ``prune_expansion`` is static PLL
    (certified nodes don't expand); patches pass False and keep walking."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    @partial(jax.jit, static_argnames=("prune_expansion",))
    def step(nbrs, dst, V, X, S, cov, *, prune_expansion=True):
        P = jnp.zeros_like(V)
        for nb, d in zip(nbrs, dst):
            cap = nb.shape[1]
            acc = None
            for c0 in range(0, cap, _DEGREE_CHUNK):
                g = X[nb[:, c0 : c0 + _DEGREE_CHUNK]]
                part = lax.reduce(g, np.uint32(0), lax.bitwise_or, (1,))
                acc = part if acc is None else acc | part
            P = P.at[d].set(acc)
        N = P & ~V
        store = N & ~cov
        V2 = V | N
        X2 = store if prune_expansion else N
        S2 = S | store
        active = jnp.any(X2 != 0)
        visits = jnp.sum(lax.population_count(N), dtype=jnp.int32)
        return V2, X2, S2, active, visits

    return step


@lru_cache(maxsize=1)
def _covered_fn():
    """covered[u] = W-bit mask of batch landmarks whose pre-batch label
    row intersects node u's row — the certification test of PLL pruning,
    vectorized as a searchsorted against the union of the batch's own
    label entries with a per-value lane-mask gather."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    @jax.jit
    def covered(lab, U, masks):
        idx = jnp.searchsorted(U, lab)
        idxc = jnp.minimum(idx, U.shape[0] - 1)
        found = U[idxc] == lab
        rows = jnp.where(found[..., None], masks[idxc], jnp.uint32(0))
        return lax.reduce(rows, np.uint32(0), lax.bitwise_or, (1,))

    return covered


@lru_cache(maxsize=1)
def _store_fn():
    """Scatter a batch's new entries onto a device label array: one
    program per (padded) entry count."""
    import jax

    @jax.jit
    def store(lab, rows, cols, vals):
        return lab.at[rows, cols].set(vals, mode="drop")

    return store


def _compute_covered(lab_d, own_rows_host: np.ndarray, lanes: int, wt: int, pad):
    """Covered bitmap ``uint32[n+1, wt]`` for one orientation: union the
    batch's own pre-batch label entries (host mirror rows), build the
    value → lane-mask table, run the searchsorted kernel row-chunked."""
    import jax.numpy as jnp

    vals: dict[int, int] = {}
    for j in range(lanes):
        row = own_rows_host[j]
        for v in row[row != pad].tolist():
            vals[v] = vals.get(v, 0) | (1 << j)
    n1 = int(lab_d.shape[0])
    if not vals:
        return jnp.zeros((n1, wt), jnp.uint32)
    # padded to a power of two with a value no label holds (zero mask): the
    # kernel is one program per size, and a batch's union has a size of its
    # own - unpadded, every batch compiled it anew, twice
    size = _ceil_pow2(len(vals))
    U = np.full(size, np.iinfo(np.int32).max, np.int32)
    U[: len(vals)] = sorted(vals)
    masks = np.zeros((size, wt), np.uint32)
    for i, v in enumerate(U[: len(vals)].tolist()):
        m = vals[v]
        for w in range(wt):
            masks[i, w] = (m >> (32 * w)) & 0xFFFFFFFF
    fn = _covered_fn()
    U_d = jnp.asarray(U)
    m_d = jnp.asarray(masks)
    parts = [
        fn(lab_d[c0 : c0 + _COVER_CHUNK], U_d, m_d)
        for c0 in range(0, n1, _COVER_CHUNK)
    ]
    return parts[0] if len(parts) == 1 else jnp.concatenate(parts)


# -- sweep drivers ------------------------------------------------------------


class _Sweeper:
    """Runs batched frontier sweeps on one device."""

    backend = "device"

    def __init__(self, fwd_groups, bwd_groups, n: int):
        import jax.numpy as jnp

        self.n = n
        self._fwd = tuple((jnp.asarray(a), jnp.asarray(b)) for a, b in fwd_groups)
        self._bwd = tuple((jnp.asarray(a), jnp.asarray(b)) for a, b in bwd_groups)

    def sweep(
        self,
        forward: bool,
        seeds: np.ndarray,  # int64 node per lane (or -1 for a dead lane)
        cov,  # uint32 [n+1, wt] device
        wt: int,
        *,
        prune_expansion: bool = True,
        budget: Optional[list] = None,
        start_rows: Optional[np.ndarray] = None,
    ) -> Optional[np.ndarray]:
        """Run one orientation's waves to fixpoint; returns the stored
        bitmap ``uint32[n+1, wt]`` on host, or None when ``budget``
        (mutable ``[remaining_visits]``) runs dry. ``start_rows``
        overrides the seed rows (patch resumption: lane j's walk begins
        at ``start_rows[j]`` but stores are still lane j's landmark)."""
        import jax.numpy as jnp

        n = self.n
        rows = seeds if start_rows is None else start_rows
        V0 = np.zeros((n + 1, wt), np.uint32)
        for j, u in enumerate(np.asarray(rows, np.int64).tolist()):
            if 0 <= u < n:
                V0[u, j // 32] |= np.uint32(1) << np.uint32(j % 32)
        V = jnp.asarray(V0)
        X = V
        S = jnp.zeros_like(V)
        groups = self._fwd if forward else self._bwd
        nbrs = tuple(a for a, _ in groups)
        dst = tuple(b for _, b in groups)
        step = _sweep_step()
        while True:
            if not groups:
                break
            V, X, S, active, visits = step(
                nbrs, dst, V, X, S, cov, prune_expansion=prune_expansion
            )
            if budget is not None:
                budget[0] -= int(visits)
                if budget[0] < 0:
                    return None
            if not bool(active):
                break
        return np.asarray(S)


class _ShardedSweeper:
    """The sweep as a ``shard_map`` program over the mesh's graph axis:
    frontier slabs row-range-sharded by the same ownership as the
    serving path (``device_build.shard_row_ranges`` via
    ``parallel/sharded.py:route_label_ell``), one halo exchange per
    wave. Bit-identical to ``_Sweeper`` — OR is OR on any topology."""

    backend = "sharded"

    def __init__(self, fwd_groups, bwd_groups, n: int, mesh, n_shards: int):
        import jax.numpy as jnp

        from keto_tpu.graph.device_build import shard_row_ranges
        from keto_tpu.parallel.sharded import route_label_ell

        self.n = n
        self._mesh = mesh
        g = max(1, int(n_shards))
        ranges = shard_row_ranges(n + 1, g)
        self._rps = ranges[0][1] - ranges[0][0] if ranges[0][1] > ranges[0][0] else 1
        self._g = g
        self._fwd = tuple(
            (jnp.asarray(a), jnp.asarray(b))
            for a, b in route_label_ell(fwd_groups, n, g, self._rps)
        )
        self._bwd = tuple(
            (jnp.asarray(a), jnp.asarray(b))
            for a, b in route_label_ell(bwd_groups, n, g, self._rps)
        )

    def _shard(self, flat: np.ndarray):
        """[n+1, wt] host → [g, rps, wt] device slabs."""
        import jax.numpy as jnp

        g, rps = self._g, self._rps
        wt = flat.shape[1]
        out = np.zeros((g * rps, wt), flat.dtype)
        out[: flat.shape[0]] = flat
        return jnp.asarray(out.reshape(g, rps, wt))

    def sweep(
        self,
        forward: bool,
        seeds: np.ndarray,
        cov,
        wt: int,
        *,
        prune_expansion: bool = True,
        budget: Optional[list] = None,
        start_rows: Optional[np.ndarray] = None,
    ) -> Optional[np.ndarray]:
        from keto_tpu.parallel.sharded import label_sweep_kernel

        n = self.n
        rows = seeds if start_rows is None else start_rows
        V0 = np.zeros((n + 1, wt), np.uint32)
        for j, u in enumerate(np.asarray(rows, np.int64).tolist()):
            if 0 <= u < n:
                V0[u, j // 32] |= np.uint32(1) << np.uint32(j % 32)
        V = self._shard(V0)
        X = V
        import jax.numpy as jnp

        S = jnp.zeros_like(V)
        cov_sh = self._shard(np.asarray(cov))
        groups = self._fwd if forward else self._bwd
        nbrs = tuple(a for a, _ in groups)
        dst = tuple(b for _, b in groups)
        kern = label_sweep_kernel(self._mesh)
        while groups:
            V, X, S, active, visits = kern(
                nbrs, dst, V, X, S, cov_sh,
                rps=self._rps, prune_expansion=prune_expansion,
            )
            if budget is not None:
                budget[0] -= int(visits)
                if budget[0] < 0:
                    return None
            if not bool(active):
                break
        flat = np.asarray(S).reshape(self._g * self._rps, wt)
        return flat[: n + 1]


# -- host-side finalize state -------------------------------------------------


class _Mirror:
    """Host mirror of the evolving label arrays plus their device twins:
    stores apply here in exact sequential (rank) order — width caps, ok
    flags, per-row entry order — and the deltas scatter onto the device
    arrays the next batch's covered kernel reads."""

    def __init__(self, n: int, max_width: int, out0=None, in0=None):
        import jax.numpy as jnp

        self.n = n
        self.max_width = max_width
        W = max(1, max_width)
        self.out_h = np.full((n + 1, W), OUT_PAD, np.int32)
        self.in_h = np.full((n + 1, W), IN_PAD, np.int32)
        # source arrays may be pow2-padded wider than max_width; entries
        # sit sorted at the front so the overflow columns are all pad
        if out0 is not None:
            span = min(W, out0.shape[1])
            self.out_h[: n + 1, :span] = out0[: n + 1, :span]
        if in0 is not None:
            span = min(W, in0.shape[1])
            self.in_h[: n + 1, :span] = in0[: n + 1, :span]
        self.out_w = np.count_nonzero(self.out_h[:n] != OUT_PAD, axis=1).astype(
            np.int32
        )
        self.in_w = np.count_nonzero(self.in_h[:n] != IN_PAD, axis=1).astype(np.int32)
        self.out_ok = np.ones(n, bool)
        self.in_ok = np.ones(n, bool)
        self.out_d = jnp.asarray(self.out_h)
        self.in_d = jnp.asarray(self.in_h)
        self._pending: dict[str, list] = {"out": [], "in": []}
        self.entries = int(self.out_w.sum() + self.in_w.sum())

    def store(self, side: str, nodes: np.ndarray, v: int) -> int:
        """Append landmark ``v`` at ``nodes`` on one side, width-capped;
        a full row trips its ok flag instead of lying (the sequential
        semantics). Returns the number actually stored."""
        nodes = np.asarray(nodes, np.int64)
        if not nodes.size:
            return 0
        h, w, ok, pend = (
            (self.out_h, self.out_w, self.out_ok, self._pending["out"])
            if side == "out"
            else (self.in_h, self.in_w, self.in_ok, self._pending["in"])
        )
        fits = w[nodes] < self.max_width
        good = nodes[fits]
        ok[nodes[~fits]] = False
        if good.size:
            cols = w[good].astype(np.int64)
            h[good, cols] = np.int32(v)
            w[good] += 1
            pend.append((good, cols, np.full(good.size, v, np.int32)))
            self.entries += int(good.size)
        return int(good.size)

    def flush_device(self) -> None:
        """Scatter pending host stores onto the device label arrays."""
        for side in ("out", "in"):
            pend = self._pending[side]
            if not pend:
                continue
            import jax.numpy as jnp

            # one scatter program per power of two, not per batch: the pad
            # entries name a row past the array and are dropped
            n_pend = sum(p[0].size for p in pend)
            pad = _ceil_pow2(n_pend) - n_pend
            rows = np.concatenate([p[0] for p in pend] + [np.full(pad, self.n + 1, np.int64)])
            cols = np.concatenate([p[1] for p in pend] + [np.zeros(pad, np.int64)])
            vals = np.concatenate([p[2] for p in pend] + [np.zeros(pad, np.int32)])
            lab = self.out_d if side == "out" else self.in_d
            lab = _store_fn()(lab, jnp.asarray(rows), jnp.asarray(cols), jnp.asarray(vals))
            if side == "out":
                self.out_d = lab
            else:
                self.in_d = lab
            self._pending[side] = []

    def row(self, side: str, u: int) -> np.ndarray:
        h = self.out_h if side == "out" else self.in_h
        w = self.out_w if side == "out" else self.in_w
        return h[u, : w[u]] if u < self.n else h[u, :0]

    def finalize(
        self, processed: np.ndarray, n_landmarks: int, backend: str
    ) -> LabelIndex:
        """Pack the mirrors into the padded, sorted device layout —
        byte-identical to ``labels._finalize`` over the same sets."""

        def pack(h, w, pad):
            wmax = int(w.max()) if self.n else 0
            Wp = _ceil_pow2(max(1, wmax))
            out = np.full((self.n + 1, Wp), pad, np.int32)
            if self.n:
                span = min(Wp, h.shape[1])
                tmp = h[: self.n, :span].copy()
                big = np.int32(2**31 - 1)
                tmp[tmp == pad] = big
                tmp.sort(axis=1)
                tmp[tmp == big] = pad
                out[: self.n, :span] = tmp
            return out

        return LabelIndex(
            n=self.n,
            out_lab=pack(self.out_h, self.out_w, OUT_PAD),
            in_lab=pack(self.in_h, self.in_w, IN_PAD),
            processed=processed,
            out_ok=self.out_ok,
            in_ok=self.in_ok,
            max_width=self.max_width,
            n_landmarks=n_landmarks,
            n_entries=int(self.out_w.sum() + self.in_w.sum()),
            backend=backend,
        )


def _lane_nodes(S: Optional[np.ndarray], nz: Optional[np.ndarray], j: int):
    """Node ids where lane ``j``'s bit is set in the stored bitmap."""
    if S is None or nz is None or not nz.size:
        return np.zeros(0, np.int64)
    hit = (S[nz, j // 32] >> np.uint32(j % 32)) & np.uint32(1)
    return nz[hit.astype(bool)]


def _lane_int(S_rows: np.ndarray, j: int, wt: int) -> int:
    """Lane bitmask at one landmark row as a Python int."""
    v = 0
    for w in range(wt):
        v |= int(S_rows[j, w]) << (32 * w)
    return v


@dataclass
class BuildInfo:
    """What the batched build did — the engine narrates this through
    BuildProgress / maintenance gauges and the truncation satellite."""

    batches: int = 0
    dispatches: int = 0
    landmarks: int = 0
    #: "" | "min_gain" | "cap" — why the landmark stream stopped early
    truncated: str = ""
    sweep_entries: int = 0
    restarts: int = 0  # lanes re-run due to intra-batch interference
    build_ms: float = 0.0
    gain_history: list = field(default_factory=list)


# -- the batched build --------------------------------------------------------


def device_build_labels(
    snap,
    max_width: int = 64,
    landmarks: int = 0,
    *,
    min_gain: float = 0.0,
    batch: int = DEFAULT_BATCH,
    mesh=None,
    shard_count: int = 0,
    progress_cb: Optional[Callable[[int, int, int], None]] = None,
) -> tuple[LabelIndex, BuildInfo]:
    """Construct the 2-hop index for ``snap`` with batched device
    sweeps; entry-set identical to ``build_labels(snap, max_width,
    landmarks=K)`` where K is the number of landmarks actually
    processed (``landmarks == 0`` streams ALL interior nodes, subject
    only to the ``min_gain`` early exit). See the module docstring for
    the batching/prefix-acceptance argument."""
    t0 = time.monotonic()
    n = snap.num_int
    info = BuildInfo()
    out_ip, out_ix, in_ip, in_ix = interior_adjacency(snap)
    order = landmark_order(out_ip, in_ip, n)
    K = n if landmarks <= 0 else min(int(landmarks), n)
    batch = max(32, (int(batch) // 32) * 32)
    wt = batch // 32

    # forward sweeps pull along in-neighbor rows (reach FROM the
    # landmark — the check kernel's orientation); backward sweeps pull
    # the transposed rows
    fwd_groups = build_ell_groups(in_ip, in_ix, n)
    bwd_groups = build_ell_groups(out_ip, out_ix, n)
    if mesh is not None and int(shard_count) > 1:
        sweeper = _ShardedSweeper(fwd_groups, bwd_groups, n, mesh, shard_count)
    else:
        sweeper = _Sweeper(fwd_groups, bwd_groups, n)

    mirror = _Mirror(n, max_width)
    processed = np.zeros(n, bool)
    pos = 0
    while pos < K:
        lanes = min(batch, K - pos)
        v_batch = order[pos : pos + lanes].astype(np.int64)
        seeds = np.full(batch, -1, np.int64)
        seeds[:lanes] = v_batch
        mirror.flush_device()
        # covered masks: certification against the FROZEN pre-batch
        # label arrays (the pruning ANDNOT of every wave this batch)
        cov_f = _compute_covered(
            mirror.in_d, mirror.out_h[v_batch], lanes, wt, OUT_PAD
        )
        cov_b = _compute_covered(
            mirror.out_d, mirror.in_h[v_batch], lanes, wt, IN_PAD
        )
        S_f = sweeper.sweep(True, seeds, cov_f, wt)
        S_b = sweeper.sweep(False, seeds, cov_b, wt)
        info.dispatches += 2
        info.batches += 1
        nz_f = np.nonzero(S_f[: n].any(axis=1))[0] if S_f.size else np.zeros(0, np.int64)
        nz_b = np.nonzero(S_b[: n].any(axis=1))[0] if S_b.size else np.zeros(0, np.int64)
        # intra-batch interference: lane i stored at lane j's landmark
        # row (either orientation) means sequential processing of j
        # would have seen i's fresh labels — accept the clean prefix
        rows_f = S_f[v_batch]
        rows_b = S_b[v_batch]
        jstar = lanes
        for j in range(lanes):
            inter = (_lane_int(rows_f, j, wt) | _lane_int(rows_b, j, wt)) & (
                (1 << j) - 1
            )
            if inter:
                jstar = j
                break
        if jstar == 0:
            raise AssertionError("lane 0 can never interfere with itself")
        info.restarts += lanes - jstar
        swept = 0
        for j in range(jstar):
            v = int(v_batch[j])
            # self entries first — reach0(v, v) must hit, the sequential
            # build's invariant (labels.build_labels)
            mirror.store("out", np.array([v]), v)
            mirror.store("in", np.array([v]), v)
            swept += mirror.store("in", _lane_nodes(S_f, nz_f, j), v)
            swept += mirror.store("out", _lane_nodes(S_b, nz_b, j), v)
            processed[v] = True
        info.sweep_entries += swept
        pos += jstar
        info.landmarks = pos
        gain = swept / max(1, jstar) / max(1, n)
        info.gain_history.append(round(gain, 9))
        if progress_cb is not None:
            progress_cb(pos, K, mirror.entries)
        if min_gain > 0.0 and gain < min_gain and pos < K:
            info.truncated = "min_gain"
            break

    if not info.truncated and K < n:
        info.truncated = "cap"
    idx = mirror.finalize(processed, pos, sweeper.backend)
    idx.build_ms = (time.monotonic() - t0) * 1e3
    info.build_ms = idx.build_ms
    info.landmarks = pos
    return idx, info


# -- incremental patch through the device path --------------------------------


def device_patch_labels(
    idx: LabelIndex,
    snap,
    added_edges,
    visit_budget: int = 65536,
    *,
    batch: int = DEFAULT_BATCH,
    mesh=None,
    shard_count: int = 0,
) -> Optional[LabelIndex]:
    """Incremental-PLL edge insertion through the batched sweep path:
    the exact ``labels.patch_labels`` semantics (per-edge landmark
    resumption, NO expansion pruning, store-certification against the
    evolving sets) with each edge's resume list processed as bit-packed
    lanes. Interference between lanes is static here — a resume
    landmark's own label row is frozen for the whole loop — so the lane
    list splits into clean groups up front. Returns None when the
    caller must rebuild (same contract as the host patch): truncated
    endpoint labels, budget dry, universe mismatch. The visit budget
    counts newly-visited (node, landmark) pairs exactly like the host
    walk, though the abort point may differ near the boundary."""
    t0 = time.monotonic()
    n = snap.num_int
    if idx.n != n:
        return None
    added = [(int(a), int(b)) for a, b in added_edges]
    for a, b in added:
        if not (0 <= a < n and 0 <= b < n):
            return None
        if not (idx.in_ok[a] and idx.out_ok[b]):
            return None

    out_ip, out_ix, in_ip, in_ix = interior_adjacency(snap)
    fwd_groups = build_ell_groups(in_ip, in_ix, n)
    bwd_groups = build_ell_groups(out_ip, out_ix, n)
    if mesh is not None and int(shard_count) > 1:
        sweeper = _ShardedSweeper(fwd_groups, bwd_groups, n, mesh, shard_count)
    else:
        sweeper = _Sweeper(fwd_groups, bwd_groups, n)
    mirror = _Mirror(n, idx.max_width, out0=idx.out_lab, in0=idx.in_lab)
    mirror.out_ok = idx.out_ok.copy()
    mirror.in_ok = idx.in_ok.copy()
    batch = max(32, (int(batch) // 32) * 32)
    wt = batch // 32
    budget = [int(visit_budget)]

    def lane_groups(lms: list[int], own_side: str) -> list[list[int]]:
        """Split the ordered resume list into clean prefix groups: lane
        j joins the open group only when no earlier member of the group
        appears in j's own (frozen) label row."""
        groups: list[list[int]] = []
        cur: list[int] = []
        cur_set: set = set()
        for lm in lms:
            own = set(int(x) for x in mirror.row(own_side, lm))
            if cur_set & own or len(cur) >= batch:
                groups.append(cur)
                cur, cur_set = [], set()
            cur.append(lm)
            cur_set.add(lm)
        if cur:
            groups.append(cur)
        return groups

    def run_side(forward: bool, resume_at: int, store_at: int, lms: list[int]) -> bool:
        """One direction of one edge: every landmark in ``lms`` stores
        at ``store_at`` (certified against the current sets) and resumes
        its walk at ``resume_at``. Returns False on budget exhaustion."""
        own_side, write_side = ("out", "in") if forward else ("in", "out")
        for group in lane_groups(lms, own_side):
            mirror.flush_device()
            lanes = len(group)
            own_rows = np.full((lanes, mirror.max_width), OUT_PAD if forward else IN_PAD, np.int32)
            for j, lm in enumerate(group):
                r = mirror.row(own_side, lm)
                own_rows[j, : r.size] = r
            cov = _compute_covered(
                mirror.in_d if forward else mirror.out_d,
                own_rows, lanes, wt, OUT_PAD if forward else IN_PAD,
            )
            seeds = np.full(batch, -1, np.int64)
            seeds[:lanes] = group
            starts = np.full(batch, -1, np.int64)
            starts[:lanes] = resume_at
            S = sweeper.sweep(
                forward, seeds, cov, wt,
                prune_expansion=False, budget=budget, start_rows=starts,
            )
            if S is None:
                return False
            nz = (
                np.nonzero(S[:n].any(axis=1))[0] if S.size else np.zeros(0, np.int64)
            )
            for j, lm in enumerate(group):
                # the explicit store at the edge endpoint runs before
                # the resumed walk, certified against the live sets —
                # exactly patch_labels' _store
                own = set(int(x) for x in mirror.row(own_side, lm))
                write_row = set(int(x) for x in mirror.row(write_side, store_at))
                if not (own & write_row):
                    mirror.store(write_side, np.array([store_at]), lm)
                nodes = _lane_nodes(S, nz, j)
                # the device covered mask was computed against the
                # group-entry sets; stores by earlier lanes of THIS
                # group can't certify (the clean-group invariant), so
                # the mask is exact for every lane
                mirror.store(write_side, nodes, lm)
        return True

    for a, b in added:
        fwd_lms = sorted(int(x) for x in mirror.row("in", a))
        if not run_side(True, b, b, fwd_lms):
            return None
        bwd_lms = sorted(int(x) for x in mirror.row("out", b))
        if not run_side(False, a, a, bwd_lms):
            return None

    new = mirror.finalize(idx.processed.copy(), idx.n_landmarks, "device")
    new.build_ms = (time.monotonic() - t0) * 1e3
    return new
