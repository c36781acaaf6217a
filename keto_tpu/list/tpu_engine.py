"""Snapshot-backed list engine: frontier-expansion BFS on device.

A listing is full-graph reachability from one seed — forward for
ListSubjects ("who can access Y" walks the grant edges outward), backward
for ListObjects ("what can X access" walks them in reverse). Both ride
the bucketed-ELL machinery the check kernel gathers through
(keto_tpu/graph/snapshot.py ``ListLayout``): per step every interior-class
row ORs the reached-bitmaps of its layout neighbors — in-neighbors in the
forward orientation, out-neighbors in the TRANSPOSED one — so the inner
loop stays pure gathers + OR-reductions, and up to 32 concurrent listings
bit-pack into one uint32 bitmap (the batched-BFS shape of the check
kernel, Banyan-style concurrent scoped traversals without head-of-line
blocking).

Host completion resolves everything outside the iterated interior rows:
seeds expand through the overlay-aware one-hop adjacency, sink answers
gather through the (tombstone-masked) sink CSR + overlay sink edges, and
static candidates resolve by one vectorized out-neighbor gather — the
same split the check engine uses (device for the fixpoint, host for the
per-query boundary).

Fallback matrix (all paths bit-identical, fuzz-verified in
tests/test_list_watch.py):

- wildcard-configured namespace in the query → Manager-backed oracle
  (keto_tpu/list/engine.py);
- overlay shape the layouts could not mirror (``lst_dirty``), device
  error, degraded mode, or the HBM governor's ``reverse`` eviction rung
  → CPU-reference lister over the SAME snapshot (host BFS over the
  masked CSRs — identical edge set, identical answers);
- oracle-backend deployments wire the Manager engine directly
  (keto_tpu/driver/registry.py).

Pagination: results are canonicalized (sorted, deduplicated) and cached
per (query, snapshot id); page tokens carry the snapshot watermark + a
VALUE cursor (keto_tpu/list/engine.py), so follow-up pages pin a
snapshot at least as fresh and survive compaction renumbering device
ids mid-pagination.
"""

from __future__ import annotations

import logging
import threading
from collections import OrderedDict
from functools import partial
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from keto_tpu import namespace as namespace_pkg
from keto_tpu.check.kernels import pull
from keto_tpu.graph.snapshot import GraphSnapshot
from keto_tpu.list.engine import (
    ListEngine,
    decode_page_token,
    encode_page_token,
    slice_page,
)
from keto_tpu.relationtuple.model import Subject, SubjectID, SubjectSet
from keto_tpu.namespace.rewrites import GATED
from keto_tpu.x.errors import ErrBadRequest, ErrNamespaceUnknown

_log = logging.getLogger("keto_tpu.list")

#: concurrent listings one device run bit-packs (one uint32 lane each)
LANES = 32


def _ceil_pow2(x: int) -> int:
    return 1 if x <= 1 else 1 << (int(x) - 1).bit_length()


def list_step(
    bucket_nbrs: tuple,
    R0: jnp.ndarray,  # uint32 [n_rows+1, 1]: seed bits (row n_rows all-zero)
    ov_nbrs: Optional[jnp.ndarray] = None,  # int32 [K, C] overlay gather
    ov_dst: Optional[jnp.ndarray] = None,  # int32 [K] dst rows (pad → n_rows+1)
    *,
    n_active: int,
    valid_rows: tuple,
    it_cap: int,
    block_iters: int = 8,
) -> jnp.ndarray:
    """Reachability fixpoint over one list layout: per step the
    bucket-covered prefix ORs its gathered neighbors (the check kernel's
    ``pull``), then overlay edges OR into their destination rows —
    inside the loop, so multi-hop paths through delta edges converge
    exactly like base edges. Returns the full fixpoint bitmap (the
    listing's answer IS the reached set, so the whole bitmap ships
    home — unlike the check kernel there is nothing to pack)."""
    if (n_active == 0 or not bucket_nbrs) and ov_nbrs is None:
        return R0

    def step(st):
        R, _, it = st
        Rn = R
        if bucket_nbrs and n_active:
            p = pull(bucket_nbrs, valid_rows, R)
            Rn = Rn.at[:n_active].set(Rn[:n_active] | p)
        if ov_nbrs is not None:
            ovo = lax.reduce(Rn[ov_nbrs], np.uint32(0), lax.bitwise_or, (1,))
            # padded dst rows point past the bitmap and drop
            Rn = Rn.at[ov_dst].set(Rn[ov_dst] | ovo, mode="drop")
        return Rn, jnp.any(Rn != R), it + 1

    def block(st):
        return lax.fori_loop(
            0, block_iters, lambda _, s: lax.cond(s[1], step, lambda x: x, s), st
        )

    R_fix, _, _ = lax.while_loop(
        lambda st: st[1] & (st[2] < it_cap),
        block,
        (R0, jnp.bool_(True), jnp.int32(0)),
    )
    return R_fix


_list_kernel = partial(
    jax.jit, static_argnames=("n_active", "valid_rows", "it_cap", "block_iters")
)(list_step)


def _out_all(snap: GraphSnapshot, nodes: np.ndarray) -> np.ndarray:
    """All out-neighbor devs of ``nodes`` — base CSR (tombstone-masked)
    merged with the COMPLETE overlay adjacency (``ov_fwd``, every added
    edge regardless of kernel class). Union only; order irrelevant."""
    rows, _ = snap.out_neighbors_bulk(np.asarray(nodes, np.int64), overlay=False)
    ov = snap.ov_fwd
    if ov:
        extras = [
            np.asarray(ov[int(u)], np.int64)
            for u in np.asarray(nodes).tolist()
            if int(u) in ov
        ]
        if extras:
            rows = np.concatenate([rows.astype(np.int64)] + extras)
    return rows


def _in_all(snap: GraphSnapshot, nodes: np.ndarray) -> np.ndarray:
    """All in-neighbor devs of ``nodes`` (transposed CSR, masked, plus
    the overlay's reverse adjacency)."""
    rows, _ = snap.in_neighbors_bulk(np.asarray(nodes, np.int64))
    return rows


class SnapshotListEngine:
    """Reverse queries over the check engine's device snapshots.

    ``check_engine`` is the registry's TpuCheckEngine — snapshots (and
    their snaptoken freshness semantics) are shared with the check path,
    so a listing issued after a write sees the write exactly like a
    check does. Device residency is governed by the check engine's HBM
    ledger under the ``reverse`` tag; its eviction rung swaps this
    engine to the CPU-reference lister bit-identically.
    """

    def __init__(self, check_engine, namespaces, *, cache_entries: int = 64):
        self._engine = check_engine
        if isinstance(namespaces, namespace_pkg.Manager):
            self._nm: Callable[[], namespace_pkg.Manager] = lambda: namespaces
        else:
            self._nm = namespaces
        #: Manager-backed oracle: wildcard-namespace queries and the
        #: degraded-store fallback route here
        self.oracle = ListEngine(check_engine._store, namespaces=self._nm)
        self._lock = threading.Lock()  # guards: _cache, device_list uploads
        self._cache: OrderedDict = OrderedDict()
        self._cache_entries = int(cache_entries)
        #: flipped by the HBM governor's ``reverse`` rung: device arrays
        #: dropped, listings run the CPU-reference path until restore
        self._suspended = False
        #: /metrics bridges read these (keto_list_* families)
        self.requests_total: dict[tuple[str, str], int] = {}
        self.device_errors = 0
        attach = getattr(check_engine, "attach_reverse_rung", None)
        if attach is not None:
            attach(self._evict_device, self._restore_device)

    # -- HBM eviction rung (called under the governor's lock: NO engine
    # -- locks may be taken here — see keto_tpu/driver/hbm.py) --------------

    def _evict_device(self) -> int:
        self._suspended = True
        snap = getattr(self._engine, "_snapshot", None)
        if snap is not None:
            snap.device_list = None
        gov = getattr(self._engine, "hbm", None)
        return int(gov.release("reverse")) if gov is not None else 0

    def _restore_device(self) -> None:
        self._suspended = False

    # -- bookkeeping ---------------------------------------------------------

    def _count(self, op: str, path: str) -> None:
        key = (op, path)
        self.requests_total[key] = self.requests_total.get(key, 0) + 1

    def _snap(self, at_least: Optional[int], latest: bool) -> GraphSnapshot:
        if latest:
            return self._engine.snapshot()  # hard read-your-writes
        if at_least is not None:
            return self._engine.snapshot(at_least=at_least)
        return self._engine.snapshot_serving()  # never stalls the read plane

    def _ns_id(self, name: str) -> Optional[int]:
        try:
            return self._nm().get_namespace_by_name(name).id
        except ErrNamespaceUnknown:
            return None

    @staticmethod
    def _refuse_gated(snap: GraphSnapshot, ns_id, namespace: str, relation: str) -> None:
        """Under userset rewrites a listing is reachability over the
        snapshot's graph, derived edges included, which is right for the
        union class by construction. A relation whose closure reaches an
        intersection or an exclusion has no such graph: refused, by name."""
        plan = snap.rewrites
        if plan is not None and ns_id is not None and (
            plan.relation_flags(ns_id, relation) & GATED
        ):
            raise ErrBadRequest(
                f"cannot list over {namespace}#{relation}: its userset rewrites reach "
                f"an intersection or an exclusion, which only Check evaluates"
            )

    # -- fixpoints -----------------------------------------------------------

    def _device_ok(self, snap: GraphSnapshot) -> bool:
        return (
            not self._suspended
            and not snap.lst_dirty
            and snap.lay_fwd is not None
            and not getattr(self._engine, "_degraded", False)
            # multi-controller lockstep meshes run one SPMD program per
            # batch; listings are per-host reads — keep them on the
            # (bit-identical) host path rather than dispatching
            # unreplicated device work
            and not getattr(self._engine, "_multiprocess", False)
        )

    def _fixpoint(self, snap: GraphSnapshot, orient: str, seeds: np.ndarray):
        """bool[sink_base]: interior-class devs reached from ``seeds``
        (which are already reached themselves — "via ≥ 1 edge" is the
        caller's seeding contract). Device BFS with CPU fallback."""
        sb = snap.sink_base
        reached = np.zeros(sb, bool)
        seeds = np.unique(np.asarray(seeds, np.int64))
        if sb == 0 or seeds.size == 0:
            reached[seeds] = True if seeds.size else False
            return reached, "host"
        if self._device_ok(snap):
            try:
                return self._fixpoint_device(snap, orient, [seeds])[0], "device"
            except Exception:
                self.device_errors += 1
                _log.warning(
                    "device list fixpoint failed; CPU-reference fallback",
                    exc_info=True,
                )
        return self._fixpoint_host(snap, orient, seeds), "host"

    def _fixpoint_host(
        self, snap: GraphSnapshot, orient: str, seeds: np.ndarray
    ) -> np.ndarray:
        """The CPU-reference lister's fixpoint: frontier BFS over the
        masked host CSRs — the same edge set the device layouts iterate
        (base minus tombstones plus overlay), so answers are
        bit-identical by construction."""
        sb = snap.sink_base
        reached = np.zeros(sb, bool)
        frontier = seeds[seeds < sb]
        reached[frontier] = True
        expand = _out_all if orient == "fwd" else _in_all
        while frontier.size:
            nbrs = np.unique(expand(snap, frontier))
            nbrs = nbrs[(nbrs >= 0) & (nbrs < sb)]
            new = nbrs[~reached[nbrs]]
            reached[new] = True
            frontier = new
        return reached

    def _fixpoint_device(
        self, snap: GraphSnapshot, orient: str, seed_lists: list
    ) -> list[np.ndarray]:
        """Up to ``LANES`` listings in one bit-packed device BFS."""
        assert len(seed_lists) <= LANES
        lay = snap.lay_fwd if orient == "fwd" else snap.lay_rev
        n_rows = lay.n_rows
        bufs = self._ensure_device(snap, orient)
        ov_nbrs, ov_dst = self._overlay_stage(snap, lay)
        R0 = np.zeros((n_rows + 1, 1), np.uint32)
        for q, seeds in enumerate(seed_lists):
            rows = lay.dev2row[np.asarray(seeds, np.int64)]
            R0[rows, 0] |= np.uint32(1 << q)
        R = _list_kernel(
            bufs,
            jnp.asarray(R0),
            ov_nbrs,
            ov_dst,
            n_active=lay.n_active,
            valid_rows=tuple(int(b.n) for b in lay.buckets),
            it_cap=n_rows + 2,
        )
        bits = np.asarray(R)[:n_rows, 0]
        outs = []
        for q in range(len(seed_lists)):
            reached = np.zeros(n_rows, bool)
            reached[lay.order] = ((bits >> np.uint32(q)) & 1).astype(bool)
            outs.append(reached)
        return outs

    def _ensure_device(self, snap: GraphSnapshot, orient: str) -> tuple:
        """Upload (or patch) one orientation's bucket matrices. Pending
        ``lst_patch`` entries past this orientation's applied counter are
        applied on device — tombstones/restores mirror the check
        engine's ell_patch protocol."""
        with self._lock:
            dl = snap.device_list
            if dl is None:
                dl = snap.device_list = {}
            lay = snap.lay_fwd if orient == "fwd" else snap.lay_rev
            patches = snap.lst_patch or []
            entry = dl.get(orient)
            if entry is None:
                need = lay.device_bytes()
                gov = getattr(self._engine, "hbm", None)
                if gov is not None:
                    if not dl:
                        # fresh base snapshot: the previous snapshot's
                        # arrays are garbage — replace the ledger figure
                        gov.register("reverse", 0)
                    if not gov.plan(need, what="reverse list layouts"):
                        self._suspended = True
                        raise MemoryError("HBM budget refused reverse layouts")
                bufs = tuple(
                    jax.device_put(np.ascontiguousarray(b.nbrs)) for b in lay.buckets
                )
                entry = dl[orient] = [bufs, 0]
                if gov is not None:
                    gov.add("reverse", need)
            if entry[1] < len(patches):
                bl = list(entry[0])
                for o, bi, row, col, val in patches[entry[1] :]:
                    if o != orient:
                        continue
                    bl[bi] = bl[bi].at[row, col].set(np.int32(val))
                entry[0] = tuple(bl)
                entry[1] = len(patches)
            return entry[0]

    def _overlay_stage(self, snap: GraphSnapshot, lay):
        """Overlay interior-class edges as a [K, C] gather + destination
        rows, in this orientation's row space (rebuilt per call — the
        overlay is budget-bounded and the upload is tiny)."""
        edges = snap.lst_ov_edges
        if not edges:
            return None, None
        if lay.orient == "fwd":
            pairs = [(int(lay.dev2row[d]), int(lay.dev2row[s])) for s, d in edges]
        else:
            pairs = [(int(lay.dev2row[s]), int(lay.dev2row[d])) for s, d in edges]
        by_dst: dict[int, list[int]] = {}
        for dst, val in pairs:
            by_dst.setdefault(dst, []).append(val)
        K = _ceil_pow2(len(by_dst))
        C = _ceil_pow2(max(len(v) for v in by_dst.values()))
        nbrs = np.full((K, C), np.int32(lay.n_rows), np.int32)
        # padded destinations index past the bitmap and drop in the kernel
        dsts = np.full(K, np.int32(lay.n_rows + 1), np.int32)
        for i, (dst, vals) in enumerate(sorted(by_dst.items())):
            dsts[i] = dst
            nbrs[i, : len(vals)] = vals
        return jnp.asarray(nbrs), jnp.asarray(dsts)

    # -- ListSubjects --------------------------------------------------------

    def list_subjects(
        self,
        namespace: str,
        object: str,
        relation: str,
        *,
        at_least: Optional[int] = None,
        latest: bool = False,
    ) -> tuple[list[str], int]:
        """(sorted subject ids reachable from namespace:object#relation,
        snaptoken of the snapshot that answered)."""
        snap = self._snap(at_least, latest)
        token = int(snap.snapshot_id)
        ns_id = self._ns_id(namespace)
        wild = namespace == "" or object == "" or relation == "" or (
            ns_id is not None and ns_id in snap.wild_ns_ids
        )
        self._refuse_gated(snap, ns_id, namespace, relation)
        if wild:
            # pattern/wildcard listings ride the Manager oracle (the
            # fallback-matrix entry for wildcard semantics)
            self._count("subjects", "oracle")
            return self.oracle.list_subjects(namespace, object, relation), token
        if ns_id is None:
            self._count("subjects", "empty")
            return [], token

        def compute() -> list[str]:
            seed = snap.resolve_set(ns_id, object, relation)
            if seed is None:
                return []
            sb = snap.sink_base
            hop = np.unique(_out_all(snap, np.asarray([seed], np.int64)))
            reached, path = self._fixpoint(snap, "fwd", hop[hop < sb])
            self._count("subjects", path)
            return self._subjects_from(snap, reached, hop[hop >= sb])

        return self._cached(("subjects", ns_id, object, relation, token), compute), token

    def _subjects_from(
        self, snap: GraphSnapshot, reached: np.ndarray, direct: np.ndarray
    ) -> list[str]:
        """Reached interior rows + direct one-hop sinks → subject-id
        strings: base sinks with a live reached in-neighbor (sink CSR,
        tombstone-masked), overlay sink edges, then the leaf filter."""
        sb, nl = snap.sink_base, snap.num_live
        out_devs = set(int(d) for d in direct)
        sp, si = snap.sink_indptr, snap.sink_indices
        if reached.any() and si is not None and si.size and nl > sb:
            src = si.astype(np.int64)
            ok = reached[np.clip(src, 0, sb - 1)] & (src < sb)
            rem = snap.ov_removed
            if rem is not None and rem.size:
                sink_dev = np.repeat(np.arange(sb, nl, dtype=np.int64), np.diff(sp))
                keys = (src << 32) | sink_dev
                pos = np.clip(np.searchsorted(rem, keys), 0, rem.size - 1)
                ok &= rem[pos] != keys
            seg = np.repeat(np.arange(nl - sb), np.diff(sp))
            hit = np.bincount(seg[ok], minlength=nl - sb) > 0
            out_devs.update((np.nonzero(hit)[0] + sb).tolist())
        for dst, srcs in (snap.ov_sink_in or {}).items():
            s = np.asarray(srcs, np.int64)
            s = s[s < sb]
            if s.size and reached[s].any():
                out_devs.add(int(dst))
        for s, dsts in (snap.ov_fwd or {}).items():
            if s < sb and reached[s]:
                out_devs.update(int(d) for d in dsts if d >= sb)
        res = set()
        for d in out_devs:
            kind, key = snap.key_of_dev(int(d))
            if kind == "leaf":
                res.add(key)
        return sorted(res)

    # -- ListObjects ---------------------------------------------------------

    def _target_dev(self, snap: GraphSnapshot, subject: Subject) -> Optional[int]:
        """The subject's device node, matching the check engine's literal
        subject resolution (_subject_target): an empty subject namespace
        can only equal a stored subject in a namespace named ""."""
        if isinstance(subject, SubjectID):
            return snap.resolve_leaf(subject.id)
        if isinstance(subject, SubjectSet):
            if subject.namespace == "":
                wild_list = list(snap.wild_ns_ids)
                if not wild_list:
                    return None
                skey = (wild_list[0], subject.object, subject.relation)
            else:
                sid = self._ns_id(subject.namespace)
                if sid is None:
                    return None
                skey = (sid, subject.object, subject.relation)
            return snap.resolve_set(*skey)
        return None

    def list_objects(
        self,
        namespace: str,
        relation: str,
        subject: Subject,
        *,
        at_least: Optional[int] = None,
        latest: bool = False,
    ) -> tuple[list[str], int]:
        """(sorted objects o in ``namespace`` with check(namespace, o,
        relation, subject) true, snaptoken). Backward reachability from
        the subject over the TRANSPOSED layout."""
        snap = self._snap(at_least, latest)
        token = int(snap.snapshot_id)
        ns_id = self._ns_id(namespace)
        wild = namespace == "" or relation == "" or (
            ns_id is not None and ns_id in snap.wild_ns_ids
        )
        self._refuse_gated(snap, ns_id, namespace, relation)
        if wild:
            if snap.rewrites is not None:
                # the Manager oracle walks stored rows backwards and cannot
                # follow derived edges; a pattern has no meaning in the
                # schema either: refused for what it is, never answered wrongly
                raise ErrBadRequest(
                    f"cannot list objects over the wildcard pattern "
                    f"{namespace!r}#{relation!r} under userset rewrites: "
                    f"name the namespace and the relation"
                )
            self._count("objects", "oracle")
            return self.oracle.list_objects(namespace, relation, subject), token
        if ns_id is None:
            self._count("objects", "empty")
            return [], token

        def compute() -> list[str]:
            t = self._target_dev(snap, subject)
            if t is None:
                return []
            sb = snap.sink_base
            preds = np.unique(_in_all(snap, np.asarray([t], np.int64)))
            reached, path = self._fixpoint(snap, "rev", preds[preds < sb])
            self._count("objects", path)
            return self._objects_from(snap, reached, ns_id, relation, int(t))

        return (
            self._cached(("objects", ns_id, relation, str(subject), token), compute),
            token,
        )

    def _objects_from(
        self,
        snap: GraphSnapshot,
        reached: np.ndarray,
        ns_id: int,
        relation: str,
        t: int,
    ) -> list[str]:
        """Candidates = every set node matching (namespace, *, relation)
        — via the snapshot's sorted pattern index, overlay included.
        Interior candidates answer from the fixpoint; static candidates
        answer by one vectorized out-neighbor gather (a static reaches
        the target iff an out-edge hits the target or a reached interior
        row); sink-class candidates have no out-edges and cannot reach."""
        sb, nl = snap.sink_base, snap.num_live
        cands = np.unique(snap.resolve_starts(ns_id, "", relation))
        answers: list[int] = []
        interior = cands[cands < sb]
        if interior.size and reached.size:
            answers.extend(interior[reached[interior]].tolist())
        statics = cands[cands >= nl]  # base statics + overlay nodes
        if statics.size:
            rows, cnts = snap.out_neighbors_bulk(statics, overlay=False)
            rows = rows.astype(np.int64)
            ok = rows == t
            m = rows < sb
            if reached.size:
                ok |= m & np.where(m, reached[np.clip(rows, 0, max(sb - 1, 0))], False)
            seg = np.repeat(np.arange(statics.size), cnts)
            hit = np.bincount(seg[ok], minlength=statics.size) > 0
            ovf = snap.ov_fwd or {}
            if ovf:
                for i, c in enumerate(statics.tolist()):
                    if hit[i]:
                        continue
                    for d in ovf.get(int(c), ()):
                        if d == t or (d < sb and reached.size and reached[d]):
                            hit[i] = True
                            break
            answers.extend(statics[hit].tolist())
        objs = set()
        for d in answers:
            kind, key = snap.key_of_dev(int(d))
            # an object named "" is a wildcard pattern, not an object —
            # never an answer (shared contract with the Manager oracle)
            if kind == "set" and key[1] != "":
                objs.add(key[1])
        return sorted(objs)

    # -- paginated surface ---------------------------------------------------

    def _cached(self, key: tuple, compute):
        with self._lock:
            hit = self._cache.get(key)
            if hit is not None:
                self._cache.move_to_end(key)
                return hit
        val = compute()
        with self._lock:
            self._cache[key] = val
            self._cache.move_to_end(key)
            while len(self._cache) > self._cache_entries:
                self._cache.popitem(last=False)
        return val

    def page_subjects(
        self,
        namespace: str,
        object: str,
        relation: str,
        *,
        page_size: int = 0,
        page_token: str = "",
        at_least: Optional[int] = None,
        latest: bool = False,
    ) -> tuple[list[str], str, int]:
        cursor = ""
        if page_token:
            w, cursor = decode_page_token(page_token)
            at_least = max(at_least or 0, w)  # pin: never older than page 1
        items, token = self.list_subjects(
            namespace, object, relation, at_least=at_least, latest=latest
        )
        page, nxt = slice_page(items, cursor, page_size)
        return page, (encode_page_token(token, nxt) if nxt else ""), token

    def page_objects(
        self,
        namespace: str,
        relation: str,
        subject: Subject,
        *,
        page_size: int = 0,
        page_token: str = "",
        at_least: Optional[int] = None,
        latest: bool = False,
    ) -> tuple[list[str], str, int]:
        cursor = ""
        if page_token:
            w, cursor = decode_page_token(page_token)
            at_least = max(at_least or 0, w)
        items, token = self.list_objects(
            namespace, relation, subject, at_least=at_least, latest=latest
        )
        page, nxt = slice_page(items, cursor, page_size)
        return page, (encode_page_token(token, nxt) if nxt else ""), token
