"""Snapshot-backed expand engine: bulk per-level BFS + exact host-tree
reconstruction.

The reference builds the tree with one Manager query per subject-set node
per page — the N+1 pattern (reference internal/expand/engine.go:30-98,
51-61). This engine answers from the SAME immutable device snapshot the
TPU check engine serves (keto_tpu/graph/snapshot.py), in two phases:

- **Phase A — bulk adjacency capture.** Breadth-first from the root set:
  ONE vectorized gather per level over the snapshot's forward CSR
  (``out_neighbors_bulk``) collects the ordered child list of every set
  node reachable within the depth budget. No storage round trips, no
  pages, no per-node work.
- **Phase B — reference-exact construction.** The host engine's
  depth-first recursion (pre-order visited-set pruning via
  ``check_and_add_visited``, ``rest_depth <= 1`` leaf conversion, ``None``
  for empty sets — reference engine.go:36-39, 51-71) replayed over the
  captured in-memory adjacency. Tree-child order equals the Manager's
  page order because the snapshot's per-node edge order preserves store
  row order (keto_tpu/graph/interner.py dedup note).

Why no device round trip: expand's output IS the edge list (a
materialized tree), not a reduction over it. The check kernel earns its
device dispatch by compressing millions of edge traversals into packed
decision bits; expand must ship every traversed edge to the host anyway,
so the snapshot CSR gather — the same arrays the device layout is built
from — is the bandwidth-optimal path; a device pass would move the same
bytes plus a D2H latency per level.

Known (documented) divergences from the Manager-backed host engine
(keto_tpu/expand/engine.py — kept as the differential oracle; the e2e
suite compares trees order-insensitively like the reference's):

- duplicate store rows collapse to one edge: a tuple inserted twice
  yields one child, not two (identical grant set);
- a wildcard-bearing set node's children dedup across the tuples its
  pattern matches (the same subject reached via two matching tuples
  appears once);
- a root pattern that exists as no set node (e.g. an empty-namespace
  root) concatenates the ordered child lists of the matching keys, which
  can interleave differently than global row order when wildcard-bearing
  keys also match.

Under userset rewrites (keto_tpu/namespace/rewrites.py) a root whose
closure has a rewrite is expanded by the host engine, by the schema: the
snapshot's adjacency holds the derived edges of the union class there, and
the bulk capture would show them as stored children.

While a delta overlay is pending, the fast path still serves: the
snapshot's unified overlay adjacency (``ov_fwd``,
keto_tpu/graph/overlay.py) is merged into each node's base child list
**in Manager order** — base children are already in subject-sort order
(one literal node's rows are contiguous in the store's ORDER BY), and
overlay children sort by the same subject key, so a two-way ordered
merge reproduces the Manager's page order exactly; tombstoned base
edges are masked in place. Only two overlay cases still delegate to the
Manager-backed engine: a graph containing wildcard-bearing set nodes
(their child order is GLOBAL row order, not subject order — not
reconstructible from the per-node merge) and a pattern root with no
literal node (same reason, via _pattern_children).
"""

from __future__ import annotations

from typing import Callable, Optional

import numpy as np

from keto_tpu import namespace as namespace_pkg
from keto_tpu.expand.tree import LEAF, UNION, Tree
from keto_tpu.graph.snapshot import WILDCARD, GraphSnapshot
from keto_tpu.namespace.rewrites import REWRITTEN
from keto_tpu.relationtuple.model import Subject, SubjectID, SubjectSet
from keto_tpu.x.errors import ErrNamespaceUnknown
from keto_tpu.x.graph import check_and_add_visited

#: virtual device id for a root pattern that exists as no set node
_PATTERN_ROOT = -1


class SnapshotExpandEngine:
    """Expand over the check engine's device snapshot.

    ``check_engine`` is the registry's TpuCheckEngine — snapshots (and
    their freshness semantics: read-your-writes via the store watermark)
    are shared with the check path, so an expand issued after a write sees
    the write exactly like a check does.
    """

    def __init__(self, check_engine, namespaces):
        self._engine = check_engine
        if isinstance(namespaces, namespace_pkg.Manager):
            self._nm: Callable[[], namespace_pkg.Manager] = lambda: namespaces
        else:
            self._nm = namespaces
        from keto_tpu.expand.engine import ExpandEngine

        #: exact-order engine for overlay-pending snapshots (see module doc)
        self._manager_engine = ExpandEngine(check_engine._store, namespaces=self._nm)

    # -- public API (host engine signature) ----------------------------------

    def build_tree(self, subject: Subject, rest_depth: int) -> Optional[Tree]:
        if rest_depth <= 0:
            return None
        if not isinstance(subject, SubjectSet):
            return Tree(type=LEAF, subject=subject)
        snap = self._engine.snapshot()
        if snap.has_overlay and snap.has_wildcards:
            # wildcard-bearing nodes order children by GLOBAL row order —
            # not reconstructible from the per-node overlay merge (module
            # doc); serve the reference's exact tree from the Manager
            return self._manager_engine.build_tree(subject, rest_depth)
        nm = self._nm()

        ns = subject.namespace
        if ns == "":
            ns_id: int = WILDCARD
        else:
            # unknown namespace raises, exactly like the host engine's
            # first Manager query (reference engine.go:51-61 propagates)
            ns_id = nm.get_namespace_by_name(ns).id

        plan = snap.rewrites
        if plan is not None and (
            ns_id == WILDCARD or subject.relation == ""
            or plan.relation_flags(ns_id, subject.relation) & REWRITTEN
        ):
            # the closure of this root has a userset rewrite: the adjacency
            # holds derived edges there, which the bulk capture would show as
            # stored children; the host engine expands by the schema
            return self._manager_engine.build_tree(subject, rest_depth)

        root_dev = None
        if ns_id != WILDCARD:
            root_dev = snap.resolve_set(ns_id, subject.object, subject.relation)
        pattern = (
            ns_id == WILDCARD
            or ns_id in snap.wild_ns_ids
            or subject.object == ""
            or subject.relation == ""
        )
        children_of: dict[int, np.ndarray] = {}
        if root_dev is None:
            if not pattern:
                return None  # literal key absent → no tuples → nil tree
            if snap.has_overlay:
                # a pattern root concatenates MATCHING KEYS' lists in
                # global row order — same non-reconstructible case
                return self._manager_engine.build_tree(subject, rest_depth)
            starts = snap.resolve_starts(ns_id, subject.object, subject.relation)
            if starts.size == 0:
                return None
            children_of[_PATTERN_ROOT] = self._pattern_children(snap, starts)
            root_dev = _PATTERN_ROOT

        self._capture_adjacency(snap, root_dev, rest_depth, children_of)

        ns_names = {n.id: n.name for n in nm.namespaces()}

        def subject_of(dev: int) -> Subject:
            kind, key = snap.key_of_dev(dev)
            if kind == "leaf":
                return SubjectID(key)
            k_ns, k_obj, k_rel = key
            name = ns_names.get(k_ns)
            if name is None:
                # tuples can outlive a namespace removed by config reload;
                # the Manager-backed engine raises from its id→name
                # resolution in the same situation
                raise ErrNamespaceUnknown(f"namespace id {k_ns}")
            return SubjectSet(name, k_obj, k_rel)

        visited: set[str] = set()

        def rec(sub: Subject, dev: int, rd: int) -> Optional[Tree]:
            # mirrors keto_tpu/expand/engine.py _build_tree line for line
            if rd <= 0:
                return None
            if not isinstance(sub, SubjectSet):
                return Tree(type=LEAF, subject=sub)
            if check_and_add_visited(visited, sub):
                return None
            ch = children_of.get(dev)
            if ch is None or ch.size == 0:
                return None
            if rd <= 1:
                return Tree(type=LEAF, subject=sub)
            node = Tree(type=UNION, subject=sub)
            for c in ch.tolist():
                cs = subject_of(c)
                t = rec(cs, c, rd - 1)
                node.children.append(t if t is not None else Tree(type=LEAF, subject=cs))
            return node

        return rec(subject, root_dev, rest_depth)

    # -- phase A -------------------------------------------------------------

    def _subject_order_key(self, snap: GraphSnapshot, dev: int):
        """Manager ORDER BY position of a child: subject sets first
        (NULL-first on the subject_id column), each group sorted by its
        key fields — comparable tuples."""
        kind, key = snap.key_of_dev(dev)
        return (0, key) if kind == "set" else (1, (key,))

    def _merge_overlay_children(
        self, snap: GraphSnapshot, dev: int, base: np.ndarray
    ) -> np.ndarray:
        """Base children (already in subject-sort order — one literal
        node's rows are contiguous in the store's ORDER BY) merged with
        the node's overlay children in the SAME order: the Manager's page
        order, reproduced without a storage round trip. Overlay lists are
        tiny by design, so each overlay child bisects into the sorted
        base list (O(k log n) key computations, not O(n)); the merged
        array memoizes on the immutable snapshot."""
        import bisect as _bisect

        extra = snap.ov_fwd.get(int(dev))
        if not extra:
            return base
        cache_key = ("_exp_merge", int(dev))
        with snap._cache_lock:
            hit = snap._pattern_cache.get(cache_key)
        if hit is not None:
            return hit
        okey = lambda d: self._subject_order_key(snap, int(d))  # noqa: E731
        ov_sorted = sorted(extra, key=okey)
        positions = [
            _bisect.bisect_left(base, okey(d), key=okey) for d in ov_sorted
        ]
        out = np.insert(base.astype(np.int64), positions, ov_sorted)
        with snap._cache_lock:
            snap._pattern_cache[cache_key] = out
        return out

    def _capture_adjacency(
        self,
        snap: GraphSnapshot,
        root_dev: int,
        rest_depth: int,
        children_of: dict[int, np.ndarray],
    ) -> None:
        """Fill ``children_of`` for every set node reachable within the
        depth budget: one ``out_neighbors_bulk`` gather per BFS level
        (base edges, tombstone-masked), plus the per-node overlay merge
        when a delta is pending."""
        if root_dev == _PATTERN_ROOT:
            ch = children_of[_PATTERN_ROOT]
            m = snap.is_set_dev_bulk(ch)
            frontier = list(dict.fromkeys(ch[m].tolist()))
        else:
            frontier = [root_dev]
        seen = set(frontier)
        level = 0
        has_ov = bool(snap.ov_fwd)
        # a node at BFS level L expands with rest_depth - L; it consults
        # its children whenever that is ≥ 1
        while frontier and level <= rest_depth - 1:
            arr = np.asarray(frontier, np.int64)
            rows, cnts = snap.out_neighbors_bulk(arr, overlay=False)
            ends = np.cumsum(cnts)
            nxt: list[int] = []
            new_children: list[np.ndarray] = []
            start = 0
            for i, dev in enumerate(frontier):
                ch = rows[start : ends[i]]
                start = int(ends[i])
                if has_ov:
                    ch = self._merge_overlay_children(snap, dev, ch)
                children_of[dev] = ch
                new_children.append(ch)
            if new_children:
                flat = np.concatenate(new_children) if len(new_children) > 1 else new_children[0]
                if flat.size:
                    m = snap.is_set_dev_bulk(flat)
                    for c in flat[m].tolist():
                        if c not in seen:
                            seen.add(c)
                            nxt.append(c)
            frontier = nxt
            level += 1

    @staticmethod
    def _pattern_children(snap: GraphSnapshot, starts: np.ndarray) -> np.ndarray:
        """Ordered union of the matching keys' child lists for a root
        pattern with no node of its own: keys sort by (ns_id, object,
        relation) — the leading columns of the store's ORDER BY — then
        each key contributes its children in its own (row-order) edge
        order; duplicates keep the first occurrence. (Never called with a
        pending overlay: build_tree delegates that case to the Manager.)"""
        keyed = []
        for dev in starts.tolist():
            kind, key = snap.key_of_dev(dev)
            if kind == "set":
                keyed.append((key, dev))
        keyed.sort(key=lambda kv: kv[0])
        if not keyed:
            return np.zeros(0, np.int64)
        rows, _ = snap.out_neighbors_bulk(np.asarray([d for _, d in keyed], np.int64))
        _, first = np.unique(rows, return_index=True)
        return rows[np.sort(first)]
