"""nested-groups: a company directory whose groups nest in groups (Keto v0.7's
"groups as subject sets": ``groups:G#member`` holds user ids and other groups'
``member`` subject sets; ``docs:D#view`` holds groups' ``member`` subject
sets), with every degree heavy-tailed: the sizes of groups, the groups a user
is in, the groups a group is nested in and the grants a document holds all
come from the Graph500 specification's Kronecker (R-MAT) generator, initiator
A, B, C, D = 0.57, 0.19, 0.19, 0.05, vertex labels permuted at random.

Per nominal 1,000,000 tuples, three matrices (rows x columns -> edges drawn):

- membership: 2^16 groups x 2^17 users -> 5 x 2^17 = 655,360,
  ``groups:group-G#member@user-U``;
- nesting: 2^16 x 2^16 groups -> 4 x 2^16 = 262,144, self-pairs dropped, the
  group with the lower permuted label is the parent (so nothing cycles),
  ``groups:group-P#member@(groups:group-C#member)``;
- grants: 2^16 groups x 2^16 documents -> 1.25 x 2^16 = 81,920,
  ``docs:doc-D#view@(groups:group-G#member)``.

A rectangular matrix takes the row bit from the quadrant's row half and the
column bit from its column half, level by level, and skips the exhausted axis.
One permutation of the groups serves all three matrices (a group is one
vertex). Duplicates are dropped (Keto stores a tuple once), which leaves about
952,000 tuples. The edge counts scale linearly with ``n_tuples``; the sides of
the matrices move one bit for every halving (floors keep small rehearsals
buildable). Documents without a grant do not exist: the query objects
``0..n_objects-1`` are the documents that hold one, in order of their label.

The analytic expectation is this file's own: a user holds a document when one
of the user's groups lies in the union of the granted groups' descendant sets
(parent -> child over the nesting), found breadth-first with numpy frontiers
over the nesting's CSR, once a distinct document of the pool.

Rows and queries have the shapes of rbac_groups.py.
"""

from __future__ import annotations

import math

import numpy as np

NAMESPACES = ("groups", "docs")
SOURCE_TUPLES = 1_000_000  # the matrices above are stated per this many
INITIATOR = (0.57, 0.19, 0.19, 0.05)
GROUP_BITS, USER_BITS, DOC_BITS = 16, 17, 16
MEMBERSHIP_EDGES, NESTING_EDGES, GRANT_EDGES = 5 * 2**17, 4 * 2**16, 5 * 2**14
MIN_BITS = 6


def rmat(gen, n_edges: int, row_bits: int, col_bits: int):
    """``n_edges`` cells of a 2^row_bits x 2^col_bits Kronecker matrix: one
    quadrant a level from the initiator, most significant bit first."""
    a, b, c, _ = INITIATOR
    rows = np.zeros(n_edges, np.int64)
    cols = np.zeros(n_edges, np.int64)
    for level in range(max(row_bits, col_bits)):
        u = gen.random(n_edges)
        if level < row_bits:  # the quadrant's row half: C or D
            rows = (rows << 1) | (u >= a + b)
        if level < col_bits:  # its column half: B or D
            cols = (cols << 1) | (((u >= a) & (u < a + b)) | (u >= a + b + c))
    return rows, cols


def _distinct(rows, cols, n_cols: int):
    """The distinct pairs, sorted by row then column."""
    key = np.unique(rows * n_cols + cols)
    return key // n_cols, key % n_cols


def _csr(rows, cols, n_rows: int):
    """``(indptr, indices)`` of pairs sorted by row."""
    indptr = np.zeros(n_rows + 1, np.int64)
    np.cumsum(np.bincount(rows, minlength=n_rows), out=indptr[1:])
    return indptr, cols


class Graph:
    def __init__(self):
        self.rows = []
        self.n_objects = 0  # documents that hold a grant, ranked by the traffic


def build(rng, n_tuples: int) -> Graph:
    scale = n_tuples / SOURCE_TUPLES
    shift = round(math.log2(scale))
    g_bits, u_bits, d_bits = (max(MIN_BITS, b + shift) for b in (GROUP_BITS, USER_BITS, DOC_BITS))
    n_groups, n_users, n_docs = 1 << g_bits, 1 << u_bits, 1 << d_bits
    gen = np.random.Generator(np.random.PCG64(rng.getrandbits(64)))
    g_perm, u_perm, d_perm = (gen.permutation(n) for n in (n_groups, n_users, n_docs))

    r, c = rmat(gen, max(64, int(MEMBERSHIP_EDGES * scale)), g_bits, u_bits)
    m_group, m_user = _distinct(g_perm[r], u_perm[c], n_users)
    r, c = rmat(gen, max(32, int(NESTING_EDGES * scale)), g_bits, g_bits)
    a, b = g_perm[r], g_perm[c]
    keep = a != b
    parent, child = _distinct(np.minimum(a, b)[keep], np.maximum(a, b)[keep], n_groups)
    r, c = rmat(gen, max(16, int(GRANT_EDGES * scale)), g_bits, d_bits)
    t_doc, t_group = _distinct(d_perm[c], g_perm[r], n_groups)

    g = Graph()
    rows = g.rows
    rows.extend(("groups", f"group-{grp}", "member", f"user-{u}", None, None, None)
                for grp, u in zip(m_group.tolist(), m_user.tolist()))
    rows.extend(("groups", f"group-{p}", "member", None, "groups", f"group-{ch}", "member")
                for p, ch in zip(parent.tolist(), child.tolist()))
    rows.extend(("docs", f"doc-{d}", "view", None, "groups", f"group-{grp}", "member")
                for d, grp in zip(t_doc.tolist(), t_group.tolist()))

    g.n_groups, g.n_users = n_groups, n_users
    g.docs = np.unique(t_doc)  # object index -> document label
    g.n_objects = int(g.docs.size)
    g.members = _csr(m_group, m_user, n_groups)  # group -> users
    order = np.argsort(m_user, kind="stable")
    g.user_groups = _csr(m_user[order], m_group[order], n_users)  # user -> groups
    g.holders = np.unique(m_user)  # users with at least one membership
    g.children = _csr(parent, child, n_groups)  # group -> groups nested in it
    g.grants = _csr(t_doc, t_group, n_docs)  # document label -> granted groups
    g.degrees = {"membership": (m_group, m_user), "nesting": (parent, child),
                 "grants": (t_group, t_doc)}
    return g


def _ranges(indptr, indices, keys):
    """The CSR rows of ``keys`` laid end to end, and each row's length."""
    starts = indptr[keys]
    counts = indptr[keys + 1] - starts
    total = int(counts.sum())
    if not total:
        return indices[:0], counts
    ends = np.cumsum(counts)
    flat = np.arange(total) + np.repeat(starts - (ends - counts), counts)
    return indices[flat], counts


def descendants(graph: Graph, doc: int):
    """The document's descendant set, breadth-first from its grants:
    ``(groups in order of discovery, where each level starts, bool by group:
    in the set)``; level 0 is the granted groups, and the last entry of the
    starts is the total."""
    ptr, idx = graph.grants
    frontier = idx[ptr[doc]:ptr[doc + 1]]
    seen = np.zeros(graph.n_groups, bool)
    seen[frontier] = True
    found, starts = [frontier], [0, frontier.size]
    while True:
        below, _ = _ranges(*graph.children, frontier)
        known = seen.copy()
        seen[below] = True
        nxt = np.flatnonzero(seen ^ known)
        if not nxt.size:
            break
        found.append(nxt)
        starts.append(starts[-1] + nxt.size)
        frontier = nxt
    return np.concatenate(found), np.asarray(starts), seen


def queries(graph: Graph, rng, objects):
    """One query per entry of ``objects`` (indices below ``graph.n_objects``).
    Even positions ask about an engineered holder: a level of the document's
    descendant set drawn uniformly from 0 to the deepest, a group of that
    level drawn uniformly among those that hold a user directly (another
    group of the level where the first drawn is empty; one of any shallower
    level where the whole level is), a member of it drawn uniformly - so the
    shortest grant paths of every length up to the deepest occur. Odd
    positions ask about a uniform user among those that hold at least one
    membership. Returns ``(queries, expected)``."""
    gen = np.random.Generator(np.random.PCG64(rng.getrandbits(64)))
    objects = np.asarray(objects, np.int64)
    n = objects.size
    users = graph.holders[gen.integers(0, graph.holders.size, n)]
    expected = np.zeros(n, bool)
    m_ptr, m_idx = graph.members
    size = np.diff(m_ptr)
    by_doc = np.argsort(objects, kind="stable")
    cuts = np.flatnonzero(np.diff(objects[by_doc])) + 1
    for where in np.split(by_doc, cuts):
        found, starts, reach = descendants(graph, int(graph.docs[objects[where[0]]]))
        even = where[where % 2 == 0]
        if even.size:
            full = size[found] > 0
            # full groups of levels 0..l, for every level l
            upto = np.concatenate([[0], np.cumsum(full)])[starts]
            if upto[-1]:
                level = gen.integers(0, starts.size - 1, even.size)
                hi = upto[level + 1]
                lo = np.where(hi > upto[level], upto[level], 0)  # an empty level: any above it
                ok = hi > lo
                pick = np.flatnonzero(full)[
                    (lo + gen.random(even.size) * (hi - lo)).astype(np.int64)[ok]]
                grp = found[pick]
                users[even[ok]] = m_idx[
                    m_ptr[grp] + (gen.random(grp.size) * size[grp]).astype(np.int64)]
        mine, counts = _ranges(*graph.user_groups, users[where])
        ends = np.cumsum(counts)
        expected[where] = np.logical_or.reduceat(reach[mine], ends - counts)
    docs = graph.docs[objects].tolist()
    out = [("docs", f"doc-{d}", "view", f"user-{u}") for d, u in zip(docs, users.tolist())]
    return out, expected.tolist()
