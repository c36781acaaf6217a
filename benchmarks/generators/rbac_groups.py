"""rbac-groups: users -> leaf -> mid -> top groups, docs grant ``view`` to a
group's members (BASELINE.json config 3). A copy of ``bench.build_workload``
and ``bench.iter_queries`` that emits plain rows instead of the program's
tuple objects, and takes the object of each query from the traffic's skew.

Rows are ``(namespace, object, relation, subject_id, sset_namespace,
sset_object, sset_relation)`` with exactly one of ``subject_id`` and the
``sset_*`` triple set. A query is ``(namespace, object, relation,
subject_id)``.
"""

from __future__ import annotations

NAMESPACES = ("docs", "groups")


class Graph:
    def __init__(self):
        self.rows = []
        self.n_objects = 0  # objects a query may ask about, ranked by the traffic


def build(rng, n_tuples: int) -> Graph:
    # proportions chosen so totals scale linearly with n_tuples
    n_users = max(100, n_tuples // 10)
    n_leaf = max(20, n_tuples // 125)
    n_mid = max(5, n_leaf // 5)
    n_top = max(2, n_mid // 4)

    g = Graph()
    rows = g.rows
    membership = {}  # user -> set of leaf groups
    leaf_users = {}  # leaf group -> users
    for u in range(n_users):
        for _ in range(rng.choice((1, 1, 2))):
            leaf = rng.randrange(n_leaf)
            membership.setdefault(u, set()).add(leaf)
            leaf_users.setdefault(leaf, []).append(u)
            rows.append(("groups", f"leaf-{leaf}", "member", f"user-{u}", None, None, None))

    leaf_parent, mid_leaves = {}, {}
    for leaf in range(n_leaf):
        parent = rng.randrange(n_mid)
        leaf_parent[leaf] = parent
        mid_leaves.setdefault(parent, []).append(leaf)
        rows.append(("groups", f"mid-{parent}", "member", None, "groups", f"leaf-{leaf}", "member"))
    mid_parent, top_mids = {}, {}
    for m in range(n_mid):
        parent = rng.randrange(n_top)
        mid_parent[m] = parent
        top_mids.setdefault(parent, []).append(m)
        rows.append(("groups", f"top-{parent}", "member", None, "groups", f"mid-{m}", "member"))

    doc_grant = []
    sizes = {"leaf": n_leaf, "mid": n_mid, "top": n_top}
    while len(rows) < n_tuples:
        kind = rng.choice(("leaf", "mid", "top"))
        grp = rng.randrange(sizes[kind])
        rows.append(("docs", f"doc-{len(doc_grant)}", "view", None, "groups", f"{kind}-{grp}", "member"))
        doc_grant.append((kind, grp))

    def user_reaches(u, kind, grp):
        leaves = membership.get(u, ())
        if kind == "leaf":
            return grp in leaves
        mids = {leaf_parent[leaf] for leaf in leaves}
        if kind == "mid":
            return grp in mids
        return grp in {mid_parent[m] for m in mids}

    def member_of(kind, grp, rng):
        """A user transitively inside group (kind, grp), or None if empty."""
        if kind == "top":
            mids = top_mids.get(grp)
            if not mids:
                return None
            kind, grp = "mid", rng.choice(mids)
        if kind == "mid":
            leaves = mid_leaves.get(grp)
            if not leaves:
                return None
            grp = rng.choice(leaves)
        users = leaf_users.get(grp)
        return rng.choice(users) if users else None

    g.n_objects = len(doc_grant)
    g.n_users = n_users
    g.doc_grant = doc_grant
    g.user_reaches = user_reaches
    g.member_of = member_of
    return g


def queries(graph: Graph, rng, objects):
    """One query per entry of ``objects`` (indices below ``graph.n_objects``):
    half ask about a user built to hold the doc's grant, half about a uniform
    user (almost always a denial). Returns ``(queries, expected)``."""
    out, expected = [], []
    for i, d in enumerate(objects):
        kind, grp = graph.doc_grant[d]
        u = graph.member_of(kind, grp, rng) if i % 2 == 0 else None
        if u is None:
            u = rng.randrange(graph.n_users)
        out.append(("docs", f"doc-{d}", "view", f"user-{u}"))
        expected.append(graph.user_reaches(u, kind, grp))
    return out, expected
