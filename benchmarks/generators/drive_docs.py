"""drive-docs: the folder/file deployment of Keto v0.7's own guide
(expand-api-display-who-has-access: ``directories:/photos#owner@maureen``,
``directories:/photos#access@(directories:/photos#owner)``,
``files:/photos/beach.jpg#access@(directories:/photos#access)``,
``files:/photos/beach.jpg#access@(files:/photos/beach.jpg#owner)``) at the
scale of BASELINE.json config 5. v0.7 has no rewrites, so inheritance is
materialised as subject-set tuples: a file inherits its folder's ``access``,
a folder its parent's, and groups are subject sets as in rbac-groups.

Per 1,000,000 tuples (every count scales linearly, with floors so that small
rehearsals still build): 40,000 users; 200 top groups and 1,800 leaf groups,
each leaf ``member`` of one top group, nine a top group, dealt in turn; an
ordinary user joins 1-3 leaf groups
and 2% of the users ("power users") join 40; 60,000 folders in 600 trees, a
folder's parent drawn among the earlier folders of depth < 9; every folder has
``owner@user``, ``access@(dir#owner)`` and, below a root,
``access@(parent#access)``; every root and 15% of the others grant ``access``
to one leaf group's members, and 3 top groups ("all-staff") are each granted
``access`` on 150 folders, distinct roots first; the rest of the tuples are
files, three each: ``owner@user``, ``access@(file#owner)``,
``access@(dir#access)``. Files live deep: the deepest of four uniformly drawn
folders, then down random subfolders to one that has none, which puts about a
fifth of the files at depth 9 - enough of the engineered grants then run the
whole chain for the control (benchmarks/control.py) to fail by a hundred or
more answers of 20,000 and not by a handful.

The deepest grant chain is file -> folder(9) -> ... -> root -> top group ->
leaf group -> user = 13 edges. Rows and queries have the shapes of
rbac_groups.py; query objects ``0..n_objects-1`` are the files.
"""

from __future__ import annotations

NAMESPACES = ("groups", "directories", "files")
SOURCE_TUPLES = 1_000_000  # the proportions above are stated per this many
MAX_DEPTH = 9
POWER_SHARE = 0.02
POWER_GROUPS = 40
GROUP_GRANT_SHARE = 0.15
STAFF_GROUPS = 3


class Graph:
    def __init__(self):
        self.rows = []
        self.n_objects = 0  # files, ranked by the traffic


def build(rng, n_tuples: int) -> Graph:
    scale = n_tuples / SOURCE_TUPLES
    n_users = max(200, int(40_000 * scale))
    n_top = max(20, int(200 * scale))
    n_leaf = max(9 * n_top, int(1_800 * scale))  # nine leaves a top group at every size
    n_dirs = max(120, int(60_000 * scale))
    n_roots = max(4, int(600 * scale))
    staff_grants = max(2, int(150 * scale))

    g = Graph()
    rows = g.rows
    group = lambda k: f"group-{k}"  # top groups first, then the leaves

    # groups: each leaf is a member of one top group, dealt in turn, so that
    # a top group holds nine at every size (a group of four or fewer is folded
    # into the host's walk by the engine's peel: the all-staff groups are what
    # this deployment leaves on the device, and a rehearsal has to have them)
    leaf_top = [leaf % n_top for leaf in range(n_leaf)]
    top_leaves = {}
    for leaf, top in enumerate(leaf_top):
        top_leaves.setdefault(top, []).append(leaf)
        rows.append(("groups", group(top), "member", None, "groups", group(n_top + leaf), "member"))

    # memberships; a user's closure is its leaves and their top groups
    user_groups, leaf_users = [], [[] for _ in range(n_leaf)]
    for u in range(n_users):
        if rng.random() < POWER_SHARE:
            leaves = rng.sample(range(n_leaf), min(POWER_GROUPS, n_leaf))
        else:
            leaves = list({rng.randrange(n_leaf) for _ in range(rng.randint(1, 3))})
        closure = set()
        for leaf in leaves:
            leaf_users[leaf].append(u)
            closure.add(n_top + leaf)
            closure.add(leaf_top[leaf])
            rows.append(("groups", group(n_top + leaf), "member", f"user-{u}", None, None, None))
        user_groups.append(frozenset(closure))

    # folders
    parent, depth, children = [-1] * n_dirs, [0] * n_dirs, [[] for _ in range(n_dirs)]
    may_parent = list(range(n_roots))  # folders of depth < MAX_DEPTH
    dir_owner, dir_groups = [], [[] for _ in range(n_dirs)]
    for d in range(n_dirs):
        if d >= n_roots:
            p = rng.choice(may_parent)
            parent[d], depth[d] = p, depth[p] + 1
            children[p].append(d)
            if depth[d] < MAX_DEPTH:
                may_parent.append(d)
        owner = rng.randrange(n_users)
        dir_owner.append(owner)
        rows.append(("directories", f"dir-{d}", "owner", f"user-{owner}", None, None, None))
        rows.append(("directories", f"dir-{d}", "access", None, "directories", f"dir-{d}", "owner"))
        if d >= n_roots:
            rows.append(("directories", f"dir-{d}", "access", None,
                         "directories", f"dir-{parent[d]}", "access"))
        if d < n_roots or rng.random() < GROUP_GRANT_SHARE:
            leaf = n_top + rng.randrange(n_leaf)
            dir_groups[d].append(leaf)
            rows.append(("directories", f"dir-{d}", "access", None, "groups", group(leaf), "member"))
    # all-staff: the top-level shared drives first, each once, then anywhere
    roots = rng.sample(range(n_roots), n_roots)
    for top in range(STAFF_GROUPS):
        picked, roots = roots[:staff_grants], roots[staff_grants:]
        picked += [rng.randrange(n_roots, n_dirs) for _ in range(staff_grants - len(picked))]
        for d in picked:
            dir_groups[d].append(top)
            rows.append(("directories", f"dir-{d}", "access", None, "groups", group(top), "member"))

    # files fill the rest, deep in the trees
    file_dir, file_owner = [], []
    while len(rows) + 3 <= n_tuples:
        d = max((rng.randrange(n_dirs) for _ in range(4)), key=depth.__getitem__)
        while children[d]:
            d = rng.choice(children[d])
        owner = rng.randrange(n_users)
        f = len(file_dir)
        file_dir.append(d)
        file_owner.append(owner)
        rows.append(("files", f"file-{f}", "owner", f"user-{owner}", None, None, None))
        rows.append(("files", f"file-{f}", "access", None, "files", f"file-{f}", "owner"))
        rows.append(("files", f"file-{f}", "access", None, "directories", f"dir-{d}", "access"))

    def chain(d):
        """The folder and its ancestors, root last."""
        out = [d]
        while parent[out[-1]] >= 0:
            out.append(parent[out[-1]])
        return out

    def holds(u, f):
        """Analytic: the file's owner, or the owner of, or a member (through
        the group closure) of a group granted on, any ancestor folder."""
        if file_owner[f] == u:
            return True
        mine = user_groups[u]
        d = file_dir[f]
        while d >= 0:
            if dir_owner[d] == u or any(grp in mine for grp in dir_groups[d]):
                return True
            d = parent[d]
        return False

    def member_of(grp, rng):
        """A user inside the group, walking down from a top group; or None."""
        if grp < n_top:
            leaves = top_leaves.get(grp)
            if not leaves:
                return None
            leaf = rng.choice(leaves)
        else:
            leaf = grp - n_top
        users = leaf_users[leaf]
        return rng.choice(users) if users else None

    g.n_objects = len(file_dir)
    g.n_users = n_users
    g.file_owner, g.dir_owner, g.dir_groups = file_owner, dir_owner, dir_groups
    g.file_dir, g.depth = file_dir, depth
    g.chain, g.holds, g.member_of = chain, holds, member_of
    return g


def queries(graph: Graph, rng, objects):
    """One query per entry of ``objects`` (file indices): even positions ask
    about an engineered holder, odd positions about a uniform user (mostly a
    denial). The engineered holder is the file's owner one time in eight;
    otherwise a granting ancestor is picked uniformly over the chain, the root
    included, and the holder is a member reached by walking down from a group
    granted there (the widest group first: that is the deepest chain), or the
    ancestor's owner where no group is granted or the group is empty."""
    out, expected = [], []
    for i, f in enumerate(objects):
        u = None
        if i % 2 == 0:
            if rng.random() < 1 / 8:
                u = graph.file_owner[f]
            else:
                anc = rng.choice(graph.chain(graph.file_dir[f]))
                grants = graph.dir_groups[anc]
                if grants:
                    u = graph.member_of(min(grants), rng)
                if u is None:
                    u = graph.dir_owner[anc]
        if u is None:
            u = rng.randrange(graph.n_users)
        out.append(("files", f"file-{f}", "access", f"user-{u}"))
        expected.append(graph.holds(u, f))
    return out, expected
