"""The fixture's generator (fixture_rewrites.json; no cell uses it): folders
with direct viewers, documents with one ``parent`` folder, one direct
``editor`` and one direct ``viewer``. The schema makes a document's viewers
its direct viewers, its editors (``computed_userset``) and its folder's
viewers (``tuple_to_userset``); by Keto v0.7's rule only the first hold
``viewer``. Rows and queries as in ``generators/rbac_groups.py``.
"""

from __future__ import annotations

FOLDER_VIEWERS = 3


class Graph:
    def __init__(self):
        self.rows = []
        self.n_objects = 0


def build(rng, n_tuples: int) -> Graph:
    n_users = max(50, n_tuples // 10)
    n_folders = max(10, n_tuples // 40)
    g = Graph()
    g.folder_viewers = []
    for f in range(n_folders):
        viewers = rng.sample(range(n_users), FOLDER_VIEWERS)
        g.folder_viewers.append(viewers)
        g.rows += [("folders", f"folder-{f}", "viewer", f"user-{u}", None, None, None)
                   for u in viewers]
    g.docs = []  # (parent folder, editor, direct viewer)
    while len(g.rows) < n_tuples:
        d, f = len(g.docs), rng.randrange(n_folders)
        editor, viewer = rng.randrange(n_users), rng.randrange(n_users)
        g.rows += [("docs", f"doc-{d}", "parent", None, "folders", f"folder-{f}", "..."),
                   ("docs", f"doc-{d}", "editor", f"user-{editor}", None, None, None),
                   ("docs", f"doc-{d}", "viewer", f"user-{viewer}", None, None, None)]
        g.docs.append((f, editor, viewer))
    g.n_objects, g.n_users = len(g.docs), n_users
    return g


def queries(graph: Graph, rng, objects):
    """``viewer`` on each object for, in turn, its direct viewer, its editor,
    a viewer of its folder and a uniform user. Returns ``(queries, expected by
    the schema)``; ``v07_answers`` is what ignoring the schema gives."""
    out, expected = [], []
    for i, d in enumerate(objects):
        f, editor, viewer = graph.docs[d]
        u = (viewer, editor, rng.choice(graph.folder_viewers[f]), rng.randrange(graph.n_users))[i % 4]
        out.append(("docs", f"doc-{d}", "viewer", f"user-{u}"))
        expected.append(u in (viewer, editor) or u in graph.folder_viewers[f])
    return out, expected


def v07_answers(graph: Graph, queries):
    """Right by Keto v0.7 (a row grants, nothing else), wrong by the schema."""
    return [int(user[5:]) == graph.docs[int(obj[4:])][2] for _, obj, _, user in queries]
