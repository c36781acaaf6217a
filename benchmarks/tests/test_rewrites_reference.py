"""``benchmarks/references/rewrites.py`` against answers written out by hand:
Zanzibar's Figure 1 and OpenFGA's sample store ``github``, a dozen tuples
each; intersection, exclusion, cycles, the refused cycle through a subtract,
``max_depth``; agreement with ``benchmarks/reference.py`` where the schema
has no rewrites; and, on random schemas and graphs with cycles, agreement
with the least solution of the docstring's equations found by iteration."""

import itertools
import random
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from benchmarks import control, reference, run  # noqa: E402

rewrites = run.load_module("references", "rewrites")

THIS = {"this": {}}


def union(*children):
    return {"union": list(children)}


def computed(relation):
    return {"computed_userset": relation}


def from_(tupleset, relation):
    return {"tuple_to_userset": {"tupleset": tupleset, "computed_userset": relation}}


def schema(**namespaces):
    return {"namespaces": [{"id": i, "name": name, "config": {"relations": relations}}
                           for i, (name, relations) in enumerate(namespaces.items())]}


def row(spec: str):
    """``ns:obj#rel@user`` or ``ns:obj#rel@ns2:obj2#rel2`` as a generator's row."""
    left, subject = spec.split("@")
    ns, rest = left.split(":", 1)
    obj, rel = rest.split("#")
    if "#" not in subject:
        return (ns, obj, rel, subject, None, None, None)
    sns, srest = subject.split(":", 1)
    sobj, srel = srest.split("#")
    return (ns, obj, rel, None, sns, sobj, srel)


def judge(config, specs):
    return rewrites.Reference([row(s) for s in specs], config)


def holders(ref, node: str, users, max_depth=None):
    ns, rest = node.split(":", 1)
    obj, rel = rest.split("#")
    return {u for u in users if ref.allowed(ns, obj, rel, u, max_depth=max_depth)}


# -- Zanzibar (USENIX ATC '19) section 2.3.1, Figure 1 ---------------------------

CONCENTRIC = {"editor": union(THIS, computed("owner")),
              "viewer": union(THIS, computed("editor"), from_("parent", "viewer"))}
FIGURE_1 = schema(doc=CONCENTRIC, folder=CONCENTRIC, group={})
FIGURE_1_TUPLES = [
    "doc:readme#owner@alice",
    "doc:readme#editor@bob",
    "doc:readme#viewer@carol",
    "doc:readme#parent@folder:eng#...",
    "doc:readme#parent@heidi",  # a plain id names no object
    "folder:eng#viewer@dave",
    "folder:eng#parent@folder:root#...",
    "folder:root#owner@erin",
    "folder:root#viewer@group:staff#member",
    "group:staff#member@frank",
    "group:staff#member@group:interns#member",
    "group:interns#member@gina",
    "doc:draft#editor@group:interns#member",
]
PEOPLE = ["alice", "bob", "carol", "dave", "erin", "frank", "gina", "heidi"]
FIGURE_1_ANSWERS = {
    "doc:readme#owner": {"alice"},
    "doc:readme#editor": {"alice", "bob"},
    "doc:readme#viewer": {"alice", "bob", "carol", "dave", "erin", "frank", "gina"},
    "doc:readme#parent": {"heidi"},  # stored, and not what the tupleset reads
    "doc:draft#editor": {"gina"},
    "doc:draft#viewer": {"gina"},
    "doc:draft#owner": set(),
    "folder:eng#viewer": {"dave", "erin", "frank", "gina"},
    "folder:eng#editor": set(),
    "folder:root#editor": {"erin"},
    "folder:root#viewer": {"erin", "frank", "gina"},
    "group:staff#member": {"frank", "gina"},
    "doc:missing#viewer": set(),
    "nowhere:readme#viewer": set(),
}


@pytest.mark.parametrize("node", sorted(FIGURE_1_ANSWERS))
def test_zanzibar_figure_1(node):
    assert holders(judge(FIGURE_1, FIGURE_1_TUPLES), node, PEOPLE) == FIGURE_1_ANSWERS[node]


@pytest.mark.parametrize("user, needs", [
    ("carol", 1),  # a direct grant
    ("bob", 2),    # viewer -> editor
    ("dave", 2),   # viewer -> the parent folder's viewer
    ("alice", 3),  # viewer -> editor -> owner
    ("erin", 5),   # readme -> eng -> root#viewer -> root#editor -> root#owner
    ("gina", 5),   # readme -> eng -> root -> staff -> interns
])
def test_max_depth_counts_every_step_to_another_node(user, needs):
    ref = judge(FIGURE_1, FIGURE_1_TUPLES)
    granted = [ref.allowed("doc", "readme", "viewer", user, max_depth=d) for d in range(8)]
    assert granted == [d >= needs for d in range(8)]


# -- OpenFGA's sample store ``github`` -------------------------------------------

GITHUB = schema(
    teams={},
    orgs={"member": union(THIS, computed("owner"))},
    repos={"admin": union(THIS, from_("owner", "repo_admin")),
           "maintainer": union(THIS, computed("admin")),
           "writer": union(THIS, computed("maintainer"), from_("owner", "repo_writer")),
           "triager": union(THIS, computed("writer")),
           "reader": union(THIS, computed("triager"), from_("owner", "repo_reader"))},
)
GITHUB_TUPLES = [
    "orgs:openfga#owner@erik",
    "orgs:openfga#member@anne",
    "orgs:openfga#repo_admin@orgs:openfga#owner",
    "orgs:openfga#repo_reader@orgs:openfga#member",
    "repos:openfga/openfga#owner@orgs:openfga#...",
    "teams:core#member@beth",
    "teams:core#member@teams:backend#member",
    "teams:backend#member@charles",
    "repos:openfga/openfga#admin@teams:core#member",
    "repos:openfga/openfga#writer@diane",
    "repos:openfga/openfga#reader@gina",
    "repos:openfga/docs#reader@teams:backend#member",
    "repos:openfga/docs#maintainer@anne",
]
OCTOCATS = ["anne", "beth", "charles", "diane", "erik", "gina", "zoe"]
GITHUB_ANSWERS = {
    "orgs:openfga#owner": {"erik"},
    "orgs:openfga#member": {"anne", "erik"},
    "orgs:openfga#repo_admin": {"erik"},
    "orgs:openfga#repo_reader": {"anne", "erik"},
    "orgs:openfga#repo_writer": set(),
    "teams:core#member": {"beth", "charles"},
    "repos:openfga/openfga#admin": {"beth", "charles", "erik"},
    "repos:openfga/openfga#maintainer": {"beth", "charles", "erik"},
    "repos:openfga/openfga#writer": {"beth", "charles", "diane", "erik"},
    "repos:openfga/openfga#triager": {"beth", "charles", "diane", "erik"},
    "repos:openfga/openfga#reader": {"anne", "beth", "charles", "diane", "erik", "gina"},
    "repos:openfga/openfga#owner": set(),  # held by an organization, by no user
    "repos:openfga/docs#admin": set(),  # no owner row: nothing comes from the organization
    "repos:openfga/docs#maintainer": {"anne"},
    "repos:openfga/docs#triager": {"anne"},
    "repos:openfga/docs#reader": {"anne", "charles"},
}


@pytest.mark.parametrize("node", sorted(GITHUB_ANSWERS))
def test_openfga_github_store(node):
    assert holders(judge(GITHUB, GITHUB_TUPLES), node, OCTOCATS) == GITHUB_ANSWERS[node]


def test_keto_v07_denies_what_the_github_rewrites_carry():
    v07 = reference.Reference([row(s) for s in GITHUB_TUPLES])
    readers = {u for u in OCTOCATS if v07.allowed("repos", "openfga/openfga", "reader", u)}
    assert readers == {"gina"}


# -- one case each -----------------------------------------------------------------

def test_intersection():
    config = schema(docs={"publish": {"intersection": [computed("editor"), computed("legal")]}})
    ref = judge(config, ["docs:a#editor@ann", "docs:a#editor@bo", "docs:a#legal@bo",
                         "docs:a#legal@cy", "docs:a#publish@cy"])
    assert holders(ref, "docs:a#publish", ["ann", "bo", "cy"]) == {"bo"}  # no ``this``: cy's row is ignored


def test_exclusion():
    config = schema(docs={"viewer": {"exclusion": {"base": union(THIS, computed("editor")),
                                                   "subtract": computed("banned")}}},
                    groups={})
    ref = judge(config, ["docs:a#viewer@ann", "docs:a#editor@bo", "docs:a#viewer@groups:g#member",
                         "groups:g#member@cy", "groups:g#member@di",
                         "docs:a#banned@bo", "docs:a#banned@groups:h#member", "groups:h#member@di"])
    assert holders(ref, "docs:a#viewer", ["ann", "bo", "cy", "di", "ed"]) == {"ann", "cy"}


def test_a_membership_cycle_terminates_and_grants_what_it_holds():
    ref = judge(schema(groups={}), ["groups:a#member@groups:b#member", "groups:b#member@groups:c#member",
                                    "groups:c#member@groups:a#member", "groups:c#member@zed"])
    for group in "abc":
        assert holders(ref, f"groups:{group}#member", ["zed", "yan"]) == {"zed"}


def test_a_denial_cut_by_a_cycle_is_not_remembered():
    """``q`` asks for ``x`` and ``y``; ``x`` is met first through ``y``'s own
    path (y -> x -> y: cut), where it is denied only for the cut, and then
    again beside it: an evaluator that kept that denial would deny ``q``."""
    config = schema(n={"q": {"intersection": [computed("y"), computed("x")]},
                       "x": union(computed("y"), THIS),
                       "y": union(computed("x"), THIS)})
    ref = judge(config, ["n:o#y@ann", "n:o#x@bo"])
    assert holders(ref, "n:o#q", ["ann", "bo", "cy"]) == {"ann", "bo"}


NEGATIVE = {"clean": {"exclusion": {"base": THIS, "subtract": computed("flagged")}}}


def test_a_cycle_through_a_subtract_is_refused_with_the_cycle():
    by_schema = schema(n=dict(NEGATIVE, flagged=union(THIS, computed("clean"))))
    with pytest.raises(rewrites.SchemaError, match="n#clean -> n#flagged -> n#clean"):
        judge(by_schema, [])
    # the same through the data: a ``flagged`` row holds a ``clean`` subject set
    with pytest.raises(rewrites.SchemaError, match="n#clean -> n#flagged -> m#via -> n#clean"):
        judge(schema(n=NEGATIVE, m={}), ["n:o#flagged@m:p#via", "m:p#via@n:o2#clean"])
    # and not without that row
    ref = judge(schema(n=NEGATIVE, m={}), ["n:o#flagged@m:p#via", "m:p#via@ann", "n:o#clean@ann",
                                           "n:o#clean@bo"])
    assert holders(ref, "n:o#clean", ["ann", "bo"]) == {"bo"}


@pytest.mark.parametrize("expr", [
    {}, {"this": {}, "union": []}, {"this": 1}, {"computed_userset": 3}, {"union": []},
    {"tuple_to_userset": {"tupleset": "parent"}}, {"exclusion": {"base": THIS}},
    {"union": [THIS, {"maybe": {}}]}, "viewer",
], ids=lambda e: str(e)[:40])
def test_a_malformed_expression_names_its_relation(expr):
    with pytest.raises(rewrites.SchemaError, match="docs#viewer"):
        judge(schema(docs={"viewer": expr}), [])


# -- no rewrites: Keto v0.7's Check ------------------------------------------------

BULK_CELLS = ["rbac-groups.bulk", "github-org.bulk", "drive-docs.bulk", "nested-groups.bulk"]


@pytest.mark.parametrize("cell_name, seed", itertools.product(BULK_CELLS, (36, 2_600_000_037)))
def test_without_rewrites_it_is_keto_v07(cell_name, seed):
    """Every query of one pool of each generator at 20k tuples, unbounded and
    cut at the control's depth."""
    cell = run.Cell(cell_name, tuples=20_000)
    ctx = cell.inputs(seed, 3.0)
    queries = sorted(set(control.requests_of(cell, ctx)[0]))
    assert len(queries) > 10_000
    v07 = reference.Reference(ctx.graph.rows)
    ref = rewrites.Reference(ctx.graph.rows, cell.config)
    depth = int(cell.config["control_max_depth"])
    for q in queries:
        assert ref.allowed(*q) == v07.allowed(*q), q
    for q in queries[::16]:
        assert ref.allowed(*q, max_depth=depth) == v07.allowed(*q, max_depth=depth), q


# -- the equations' least solution, by iteration -----------------------------------

def random_case(rng):
    """A schema without exclusion (so the least solution is what iteration
    from nothing reaches) and rows full of cycles and diamonds."""
    relations, objects, users = ["r0", "r1", "r2", "r3"], ["a", "b", "c", "d"], ["u0", "u1", "u2"]

    def expr(depth):
        kind = rng.choice(["this", "cu", "ttu"] if depth == 0 else
                          ["this", "cu", "ttu", "union", "union", "intersection"])
        if kind == "this":
            return THIS
        if kind == "cu":
            return computed(rng.choice(relations))
        if kind == "ttu":
            return from_(rng.choice(relations), rng.choice(relations))
        return {kind: [expr(depth - 1) for _ in range(rng.choice((2, 3)))]}

    config = schema(n={r: expr(2) for r in relations if rng.random() < 0.75})
    rows = []
    for _ in range(rng.randrange(6, 22)):
        obj, rel = rng.choice(objects), rng.choice(relations)
        if rng.random() < 0.4:
            rows.append(("n", obj, rel, rng.choice(users), None, None, None))
        else:
            rows.append(("n", obj, rel, None, "n", rng.choice(objects), rng.choice(relations)))
    nodes = [("n", o, r) for o in objects for r in relations]
    return config, rows, nodes, users


def least_solution(config, rows, nodes, user):
    """The docstring's equations, applied to every node until nothing changes."""
    exprs = {("n", r): e for r, e in config["namespaces"][0]["config"]["relations"].items()}
    ids = {(ns, o, r) for ns, o, r, sid, *_ in rows if sid == user}
    sets = {}
    for ns, o, r, sid, sns, sobj, srel in rows:
        if sid is None:
            sets.setdefault((ns, o, r), []).append((sns, sobj, srel))
    allow = {n: False for n in nodes}

    def ev(e, n):
        (op, arg), = e.items()
        if op == "this":
            return n in ids or any(allow[s] for s in sets.get(n, ()))
        if op == "computed_userset":
            return allow[(n[0], n[1], arg)]
        if op == "tuple_to_userset":
            return any(allow[(ns2, o2, arg["computed_userset"])]
                       for ns2, o2, _ in sets.get((n[0], n[1], arg["tupleset"]), ()))
        return (any if op == "union" else all)(ev(c, n) for c in arg)

    changed = True
    while changed:
        changed = False
        for n in nodes:
            if not allow[n] and ev(exprs.get((n[0], n[2]), THIS), n):
                allow[n] = changed = True
    return allow


@pytest.mark.parametrize("block", range(4))
def test_the_descent_finds_the_least_solution(block):
    rng = random.Random(3600 + block)
    for _ in range(150):
        config, rows, nodes, users = random_case(rng)
        ref = rewrites.Reference(rows, config)
        for user in users:
            want = least_solution(config, rows, nodes, user)
            got = {n: ref.allowed(*n, user) for n in nodes}
            assert got == want, (config, rows, user)
