"""What the rewrite suites share: the two hand-written stores of
``benchmarks/tests/test_rewrites_reference.py`` (Zanzibar's Figure 1 and
OpenFGA's ``github``), a seeded generator of random schemas and graphs with
cycles, and the bridge between a benchmark configuration (names, rows as
7-tuples) and the program (namespaces, ``RelationTuple``s)."""

import importlib.util
import random
import sys
from pathlib import Path

from keto_tpu import namespace as namespace_pkg
from keto_tpu.persistence.memory import MemoryPersister
from keto_tpu.relationtuple import RelationTuple, SubjectID, SubjectSet

ROOT = Path(__file__).resolve().parents[1]
THIS = {"this": {}}


def load_reference():
    """``benchmarks/references/rewrites.py``, the judge, found by path (it
    imports nothing of the program)."""
    name = "benchmarks.references.rewrites"
    if name not in sys.modules:
        spec = importlib.util.spec_from_file_location(
            name, ROOT / "benchmarks" / "references" / "rewrites.py"
        )
        module = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(module)
        sys.modules[name] = module
    return sys.modules[name]


def union(*children):
    return {"union": list(children)}


def computed(relation):
    return {"computed_userset": relation}


def from_(tupleset, relation):
    return {"tuple_to_userset": {"tupleset": tupleset, "computed_userset": relation}}


def schema(**namespaces):
    """A configuration's ``namespaces`` with ids 0.. in the order given."""
    return {"namespaces": [
        {"id": i, "name": name, **({"config": {"relations": relations}} if relations else {})}
        for i, (name, relations) in enumerate(namespaces.items())
    ]}


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


def as_tuple(r) -> RelationTuple:
    ns, obj, rel, sid, sns, sobj, srel = r
    subject = SubjectID(sid) if sid is not None else SubjectSet(sns, sobj, srel)
    return RelationTuple(namespace=ns, object=obj, relation=rel, subject=subject)


def manager_of(config: dict) -> namespace_pkg.MemoryManager:
    return namespace_pkg.MemoryManager(
        namespace_pkg.namespace_from_json(n) for n in config["namespaces"]
    )


def store_of(config: dict, rows) -> MemoryPersister:
    p = MemoryPersister(manager_of(config))
    if rows:
        p.write_relation_tuples(*[as_tuple(r) for r in rows])
    return p


CONCENTRIC = {"editor": union(THIS, computed("owner")),
              "viewer": union(THIS, computed("editor"), from_("parent", "viewer"))}
FIGURE_1 = schema(doc=CONCENTRIC, folder=CONCENTRIC, group={})
FIGURE_1_ROWS = [row(s) for s in (
    "doc:readme#owner@alice",
    "doc:readme#editor@bob",
    "doc:readme#viewer@carol",
    "doc:readme#parent@folder:eng#...",
    "doc:readme#parent@heidi",
    "folder:eng#viewer@dave",
    "folder:eng#parent@folder:root#...",
    "folder:root#owner@erin",
    "folder:root#viewer@group:staff#member",
    "group:staff#member@frank",
    "group:staff#member@group:interns#member",
    "group:interns#member@gina",
    "doc:draft#editor@group:interns#member",
)]
PEOPLE = ["alice", "bob", "carol", "dave", "erin", "frank", "gina", "heidi"]
FIGURE_1_ANSWERS = {
    "doc:readme#owner": {"alice"},
    "doc:readme#editor": {"alice", "bob"},
    "doc:readme#viewer": {"alice", "bob", "carol", "dave", "erin", "frank", "gina"},
    "doc:readme#parent": {"heidi"},
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

GITHUB = schema(
    teams={},
    orgs={"member": union(THIS, computed("owner"))},
    repos={"admin": union(THIS, from_("owner", "repo_admin")),
           "maintainer": union(THIS, computed("admin")),
           "writer": union(THIS, computed("maintainer"), from_("owner", "repo_writer")),
           "triager": union(THIS, computed("writer")),
           "reader": union(THIS, computed("triager"), from_("owner", "repo_reader"))},
)
GITHUB_ROWS = [row(s) for s in (
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
)]
OCTOCATS = ["anne", "beth", "charles", "diane", "erik", "gina", "zoe"]
GITHUB_ANSWERS = {
    "orgs:openfga#owner": {"erik"},
    "orgs:openfga#member": {"anne", "erik"},
    "orgs:openfga#repo_admin": {"erik"},
    "orgs:openfga#repo_reader": {"anne", "erik"},
    "orgs:openfga#repo_writer": set(),
    "teams:core#member": {"beth", "charles"},
    "repos:openfga/openfga#owner": set(),
    "repos:openfga/openfga#admin": {"beth", "charles", "erik"},
    "repos:openfga/openfga#maintainer": {"beth", "charles", "erik"},
    "repos:openfga/openfga#writer": {"beth", "charles", "diane", "erik"},
    "repos:openfga/openfga#triager": {"beth", "charles", "diane", "erik"},
    "repos:openfga/openfga#reader": {"anne", "beth", "charles", "diane", "erik", "gina"},
    "repos:openfga/docs#admin": set(),
    "repos:openfga/docs#maintainer": {"anne"},
    "repos:openfga/docs#triager": {"anne"},
    "repos:openfga/docs#reader": {"anne", "charles"},
}


def node_of(spec: str):
    ns, rest = spec.split(":", 1)
    obj, rel = rest.split("#")
    return ns, obj, rel


# -- random schemas and graphs ---------------------------------------------------

NAMESPACES = ("a", "b", "c")
RELATIONS = ("r0", "r1", "r2", "r3", "t")
OBJECTS = ("o0", "o1", "o2", "o3")
USERS = ("u0", "u1", "u2", "u3", "u4")


def random_expr(rng, depth: int, gated: bool):
    ops = ["this", "computed_userset", "tuple_to_userset"]
    if depth > 0:
        ops += ["union", "union"] + (["intersection", "exclusion"] if gated else [])
    op = rng.choice(ops)
    if op == "this":
        return THIS
    if op == "computed_userset":
        return computed(rng.choice(RELATIONS[:4]))
    if op == "tuple_to_userset":
        return from_("t", rng.choice(RELATIONS[:4]))
    if op == "exclusion":
        return {"exclusion": {"base": random_expr(rng, depth - 1, gated),
                              "subtract": random_expr(rng, depth - 1, gated)}}
    return {op: [random_expr(rng, depth - 1, gated) for _ in range(rng.randint(1, 3))]}


def random_world(rng, gated: bool, n_rows: int = 40):
    """``(config, rows)``: a random schema over three namespaces (every
    operator when ``gated``, the union class otherwise) and a random graph
    with cycles. With ``gated`` the schema is one the reference accepts (no
    cycle through a subtract, by its own test)."""
    reference = load_reference()
    while True:
        relations = {
            ns: {rel: random_expr(rng, 2, gated)
                 for rel in RELATIONS[:4] if rng.random() < 0.6}
            for ns in NAMESPACES
        }
        config = schema(**relations)
        rows = []
        for _ in range(n_rows):
            ns, obj, rel = rng.choice(NAMESPACES), rng.choice(OBJECTS), rng.choice(RELATIONS)
            if rel != "t" and rng.random() < 0.45:
                rows.append((ns, obj, rel, rng.choice(USERS), None, None, None))
            else:
                srel = "..." if rel == "t" else rng.choice(RELATIONS[:4])
                rows.append((ns, obj, rel, None, rng.choice(NAMESPACES), rng.choice(OBJECTS), srel))
        rows = list(dict.fromkeys(rows))
        try:
            reference.Reference(rows, config)
        except reference.SchemaError:
            continue
        return config, rows


def every_query(users=USERS):
    return [(ns, obj, rel, u) for ns in NAMESPACES for obj in OBJECTS
            for rel in RELATIONS for u in users]


def seeded(seed: int) -> random.Random:
    return random.Random(seed)
