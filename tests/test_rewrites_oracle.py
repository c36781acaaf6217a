"""The CPU oracle under userset rewrites (keto_tpu/check/engine.py,
keto_tpu/expand/engine.py) against the plain reference a benchmark
configuration names as its judge, ``benchmarks/references/rewrites.py``:
the hand-written answers of Zanzibar's Figure 1 and OpenFGA's ``github``
store, and random schemas and graphs with cycles, all six operators."""

import pytest

from keto_tpu import namespace as namespace_pkg
from keto_tpu.check import CheckEngine
from keto_tpu.expand.engine import ExpandEngine
from keto_tpu.expand.tree import EXCLUSION, INTERSECTION, LEAF, UNION
from keto_tpu.namespace.rewrites import RewriteSchema, SchemaError
from keto_tpu.relationtuple import RelationTuple, SubjectID, SubjectSet

from rewrites_util import (
    FIGURE_1, FIGURE_1_ANSWERS, FIGURE_1_ROWS, GITHUB, GITHUB_ANSWERS, GITHUB_ROWS, OCTOCATS,
    PEOPLE, THIS, computed, every_query, from_, load_reference, manager_of, node_of,
    random_world, row, schema, seeded, store_of, union,
)

reference = load_reference()


def holders(engine, node: str, users):
    ns, obj, rel = node_of(node)
    return {u for u in users if engine.subject_is_allowed(
        RelationTuple(namespace=ns, object=obj, relation=rel, subject=SubjectID(u)))}


@pytest.mark.parametrize("node", sorted(FIGURE_1_ANSWERS))
def test_zanzibar_figure_1(node):
    engine = CheckEngine(store_of(FIGURE_1, FIGURE_1_ROWS))
    assert holders(engine, node, PEOPLE) == FIGURE_1_ANSWERS[node]


@pytest.mark.parametrize("node", sorted(GITHUB_ANSWERS))
def test_openfga_github_store(node):
    engine = CheckEngine(store_of(GITHUB, GITHUB_ROWS))
    assert holders(engine, node, OCTOCATS) == GITHUB_ANSWERS[node]


@pytest.mark.parametrize("page_size", [1, 2])
def test_pages_do_not_change_the_answer(page_size):
    engine = CheckEngine(store_of(GITHUB, GITHUB_ROWS), page_size=page_size)
    for node, want in GITHUB_ANSWERS.items():
        assert holders(engine, node, OCTOCATS) == want, node


@pytest.mark.parametrize("seed", range(12))
def test_random_schemas_all_six_operators(seed):
    """25 worlds a seed, every query of each: 300 schemas in all."""
    rng = seeded(7100 + seed)
    for _ in range(25):
        config, rows = random_world(rng, gated=True)
        judge = reference.Reference(rows, config)
        engine = CheckEngine(store_of(config, rows))
        for ns, obj, rel, u in every_query():
            got = engine.subject_is_allowed(
                RelationTuple(namespace=ns, object=obj, relation=rel, subject=SubjectID(u)))
            assert got == judge.allowed(ns, obj, rel, u), (config, rows, (ns, obj, rel, u))


def test_without_relations_the_engine_runs_the_reference_code():
    """No rewrite anywhere: the legacy traversal answers, visited set and all."""
    config = schema(doc={}, group={})
    rows = [row("doc:a#view@group:g#member"), row("group:g#member@group:g#member"),
            row("group:g#member@ann")]
    engine = CheckEngine(store_of(config, rows))
    calls = []
    engine._check_one_indirection_further = (
        lambda *a, _f=engine._check_one_indirection_further, **k: calls.append(1) or _f(*a, **k))
    assert engine.subject_is_allowed(
        RelationTuple(namespace="doc", object="a", relation="view", subject=SubjectID("ann")))
    assert calls


def test_a_subject_set_is_found_at_a_computed_userset_step():
    engine = CheckEngine(store_of(GITHUB, GITHUB_ROWS))
    ask = lambda rel, s: engine.subject_is_allowed(RelationTuple(
        namespace="repos", object="openfga/openfga", relation=rel, subject=s))
    assert ask("reader", SubjectSet("repos", "openfga/openfga", "admin"))
    assert ask("admin", SubjectSet("orgs", "openfga", "repo_admin"))
    assert ask("admin", SubjectSet("teams", "backend", "member"))
    assert not ask("admin", SubjectSet("repos", "openfga/openfga", "reader"))


def test_unknown_namespace_is_denied():
    engine = CheckEngine(store_of(GITHUB, GITHUB_ROWS))
    assert not engine.subject_is_allowed(RelationTuple(
        namespace="nowhere", object="x", relation="admin", subject=SubjectID("erik")))


# -- the schema: validation, fingerprint, the one refusal ------------------------

def _ns(relations, name="doc", id=0):
    return namespace_pkg.Namespace(id=id, name=name, config={"relations": relations})


@pytest.mark.parametrize("bad", [
    {"viewer": {"this": {}, "union": []}},
    {"viewer": {"this": 1}},
    {"viewer": {"computed_userset": 3}},
    {"viewer": {"tuple_to_userset": {"tupleset": "parent"}}},
    {"viewer": {"union": []}},
    {"viewer": {"exclusion": {"base": THIS}}},
    {"viewer": {"nothing": {}}},
    {"viewer": "this"},
])
def test_a_malformed_schema_is_a_configuration_error_naming_the_relation(bad):
    with pytest.raises(SchemaError, match="doc#viewer"):
        namespace_pkg.MemoryManager([_ns(bad)])


def test_a_cycle_through_a_subtract_is_refused_when_the_namespaces_load():
    bad = {"viewer": {"exclusion": {"base": THIS, "subtract": computed("banned")}},
           "banned": computed("viewer")}
    with pytest.raises(SchemaError, match="doc#viewer.*subtract"):
        namespace_pkg.MemoryManager([_ns(bad)])


def test_the_fingerprint_is_stable_and_tells_schemas_apart():
    a = RewriteSchema([_ns({"v": union(THIS, computed("e")), "e": THIS})])
    b = RewriteSchema([_ns({"e": THIS, "v": union(THIS, computed("e"))})])
    c = RewriteSchema([_ns({"v": union(THIS, computed("o"))})])
    assert a.fingerprint == b.fingerprint != c.fingerprint
    assert RewriteSchema([_ns({"e": THIS})]).fingerprint == "" == RewriteSchema([]).fingerprint
    assert not RewriteSchema([_ns({"e": THIS})])


def test_a_reload_with_a_bad_schema_keeps_the_last_good_one(tmp_path):
    import json
    import os

    from keto_tpu.config.provider import NamespaceWatcher

    f = tmp_path / "ns.json"
    f.write_text(json.dumps([{"id": 0, "name": "doc", "config": {"relations": {"v": computed("e")}}}]))
    w = NamespaceWatcher(f"file://{f}")
    good = w.manager().rewrites.fingerprint
    f.write_text(json.dumps([{"id": 0, "name": "doc", "config": {"relations": {"v": {"oops": 1}}}}]))
    os.utime(f, ns=(1, 1))
    assert not w.check_reload()
    assert w.manager().rewrites.fingerprint == good


# -- Expand ------------------------------------------------------------------------

def _shape(tree):
    if tree is None:
        return None
    return (tree.type, str(tree.subject), sorted(_shape(c) for c in tree.children))


def test_expand_emits_a_computed_userset_as_a_child_subject_set():
    engine = ExpandEngine(store_of(GITHUB, GITHUB_ROWS))
    tree = engine.build_tree(SubjectSet("repos", "openfga/docs", "triager"), 4)
    assert tree.type == UNION
    kids = {str(c.subject): c for c in tree.children}
    assert set(kids) == {"repos:openfga/docs#writer"}
    writer = kids["repos:openfga/docs#writer"]
    assert {str(c.subject) for c in writer.children} == {"repos:openfga/docs#maintainer"}
    maint = writer.children[0]
    assert {str(c.subject) for c in maint.children} == {"anne", "repos:openfga/docs#admin"}


def test_expand_follows_a_tupleset_to_the_object_behind_it():
    engine = ExpandEngine(store_of(GITHUB, GITHUB_ROWS))
    tree = engine.build_tree(SubjectSet("repos", "openfga/openfga", "admin"), 3)
    assert {str(c.subject) for c in tree.children} == {
        "teams:core#member", "orgs:openfga#repo_admin"}
    org = next(c for c in tree.children if str(c.subject) == "orgs:openfga#repo_admin")
    assert {str(c.subject) for c in org.children} == {"orgs:openfga#owner"}


def test_expand_emits_intersection_and_exclusion_nodes():
    config = schema(doc={
        "viewer": {"intersection": [THIS, computed("member")]},
        "reader": {"exclusion": {"base": computed("viewer"), "subtract": computed("banned")}},
    })
    rows = [row("doc:a#viewer@ann"), row("doc:a#member@ann"), row("doc:a#banned@bob")]
    engine = ExpandEngine(store_of(config, rows))
    viewer = engine.build_tree(SubjectSet("doc", "a", "viewer"), 3)
    assert viewer.type == INTERSECTION and len(viewer.children) == 2
    assert {c.type for c in viewer.children} == {UNION}
    reader = engine.build_tree(SubjectSet("doc", "a", "reader"), 4)
    assert reader.type == EXCLUSION
    base, subtract = reader.children
    assert str(base.subject) == "doc:a#viewer" and base.type == INTERSECTION
    assert str(subtract.subject) == "doc:a#banned"
    assert [str(c.subject) for c in subtract.children] == ["bob"]
    assert subtract.children[0].type == LEAF


def test_expand_without_relations_is_the_reference_tree():
    config = schema(doc={}, group={})
    rows = [row("doc:a#view@group:g#member"), row("group:g#member@ann")]
    with_schema_elsewhere = schema(doc={}, group={}, other={"x": computed("y")})
    plain = ExpandEngine(store_of(config, rows)).build_tree(SubjectSet("doc", "a", "view"), 5)
    mixed = ExpandEngine(store_of(with_schema_elsewhere, rows)).build_tree(
        SubjectSet("doc", "a", "view"), 5)
    assert _shape(plain) == _shape(mixed)
    assert plain.type == UNION and plain.children[0].children[0].type == LEAF
