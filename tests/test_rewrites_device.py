"""Userset rewrites on the device path (keto_tpu/namespace/rewrites.py): the
union class is compiled into the snapshot's edges at the ingest seam, and
everything downstream of the interner is as it was. The device engine
against the CPU oracle and the plain reference on random union-class
schemas (``batch_check`` and a framed ``/check/batch`` body); writes and
deletes through the overlay against a rebuild; a reload of the namespaces
with another schema, from memory and from the snapshot cache; the gated
class on the device and its patterns through the oracle route, counted
(tests/test_gates_device.py has the rest); List, Expand and explain under a
schema; a store without ``config.relations`` builds a byte-identical
snapshot."""

import json
from urllib.parse import parse_qs, urlparse

import numpy as np
import pytest

from keto_tpu import namespace as namespace_pkg
from keto_tpu.check import CheckEngine
from keto_tpu.check.frame import QueryBatch, QueryFrame
from keto_tpu.check.tpu_engine import TpuCheckEngine
from keto_tpu.expand.engine import ExpandEngine
from keto_tpu.expand.tpu_engine import SnapshotExpandEngine
from keto_tpu.graph.native import FrameTable
from keto_tpu.graph.stream_build import full_build
from keto_tpu.list.tpu_engine import SnapshotListEngine
from keto_tpu.namespace.rewrites import GATED, REWRITTEN, SchemaError
from keto_tpu.persistence.memory import MemoryPersister
from keto_tpu.relationtuple import RelationTuple, SubjectID, SubjectSet
from keto_tpu.x.errors import ErrBadRequest

from rewrites_util import (
    FIGURE_1, FIGURE_1_ANSWERS, FIGURE_1_ROWS, GITHUB, GITHUB_ANSWERS, GITHUB_ROWS, NAMESPACES,
    OBJECTS, OCTOCATS, PEOPLE, RELATIONS, THIS, as_tuple, computed, every_query, from_,
    load_reference, manager_of, node_of, random_world, row, schema, seeded, store_of, union,
)

reference = load_reference()


def quiet_engine(p, nm=None, **kw):
    kw.setdefault("compact_after_s", 3600.0)
    kw.setdefault("overlay_edge_budget", 1 << 20)
    return TpuCheckEngine(p, nm if nm is not None else p.namespaces, **kw)


def ask(ns, obj, rel, user):
    return RelationTuple(namespace=ns, object=obj, relation=rel, subject=SubjectID(user))


def counters(engine):
    return engine.maintenance.snapshot()


# -- the union class against the oracle and the reference --------------------------

@pytest.mark.parametrize("config, rows, answers, users", [
    (FIGURE_1, FIGURE_1_ROWS, FIGURE_1_ANSWERS, PEOPLE),
    (GITHUB, GITHUB_ROWS, GITHUB_ANSWERS, OCTOCATS),
], ids=["figure_1", "github"])
def test_hand_written_answers_on_the_device(config, rows, answers, users):
    p = store_of(config, rows)
    engine = quiet_engine(p)
    try:
        qs = [ask(*node_of(n), u) for n in answers for u in users]
        got = engine.batch_check(qs)
        assert list(got) == [u in answers[n] for n in answers for u in users]
        c = counters(engine)
        assert c.get("fallback_checks", 0) == 0
        assert c["rewrite_checks_rewritten"] + c["rewrite_checks_plain"] == len(qs)
        assert c["rewrite_route_device"] == c["rewrite_checks_rewritten"] > 0
        assert c.get("rewrite_route_oracle", 0) == 0
        assert engine.route_slice_counts().get("cpu", 0) == 0
    finally:
        engine.close()


def subject_set_queries(rng, n):
    return [RelationTuple(
        namespace=rng.choice(NAMESPACES), object=rng.choice(OBJECTS), relation=rng.choice(RELATIONS),
        subject=SubjectSet(rng.choice(NAMESPACES), rng.choice(OBJECTS), rng.choice(RELATIONS[:4])),
    ) for _ in range(n)]


@pytest.mark.parametrize("seed", range(8))
def test_random_union_class_schemas_device_equals_oracle_equals_reference(seed):
    rng = seeded(7300 + seed)
    for _ in range(6):
        config, rows = random_world(rng, gated=False)
        judge = reference.Reference(rows, config)
        p = store_of(config, rows)
        oracle = CheckEngine(p)
        engine = quiet_engine(p)
        try:
            qs = every_query()
            tuples = [ask(*q) for q in qs]
            got = list(engine.batch_check(tuples))
            assert got == [judge.allowed(*q) for q in qs], (config, rows)
            assert got == [oracle.subject_is_allowed(t) for t in tuples]
            sets = subject_set_queries(rng, 120)
            assert list(engine.batch_check(sets)) == [oracle.subject_is_allowed(t) for t in sets], (
                config, rows)
            assert counters(engine).get("rewrite_route_oracle", 0) == 0
        finally:
            engine.close()


class _OneBatch:
    def __init__(self, batch):
        self._batch = batch

    def take(self, cap):
        batch, self._batch = self._batch, []
        return batch


@pytest.mark.parametrize("seed", range(4))
def test_a_framed_body_resolves_derived_nodes_at_the_door(seed):
    """Nodes that hold no stored row (a repo's ``triager``) resolve through
    the same tables as any other, on the thread that framed the body."""
    rng = seeded(7400 + seed)
    config, rows = random_world(rng, gated=False) if seed else (GITHUB, GITHUB_ROWS)
    manager = manager_of(config)
    table = FrameTable.build(manager)
    if table is None:
        pytest.skip("native library not built")
    p = MemoryPersister(manager)
    p.write_relation_tuples(*[as_tuple(r) for r in rows])
    judge = reference.Reference(rows, config)
    engine = quiet_engine(p, manager)
    try:
        if seed:
            qs = every_query()
        else:
            qs = [(*node_of(n), u) for n in GITHUB_ANSWERS for u in OCTOCATS]
        body = json.dumps({"tuples": [ask(*q).to_json() for q in qs]}).encode()
        got = table.frame(body, 65536)
        assert not isinstance(got, str), got
        frame = QueryFrame(*got, body, manager)
        snap = engine.snapshot()
        frame.resolve_at_door(snap)
        assert frame.door is not None and frame.door[0] is snap.interned
        gen, _ = engine.batch_check_stream_with_token(
            _OneBatch(QueryBatch([(frame, 0, len(qs))])), ordered=True)
        served = np.concatenate(list(gen)).tolist()
        assert served == [judge.allowed(*q) for q in qs]
        c = counters(engine)
        assert c.get("resolve_tuples_door", 0) == len(qs)
        assert c.get("resolve_tuples_thread", 0) == 0
    finally:
        engine.close()


# -- the gated class: expanded on the device; a pattern over it is the oracle's --------

GATED_SCHEMA = schema(
    doc={"viewer": {"intersection": [THIS, computed("member")]},
         "reader": {"exclusion": {"base": computed("viewer"), "subtract": computed("banned")}},
         "editor": union(THIS, computed("owner"))},
    group={},
)
GATED_ROWS = [row(s) for s in (
    "doc:a#viewer@ann", "doc:a#member@ann", "doc:a#viewer@bob", "doc:a#banned@cat",
    "doc:a#member@cat", "doc:a#viewer@cat", "doc:a#owner@dan", "doc:b#member@group:g#member",
    "doc:b#viewer@eve", "group:g#member@eve", "doc:c#page@doc:a#reader",
)]


@pytest.fixture(scope="module")
def gated_run():
    """One engine and one batch for the cases below: every (node, user) of
    ``GATED_SCHEMA``'s store asked once, and the counters after it."""
    p = store_of(GATED_SCHEMA, GATED_ROWS)
    engine = quiet_engine(p)
    try:
        users = ["ann", "bob", "cat", "dan", "eve", "zed"]
        nodes = [("doc", o, r) for o in ("a", "b", "c", "nowhere")
                 for r in ("viewer", "reader", "editor", "owner", "member", "page")]
        qs = [(*n, u) for n in nodes for u in users]
        got = list(engine.batch_check([ask(*q) for q in qs]))
        plan = engine.snapshot().rewrites
        gated_nodes = [n for n in nodes if plan.relation_flags(0, n[2]) & GATED]
        yield users, qs, got, counters(engine), gated_nodes, engine.route_slice_counts()
    finally:
        engine.close()


@pytest.mark.parametrize("what", [
    "answers_are_the_references", "gated_checks_are_served_by_the_device",
    "the_oracle_is_not_asked", "the_union_class_is_not_gated",
])
def test_a_gated_closure_is_expanded_on_the_device_and_is_counted(gated_run, what):
    """Until PR 43 every check of a gated closure was one descent of the CPU
    oracle; now it is a few device positions and a combine
    (tests/test_gates_device.py holds the route to more)."""
    users, qs, got, c, gated_nodes, routes = gated_run
    if what == "answers_are_the_references":
        judge = reference.Reference(GATED_ROWS, GATED_SCHEMA)
        assert got == [judge.allowed(*q) for q in qs]
        assert judge.allowed("doc", "a", "reader", "ann") and not judge.allowed("doc", "a", "reader", "cat")
    elif what == "gated_checks_are_served_by_the_device":
        assert {n[2] for n in gated_nodes} == {"viewer", "reader", "page"}
        # every one of them, the ones that found no start included (a gated
        # relation on an object no row mentions is denied at resolve)
        assert c["gate_checks_device"] == len(gated_nodes) * len(users)
        assert c["gate_positions_operand"] > c["gate_checks_device"]
    elif what == "the_oracle_is_not_asked":
        assert c.get("rewrite_route_oracle", 0) == c.get("gate_checks_oracle", 0) == 0
        assert not any(k.startswith("rewrite_oracle_") for k in c)
        assert c.get("fallback_checks", 0) == 0
        assert routes.get("cpu", 0) == 0
    else:
        # editor (rewritten, union class) on the three objects that rows
        # name, and the gated checks that found a start
        editor = 3 * len(users)
        assert c["rewrite_route_device"] == c["rewrite_checks_rewritten"]
        assert c["rewrite_checks_rewritten"] >= editor
        assert c["rewrite_checks_rewritten"] + c["rewrite_checks_plain"] == len(qs)


def test_a_wildcard_pattern_over_a_gated_closure_goes_to_the_oracle_too():
    """A pattern's starts ride in ``multi``, not in the start row: one that
    names a gated relation, or matches a node of one, is not expanded (its
    starts are many) and must not ride the device over the gates' operands."""
    p = store_of(GATED_SCHEMA, GATED_ROWS)
    oracle = CheckEngine(p)
    engine = quiet_engine(p)
    try:
        users = ["ann", "bob", "cat", "dan", "eve", "zed"]
        gated = [("doc", "", "viewer"), ("doc", "", "reader"), ("doc", "a", ""), ("doc", "", "")]
        plain = [("doc", "", "editor"), ("doc", "", "owner"), ("group", "", "member")]
        qs = [ask(*n, u) for n in gated + plain for u in users]
        got = list(engine.batch_check(qs))
        assert got == [oracle.subject_is_allowed(t) for t in qs]
        # bob holds a ``viewer`` row and is no member: the device, walking the
        # gated relation's stored rows, would have granted him
        bob = qs.index(ask("doc", "", "viewer", "bob"))
        assert not got[bob] and got[qs.index(ask("doc", "", "viewer", "ann"))]
        c = counters(engine)
        assert c["rewrite_oracle_gated_pattern"] == len(gated) * len(users)
        assert c["rewrite_route_oracle"] == c["rewrite_oracle_gated_pattern"]
        assert c["rewrite_route_device"] == len(users)  # doc::editor; the others are plain
        assert c.get("fallback_checks", 0) == 0
    finally:
        engine.close()


@pytest.mark.parametrize("seed", range(6))
def test_random_schemas_with_all_six_operators_through_the_engine(seed):
    rng = seeded(7500 + seed)
    for _ in range(5):
        config, rows = random_world(rng, gated=True)
        judge = reference.Reference(rows, config)
        p = store_of(config, rows)
        engine = quiet_engine(p)
        try:
            qs = every_query()
            got = list(engine.batch_check([ask(*q) for q in qs]))
            assert got == [judge.allowed(*q) for q in qs], (config, rows)
        finally:
            engine.close()


def test_rows_that_close_a_cycle_through_a_subtract_fail_the_build_by_name():
    config = schema(doc={"viewer": {"exclusion": {"base": THIS, "subtract": from_("parent", "viewer")}}})
    manager_of(config)  # the schema alone is fine
    p = store_of(config, [row("doc:a#parent@doc:a#..."), row("doc:a#viewer@ann")])
    with pytest.raises(SchemaError, match="doc#viewer"):
        full_build(p, rewrites=p.namespaces().rewrites)


# -- writes and deletes through the overlay, against a rebuild ----------------------

def rebuilt_answers(p, tuples):
    fresh = quiet_engine(p)
    try:
        return list(fresh.batch_check(tuples))
    finally:
        fresh.close()


def github_universe():
    repos = ["openfga/openfga", "openfga/docs", "acme/site"]
    roles = ["owner", "admin", "maintainer", "writer", "triager", "reader"]
    users = OCTOCATS + ["hugo", "ines"]
    qs = [ask("repos", r, role, u) for r in repos for role in roles for u in users]
    qs += [ask("orgs", o, rel, u) for o in ("openfga", "acme")
           for rel in ("owner", "member", "repo_admin", "repo_reader", "repo_writer") for u in users]
    return qs


@pytest.mark.parametrize("step", [
    "an_owner_row_added",
    "the_last_of_two_rows_stating_one_derived_edge_deleted",
    "a_first_row_on_a_new_object",
    "an_org_owner_added_and_removed",
    "a_stored_row_on_a_computed_edge_deleted",
    "a_stored_row_on_a_tupleset_edge_deleted",
    "the_last_owner_row_deleted_under_a_stored_row_on_its_edge",
    "a_stored_row_on_a_derived_edge_written_then_both_deleted",
])
def test_overlay_writes_against_a_rebuild(step):
    rows = list(GITHUB_ROWS)
    if step.startswith("the_last_of_two"):
        # two rows that differ in the subject's relation state the same edges
        rows.append(row("repos:openfga/openfga#owner@orgs:openfga#member"))
    # Keto's store is type-free: a row may state the very edge the schema
    # derives (an operator moving off flattened rows holds such rows). The
    # graph holds the edge once; it goes with the last that states it
    if step == "a_stored_row_on_a_computed_edge_deleted":
        rows.append(row("repos:openfga/openfga#maintainer@repos:openfga/openfga#admin"))
    elif step in ("a_stored_row_on_a_tupleset_edge_deleted",
                  "the_last_owner_row_deleted_under_a_stored_row_on_its_edge"):
        rows.append(row("repos:openfga/openfga#admin@orgs:openfga#repo_admin"))
    p = store_of(GITHUB, rows)
    oracle = CheckEngine(p)
    engine = quiet_engine(p)
    qs = github_universe()
    try:
        assert list(engine.batch_check(qs)) == [oracle.subject_is_allowed(t) for t in qs]
        if step == "an_owner_row_added":
            writes = [[("ins", "repos:openfga/docs#owner@orgs:openfga#...")]]
        elif step.startswith("the_last_of_two"):
            writes = [[("del", "repos:openfga/openfga#owner@orgs:openfga#...")],
                      [("del", "repos:openfga/openfga#owner@orgs:openfga#member")]]
        elif step == "a_stored_row_on_a_computed_edge_deleted":
            writes = [[("del", "repos:openfga/openfga#maintainer@repos:openfga/openfga#admin")]]
        elif step == "a_stored_row_on_a_tupleset_edge_deleted":
            writes = [[("del", "repos:openfga/openfga#admin@orgs:openfga#repo_admin")],
                      [("del", "repos:openfga/openfga#owner@orgs:openfga#...")]]
        elif step.startswith("the_last_owner_row"):
            writes = [[("del", "repos:openfga/openfga#owner@orgs:openfga#...")],
                      [("del", "repos:openfga/openfga#admin@orgs:openfga#repo_admin")]]
        elif step.startswith("a_stored_row_on_a_derived_edge_written"):
            writes = [[("ins", "repos:openfga/openfga#admin@orgs:openfga#repo_admin")],
                      [("del", "repos:openfga/openfga#owner@orgs:openfga#...")],
                      [("ins", "repos:openfga/openfga#owner@orgs:openfga#..."),
                       ("del", "repos:openfga/openfga#admin@orgs:openfga#repo_admin")],
                      [("del", "repos:openfga/openfga#owner@orgs:openfga#...")]]
        elif step == "a_first_row_on_a_new_object":
            writes = [[("ins", "orgs:acme#owner@hugo"), ("ins", "orgs:acme#repo_admin@orgs:acme#owner")],
                      [("ins", "repos:acme/site#owner@orgs:acme#...")],
                      [("ins", "repos:acme/site#triager@ines")]]
        else:
            writes = [[("ins", "orgs:openfga#owner@hugo")], [("del", "orgs:openfga#owner@hugo")],
                      [("del", "orgs:openfga#owner@erik")]]
        seen = []
        for batch in writes:
            for kind, spec in batch:
                t = as_tuple(row(spec))
                (p.write_relation_tuples if kind == "ins" else p.delete_relation_tuples)(t)
            got = list(engine.batch_check(qs))
            want = [oracle.subject_is_allowed(t) for t in qs]
            assert got == want, (step, batch)
            assert got == rebuilt_answers(p, qs)
            seen.append(got)
        erik_admin = qs.index(ask("repos", "openfga/openfga", "admin", "erik"))
        erik_maintainer = qs.index(ask("repos", "openfga/openfga", "maintainer", "erik"))
        if step == "a_stored_row_on_a_computed_edge_deleted":
            assert seen[0][erik_maintainer]  # admins keep maintainer: the schema says so
        elif step == "a_stored_row_on_a_tupleset_edge_deleted":
            assert seen[0][erik_admin] and not seen[1][erik_admin]
        elif step.startswith("the_last_owner_row"):
            assert seen[0][erik_admin] and not seen[1][erik_admin]  # the stored row held the edge
        elif step.startswith("a_stored_row_on_a_derived_edge_written"):
            assert [s[erik_admin] for s in seen] == [True, True, True, False]
        if "stored_row" in step:
            assert counters(engine).get("delta_applies", 0) >= len(writes)  # no rebuild hid it
        if step.startswith("the_last_of_two"):
            # the first delete retires nothing (the other row still states the
            # edges), the second retires them: erik loses the repo
            erik = qs.index(ask("repos", "openfga/openfga", "admin", "erik"))
            assert seen[0][erik] and not seen[1][erik]
            assert counters(engine).get("delta_applies", 0) >= 1
        assert counters(engine).get("fallback_checks", 0) == 0
    finally:
        engine.close()


@pytest.mark.parametrize("seed", range(4))
def test_random_writes_and_deletes_against_a_rebuild(seed):
    rng = seeded(7600 + seed)
    config, rows = random_world(rng, gated=bool(seed % 2))
    p = store_of(config, rows)
    engine = quiet_engine(p)
    qs = [ask(*q) for q in every_query()]
    live = list(rows)
    try:
        engine.batch_check(qs[:8])
        for _ in range(10):
            if live and rng.random() < 0.45:
                r = live.pop(rng.randrange(len(live)))
                p.delete_relation_tuples(as_tuple(r))
            else:
                _, more = random_world(rng, gated=False, n_rows=1)
                try:
                    reference.Reference(live + more, config)
                except reference.SchemaError:
                    continue
                live += more
                p.write_relation_tuples(*[as_tuple(r) for r in more])
            judge = reference.Reference(live, config)
            got = list(engine.batch_check(qs))
            assert got == [judge.allowed(*q) for q in every_query()], (config, live)
    finally:
        engine.close()


COINCIDING = [
    "repos:openfga/openfga#owner@orgs:openfga#...",
    "repos:openfga/openfga#owner@orgs:openfga#member",
    "repos:openfga/openfga#admin@orgs:openfga#repo_admin",
    "repos:openfga/openfga#reader@orgs:openfga#repo_reader",
    "repos:openfga/openfga#maintainer@repos:openfga/openfga#admin",
    "repos:openfga/openfga#triager@repos:openfga/openfga#writer",
    "orgs:openfga#member@orgs:openfga#owner",
    "orgs:openfga#repo_admin@orgs:openfga#owner",
]


@pytest.mark.parametrize("seed", range(4))
def test_rows_that_coincide_with_derived_edges_come_and_go_against_the_oracle(seed):
    """Every write toggles a row that states, or derives, an edge another
    may state too; one or two a delta. The overlay never retires an edge
    something still states, and never keeps one nothing does."""
    rng = seeded(7700 + seed)
    p = store_of(GITHUB, GITHUB_ROWS)
    oracle = CheckEngine(p)
    engine = quiet_engine(p)
    qs = github_universe()
    held = {s for s in COINCIDING if row(s) in GITHUB_ROWS}
    try:
        engine.batch_check(qs[:8])
        for _ in range(24):
            for spec in rng.sample(COINCIDING, rng.choice((1, 1, 2))):
                t = as_tuple(row(spec))
                (p.delete_relation_tuples if spec in held else p.write_relation_tuples)(t)
                held ^= {spec}
            assert list(engine.batch_check(qs)) == [oracle.subject_is_allowed(t) for t in qs], held
        stored_too = engine.snapshot().rewrites.stored_too
        assert stored_too == {_key7(p, s) for s in held if _derivable(s)}
        assert counters(engine).get("delta_applies", 0) >= 12
    finally:
        engine.close()


def _derivable(spec):
    return spec.split("#")[1].split("@")[0] in ("admin", "reader", "maintainer", "triager", "member")


def _key7(p, spec):
    ns, obj, rel, _sid, sns, sobj, srel = row(spec)
    ids = {n.name: n.id for n in p.namespaces().namespaces()}
    return (ids[ns], obj, rel, None, ids[sns], sobj, srel)


def test_the_plan_keeps_coinciding_stored_rows_through_the_snapshot_cache_meta():
    rows = GITHUB_ROWS + [row("repos:openfga/openfga#admin@orgs:openfga#repo_admin")]
    p = store_of(GITHUB, rows)
    plan = full_build(p, rewrites=p.namespaces().rewrites).rewrites
    assert plan.stored_too == {_key7(p, "repos:openfga/openfga#admin@orgs:openfga#repo_admin")}
    again = type(plan).from_meta(json.loads(json.dumps(plan.to_meta())), plan.schema)
    assert again.stored_too == plan.stored_too and again.flags == plan.flags


# -- the schema is a build input: reloads and the snapshot cache ----------------------

FLAT = schema(teams={}, orgs={}, repos={})


def test_a_reload_with_another_schema_never_serves_the_old_snapshot():
    holder = {"nm": manager_of(GITHUB)}
    p = MemoryPersister(lambda: holder["nm"])
    p.write_relation_tuples(*[as_tuple(r) for r in GITHUB_ROWS])
    engine = quiet_engine(p, lambda: holder["nm"])
    q = [ask("repos", "openfga/openfga", "reader", "erik"), ask("repos", "openfga/openfga", "reader", "gina")]
    try:
        assert list(engine.batch_check(q)) == [True, True]
        first = engine.snapshot()
        holder["nm"] = manager_of(FLAT)  # the same rows, no rewrites, no write
        assert list(engine.batch_check(q)) == [False, True]
        assert engine.snapshot() is not first and engine.snapshot().rewrites is None
        assert engine.snapshot_serving().rewrites is None
        holder["nm"] = manager_of(GITHUB)
        assert list(engine.batch_check(q)) == [True, True]
        assert engine.snapshot().rewrites.fingerprint == holder["nm"].rewrites.fingerprint
    finally:
        engine.close()


def test_a_cached_snapshot_of_another_schema_is_not_loaded(tmp_path):
    p = store_of(GITHUB, GITHUB_ROWS)
    q = [ask("repos", "openfga/openfga", "reader", "erik")]
    a = quiet_engine(p, snapshot_cache_dir=str(tmp_path))
    try:
        assert list(a.batch_check(q)) == [True]
        assert a.save_snapshot_cache() is not None
    finally:
        a.close()
    # the same schema: the cache is the snapshot, plan and all
    b = quiet_engine(p, snapshot_cache_dir=str(tmp_path))
    try:
        assert list(b.batch_check(q)) == [True]
        assert counters(b).get("cache_loads", 0) == 1
        assert b.snapshot().rewrites.flags == a.snapshot().rewrites.flags
        assert b.snapshot().rewrites.n_ttu == 3
    finally:
        b.close()
    # another schema over the same rows: refused, rebuilt, and saved over
    flat = MemoryPersister(manager_of(FLAT))
    flat.write_relation_tuples(*[as_tuple(r) for r in GITHUB_ROWS])
    c = quiet_engine(flat, snapshot_cache_dir=str(tmp_path))
    try:
        assert list(c.batch_check(q)) == [False]
        assert counters(c).get("cache_loads", 0) == 0
        assert c.save_snapshot_cache() is not None
    finally:
        c.close()
    d = quiet_engine(p, snapshot_cache_dir=str(tmp_path))
    try:
        assert list(d.batch_check(q)) == [True]
        assert counters(d).get("cache_loads", 0) == 0
    finally:
        d.close()


def test_without_config_relations_the_snapshot_is_byte_identical():
    """A namespace without ``config.relations`` takes the path it took
    before rewrites existed: the same arrays, no plan, no counter."""
    rng = seeded(7700)
    _, rows = random_world(rng, gated=False, n_rows=200)
    plain = schema(a={}, b={}, c={})
    elsewhere = schema(a={}, b={}, c={}, unused={"x": computed("y")})
    snaps = []
    for config, rewrites in ((plain, None), (plain, manager_of(plain).rewrites),
                             (elsewhere, manager_of(elsewhere).rewrites)):
        p = store_of(config, rows)
        snaps.append(full_build(p, rewrites=rewrites))
    base = snaps[0]
    assert base.rewrites is None and snaps[1].rewrites is None
    for other in snaps[1:]:
        for name in ("raw2dev", "fwd_indptr", "fwd_indices", "sink_indptr", "sink_indices",
                     "rev_indptr", "rev_indices"):
            assert np.asarray(getattr(base, name)).tobytes() == np.asarray(getattr(other, name)).tobytes()
        assert [(b.offset, b.n) for b in base.buckets] == [(b.offset, b.n) for b in other.buckets]
        for x, y in zip(base.buckets, other.buckets):
            assert np.asarray(x.nbrs).tobytes() == np.asarray(y.nbrs).tobytes()
        assert (base.num_sets, base.num_leaves, base.num_int, base.num_live) == (
            other.num_sets, other.num_leaves, other.num_int, other.num_live)
    p = store_of(plain, rows)
    engine = quiet_engine(p)
    try:
        engine.batch_check([ask(*q) for q in every_query()[:64]])
        assert not any(k.startswith(("rewrite_checks", "rewrite_route", "rewrite_oracle"))
                       for k in counters(engine))
    finally:
        engine.close()


def test_the_plan_counts_derived_edges_and_knows_each_closure():
    p = store_of(GITHUB, GITHUB_ROWS)
    snap = full_build(p, rewrites=p.namespaces().rewrites)
    plan = snap.rewrites
    # one computed edge an object a rewrite: orgs 1 x 1, repos 2 x 4
    assert plan.n_computed == 9
    # one tupleset row x admin, writer, reader
    assert plan.n_ttu == 3
    assert plan.relation_flags(2, "triager") == REWRITTEN
    assert plan.relation_flags(1, "repo_reader") == REWRITTEN  # held by orgs#member
    assert plan.relation_flags(1, "repo_admin") == 0  # held by orgs#owner, plain
    assert plan.relation_flags(0, "member") == 0
    assert not plan.has_gated
    assert snap.resolve_set(2, "openfga/docs", "triager") is not None  # no stored row
    assert snap.resolve_set(2, "openfga/docs", "owner") is None


# -- what else reads the adjacency ----------------------------------------------------

def test_list_over_derived_edges_equals_check():
    p = store_of(GITHUB, GITHUB_ROWS)
    oracle = CheckEngine(p)
    engine = quiet_engine(p)
    lister = SnapshotListEngine(engine, p.namespaces)
    try:
        for node, want in GITHUB_ANSWERS.items():
            ns, obj, rel = node_of(node)
            got, _ = lister.list_subjects(ns, obj, rel)
            assert set(got) == want, node
            assert lister.oracle.list_subjects(ns, obj, rel) == sorted(want), node
        for u in OCTOCATS:
            for rel in ("admin", "writer", "reader"):
                got, _ = lister.list_objects("repos", rel, SubjectID(u))
                want = [o for o in ("openfga/docs", "openfga/openfga")
                        if oracle.subject_is_allowed(ask("repos", o, rel, u))]
                assert got == want, (u, rel)
        with pytest.raises(ErrBadRequest, match="snapshot"):
            lister.oracle.list_objects("repos", "reader", SubjectID("erik"))
    finally:
        engine.close()


def test_list_after_overlay_writes_and_with_patterns_under_a_schema():
    """What the snapshot lister hands on under a schema: an overlay its
    layouts cannot mirror (``lst_dirty``) is walked on the host over the
    same snapshot, derived edges included; a pattern's subjects come from
    the Manager engine, which walks the schema; a pattern's objects are
    refused for what they are."""
    p = store_of(GITHUB, GITHUB_ROWS)
    oracle = CheckEngine(p)
    engine = quiet_engine(p)
    lister = SnapshotListEngine(engine, p.namespaces)
    repos = ("openfga/docs", "openfga/openfga")

    def objects_by_check(rel, u):
        return [o for o in repos if oracle.subject_is_allowed(ask("repos", o, rel, u))]

    try:
        engine.batch_check([ask("repos", "openfga/docs", "reader", "erik")])
        p.write_relation_tuples(as_tuple(row("repos:openfga/docs#owner@orgs:openfga#...")))
        p.delete_relation_tuples(as_tuple(row("repos:openfga/openfga#owner@orgs:openfga#...")))
        for dirty in (False, True):
            snap = engine.snapshot()
            snap.lst_dirty = snap.lst_dirty or dirty
            lister._cache.clear()
            for u in OCTOCATS:
                for rel in ("admin", "maintainer", "reader"):
                    got, _ = lister.list_objects("repos", rel, SubjectID(u), latest=True)
                    assert got == objects_by_check(rel, u), (u, rel, dirty)
            got, _ = lister.list_subjects("repos", "openfga/docs", "triager", latest=True)
            assert got == [u for u in OCTOCATS
                           if oracle.subject_is_allowed(ask("repos", "openfga/docs", "triager", u))]
        got, _ = lister.list_subjects("repos", "", "admin", latest=True)
        assert got == sorted({u for u in OCTOCATS for o in repos
                              if oracle.subject_is_allowed(ask("repos", o, "admin", u))})
        with pytest.raises(ErrBadRequest, match="wildcard pattern 'repos'#''"):
            lister.list_objects("repos", "", SubjectID("erik"), latest=True)
    finally:
        engine.close()


def test_list_refuses_a_gated_closure_by_name():
    p = store_of(GATED_SCHEMA, GATED_ROWS)
    engine = quiet_engine(p)
    lister = SnapshotListEngine(engine, p.namespaces)
    try:
        with pytest.raises(ErrBadRequest, match="doc#reader"):
            lister.list_subjects("doc", "a", "reader")
        with pytest.raises(ErrBadRequest, match="doc#page"):
            lister.list_objects("doc", "page", SubjectID("ann"))
        with pytest.raises(ErrBadRequest, match="doc#viewer"):
            lister.oracle.list_subjects("doc", "a", "viewer")
        got, _ = lister.list_subjects("doc", "a", "editor")
        assert got == ["dan"]
    finally:
        engine.close()


def test_expand_of_a_rewritten_root_is_the_host_engines_tree():
    p = store_of(GITHUB, GITHUB_ROWS)
    engine = quiet_engine(p)
    on_snapshot = SnapshotExpandEngine(engine, p.namespaces)
    host = ExpandEngine(p)
    try:
        for node in ("repos:openfga/openfga#reader", "repos:openfga/docs#triager",
                     "orgs:openfga#member", "teams:core#member", "orgs:openfga#repo_reader"):
            root = SubjectSet(*node_of(node))
            a, b = on_snapshot.build_tree(root, 6), host.build_tree(root, 6)
            assert a is not None and a.equals(b), node
        tree = on_snapshot.build_tree(SubjectSet("repos", "openfga/docs", "triager"), 3)
        # the derived edge is a child subject set, not a stored row's subject
        assert [str(c.subject) for c in tree.children] == ["repos:openfga/docs#writer"]
    finally:
        engine.close()


# -- a daemon's surfaces ---------------------------------------------------------------

def _call(app, method, url, body=None):
    u = urlparse(url)
    st, payload, hdrs = app.handle(
        method, u.path, parse_qs(u.query),
        json.dumps(body).encode() if body is not None else b"", {})
    if isinstance(payload, (bytes, bytearray)):
        payload = json.loads(payload) if payload else None
    return st, payload, hdrs


@pytest.fixture
def github_registry():
    from keto_tpu.config.provider import Config
    from keto_tpu.driver.registry import Registry

    reg = Registry(Config(overrides={"namespaces": GITHUB["namespaces"]}))
    reg.relation_tuple_manager().write_relation_tuples(*[as_tuple(r) for r in GITHUB_ROWS])
    yield reg
    reg.close()


def test_rest_check_batch_and_metrics_under_the_github_schema(github_registry):
    from keto_tpu.servers.rest import READ, RestApp

    reg = github_registry
    rapp = RestApp(reg, READ)
    qs = [(*node_of(n), u) for n in GITHUB_ANSWERS for u in OCTOCATS]
    want = [u in GITHUB_ANSWERS[n] for n in GITHUB_ANSWERS for u in OCTOCATS]
    for q, w in list(zip(qs, want))[::7]:
        st, p, _ = _call(rapp, "GET", f"/check?namespace={q[0]}&object={q[1]}&relation={q[2]}&subject_id={q[3]}")
        assert (st, p["allowed"]) == ((200, True) if w else (403, False)), q
    body = {"tuples": [ask(*q).to_json() for q in qs]}
    for extra in ({}, {"x": 1}):  # framed, then declined to the general decode
        st, p, _ = _call(rapp, "POST", "/check/batch", {**body, **extra})
        assert st == 200, p
        assert list(p["results"]) == want
    st, text, _ = rapp.handle("GET", "/metrics", {}, b"", {})
    text = text.data.decode()
    assert 'keto_check_rewrite_checks_total{closure="rewritten"}' in text
    assert 'keto_check_rewrite_route_total{route="oracle"} 0' in text
    assert 'keto_snapshot_rewrite_edges{kind="tuple_to_userset"} 3' in text
    assert 'keto_snapshot_rewrite_edges{kind="computed_userset"} 9' in text
    assert 'keto_check_rewrite_oracle_total{reason="gated_closure"} 0' in text
    assert 'keto_build_phase_duration_seconds_count{phase="rewrites"} 1' in text
    assert 'keto_stream_route_slices_total{route="cpu"} 0' in text or "route=\"cpu\"" not in text


def test_rest_expand_and_explain_under_the_github_schema(github_registry):
    from keto_tpu.servers.rest import READ, RestApp

    rapp = RestApp(github_registry, READ)
    st, p, _ = _call(rapp, "GET", "/expand?namespace=repos&object=openfga/docs&relation=triager&max-depth=4")
    assert st == 200 and p["type"] == "union"
    assert p["children"][0]["subject_set"]["relation"] == "writer"
    st, p, _ = _call(rapp, "GET", "/check/explain?namespace=repos&object=openfga/openfga"
                                   "&relation=reader&subject_id=erik")
    assert st == 200 and p["allowed"] and p["verified"], p
    kinds = [e.get("rewrite") for e in p["witness"]]
    assert "tuple_to_userset" in kinds and kinds[-1] is None  # ends on the stored owner row
    step = next(e for e in p["witness"] if e.get("rewrite") == "tuple_to_userset")
    assert step["via"]["relation"] == "owner"


def test_a_compaction_fold_keeps_the_plan_and_the_answers():
    """Folding the overlay into the base layout renumbers nothing the plan
    reads by name: the folded snapshot carries the plan, derives its flags
    anew, and answers as a rebuild does."""
    p = store_of(GITHUB, GITHUB_ROWS)
    oracle = CheckEngine(p)
    engine = quiet_engine(p)
    qs = github_universe()
    try:
        engine.batch_check(qs[:4])
        p.write_relation_tuples(as_tuple(row("orgs:openfga#owner@hugo")),
                                as_tuple(row("teams:core#member@ines")))
        snap = engine.snapshot()
        assert snap.has_overlay and snap.rewrites is not None
        with engine._lock:
            folded = engine._compact_locked(snap)
            if folded is not None:
                engine._snapshot = folded
        assert folded is not None and not folded.has_overlay
        assert folded.rewrites is snap.rewrites
        got = list(engine.batch_check(qs))
        assert got == [oracle.subject_is_allowed(t) for t in qs]
        assert got[qs.index(ask("repos", "openfga/openfga", "reader", "hugo"))]
        c = counters(engine)
        assert c["rewrite_route_device"] == c["rewrite_checks_rewritten"]
    finally:
        engine.close()
