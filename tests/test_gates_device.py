"""Gates on the device (keto_tpu/check/gates.py): a check whose closure
reaches an intersection or an exclusion is several device positions and one
combine, not a descent of the CPU oracle. The served path (the engine, and
``POST /check/batch`` through ``RestApp``) against the oracle and the plain
reference on seeded random schemas with all six operators and on a small
``github`` store with a blocklist; every reason that still reaches the oracle
fired and counted; the door's ids, the snapshot cache, a reload, a fold, the
families on ``/metrics`` and the dispatch spans."""

import json

import numpy as np
import pytest

from keto_tpu.check import CheckEngine, gates
from keto_tpu.check.frame import QueryBatch, QueryFrame
from keto_tpu.check.tpu_engine import TpuCheckEngine
from keto_tpu.graph import gate_tables
from keto_tpu.graph.native import FrameTable
from keto_tpu.graph.stream_build import full_build
from keto_tpu.namespace.rewrites import (
    AND, AND_NOT, ANCHOR, GATED, HIDDEN, IS_GATE, OR, GateDef, operand_name,
)
from keto_tpu.persistence.memory import MemoryPersister
from keto_tpu.relationtuple import RelationTuple, SubjectID, SubjectSet
from keto_tpu.x.timeline import (
    FILL, RESOLVE, DispatchClock, bind_dispatch_clock,
)

from rewrites_util import (
    GITHUB, NAMESPACES, OBJECTS, RELATIONS, THIS, as_tuple, computed, every_query, from_, load_reference, manager_of,
    random_world, row, schema, seeded, store_of, union,
)
from test_dispatch_tracing import FakeSession

reference = load_reference()


def quiet_engine(p, nm=None, **kw):
    kw.setdefault("compact_after_s", 3600.0)
    kw.setdefault("overlay_edge_budget", 1 << 20)
    return TpuCheckEngine(p, nm if nm is not None else p.namespaces, **kw)


def ask(ns, obj, rel, user):
    return RelationTuple(namespace=ns, object=obj, relation=rel, subject=SubjectID(user))


def counters(engine):
    return engine.maintenance.snapshot()


def oracle_total(c):
    return sum(c.get(f"rewrite_oracle_{r}", 0) for r in gates.ORACLE_REASONS)


def but_not(base, subtract):
    return {"exclusion": {"base": base, "subtract": subtract}}


def both(*children):
    return {"intersection": list(children)}


# OpenFGA's ``github`` store with the blocklist and the two-condition
# permission of benchmarks/configs/github-blocklist.json, and one gate over
# another (``can_purge``: two strata)
BLOCKLIST = schema(
    teams={},
    orgs={"member": union(THIS, computed("owner")), "blocked": THIS},
    repos=dict(
        GITHUB["namespaces"][2]["config"]["relations"],
        can_read=but_not(computed("reader"), from_("owner", "blocked")),
        can_maintain=but_not(computed("maintainer"), from_("owner", "blocked")),
        can_delete=both(computed("admin"), from_("owner", "member")),
        can_purge=but_not(computed("can_delete"), from_("owner", "blocked")),
    ),
    issues={},
)
BLOCKLIST_ROWS = [row(s) for s in (
    "orgs:acme#owner@erik",
    "orgs:acme#member@anne",
    "orgs:acme#member@teams:core#member",
    "orgs:acme#repo_admin@orgs:acme#owner",
    "orgs:acme#repo_reader@orgs:acme#member",
    "orgs:acme#blocked@gina",
    "orgs:acme#blocked@erik",
    "orgs:acme#blocked@teams:backend#member",  # a blocked team, its forest with it
    "teams:core#member@beth",
    "teams:core#member@teams:backend#member",
    "teams:backend#member@charles",
    "teams:backend#member@teams:interns#member",
    "teams:interns#member@ivan",
    "repos:acme/site#owner@orgs:acme#...",
    "repos:acme/site#admin@teams:core#member",
    "repos:acme/site#admin@zoe",  # an admin who is no member
    "repos:acme/site#reader@gina",
    "repos:acme/site#writer@diane",
    "repos:acme/docs#owner@orgs:acme#...",
    "repos:acme/docs#maintainer@anne",
    "repos:acme/docs#reader@hugo",
    "repos:lone/repo#reader@hugo",  # no owner row: nobody is blocked from it
    "issues:1#view@repos:acme/site#can_read",
    "issues:2#view@repos:acme/docs#can_maintain",
    "issues:2#view@hugo",  # a gated row that reaches something besides its gate
)]
FOLK = ["anne", "beth", "charles", "diane", "erik", "gina", "hugo", "ivan", "zoe", "nobody"]
BLOCKLIST_NODES = (
    [("repos", r, rel) for r in ("acme/site", "acme/docs", "lone/repo", "no/where")
     for rel in ("admin", "maintainer", "writer", "triager", "reader",
                 "can_read", "can_maintain", "can_delete", "can_purge", "owner")]
    + [("issues", i, "view") for i in ("1", "2", "3")]
    + [("orgs", "acme", rel) for rel in ("member", "blocked", "owner")]
)


def blocklist_queries():
    return [(*n, u) for n in BLOCKLIST_NODES for u in FOLK]


@pytest.fixture(scope="module")
def blocklist():
    p = store_of(BLOCKLIST, BLOCKLIST_ROWS)
    engine = quiet_engine(p)
    yield p, engine, reference.Reference(BLOCKLIST_ROWS, BLOCKLIST)
    engine.close()


# -- the plan: how a gate is cut -----------------------------------------------------

@pytest.mark.parametrize("expr, operands, program", [
    (but_not(computed("reader"), from_("owner", "blocked")),
     [computed("reader"), from_("owner", "blocked")], (0, 1, AND_NOT)),
    (both(computed("admin"), from_("owner", "member")),
     [computed("admin"), from_("owner", "member")], (0, 1, AND)),
    (union(THIS, both(computed("a"), computed("b")), computed("c")),
     [union(THIS, computed("c")), computed("a"), computed("b")], (0, 1, 2, AND, OR)),
    (but_not(both(THIS, computed("a")), but_not(computed("b"), THIS)),
     [THIS, computed("a"), computed("b"), THIS], (0, 1, AND, 2, 3, AND_NOT, AND_NOT)),
    (both(union(THIS, computed("a"))), [union(THIS, computed("a"))], (0,)),
], ids=["exclusion", "intersection", "a_union_over_an_intersection", "nested", "one_child"])
def test_a_gated_expression_is_cut_into_union_only_operands_and_a_program(expr, operands, program):
    gate = GateDef("r", expr)
    assert [e for _, e in gate.operands] == operands
    assert [name for name, _ in gate.operands] == [operand_name("r", k) for k in range(len(operands))]
    assert gate.program == program


def test_the_plan_knows_gates_from_what_reaches_them_and_hides_its_own_names():
    p = store_of(BLOCKLIST, BLOCKLIST_ROWS)
    snap = full_build(p, rewrites=p.namespaces().rewrites)
    plan = snap.rewrites
    assert plan.has_gated
    assert plan.relation_flags(2, "can_read") & IS_GATE and plan.relation_flags(2, "can_read") & GATED
    assert plan.relation_flags(3, "view") & GATED and not plan.relation_flags(3, "view") & IS_GATE
    assert not plan.relation_flags(2, "reader") & GATED
    assert plan.relation_flags(2, operand_name("can_read", 0)) & HIDDEN
    assert plan.relation_flags(2, ANCHOR) & HIDDEN
    # a gate over a gate: the operand reaches one, the relation is one
    assert plan.relation_flags(2, operand_name("can_purge", 0)) & GATED
    # every object the store mentions has its gate nodes, named by a row or not
    assert snap.resolve_set(2, "lone/repo", "can_delete") is not None
    assert snap.resolve_set(2, "no/where", "can_delete") is None
    tables = gate_tables.tables_of(snap)
    assert tables.n_gate_nodes == 3 * 4  # three repos, four gates each
    gate = snap.resolve_set(2, "acme/site", "can_read")
    issue = snap.resolve_set(3, "1", "view")
    assert tables.row_mode[gate] == gate_tables.DEVICE | gate_tables.GATE_ROW  # a gate reaches nothing itself
    assert tables.row_mode[issue] == gate_tables.DEVICE  # its one row names the gate
    assert tables.row_mode[snap.resolve_set(3, "2", "view")] == gate_tables.DEVICE | gate_tables.SELF
    assert tables.row_npos[issue] == 2
    purge = snap.resolve_set(2, "acme/site", "can_purge")
    assert tables.row_npos[purge] == 4  # its two operands, and can_delete's two
    assert tables.row_mode[snap.resolve_set(2, "acme/site", "reader")] == 0


# -- the served path against the oracle and the reference ---------------------------------

def test_the_blocklist_store_on_the_device_equals_oracle_equals_reference(blocklist):
    p, engine, judge = blocklist
    oracle = CheckEngine(p)
    qs = blocklist_queries()
    before = counters(engine)
    got = list(engine.batch_check([ask(*q) for q in qs]))
    assert got == [judge.allowed(*q) for q in qs]
    assert got == [oracle.subject_is_allowed(ask(*q)) for q in qs]
    # what the gates are there for
    for who, node, want in (
        ("gina", ("repos", "acme/site", "can_read"), False),  # blocked, though a reader
        ("gina", ("repos", "acme/site", "reader"), True),
        ("charles", ("issues", "1", "view"), False),  # a member of the blocked team
        ("ivan", ("issues", "1", "view"), False),  # of a team nested in it
        ("beth", ("issues", "1", "view"), True),
        ("zoe", ("repos", "acme/site", "can_delete"), False),  # an admin, no member
        ("beth", ("repos", "acme/site", "can_delete"), True),
        ("erik", ("repos", "acme/site", "can_delete"), True),
        ("erik", ("repos", "acme/site", "can_purge"), False),  # the owner blocked himself
        ("beth", ("repos", "acme/site", "can_purge"), True),
        ("hugo", ("issues", "2", "view"), True),  # by the row beside the gate
        ("hugo", ("repos", "lone/repo", "can_read"), True),
    ):
        assert got[qs.index((*node, who))] is want, (who, node)
    c = counters(engine)
    assert oracle_total(c) == oracle_total(before) == 0
    assert c.get("fallback_checks", 0) == 0
    assert engine.route_slice_counts().get("cpu", 0) == 0


@pytest.mark.parametrize("pattern", [
    ("repos", "", "admin"), ("repos", "", "reader"), ("orgs", "", "member"), ("repos", "acme/site", "admin"),
], ids=["admin", "reader", "orgs_member", "no_pattern"])
def test_a_plain_wildcard_pattern_keeps_its_target_beside_an_expanded_check(blocklist, pattern):
    """A pattern that reaches no gate rides the device with ``sd == -2`` and
    its starts in ``multi``: in one chunk with expanded checks it is still
    one position with its own target (only an operand that has no node is
    cleared)."""
    p, engine, judge = blocklist
    oracle = CheckEngine(p)
    before = counters(engine)
    qs = []
    for u in FOLK:  # interleaved, so the patterns sit between expanded checks
        qs += [(*pattern, u), ("issues", "1", "view", u), ("repos", "acme/site", "can_purge", u)]
    tuples = [ask(*q) for q in qs]
    got = list(engine.batch_check(tuples))
    assert got == [oracle.subject_is_allowed(t) for t in tuples]
    if pattern[1]:
        assert got == [judge.allowed(*q) for q in qs]
    assert any(got[0::3]) and any(got[1::3])  # some pattern granted, some gated check granted
    c = counters(engine)
    assert oracle_total(c) == oracle_total(before)
    assert c["gate_checks_device"] - before.get("gate_checks_device", 0) == 2 * len(FOLK)
    assert c.get("fallback_checks", 0) == 0


@pytest.fixture(scope="module")
def counted_run():
    """One engine that has answered ``blocklist_queries()`` once, for the
    cases below: its counters, and what its tables say of the rows asked."""
    p = store_of(BLOCKLIST, BLOCKLIST_ROWS)
    engine = quiet_engine(p)
    try:
        qs = blocklist_queries()
        engine.batch_check([ask(*q) for q in qs])
        c = counters(engine)
        snap = engine.snapshot()
        tables = gate_tables.tables_of(snap)
        ns_ids = {"teams": 0, "orgs": 1, "repos": 2, "issues": 3}
        rows = [snap.resolve_set(ns_ids[ns], obj, rel) for ns, obj, rel, _ in qs]
        gated = [r for r in rows if r is not None and tables.row_mode[r]]
        # a gated relation on an object no row mentions is denied at resolve: no position
        unresolved = sum(1 for (ns, _obj, rel, _), r in zip(qs, rows)
                         if r is None and snap.rewrites.relation_flags(ns_ids[ns], rel) & GATED)
        yield qs, c, tables, gated, unresolved
    finally:
        engine.close()


@pytest.mark.parametrize("counted", [
    "every_gated_check_once", "positions_by_kind", "checks_not_positions", "seconds_by_phase",
])
def test_what_the_expansion_counts(counted_run, counted):
    qs, c, tables, gated, unresolved = counted_run
    if counted == "every_gated_check_once":
        assert c["gate_checks_device"] == len(gated) + unresolved
        assert c.get("gate_checks_oracle", 0) == 0
        assert unresolved == 5 * len(FOLK)  # no/where's four gates, and issue 3
    elif counted == "positions_by_kind":
        selfs = sum(1 for r in gated if tables.row_mode[r] & gate_tables.SELF)
        assert c["gate_positions_self"] == selfs == len(FOLK)  # issues:2#view alone
        assert c["gate_positions_self"] + c["gate_positions_operand"] == sum(
            int(tables.row_npos[r]) for r in gated)
    elif counted == "checks_not_positions":
        assert c["rewrite_checks_rewritten"] + c["rewrite_checks_plain"] == len(qs)
        assert c["rewrite_route_device"] == c["rewrite_checks_rewritten"]
        assert c["packed_checks"] == len(qs) - len(gated) + sum(
            int(tables.row_npos[r]) for r in gated)
    else:
        assert c["gate_seconds_expand"] > 0 and c["gate_seconds_combine"] > 0
        assert c.get("rewrite_oracle_seconds", 0) == 0


@pytest.mark.parametrize("seed", range(8))
def test_random_schemas_with_all_six_operators_device_equals_oracle_equals_reference(seed):
    """Gates at the root and in the interior, two strata and more, cycles in
    the data through unions and through intersections: whoever answers, the
    answer is the reference's, and what the oracle took is counted by reason."""
    rng = seeded(8100 + seed)
    served = taken = 0
    for _ in range(5):
        config, rows = random_world(rng, gated=True)
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
            # a check that asks about a userset, a gate's own among them
            sets = [RelationTuple(
                namespace=rng.choice(NAMESPACES), object=rng.choice(OBJECTS),
                relation=rng.choice(RELATIONS),
                subject=SubjectSet(rng.choice(NAMESPACES), rng.choice(OBJECTS), rng.choice(RELATIONS[:4])),
            ) for _ in range(200)]
            assert list(engine.batch_check(sets)) == [oracle.subject_is_allowed(t) for t in sets], (
                config, rows)
            # wildcard patterns between expanded checks: the oracle's where a
            # gate is in reach, one device position with its target where not
            mixed = [ask(ns, rng.choice(["", obj, obj]), rel, u)
                     for ns, obj, rel, u in rng.sample(qs, min(len(qs), 300))]
            assert list(engine.batch_check(mixed)) == [oracle.subject_is_allowed(t) for t in mixed], (
                config, rows)
            c = counters(engine)
            assert c.get("gate_checks_oracle", 0) == oracle_total(c) == c.get("rewrite_route_oracle", 0)
            assert c.get("rewrite_oracle_gated_closure", 0) == 0
            assert c.get("fallback_checks", 0) == 0
            served += c.get("gate_checks_device", 0)
            taken += c.get("gate_checks_oracle", 0)
        finally:
            engine.close()
    assert served > 0 and served + taken > 0  # the device serves; cycles in such data are common


def _call(app, body):
    st, payload, _ = app.handle("POST", "/check/batch", {}, json.dumps(body).encode(), {})
    return st, json.loads(payload) if isinstance(payload, (bytes, bytearray)) else payload


@pytest.mark.parametrize("world", ["blocklist", "random_0", "random_1"])
def test_post_check_batch_through_the_rest_app_equals_the_reference(world):
    from keto_tpu.config.provider import Config
    from keto_tpu.driver.registry import Registry
    from keto_tpu.servers.rest import READ, RestApp

    if world == "blocklist":
        config, rows, qs = BLOCKLIST, BLOCKLIST_ROWS, blocklist_queries()
    else:
        config, rows = random_world(seeded(8200 + int(world[-1])), gated=True)
        qs = every_query()
    judge = reference.Reference(rows, config)
    reg = Registry(Config(overrides={"namespaces": config["namespaces"]}))
    try:
        reg.relation_tuple_manager().write_relation_tuples(*[as_tuple(r) for r in rows])
        app = RestApp(reg, READ)
        want = [judge.allowed(*q) for q in qs]
        body = {"tuples": [ask(*q).to_json() for q in qs]}
        for extra in ({}, {}, {"x": 1}):  # framed (the second resolved at the door), then declined
            st, p = _call(app, {**body, **extra})
            assert st == 200, p
            assert list(p["results"]) == want
        text = app.handle("GET", "/metrics", {}, b"", {})[1].data.decode()
        assert 'keto_check_gate_checks_total{served="device"}' in text
        if world == "blocklist":
            assert 'keto_check_rewrite_route_total{route="oracle"} 0' in text
            assert 'keto_snapshot_gate_nodes{kind="gate"} 12' in text
    finally:
        reg.close()


# -- what still reaches the oracle, each reason fired and counted -----------------------

FANOUT = schema(doc={
    "viewer": both(THIS, computed("member")),
    "page": THIS,
})
FANOUT_ROWS = (
    [row(f"doc:d{i}#viewer@ann") for i in range(gate_tables.GATE_FANOUT_CAP + 1)]
    + [row(f"doc:d{i}#member@ann") for i in range(gate_tables.GATE_FANOUT_CAP + 1)]
    + [row(f"doc:wide#page@doc:d{i}#viewer") for i in range(gate_tables.GATE_FANOUT_CAP + 1)]
    + [row(f"doc:narrow#page@doc:d{i}#viewer") for i in range(gate_tables.GATE_FANOUT_CAP)]
    + [row("doc:above#page@doc:wide#page")]
)
# ``viewer`` holds itself through its own intersection, by a row
CYCLE = schema(doc={"viewer": both(union(THIS, computed("editor")), computed("member"))})
CYCLE_ROWS = [row(s) for s in (
    "doc:a#viewer@doc:a#viewer", "doc:a#viewer@ann", "doc:a#member@ann", "doc:a#editor@bob",
    "doc:a#member@bob", "doc:b#viewer@doc:a#viewer", "doc:b#member@ann", "doc:c#viewer@cat",
    "doc:c#member@cat",
)]


@pytest.mark.parametrize("reason", ["fanout", "cycle", "overlay", "gated_pattern"])
def test_each_reason_that_still_reaches_the_oracle_is_fired_and_counted(reason):
    if reason == "fanout":
        config, rows = FANOUT, FANOUT_ROWS
        users = ["ann", "zed"]
        taken = [("doc", "wide", "page"), ("doc", "above", "page")]
        served = [("doc", "narrow", "page"), ("doc", "d0", "viewer")]
    elif reason == "cycle":
        config, rows = CYCLE, CYCLE_ROWS
        users = ["ann", "bob", "cat"]
        taken = [("doc", "a", "viewer"), ("doc", "b", "viewer")]
        served = [("doc", "c", "viewer")]
    else:
        config, rows = BLOCKLIST, BLOCKLIST_ROWS
        users = ["beth", "gina", "hugo"]
        if reason == "overlay":
            # a base row under stale tables, and a start the overlay brought
            taken = [("issues", "1", "view"), ("issues", "9", "view")]
            served = []
        else:
            taken = [("repos", "", "can_read"), ("repos", "acme/site", "")]
            served = [("issues", "1", "view")]
    p = store_of(config, rows)
    judge = reference.Reference(rows, config)
    engine = quiet_engine(p)
    try:
        if reason == "overlay":
            engine.batch_check([ask("issues", "1", "view", "beth")])
            more = [row("issues:9#view@repos:acme/docs#can_read")]  # an edge into a gate
            p.write_relation_tuples(*[as_tuple(r) for r in more])
            judge = reference.Reference(rows + more, config)
        before = counters(engine)
        qs = [(*n, u) for n in taken + served for u in users]
        tuples = [ask(*q) for q in qs]
        got = list(engine.batch_check(tuples))
        if reason == "gated_pattern":
            oracle = CheckEngine(p)
            assert got == [oracle.subject_is_allowed(t) for t in tuples]
        else:
            assert got == [judge.allowed(*q) for q in qs]
        c = counters(engine)

        def moved(key):
            return c.get(key, 0) - before.get(key, 0)

        assert moved(f"rewrite_oracle_{reason}") == len(taken) * len(users)
        assert moved("rewrite_route_oracle") == moved("gate_checks_oracle") == len(taken) * len(users)
        assert oracle_total(c) - oracle_total(before) == len(taken) * len(users)
        assert moved("gate_checks_device") == len(served) * len(users)
        assert moved("rewrite_oracle_seconds") > 0
        assert c.get("fallback_checks", 0) == 0
        if reason == "overlay":
            assert engine.snapshot().rewrites.gates_stale
            assert counters(engine).get("delta_applies", 0) >= 1  # no rebuild hid it
    finally:
        engine.close()


def test_a_gate_on_an_object_the_overlay_brought_is_the_oracles():
    """The base rows have a gate node for every object the store mentioned
    when they were built: one that came since has none, and is not denied."""
    config = schema(doc={"viewer": both(THIS, from_("parent", "member"))}, group={})
    rows = [row(s) for s in ("doc:a#viewer@ann", "doc:a#parent@group:g#...", "group:g#member@ann")]
    p = store_of(config, rows)
    engine = quiet_engine(p)
    try:
        assert list(engine.batch_check([ask("doc", "a", "viewer", "ann"), ask("doc", "new", "viewer", "ann")])) == [
            True, False]
        assert oracle_total(counters(engine)) == 0  # no row mentions doc:new: denied at resolve
        more = [row("doc:new#viewer@ann"), row("doc:new#parent@group:g#...")]
        p.write_relation_tuples(*[as_tuple(r) for r in more])
        judge = reference.Reference(rows + more, config)
        qs = [("doc", o, "viewer", u) for o in ("a", "new", "other") for u in ("ann", "zed")]
        assert list(engine.batch_check([ask(*q) for q in qs])) == [judge.allowed(*q) for q in qs]
        assert judge.allowed("doc", "new", "viewer", "ann")
        c = counters(engine)
        assert c.get("delta_applies", 0) >= 1 and c["rewrite_oracle_overlay"] == 4  # not doc:other
    finally:
        engine.close()


def test_an_overlay_that_leaves_the_gates_alone_keeps_them_on_the_device():
    """A grant, a block and an unblock are edges of operands' closures: the
    device's overlay carries them, the tables stand, the oracle is not asked."""
    p = store_of(BLOCKLIST, BLOCKLIST_ROWS)
    engine = quiet_engine(p)
    qs = blocklist_queries()
    tuples = [ask(*q) for q in qs]
    live = list(BLOCKLIST_ROWS)
    try:
        engine.batch_check(tuples[:4])
        for kind, spec in (
            ("ins", "orgs:acme#blocked@beth"),
            ("ins", "repos:acme/docs#reader@diane"),
            ("del", "orgs:acme#blocked@gina"),
            ("ins", "issues:1#view@ivan"),  # a row beside the gate: the issue is asked itself now
            ("del", "orgs:acme#blocked@teams:backend#member"),
        ):
            t = as_tuple(row(spec))
            if kind == "ins":
                p.write_relation_tuples(t)
                live.append(row(spec))
            else:
                p.delete_relation_tuples(t)
                live.remove(row(spec))
            judge = reference.Reference(live, BLOCKLIST)
            assert list(engine.batch_check(tuples)) == [judge.allowed(*q) for q in qs], spec
        c = counters(engine)
        assert not engine.snapshot().rewrites.gates_stale
        assert oracle_total(c) == 0 and c["delta_applies"] >= 5
    finally:
        engine.close()


def test_a_fold_makes_the_tables_anew_and_the_device_serves_again():
    p = store_of(BLOCKLIST, BLOCKLIST_ROWS)
    engine = quiet_engine(p)
    qs = blocklist_queries()
    tuples = [ask(*q) for q in qs]
    try:
        engine.batch_check(tuples[:4])
        more = [row("issues:9#view@repos:acme/docs#can_read"), row("repos:acme/docs#reader@ivan")]
        p.write_relation_tuples(*[as_tuple(r) for r in more])
        judge = reference.Reference(BLOCKLIST_ROWS + more, BLOCKLIST)
        extra = [("issues", "9", "view", u) for u in FOLK]
        assert list(engine.batch_check(tuples + [ask(*q) for q in extra])) == [
            judge.allowed(*q) for q in qs + extra]
        snap = engine.snapshot()
        assert snap.rewrites.gates_stale and counters(engine)["rewrite_oracle_overlay"] > 0
        with engine._lock:
            folded = engine._compact_locked(snap)
            if folded is not None:
                engine._snapshot = folded
        assert folded is not None and not folded.rewrites.gates_stale
        # made by the fold itself (the compaction thread), of the new rows:
        # no gated check builds them on the dispatch thread
        tables = gate_tables.tables_of(folded, build=False)
        assert tables is not None and tables is not gate_tables.tables_of(snap, build=False)
        nine = folded.resolve_set(3, "9", "view")
        assert nine < folded.n_base_nodes and tables.row_mode[nine] == gate_tables.DEVICE
        before = counters(engine)
        assert list(engine.batch_check(tuples + [ask(*q) for q in extra])) == [
            judge.allowed(*q) for q in qs + extra]
        c = counters(engine)
        assert oracle_total(c) == oracle_total(before)
        assert c["gate_checks_device"] > before["gate_checks_device"]
    finally:
        engine.close()


# -- the door, the cache, a reload --------------------------------------------------------

class _OneBatch:
    def __init__(self, batch):
        self._batch = batch

    def take(self, cap):
        batch, self._batch = self._batch, []
        return batch


def test_a_framed_bodys_door_resolved_ids_survive_the_expansion():
    manager = manager_of(BLOCKLIST)
    table = FrameTable.build(manager)
    if table is None:
        pytest.skip("native library not built")
    p = MemoryPersister(manager)
    p.write_relation_tuples(*[as_tuple(r) for r in BLOCKLIST_ROWS])
    judge = reference.Reference(BLOCKLIST_ROWS, BLOCKLIST)
    engine = quiet_engine(p, manager)
    try:
        qs = blocklist_queries()
        body = json.dumps({"tuples": [ask(*q).to_json() for q in qs]}).encode()
        got = table.frame(body, 65536)
        assert not isinstance(got, str), got
        frame = QueryFrame(*got, body, manager)
        snap = engine.snapshot()
        frame.resolve_at_door(snap)
        assert frame.door is not None and frame.door[0] is snap.interned
        gen, _ = engine.batch_check_stream_with_token(
            _OneBatch(QueryBatch([(frame, 0, len(qs))])), ordered=True)
        assert np.concatenate(list(gen)).tolist() == [judge.allowed(*q) for q in qs]
        c = counters(engine)
        assert c.get("resolve_tuples_door", 0) == len(qs)
        assert c.get("resolve_tuples_thread", 0) == 0
        assert c["gate_checks_device"] > 0 and oracle_total(c) == 0
    finally:
        engine.close()


@pytest.mark.parametrize("width", [32, 64, 256])
def test_a_stream_narrower_than_its_positions_cuts_between_checks(width):
    """Positions outnumber checks: a chunk is cut into slices by positions,
    and a check's positions never part."""
    p = store_of(BLOCKLIST, BLOCKLIST_ROWS)
    judge = reference.Reference(BLOCKLIST_ROWS, BLOCKLIST)
    engine = quiet_engine(p, max_batch=width)
    try:
        qs = blocklist_queries() * 2
        got = list(engine.batch_check([ask(*q) for q in qs]))
        assert got == [judge.allowed(*q) for q in qs]
        assert oracle_total(counters(engine)) == 0
    finally:
        engine.close()


OTHER_GATES = schema(
    teams={},
    orgs={"member": union(THIS, computed("owner")), "blocked": THIS},
    repos=dict(
        GITHUB["namespaces"][2]["config"]["relations"],
        can_read=but_not(computed("reader"), from_("owner", "blocked")),
        can_maintain=but_not(computed("maintainer"), from_("owner", "blocked")),
        can_delete=both(computed("admin"), from_("owner", "member"), computed("maintainer")),
        can_purge=but_not(computed("can_delete"), from_("owner", "blocked")),
    ),
    issues={},
)


def test_a_snapshot_cache_carries_the_gate_tables_and_one_of_another_gate_plan_is_not_loaded(tmp_path):
    p = store_of(BLOCKLIST, BLOCKLIST_ROWS)
    judge = reference.Reference(BLOCKLIST_ROWS, BLOCKLIST)
    qs = blocklist_queries()
    tuples = [ask(*q) for q in qs]
    a = quiet_engine(p, snapshot_cache_dir=str(tmp_path))
    try:
        assert list(a.batch_check(tuples)) == [judge.allowed(*q) for q in qs]
        assert a.save_snapshot_cache() is not None
        built = gate_tables.tables_of(a.snapshot())
    finally:
        a.close()
    b = quiet_engine(p, snapshot_cache_dir=str(tmp_path))
    try:
        assert list(b.batch_check(tuples)) == [judge.allowed(*q) for q in qs]
        assert counters(b).get("cache_loads", 0) == 1
        loaded = gate_tables.tables_of(b.snapshot(), build=False)
        assert loaded is not None and loaded.defs == built.defs
        for name in ("row_ptr", "row_gates", "row_mode", "row_npos", "gate_row", "gate_ops"):
            assert np.array_equal(getattr(loaded, name), getattr(built, name)), name
        assert oracle_total(counters(b)) == 0
    finally:
        b.close()
    other = MemoryPersister(manager_of(OTHER_GATES))
    other.write_relation_tuples(*[as_tuple(r) for r in BLOCKLIST_ROWS])
    other_judge = reference.Reference(BLOCKLIST_ROWS, OTHER_GATES)
    c = quiet_engine(other, snapshot_cache_dir=str(tmp_path))
    try:
        assert list(c.batch_check(tuples)) == [other_judge.allowed(*q) for q in qs]
        assert counters(c).get("cache_loads", 0) == 0
    finally:
        c.close()


def test_a_reload_with_another_gate_plan_never_serves_the_old_snapshot():
    holder = {"nm": manager_of(BLOCKLIST)}
    p = MemoryPersister(lambda: holder["nm"])
    p.write_relation_tuples(*[as_tuple(r) for r in BLOCKLIST_ROWS])
    engine = quiet_engine(p, lambda: holder["nm"])
    q = [ask("repos", "acme/site", "can_delete", "beth"), ask("issues", "1", "view", "gina"),
         ask("repos", "acme/site", "can_read", "beth")]
    try:
        assert list(engine.batch_check(q)) == [True, False, True]
        first = engine.snapshot()
        holder["nm"] = manager_of(OTHER_GATES)  # beth is no maintainer by a row... but an admin is
        want = [reference.Reference(BLOCKLIST_ROWS, OTHER_GATES).allowed(
            t.namespace, t.object, t.relation, t.subject.id) for t in q]
        assert list(engine.batch_check(q)) == want
        assert engine.snapshot() is not first
        assert engine.snapshot().rewrites.fingerprint == holder["nm"].rewrites.fingerprint
        ungated = dict(BLOCKLIST)
        ungated["namespaces"] = [dict(n) for n in BLOCKLIST["namespaces"]]
        ungated["namespaces"][2] = dict(ungated["namespaces"][2], config={"relations": dict(
            GITHUB["namespaces"][2]["config"]["relations"], can_read=computed("reader"))})
        holder["nm"] = manager_of(ungated)  # the gate gone: gina reads again
        assert list(engine.batch_check(q)) == [False, True, True]
        assert not engine.snapshot().rewrites.has_gated
        assert gate_tables.tables_of(engine.snapshot()) is None
    finally:
        engine.close()


def test_a_name_of_the_plans_own_is_no_relation_to_ask_or_to_store():
    rows = BLOCKLIST_ROWS + [
        ("repos", "acme/site", operand_name("can_read", 1), "beth", None, None, None)]
    p = store_of(BLOCKLIST, rows)
    judge = reference.Reference(BLOCKLIST_ROWS, BLOCKLIST)
    engine = quiet_engine(p)
    try:
        hidden = [ask("repos", "acme/site", operand_name("can_read", 0), "beth"),
                  ask("repos", "acme/site", ANCHOR, "beth")]
        assert list(engine.batch_check(hidden)) == [False, False]
        # the stored row on a reserved name blocked nobody
        assert engine.batch_check([ask("issues", "1", "view", "beth")])[0]
        qs = blocklist_queries()
        assert list(engine.batch_check([ask(*q) for q in qs])) == [judge.allowed(*q) for q in qs]
    finally:
        engine.close()


# -- a daemon without gates ------------------------------------------------------------

def test_a_daemon_without_gates_takes_the_early_return():
    from rewrites_util import GITHUB_ROWS

    p = store_of(GITHUB, GITHUB_ROWS)
    engine = quiet_engine(p)
    try:
        engine.batch_check([ask("repos", "openfga/openfga", "reader", u) for u in ("erik", "zoe")])
        snap = engine.snapshot()
        assert not snap.rewrites.has_gated and gate_tables.tables_of(snap) is None
        assert snap.resolve_set(2, "openfga/openfga", ANCHOR) is None
        c = counters(engine)
        assert not any(k.startswith(("gate_checks", "gate_positions", "gate_seconds",
                                     "rewrite_oracle")) for k in c), c
        assert c["rewrite_route_device"] == c["rewrite_checks_rewritten"] == 2
        assert c["gate_nodes_gate"] == c["gate_reach_rows"] == 0
    finally:
        engine.close()


# -- /metrics and the spans --------------------------------------------------------------

FAMILIES = {
    "keto_check_gate_checks_total": ("served", gates.SERVED),
    "keto_check_gate_positions_total": ("kind", gates.POSITION_KINDS),
    "keto_check_gate_seconds_total": ("phase", gates.PHASES),
    "keto_check_rewrite_oracle_total": ("reason", gates.ORACLE_REASONS),
    "keto_check_rewrite_oracle_seconds_total": (None, ()),
    "keto_snapshot_gate_nodes": ("kind", gates.NODE_KINDS),
    "keto_snapshot_gate_reach_rows": (None, ()),
    "keto_snapshot_set_nodes": (None, ()),
}


@pytest.fixture(scope="module")
def daemon():
    from test_dispatch_tracing import _boot

    d = _boot()
    yield d
    d.shutdown()


@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_every_family_is_on_metrics_of_a_fresh_daemon_at_0_with_its_label_set(daemon, family):
    from test_dispatch_tracing import _scrape

    fams = _scrape(daemon)
    label, values = FAMILIES[family]
    samples = [(labels, v) for name, labels, v in fams[family]["samples"] if name == family]
    if label is None:
        assert [labels for labels, _ in samples] == [{}]
    else:
        assert sorted(labels[label] for labels, _ in samples) == sorted(values)
    if fams[family]["type"] == "counter":
        assert {v for _, v in samples} == {0.0}


def test_the_families_are_declared_by_the_module_that_counts_them():
    from keto_tpu.x.metrics import MetricsRegistry, parse_exposition

    m = MetricsRegistry()
    gates.gate_metrics(m, lambda: ({"gate_checks_device": 7, "gate_seconds_combine": 0.25},
                                   {"gate_nodes_operand": 4}, {}))
    fams = parse_exposition(m.render())
    assert set(FAMILIES) - {"keto_check_rewrite_oracle_total"} <= set(fams)
    rows = {tuple(labels.items()): v for _, labels, v in
            fams["keto_check_gate_checks_total"]["samples"]}
    assert rows == {(("served", "device"),): 7.0, (("served", "oracle"),): 0.0}


def test_the_resolve_and_fill_spans_carry_gated_and_positions_under_a_session():
    session = FakeSession()
    session.open = True
    clock = DispatchClock(session)
    p = store_of(BLOCKLIST, BLOCKLIST_ROWS)
    engine = quiet_engine(p)
    bind_dispatch_clock(clock)
    try:
        qs = [("issues", "1", "view", u) for u in FOLK] + [("repos", "acme/site", "reader", "gina")]
        clock.round(len(qs), 0)
        engine.batch_check([ask(*q) for q in qs])
        spans = [(name, args) for name, args in session.made if "gated" in args]
        by_state = {name for name, _ in spans}
        assert {"keto.dispatch.resolve", "keto.dispatch.fill"} <= by_state
        for name, args in spans:
            assert args["gated"] == len(FOLK) and args["positions"] == 2 * len(FOLK) + 1, (name, args)
        # the resolve before the chunk was split says nothing of gates yet
        first = next(args for name, args in session.made if name == "keto.dispatch.resolve")
        assert "gated" not in first
        clock.round(1, 0)
        clock.enter(RESOLVE)
        assert "gated" not in session.made[-1][1]  # a new round starts clean
    finally:
        bind_dispatch_clock(None)
        engine.close()


def test_an_oracle_hand_off_is_a_zero_length_mark_with_its_reason():
    session = FakeSession()
    session.open = True
    clock = DispatchClock(session)
    p = store_of(CYCLE, CYCLE_ROWS)
    engine = quiet_engine(p)
    bind_dispatch_clock(clock)
    try:
        clock.round(2, 0)
        engine.batch_check([ask("doc", "a", "viewer", "ann"), ask("doc", "b", "viewer", "ann")])
        marks = [args for name, args in session.made if name == "keto.gate.oracle"]
        assert marks == [{"reason": "cycle", "n": 2}]
    finally:
        bind_dispatch_clock(None)
        engine.close()


def test_the_clock_off_the_dispatch_thread_takes_the_calls():
    from keto_tpu.x.timeline import dispatch_clock

    bind_dispatch_clock(None)
    clk = dispatch_clock()
    clk.gates(3, 9)
    clk.enter(FILL)
    clk.mark("keto.gate.oracle", reason="cycle", n=1)
