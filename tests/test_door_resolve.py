"""Resolve at the door (keto_tpu/check/frame.py ``QueryFrame.resolve_at_door``,
servers/rest.py ``_frame_body``, check/dispatch.py ``_records_of``): a framed
``/check/batch`` body is resolved to raw node ids on the thread that framed
it, and the dispatch thread takes slices of those arrays where its round's
snapshot still has the tables that gave them.

Door ids may change nothing: ``(sd, tg, multi)`` and the served answers are
those of the same frame without them, for every kind of record, for rounds
that mix sources, for a frame cut over rounds and across a write. And they
are only good against the very object that gave them: after a rebuild, a
compaction fold or a namespace reload the thread resolves the records
itself, as it always did."""

import gc
import json
import random
import sys
import threading
import weakref

import numpy as np
import pytest

from keto_tpu import namespace as namespace_pkg
from keto_tpu.check import CheckEngine
from keto_tpu.check.frame import QueryBatch, QueryFrame
from keto_tpu.check.tpu_engine import TpuCheckEngine
from keto_tpu.config.provider import Config
from keto_tpu.driver.registry import Registry
from keto_tpu.graph.interner import ExtendedInterned
from keto_tpu.graph.native import FrameTable
from keto_tpu.persistence.memory import MemoryPersister
from keto_tpu.relationtuple.model import RelationTuple, SubjectID, SubjectSet
from keto_tpu.servers.rest import READ, RestApp

NAMESPACES = [namespace_pkg.Namespace(id=1, name="docs"), namespace_pkg.Namespace(id=2, name="groups")]
MANAGER = namespace_pkg.MemoryManager(NAMESPACES)

TABLE = FrameTable.build(MANAGER)

pytestmark = pytest.mark.skipif(TABLE is None, reason="native library not built")


def T(ns, obj, rel, sub):
    return RelationTuple(namespace=ns, object=obj, relation=rel, subject=sub)


def make_frame(tuples, snap=None) -> QueryFrame:
    """``tuples`` framed under ``MANAGER`` from the body a client would send;
    resolved at the door against ``snap`` when one is given."""
    body = json.dumps({"tuples": [t.to_json() for t in tuples]}).encode()
    got = TABLE.frame(body, 65536)
    assert not isinstance(got, str), got
    frame = QueryFrame(*got, body, MANAGER)
    if snap is not None:
        frame.resolve_at_door(snap)
        assert frame.door is not None and frame.door[0] is snap.interned
    return frame


def base_rows():
    """docs d<i> are viewable by members of group g<i % 5>; group g<j> has
    users u<j>_0..3 and, nested under it, group n<j> with user v<j>."""
    rows = [T("groups", f"g{j}", "member", SubjectID(f"u{j}_{k}")) for j in range(5) for k in range(4)]
    rows += [T("groups", f"g{j}", "member", SubjectSet("groups", f"n{j}", "member")) for j in range(5)]
    rows += [T("groups", f"n{j}", "member", SubjectID(f"v{j}")) for j in range(5)]
    rows += [T("docs", f"d{i}", "view", SubjectSet("groups", f"g{i % 5}", "member")) for i in range(40)]
    return rows


class World:
    def __init__(self):
        self.store = MemoryPersister(MANAGER)
        self.store.write_relation_tuples(*base_rows())
        # never compacts on its own: a test folds when it means to
        self.tpu = TpuCheckEngine(
            self.store, MANAGER, compact_after_s=3600.0, overlay_edge_budget=1 << 20
        )
        self.oracle = CheckEngine(self.store)
        self.rng = random.Random(35)

    def literal(self, n):
        return [
            T("docs", f"d{self.rng.randrange(40)}", "view",
              SubjectID(f"u{self.rng.randrange(5)}_{self.rng.randrange(4)}"))
            for _ in range(n)
        ]

    def counted(self):
        c = self.tpu.maintenance.snapshot()
        return c.get("resolve_tuples_door", 0), c.get("resolve_tuples_thread", 0)

    def expected(self, tuples):
        return [self.oracle.subject_is_allowed(t) for t in tuples]

    def served(self, batch):
        gen, _ = self.tpu.batch_check_stream_with_token(_OneBatch(batch), ordered=True)
        return np.concatenate(list(gen)).tolist()


class _OneBatch:
    """A stream source with the ``take`` API that hands over one batch."""

    def __init__(self, batch):
        self._batch = batch

    def take(self, cap):
        batch, self._batch = self._batch, []
        return batch


@pytest.fixture
def world():
    w = World()
    yield w
    w.tpu.close()


def assert_same_rows(got, want):
    (sd, tg, multi), (sd_w, tg_w, multi_w) = got, want
    assert np.array_equal(sd, sd_w) and np.array_equal(tg, tg_w)
    assert multi.keys() == multi_w.keys()
    for i in multi:
        assert np.array_equal(multi[i][0], multi_w[i][0])
        assert np.array_equal(multi[i][1], multi_w[i][1])


# -- parity ------------------------------------------------------------------------
#
# A case is ``case(world) -> (sources, rounds, between)``: the sources the
# queries arrive in, each ``(kind, tuples)`` (``door``: a frame resolved at
# the door; ``plain``: a frame without ids; ``list``: tuples), the rounds
# they reach the engine in, each a list of parts ``(source, a, b)``, and
# what happens between framing and the first round.


def _one_frame(tuples):
    return [("door", tuples)], [[(0, 0, len(tuples))]], None


def case_literal(w):
    return _one_frame(w.literal(24))


def case_special(w):
    return _one_frame(w.literal(3) + [
        T("docs", "", "view", SubjectID("u1_1")),  # pattern: any doc
        T("", "d1", "view", SubjectID("u1_1")),  # pattern: any namespace
        T("docs", "d1", "", SubjectID("u1_1")),
    ] + w.literal(2))


def case_dead(w):
    return _one_frame([
        T("nope", "d1", "view", SubjectID("u1_1")),  # unknown namespace
        T("docs", "d1", "view", SubjectSet("nope", "g1", "member")),  # and the subject set's
    ] + w.literal(4))


def case_no_target(w):
    return _one_frame(w.literal(2) + [
        T("docs", "d1", "view", SubjectSet("", "g1", "member")),
        T("docs", "d6", "view", SubjectSet("", "", "")),
    ])


def case_unknown_object_or_user(w):
    return _one_frame([
        T("docs", "no-such-doc", "view", SubjectID("u1_1")),
        T("docs", "d1", "view", SubjectID("nobody")),
        T("docs", "no-such-doc", "view", SubjectID("nobody")),
        T("docs", "d1", "no-such-relation", SubjectID("u1_1")),
    ] + w.literal(3))


def case_subject_set_subjects(w):
    return _one_frame([
        T("docs", "d1", "view", SubjectSet("groups", "g1", "member")),  # direct
        T("docs", "d1", "view", SubjectSet("groups", "n1", "member")),  # nested
        T("docs", "d1", "view", SubjectSet("groups", "g2", "member")),  # another doc's
        T("docs", "d1", "view", SubjectSet("groups", "g1", "owner")),  # no such set
        T("groups", "g3", "member", SubjectSet("groups", "n3", "member")),
    ])


def case_mixed_round(w):
    """One round: a single, a door-resolved frame, a plain frame cut at
    both ends, a list, and the tail of a second door-resolved frame."""
    sources = [("list", w.literal(1)), ("door", w.literal(10)), ("plain", w.literal(8)),
               ("list", w.literal(5)), ("door", w.literal(9))]
    return sources, [[(0, 0, 1), (1, 0, 10), (2, 1, 7), (3, 0, 5), (4, 5, 9)]], None


def case_cut_over_rounds(w):
    return [("door", w.literal(50))], [[(0, a, min(a + 16, 50))] for a in range(0, 50, 16)], None


def case_write_between(w):
    """A new user in an old group and a new group on an old doc, both
    acknowledged after framing: nodes the base tables never held, found at
    the round through the miss path."""
    tuples = [
        T("docs", "d2", "view", SubjectID("hired-today")),
        T("docs", "d3", "view", SubjectSet("groups", "made-today", "member")),
        T("docs", "d3", "view", SubjectID("hired-today")),
    ] + w.literal(5)

    def between():
        w.store.write_relation_tuples(
            T("groups", "g2", "member", SubjectID("hired-today")),
            T("docs", "d3", "view", SubjectSet("groups", "made-today", "member")),
        )

    return [("door", tuples)], [[(0, 0, len(tuples))]], between


PARITY = {
    "literal": case_literal,
    "special": case_special,
    "dead": case_dead,
    "no_target": case_no_target,
    "unknown object or user": case_unknown_object_or_user,
    "subject-set subjects": case_subject_set_subjects,
    "a round mixing a door frame, a plain frame and lists": case_mixed_round,
    "a frame cut over several rounds": case_cut_over_rounds,
    "a write acknowledged between framing and the round": case_write_between,
}


@pytest.mark.parametrize("case", sorted(PARITY))
def test_door_ids_change_neither_rows_nor_answers(world, case):
    sources, rounds, between = PARITY[case](world)
    framed_on = world.tpu.snapshot()
    with_ids = [
        chunk if kind == "list" else make_frame(chunk, framed_on if kind == "door" else None)
        for kind, chunk in sources
    ]
    without = [chunk if kind == "list" else make_frame(chunk) for kind, chunk in sources]
    if between is not None:
        between()
    snap = world.tpu.snapshot()
    assert snap.interned is framed_on.interned  # an overlay at most: the same tables
    assert (snap is not framed_on) == (between is not None)

    def batches(srcs):
        return [QueryBatch([(srcs[k], a, b) for k, a, b in parts]) for parts in rounds]

    tuples = [t for parts in rounds for k, a, b in parts for t in sources[k][1][a:b]]
    n_door = sum(b - a for parts in rounds for k, a, b in parts if sources[k][0] == "door")
    door0, thread0 = world.counted()
    got = [world.tpu.dispatch._resolve_bulk(snap, b) for b in batches(with_ids)]
    door1, thread1 = world.counted()
    assert (door1 - door0, thread1 - thread0) == (n_door, len(tuples) - n_door)
    want = [world.tpu.dispatch._resolve_bulk(snap, b) for b in batches(without)]
    assert world.counted() == (door1, thread1 + len(tuples))
    for rows, rows_without in zip(got, want):
        assert_same_rows(rows, rows_without)
    flat = [np.concatenate([rows[k] for rows in got]) for k in (0, 1)]
    by_list = world.tpu.dispatch._resolve_bulk(snap, tuples)
    assert np.array_equal(flat[0], by_list[0]) and np.array_equal(flat[1], by_list[1])
    served = [x for b in batches(with_ids) for x in world.served(b)]
    assert served == [x for b in batches(without) for x in world.served(b)]
    assert served == world.expected(tuples)
    if between is not None:
        assert served[:3] == [True, True, False]  # the write is seen


# -- identity ----------------------------------------------------------------------


def _rebuild(w):
    """A write no delta can carry rebuilds in full, into new tables: here
    the first member of a group that was a sink until now."""
    w.store.write_relation_tuples(T("docs", "d1", "view", SubjectSet("groups", "fresh", "member")))
    w.tpu.snapshot()
    w.store.write_relation_tuples(T("groups", "fresh", "member", SubjectID("u0_0")))
    snap = w.tpu.snapshot()
    assert type(snap.interned) is not ExtendedInterned
    return snap


def _fold(w):
    w.store.write_relation_tuples(
        T("groups", "g2", "member", SubjectID("hired-today")),
        T("docs", "d3", "view", SubjectSet("groups", "made-today", "member")),
    )
    folded = w.tpu._compact_locked(w.tpu.snapshot())
    assert isinstance(folded.interned, ExtendedInterned)
    w.tpu._snapshot = folded
    return folded


@pytest.mark.parametrize("event", ["rebuild", "compaction fold"])
def test_ids_from_replaced_tables_send_the_frame_down_the_threads_path(world, event):
    queries = world.literal(12) + [
        T("docs", "d2", "view", SubjectID("hired-today")),
        T("docs", "d1", "view", SubjectID("u0_0")),
        T("docs", "d3", "view", SubjectSet("groups", "made-today", "member")),
    ]
    framed_on = world.tpu.snapshot()
    stale = make_frame(queries, framed_on)
    snap = {"rebuild": _rebuild, "compaction fold": _fold}[event](world)
    assert snap.interned is not framed_on.interned
    want = world.expected(queries)
    assert want[-3:] == {"rebuild": [False, True, False], "compaction fold": [True, False, True]}[event]
    door0, thread0 = world.counted()
    rows = world.tpu.dispatch._resolve_bulk(snap, QueryBatch([(stale, 0, 15)]))
    assert world.counted() == (door0, thread0 + 15)
    assert_same_rows(rows, world.tpu.dispatch._resolve_bulk(snap, queries))
    assert world.served(QueryBatch([(stale, 0, 15)])) == want
    # framed again, against the tables now served, the door's ids are used:
    # an ExtendedInterned answers for its base and the round finds the
    # folded nodes through the miss path
    fresh = make_frame(queries, snap)
    door0, thread0 = world.counted()
    assert_same_rows(world.tpu.dispatch._resolve_bulk(snap, QueryBatch([(fresh, 0, 15)])), rows)
    assert world.counted() == (door0 + 15, thread0)
    assert world.served(QueryBatch([(fresh, 0, 15)])) == want
    # and one round may hold both
    mixed = QueryBatch([(stale, 3, 9), (fresh, 0, 15), (stale, 9, 15)])
    assert world.served(mixed) == want[3:9] + want + want[9:15]


def test_namespace_hot_reload_between_framing_and_the_round():
    """The tables are the same object, the namespace ids in the records are
    not the manager's any more (here SWAPPED): the frame is decoded and
    resolved by name, on the thread."""
    swapped = namespace_pkg.MemoryManager(
        [namespace_pkg.Namespace(id=2, name="docs"), namespace_pkg.Namespace(id=1, name="groups")]
    )
    current = {"nm": swapped}
    store = MemoryPersister(lambda: current["nm"])
    store.write_relation_tuples(*base_rows())
    eng = TpuCheckEngine(store, lambda: current["nm"])
    try:
        snap = eng.snapshot()
        queries = [T("docs", "d1", "view", SubjectID("u1_0")), T("groups", "g1", "member", SubjectID("v1")),
                   T("docs", "d1", "view", SubjectID("u2_0"))]
        stale = make_frame(queries, snap)  # ids of another manager, resolved all the same
        assert eng.dispatch._frame_blocker(snap, QueryBatch([(stale, 0, 3)])) == "reload"
        gen, _ = eng.batch_check_stream_with_token(_OneBatch(QueryBatch([(stale, 0, 3)])), ordered=True)
        assert np.concatenate(list(gen)).tolist() == [True, True, False]
        c = eng.maintenance.snapshot()
        assert (c.get("resolve_tuples_door", 0), c["resolve_tuples_thread"]) == (0, 3)
    finally:
        eng.close()


def test_a_snapshot_without_the_bulk_entry_point_leaves_the_frame_as_it_was(world):
    class NoNative:
        interned = object()

    class Rejects:
        class interned:
            @staticmethod
            def resolve_queries(buf, n):
                return None

    for snap in (None, NoNative(), Rejects()):
        frame = make_frame(world.literal(3))
        frame.resolve_at_door(snap)
        assert frame.door is None


# -- lifetime ----------------------------------------------------------------------


def test_the_native_tables_outlive_every_frame_that_points_at_them():
    w = World()
    queries = w.literal(64)
    snap = w.tpu.snapshot()
    frame = make_frame(queries, snap)
    tables = weakref.ref(snap.interned)
    start, sub = frame.door[1].copy(), frame.door[2].copy()
    # a rebuild replaces the snapshot, then the engine goes altogether
    _rebuild(w)
    w.tpu.close()
    del w, snap
    gc.collect()
    assert tables() is frame.door[0]  # the frame alone keeps them
    again = frame.door[0].resolve_queries(frame.buf, frame.n)  # a late pool thread still probes live memory
    assert np.array_equal(again[0], start) and np.array_equal(again[1], sub)
    del frame, again
    gc.collect()
    assert tables() is None  # and nothing else did


def test_eight_threads_at_the_door_give_the_arrays_one_thread_gives():
    """More threads than cores, a short switch interval, one set of native
    tables probed with the GIL released."""
    store = MemoryPersister(MANAGER)
    rng = random.Random(8)
    rows = [T("groups", f"g{j}", "member", SubjectID(f"u{k}")) for j in range(300) for k in rng.sample(range(2000), 8)]
    rows += [T("docs", f"d{i}", "view", SubjectSet("groups", f"g{rng.randrange(300)}", "member")) for i in range(3000)]
    store.write_relation_tuples(*rows)
    eng = TpuCheckEngine(store, MANAGER)
    try:
        snap = eng.snapshot()
        bodies = [
            [T("docs", f"d{rng.randrange(3300)}", "view", SubjectID(f"u{rng.randrange(2200)}")) for _ in range(512)]
            for _ in range(8)
        ]
        want = []
        for chunk in bodies:
            f = make_frame(chunk, snap)
            want.append((f.door[1], f.door[2]))
        assert any((s < 0).any() for s, _ in want) and any((s >= 0).any() for s, _ in want)
        frames = [[make_frame(chunk) for _ in range(20)] for chunk in bodies]
        go = threading.Barrier(8)

        def door(k):
            go.wait(timeout=30)
            for f in frames[k]:
                f.resolve_at_door(eng.peek_snapshot())

        old = sys.getswitchinterval()
        sys.setswitchinterval(1e-5)
        try:
            threads = [threading.Thread(target=door, args=(k,), daemon=True) for k in range(8)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=60)
            assert not any(t.is_alive() for t in threads)
        finally:
            sys.setswitchinterval(old)
        for k, (start, sub) in enumerate(want):
            for f in frames[k]:
                assert f.door[0] is snap.interned
                assert np.array_equal(f.door[1], start) and np.array_equal(f.door[2], sub)
    finally:
        eng.close()


# -- through RestApp ---------------------------------------------------------------


def _sample(text: str, where: str) -> float:
    (line,) = [ln for ln in text.splitlines() if ln.startswith('keto_check_resolve_tuples_total{where="%s"}' % where)]
    return float(line.split()[-1])


def test_rest_resolves_a_framed_body_at_the_door_and_counts_where():
    reg = Registry(Config(overrides={"namespaces": [{"id": ns.id, "name": ns.name} for ns in NAMESPACES]}))
    try:
        reg.relation_tuple_manager().write_relation_tuples(*base_rows())
        app = RestApp(reg, READ)
        queries = [
            T("docs", "d1", "view", SubjectID("u1_0")), T("docs", "d1", "view", SubjectID("u2_0")),
            T("docs", "d7", "view", SubjectID("v2")), T("nope", "d1", "view", SubjectID("u1_0")),
        ]
        body = json.dumps({"tuples": [t.to_json() for t in queries]}).encode()
        want = {"results": [True, False, True, False]}
        m = reg.metrics()
        assert reg.check_batcher().peek_snapshot() is None  # nothing built yet: nothing to peek
        status, payload, _ = app.handle("POST", "/check/batch", {}, body, {})
        assert (status, payload) == (200, want)
        text = m.render()
        assert (_sample(text, "door"), _sample(text, "thread")) == (0, 4)
        assert reg.check_batcher().peek_snapshot() is reg.permission_engine().snapshot()
        status, payload, _ = app.handle("POST", "/check/batch", {}, body, {})
        assert (status, payload) == (200, want)
        text = m.render()
        assert (_sample(text, "door"), _sample(text, "thread")) == (4, 4)
        # a body the framer declines, and a single check, are the thread's
        status, payload, _ = app.handle("POST", "/check/batch", {}, body.replace(b"{", b'{"x": 1, ', 1), {})
        assert (status, payload) == (200, want)
        status, _payload, _ = app.handle(
            "GET", "/check", {"namespace": ["docs"], "object": ["d1"], "relation": ["view"], "subject_id": ["u1_0"]}, b"", {}
        )
        assert status == 200
        text = m.render()
        assert (_sample(text, "door"), _sample(text, "thread")) == (4, 9)
    finally:
        reg.close()


def test_looking_at_a_cold_tenant_faults_nothing_in():
    reg = Registry(Config(overrides={"namespaces": [{"id": ns.id, "name": ns.name} for ns in NAMESPACES]}))
    try:
        ctx = reg.tenant_pool().get("acme")
        ctx.relation_tuple_manager().write_relation_tuples(*base_rows())
        app = RestApp(reg, READ)
        body = json.dumps({"tuples": [T("docs", "d1", "view", SubjectID("u1_0")).to_json()] * 3}).encode()
        assert ctx.check_batcher().peek_snapshot() is None
        assert (ctx.resident, ctx.faultins) == (False, 0)
        headers = {"x-keto-tenant": "acme"}
        for _ in range(2):
            status, payload, _ = app.handle("POST", "/check/batch", {}, body, headers)
            assert (status, payload) == (200, {"results": [True] * 3})
        assert ctx.faultins == 1
        engine = ctx.permission_engine()
        if hasattr(engine, "peek_snapshot"):  # the tenant's own engine, its own tables
            assert ctx.check_batcher().peek_snapshot() is engine.peek_snapshot() is not None
            c = engine.maintenance.snapshot()
            assert (c.get("resolve_tuples_door", 0), c.get("resolve_tuples_thread", 0)) == (3, 3)
    finally:
        reg.close()
