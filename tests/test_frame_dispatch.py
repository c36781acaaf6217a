"""A framed ``/check/batch`` body on its way through the batcher and the
engine (keto_tpu/check/frame.py, driver/batch.py ``_Round``,
check/dispatch.py ``_resolve_bulk``): rounds that mix singles with a
framed sub-slice, a frame cut over several rounds, deadlines, the retry
path, engines that want objects, a namespace reload between framing and
resolve, and the range fill."""

import json
import random
import threading
import time
from concurrent.futures import Future

import numpy as np
import pytest

from keto_tpu import namespace as namespace_pkg
from keto_tpu.check import CheckEngine
from keto_tpu.check import frame as frame_mod
from keto_tpu.check.frame import QueryBatch, QueryFrame
from keto_tpu.check.tpu_engine import TpuCheckEngine
from keto_tpu.driver.batch import BATCH, INTERACTIVE, CheckBatcher, _Item, _Round
from keto_tpu.graph.native import FrameTable
from keto_tpu.persistence.memory import MemoryPersister
from keto_tpu.relationtuple.model import RelationTuple, SubjectID, SubjectSet
from keto_tpu.x.errors import ErrDeadlineExceeded

NAMESPACES = [namespace_pkg.Namespace(id=1, name="docs"), namespace_pkg.Namespace(id=2, name="groups")]


def T(ns, obj, rel, sub):
    return RelationTuple(namespace=ns, object=obj, relation=rel, subject=sub)


def make_frame(manager, tuples) -> QueryFrame:
    body = json.dumps({"tuples": [t.to_json() for t in tuples]}).encode()
    table = FrameTable.build(manager)
    if table is None:
        pytest.skip("native library not built")
    got = table.frame(body, 65536)
    assert not isinstance(got, str), got
    return QueryFrame(*got, body, manager)


def wait_for(cond, timeout=10.0, interval=0.01, msg="condition"):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if cond():
            return
        time.sleep(interval)
    raise AssertionError(f"timed out waiting for {msg}")


@pytest.fixture
def world():
    """A store, its engines and queries with known answers: docs d<i> are
    viewable by members of group g<i % 5>, group g<j> has users u<j>_0..3."""
    manager = namespace_pkg.MemoryManager(NAMESPACES)
    p = MemoryPersister(manager)
    rows = [T("groups", f"g{j}", "member", SubjectID(f"u{j}_{k}")) for j in range(5) for k in range(4)]
    rows += [T("docs", f"d{i}", "view", SubjectSet("groups", f"g{i % 5}", "member")) for i in range(40)]
    p.write_relation_tuples(*rows)
    tpu = TpuCheckEngine(p, manager)
    rng = random.Random(11)

    def queries(n):
        out = []
        for _ in range(n):
            i, j, k = rng.randrange(40), rng.randrange(5), rng.randrange(4)
            out.append(T("docs", f"d{i}", "view", SubjectID(f"u{j}_{k}")))
        return out

    def expected(tuples):
        return [int(t.object[1:]) % 5 == int(t.subject.id[1:].split("_")[0]) for t in tuples]

    class World:
        pass

    w = World()
    w.manager, w.store, w.tpu, w.queries, w.expected = manager, p, tpu, queries, expected
    yield w
    tpu.close()


def slices_landed(engine) -> int:
    return sum(engine.route_slice_counts().values())


# -- the batcher -------------------------------------------------------------------


def test_round_mixing_singles_with_a_framed_sub_slice_resolves_as_one_slice(world):
    """Interactive singles ride ahead of a framed sub-slice in ONE round,
    and the engine resolves that round as one slice."""
    frame_mod.MATERIALIZED.clear()
    b = CheckBatcher(world.tpu, batch_size=64, window_ms=200.0, batch_sub_slice=32)
    chunk = world.queries(24)
    singles = world.queries(3)
    framed = make_frame(world.manager, chunk)
    world.tpu.batch_check(singles)  # the snapshot is built; slices count from here
    before = slices_landed(world.tpu)
    res = {}
    threads = [
        threading.Thread(target=lambda: res.update(batch=b.check_batch(framed, timeout=30, lane=BATCH)))
    ] + [
        threading.Thread(target=lambda i=i, t=t: res.update({i: b.check(t, timeout=30)}))
        for i, t in enumerate(singles)
    ]
    for t in threads:
        t.start()
    wait_for(lambda: b.lane_depths == {INTERACTIVE: 3, BATCH: 24}, msg="all queued")
    b.start()  # the collector finds everything there: one round
    try:
        for t in threads:
            t.join(timeout=30)
    finally:
        b.stop()
    assert res["batch"] == world.expected(chunk)
    assert [res[i] for i in range(3)] == world.expected(singles)
    assert b.clock.rounds == 1
    assert slices_landed(world.tpu) - before == 1
    assert frame_mod.MATERIALIZED == {}  # nobody asked for objects


@pytest.mark.parametrize("batch_size, n_rounds", [(7, 8), (16, 4)])
def test_framed_item_cut_over_several_rounds(world, batch_size, n_rounds):
    """50 framed tuples on a quiet interactive lane: cut at the round's own
    cap, be that the sub-slice (7 a round) or wider (16, 16, 16, 2)."""
    b = CheckBatcher(world.tpu, batch_size=batch_size, window_ms=0.5, batch_sub_slice=7)
    chunk = world.queries(50)
    framed = make_frame(world.manager, chunk)
    b.start()
    try:
        got, token = b.check_batch_with_token(framed, timeout=30, lane=BATCH)
    finally:
        b.stop()
    assert got == world.expected(chunk)
    assert token == world.tpu.snapshot().snapshot_id
    assert (b.clock.rounds, b.clock.round_tuples) == (n_rounds, 50)


def test_round_take_cuts_across_segments_by_count(world):
    chunk = world.queries(10)
    framed = make_frame(world.manager, chunk)
    single = world.queries(1)
    a = _Item(single, Future(), None, False, None, INTERACTIVE)
    f = _Item(framed, Future(), None, False, None, BATCH)
    round_ = _Round([(a, 0, 1), (f, 2, 6)], expire=lambda item: None)
    first = round_.take(4)
    assert isinstance(first, QueryBatch) and len(first) == 4
    assert first.parts == [(single, 0, 1), (framed, 2, 5)]
    second = round_.take(4)
    assert second.parts == [(framed, 5, 8)]
    assert len(round_.take(4)) == 0
    assert round_.offs == [0, 1]
    # a slice landing over [2, 7) answers the frame's tuples 3..7
    assert [(i is f, idx, lo, hi) for i, idx, lo, hi in round_.riders(2, 5)] == [
        (True, range(3, 8), 0, 5)
    ]
    # and one over [0, 3) both riders
    assert [(idx, lo, hi) for _i, idx, lo, hi in round_.riders(0, 3)] == [
        (range(0, 1), 0, 1), (range(2, 4), 1, 3)
    ]


def test_round_of_plain_lists_hands_the_engine_lists(world):
    one, two = world.queries(1), world.queries(2)
    a = _Item(one, Future(), None, False, None, INTERACTIVE)
    c = _Item(two, Future(), None, False, None, INTERACTIVE)
    assert _Round([(a, 0, 1)], expire=None).take(8) is one  # no copy of a single
    got = _Round([(a, 0, 1), (c, 0, 2)], expire=None).take(8)
    assert got == one + two and isinstance(got, list)


def test_deadline_expiry_of_a_queued_framed_item_is_a_504(world):
    class Gate:
        def __init__(self):
            self.release = threading.Event()

        def batch_check_with_token(self, tuples, **kw):
            assert self.release.wait(10)
            return [True] * len(tuples), 1

    gate = Gate()
    b = CheckBatcher(gate, batch_size=8, window_ms=0.5, batch_sub_slice=8)
    framed = make_frame(world.manager, world.queries(6))
    b.start()
    try:
        first = threading.Thread(target=lambda: b.check(world.queries(1)[0], timeout=30))
        first.start()
        wait_for(lambda: b.clock.rounds == 1, msg="collector inside round 1")
        with pytest.raises(ErrDeadlineExceeded):
            b.check_batch(framed, deadline=time.monotonic() + 0.05, lane=BATCH)
        gate.release.set()
        first.join(timeout=10)
        wait_for(lambda: b.deadline_drop_count == 1, msg="expired item dropped at dispatch")
    finally:
        gate.release.set()
        b.stop()


def test_failed_stream_retries_a_framed_item_as_objects(world):
    """A stream that dies after landing part of a frame: the rest is
    retried through the plain batch call, as ``RelationTuple``s, and no
    index is answered twice."""
    frame_mod.MATERIALIZED.clear()
    chunk = world.queries(20)
    framed = make_frame(world.manager, chunk)
    want = world.expected(chunk)
    retried = []

    class Flaky:
        def batch_check_stream_with_token(self, source, ordered=False, **kw):
            def gen():
                batch = source.take(8)
                assert isinstance(batch, QueryBatch)
                yield 0, np.array(want[:8])
                raise RuntimeError("device fell over")

            return gen(), 5

        def batch_check_with_token(self, tuples, **kw):
            retried.extend(tuples)
            return [world.expected([t])[0] for t in tuples], 6

    b = CheckBatcher(Flaky(), batch_size=32, window_ms=0.5, batch_sub_slice=32)
    b.start()
    try:
        got = b.check_batch(framed, timeout=30, lane=BATCH)
    finally:
        b.stop()
    assert got == want
    # only what the frame had handed over and not got back: 8 were
    # answered of the 20 the round held
    assert retried == chunk[8:]
    assert frame_mod.MATERIALIZED == {"retry": 1}


def test_cpu_oracle_engine_is_fed_a_frame_as_objects(world):
    frame_mod.MATERIALIZED.clear()
    chunk = world.queries(30)
    framed = make_frame(world.manager, chunk)
    b = CheckBatcher(CheckEngine(world.store), batch_size=16, window_ms=0.5, batch_sub_slice=16)
    b.start()
    try:
        assert b.check_batch(framed, timeout=30, lane=BATCH) == world.expected(chunk)
    finally:
        b.stop()
    assert frame_mod.MATERIALIZED == {"oracle": 2}  # once a round


def test_degraded_engine_is_fed_a_frame_as_objects(world):
    frame_mod.MATERIALIZED.clear()
    chunk = world.queries(30)
    framed = make_frame(world.manager, chunk)
    world.tpu.batch_check(chunk[:1])
    world.tpu._degraded = True
    world.tpu._probe_after = time.monotonic() + 60
    before = world.tpu.maintenance.snapshot().get("fallback_checks", 0)
    b = CheckBatcher(world.tpu, batch_size=64, window_ms=0.5, batch_sub_slice=64)
    b.start()
    try:
        assert b.check_batch(framed, timeout=30, lane=BATCH) == world.expected(chunk)
    finally:
        b.stop()
    assert world.tpu.maintenance.snapshot()["fallback_checks"] - before == 30
    assert frame_mod.MATERIALIZED == {"oracle": 1}


# -- the engine ---------------------------------------------------------------------


def test_namespace_reload_between_framing_and_resolve(make_persister):
    """The frame's ids are those of the manager it was framed under. After
    a reload the engine's manager is another object (here with the ids
    SWAPPED, the worst case): the frame is decoded and resolved by name."""
    frame_mod.MATERIALIZED.clear()
    old = namespace_pkg.MemoryManager(NAMESPACES)
    new = namespace_pkg.MemoryManager(
        [namespace_pkg.Namespace(id=2, name="docs"), namespace_pkg.Namespace(id=1, name="groups")]
    )
    current = {"nm": new}
    p = MemoryPersister(lambda: current["nm"])
    p.write_relation_tuples(
        T("docs", "d", "view", SubjectSet("groups", "g", "member")),
        T("groups", "g", "member", SubjectID("u")),
    )
    eng = TpuCheckEngine(p, lambda: current["nm"])
    try:
        queries = [T("docs", "d", "view", SubjectID("u")), T("groups", "g", "member", SubjectID("x"))]
        snap = eng.snapshot()
        if not hasattr(snap.interned, "resolve_queries"):
            pytest.skip("native interner not in use")
        stale = make_frame(old, queries)
        assert eng.dispatch._frame_blocker(snap, QueryBatch([(stale, 0, 2)])) == "reload"
        gen, _ = eng.batch_check_stream_with_token(_OneBatch(QueryBatch([(stale, 0, 2)])), ordered=True)
        assert np.concatenate(list(gen)).tolist() == [True, False]
        assert frame_mod.MATERIALIZED == {"reload": 1}
        # framed under the current manager it resolves as it is
        fresh = make_frame(new, queries)
        assert eng.dispatch._frame_blocker(snap, QueryBatch([(fresh, 0, 2)])) is None
        gen, _ = eng.batch_check_stream_with_token(_OneBatch(QueryBatch([(fresh, 0, 2)])), ordered=True)
        assert np.concatenate(list(gen)).tolist() == [True, False]
        assert frame_mod.MATERIALIZED == {"reload": 1}
    finally:
        eng.close()


class _OneBatch:
    """A stream source with the ``take`` API that hands over one batch."""

    def __init__(self, batch):
        self._batch = batch

    def take(self, cap):
        batch, self._batch = self._batch, []
        return batch


def test_stream_cuts_a_frame_into_slices_by_count(world):
    chunk = world.queries(200)
    framed = make_frame(world.manager, chunk)
    gen, _ = world.tpu.batch_check_stream_with_token(
        _Ranges(framed, step=96), ordered=False, slice_cap=64
    )
    got = [None] * 200
    widths = []
    for off, out in gen:
        got[off:off + len(out)] = out.tolist()
        widths.append(len(out))
    assert got == world.expected(chunk)
    assert max(widths) <= 64 and sum(widths) == 200


class _Ranges:
    """Hands a frame over in ranges of ``step``, as a batcher round would."""

    def __init__(self, framed, step):
        self._frame, self._step, self._at = framed, step, 0

    def take(self, cap):
        a = self._at
        b = min(a + min(cap, self._step), len(self._frame))
        self._at = b
        return QueryBatch([(self._frame, a, b)]) if b > a else []


def test_framed_specials_and_denies_get_the_object_paths_answers(world):
    """Flagged records — pattern queries, unknown namespaces, an empty
    subject-set namespace — are the only ones turned into objects."""
    frame_mod.MATERIALIZED.clear()
    queries = world.queries(5) + [
        T("docs", "", "view", SubjectID("u1_1")),  # pattern: any doc
        T("nope", "d1", "view", SubjectID("u1_1")),  # unknown namespace
        T("docs", "d1", "view", SubjectSet("", "g1", "member")),  # no such target
        T("docs", "d1", "view", SubjectSet("groups", "g1", "member")),
        T("", "d1", "view", SubjectID("u1_1")),
    ]
    framed = make_frame(world.manager, queries)
    assert framed.flags.tolist() == [0] * 5 + [1, 2, 3, 0, 1]
    want = world.tpu.batch_check(queries)
    gen, _ = world.tpu.batch_check_stream_with_token(_OneBatch(QueryBatch([(framed, 0, 10)])))
    assert np.concatenate(list(gen)).tolist() == want
    assert want[5] is True and want[6] is False and want[8] is True
    assert frame_mod.MATERIALIZED == {"special": 1}
    assert framed._tuples is None  # two objects were built, not ten


def test_audit_sample_draws_first_and_builds_only_the_sampled(world):
    frame_mod.MATERIALIZED.clear()
    chunk = world.queries(64)
    framed = make_frame(world.manager, chunk)
    eng = world.tpu
    eng._audit_rate = 0.1
    eng._audit_rng = random.Random(3)
    kicked = []
    eng._audit_task.kick = lambda: kicked.append(1)
    decisions = np.array(world.expected(chunk))
    eng._audit_sample(QueryBatch([(framed, 0, 64)]), decisions, 9)
    draws = random.Random(3)
    want = [i for i in range(64) if draws.random() < 0.1]
    assert want and kicked == [1]
    assert [(rt, d) for rt, d, _tok in eng._audit_pending] == [
        (chunk[i], bool(decisions[i])) for i in want
    ]
    assert frame_mod.MATERIALIZED == {"audit": 1} and framed._tuples is None
    # and a list is sampled with the same draws
    eng._audit_pending.clear()
    eng._audit_rng = random.Random(3)
    eng._audit_sample(chunk, decisions, 9)
    assert [rt for rt, _d, _t in eng._audit_pending] == [chunk[i] for i in want]


def test_framed_and_object_bodies_from_many_threads_get_their_own_answers(world):
    """More callers than cores, a short switch interval, one batcher: each
    caller's answers are its own, whichever form its body took."""
    import sys

    b = CheckBatcher(world.tpu, batch_size=64, window_ms=0.5, batch_sub_slice=16)
    world.tpu.batch_check(world.queries(1))
    jobs = []
    for k in range(24):
        chunk = world.queries(20 + k)
        jobs.append((chunk, make_frame(world.manager, chunk) if k % 2 else chunk))
    got = [None] * len(jobs)

    def call(k):
        got[k] = b.check_batch(jobs[k][1], timeout=60, lane=BATCH if k % 3 else INTERACTIVE)

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    b.start()
    try:
        threads = [threading.Thread(target=call, args=(k,), daemon=True) for k in range(len(jobs))]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        assert not any(t.is_alive() for t in threads)
    finally:
        sys.setswitchinterval(old)
        b.stop()
    for (chunk, _sent), answers in zip(jobs, got):
        assert answers == world.expected(chunk)


# -- the range fill ----------------------------------------------------------------


def test_range_fill_never_resolves_a_future_twice():
    b = CheckBatcher(engine=None)
    resolved = []

    class OnceFuture(Future):
        def set_result(self, result):
            resolved.append(result)
            super().set_result(result)

    item = _Item([object()] * 6, OnceFuture(), None, False, None, BATCH)
    b._fill(item, range(0, 4), [True, False, True, False], 1)
    assert item.remaining == 2 and not item.fut.done()
    # a retry answers an overlapping range: what was answered stays
    b._fill(item, range(2, 6), [False, False, True, True], 2)
    assert item.remaining == 0
    assert item.fut.result() == ([True, False, True, False, True, True], 2)
    # anything after that is a no-op
    b._fill(item, range(0, 6), [False] * 6, 3)
    b._fill(item, range(1, 2), True, 3)
    assert item.remaining == 0 and len(resolved) == 1
    assert item.results == [True, False, True, False, True, True]


def test_a_landed_slice_is_written_back_in_runs(world, monkeypatch):
    """``_fill`` stays the seam where decisions reach their request, at a
    grain of at most ``_FILL_RUN``: the benchmark's broken-path test
    counts on it (benchmarks/tests/broken_entry.py)."""
    from keto_tpu.driver import batch as batch_mod

    calls = []
    real = CheckBatcher._fill

    def counting(self, item, idx, allowed, token):
        calls.append((len(idx), type(allowed)))
        return real(self, item, idx, allowed, token)

    monkeypatch.setattr(CheckBatcher, "_fill", counting)
    b = CheckBatcher(world.tpu, batch_size=256, window_ms=0.5, batch_sub_slice=256)
    chunk = world.queries(200)
    b.start()
    try:
        assert b.check_batch(make_frame(world.manager, chunk), timeout=30, lane=BATCH) == world.expected(chunk)
        assert b.check(chunk[0], timeout=30) == world.expected(chunk[:1])[0]
    finally:
        b.stop()
    run = batch_mod._FILL_RUN
    assert calls[:-1] == [(run, list)] * (200 // run) + [(200 % run, list)]
    assert calls[-1] == (1, bool)  # a single check: its one decision, bare


def test_range_fill_takes_one_decision_for_a_whole_range():
    b = CheckBatcher(engine=None)
    item = _Item([object()] * 3, Future(), None, False, None, BATCH)
    b._fill(item, range(0, 3), False, 4)
    assert item.fut.result() == ([False, False, False], 4)


def test_fill_after_the_caller_gave_up_is_swallowed():
    b = CheckBatcher(engine=None)
    item = _Item([object()] * 2, Future(), None, False, None, BATCH)
    item.fut.set_exception(ErrDeadlineExceeded("gone"))
    b._fill(item, range(0, 2), [True, True], 1)  # must not raise InvalidStateError
    assert item.remaining == 0
