"""One round of look-ahead in the batcher's collector (keto_tpu/driver/batch.py
``_loop`` / ``_take_ahead`` / ``_launch`` / ``_land``, with the seam it needs in
the engine: check/dispatch.py ``_stream``'s launch mark and x/timeline.py
``DispatchClock.watch``).

Against a fake stream engine whose rounds land on command, and against the real
engine on the CPU: the order of launches and landings, the counter that says
how often a round was launched behind another, answers and snaptokens against
the serial loop (the same engine with ``STREAM_LAUNCH_MARK`` off), failure and
``stop()`` with two rounds open, a round's own snapshot, the two clocks that
must not count the host's work on round n+1 as round n's service time, and how
wide a round is taken: up to the round's own cap while the interactive lane is
quiet, cut at the sub-slice from the first single on (``_take_locked``)."""

import json
import random
import threading
import time

import numpy as np
import pytest

from keto_tpu import namespace as namespace_pkg
from keto_tpu.check.frame import QueryFrame
from keto_tpu.check.dispatch import CheckDispatch
from keto_tpu.check.tpu_engine import TpuCheckEngine
from keto_tpu.driver import batch as batch_mod
from keto_tpu.driver.admission import AdmissionController
from keto_tpu.driver.batch import BATCH, INTERACTIVE, CheckBatcher
from keto_tpu.graph.native import FrameTable
from keto_tpu.persistence.memory import MemoryPersister
from keto_tpu.relationtuple.model import RelationTuple, SubjectID, SubjectSet
from keto_tpu.x import faults
from keto_tpu.x.errors import ErrDeadlineExceeded
from keto_tpu.x.timeline import DispatchClock

NAMESPACES = [namespace_pkg.Namespace(id=1, name="docs"), namespace_pkg.Namespace(id=2, name="groups")]


def T(ns, obj, rel, sub):
    return RelationTuple(namespace=ns, object=obj, relation=rel, subject=sub)


def wait_for(cond, timeout=10.0, interval=0.005, msg="condition"):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if cond():
            return
        time.sleep(interval)
    raise AssertionError(f"timed out waiting for {msg}")


@pytest.fixture(autouse=True)
def _clean_faults():
    faults.clear()
    yield
    faults.clear()


def _q(i):
    return T("docs", f"d{i}", "view", SubjectID(f"u{i}"))


def _want(t):
    return int(t.object[1:]) % 3 == 0


# -- a fake stream engine whose rounds land on command ---------------------------------


class GatedEngine:
    """The engine's stream contract with the launch mark: a round is one
    slice, "launched" when its tuples are pulled, and lands when its gate
    opens (at once with ``hold=False``). ``events`` is what happened, in the
    order it happened: ("launch" | "land", round number)."""

    STREAM_LAUNCH_MARK = True

    def __init__(self, hold=False, fail_land=(), fail_launch=()):
        self.hold = hold
        self.fail_land, self.fail_launch = set(fail_land), set(fail_launch)
        self.events = []
        self.kw = []
        self.launched = {}  # round number -> its tuples
        self.gates = {}
        self.retried = []
        self.max_open = 0
        self._open = 0

    def gate(self, n):
        return self.gates.setdefault(n, threading.Event())

    def n_launched(self):
        return sum(1 for what, _ in self.events if what == "launch")

    def batch_check_stream_with_token(self, source, ordered=False, launch_mark=False, **kw):
        n = len(self.kw)
        self.kw.append(kw)

        def gen():
            tuples = list(source)
            if not tuples:
                return
            if n in self.fail_launch:
                raise RuntimeError(f"launch of round {n} failed")
            self.launched[n] = tuples
            self._open += 1
            self.max_open = max(self.max_open, self._open)
            self.events.append(("launch", n))
            try:
                if launch_mark:
                    yield None
                if self.hold:
                    assert self.gate(n).wait(timeout=30), f"round {n} was never released"
                if n in self.fail_land:
                    raise RuntimeError(f"device lost round {n}")
                self.events.append(("land", n))
            finally:
                self._open -= 1
            yield 0, np.array([_want(t) for t in tuples], dtype=bool)

        return gen(), 100 + n

    def batch_check_with_token(self, tuples, **kw):
        self.retried.append(list(tuples))
        return [_want(t) for t in tuples], 999


def _call(b, res, key, tuples, **kw):
    def run():
        try:
            res[key] = b.check_batch_with_token(tuples, **kw)
        except Exception as e:  # the test reads what the caller saw
            res[key] = e

    t = threading.Thread(target=run, daemon=True)
    t.start()
    return t


def _batcher(engine, **kw):
    """Rounds of 8 whatever the lanes hold: the round's cap is the sub-slice.
    (With a larger ``batch_size`` a quiet interactive lane lets a round take
    batch-lane work up to that: the tests of the take, further down.)"""
    kw = {"batch_size": 8, "window_ms": 0.0, "batch_sub_slice": 8, **kw}
    return CheckBatcher(engine, **kw)


def test_with_batch_work_queued_the_next_round_launches_before_this_one_lands():
    eng = GatedEngine()
    b = _batcher(eng)
    res = {}
    tuples = [_q(i) for i in range(32)]  # four rounds of 8
    t = _call(b, res, "a", tuples, lane=BATCH, timeout=30)
    wait_for(lambda: b.lane_depths[BATCH] == 32, msg="queued")
    b.start()
    try:
        t.join(timeout=30)
    finally:
        b.stop()
    assert res["a"] == ([_want(x) for x in tuples], 103)  # the token of the round that finished it
    assert eng.events == [
        ("launch", 0), ("launch", 1), ("land", 0), ("launch", 2), ("land", 1),
        ("launch", 3), ("land", 2), ("land", 3),
    ]
    assert eng.max_open == 2
    assert (b.clock.rounds, b.clock.overlapped) == (4, 3)
    assert [len(eng.launched[n]) for n in range(4)] == [8, 8, 8, 8]  # rounds as _take_locked cuts them


@pytest.mark.parametrize("n_rounds", [2, 3, 7, 16])
def test_never_more_than_two_rounds_open(n_rounds):
    eng = GatedEngine()
    b = _batcher(eng)
    res = {}
    t = _call(b, res, "a", [_q(i) for i in range(8 * n_rounds)], lane=BATCH, timeout=30)
    wait_for(lambda: b.lane_depths[BATCH] == 8 * n_rounds, msg="queued")
    b.start()
    try:
        t.join(timeout=30)
    finally:
        b.stop()
    assert eng.max_open == 2
    assert (b.clock.rounds, b.clock.overlapped) == (n_rounds, n_rounds - 1)
    launches = [n for what, n in eng.events if what == "launch"]
    lands = [n for what, n in eng.events if what == "land"]
    assert launches == lands == list(range(n_rounds))  # launch order and landing order are the lanes' order


@pytest.mark.parametrize("queued_behind", ["nothing", "singles"])
def test_without_batch_work_queued_a_round_lands_before_the_next_is_taken(queued_behind):
    eng = GatedEngine(hold=True)
    b = _batcher(eng)
    res = {}
    b.start()
    try:
        first = _call(b, res, "first", [_q(0)], lane=INTERACTIVE, timeout=30)
        wait_for(lambda: eng.events == [("launch", 0)], msg="round 0 on the device")
        later = []
        if queued_behind == "singles":
            later = [_call(b, res, i, [_q(i)], lane=INTERACTIVE, timeout=30) for i in (3, 4)]
            wait_for(lambda: b.lane_depths[INTERACTIVE] == 2, msg="singles queued behind round 0")
            time.sleep(0.05)  # the collector has every chance to look ahead, and must not
        assert eng.events == [("launch", 0)]
        eng.gate(0).set()
        eng.gate(1).set()
        for t in [first, *later]:
            t.join(timeout=30)
    finally:
        b.stop()
    assert res["first"] == ([True], 100)
    if queued_behind == "singles":
        assert eng.events == [("launch", 0), ("land", 0), ("launch", 1), ("land", 1)]
        assert res[3] == ([True], 101) and res[4] == ([False], 101)
    else:
        assert eng.events == [("launch", 0), ("land", 0)]
    assert b.clock.overlapped == 0 and b.clock.rounds == len(eng.kw)


def test_a_single_that_arrives_while_a_round_is_out_rides_the_look_ahead_round():
    """Interactive items first, as ``_take_locked`` always had it: with batch
    work queued the next round launches before this one lands, and a single
    queued by then is at its head."""
    eng = GatedEngine(hold=True)
    b = _batcher(eng, batch_size=32)
    res = {}
    bulk = _call(b, res, "bulk", [_q(i) for i in range(16)], lane=BATCH, timeout=30)
    single = _call(b, res, "single", [_q(3)], lane=INTERACTIVE, timeout=30)
    wait_for(lambda: b.lane_depths == {INTERACTIVE: 1, BATCH: 16}, msg="queued")
    b.start()
    try:
        # round 0 takes the single and 8 of the bulk; round 1 the rest, behind it
        wait_for(lambda: eng.n_launched() == 2, msg="round 1 launched with round 0 still out")
        assert eng.events == [("launch", 0), ("launch", 1)]
        assert eng.launched[0][0] == _q(3) and len(eng.launched[0]) == 9
        eng.gate(0).set()
        eng.gate(1).set()
        bulk.join(timeout=30)
        single.join(timeout=30)
    finally:
        b.stop()
    assert res["single"] == ([True], 100)
    assert res["bulk"][0] == [_want(_q(i)) for i in range(16)]


# -- how much batch-lane work a round takes ---------------------------------------------
# (the take itself, scripted segment by segment: tests/test_overload.py)


@pytest.mark.parametrize("call, want", [(4096, [4096]), (10000, [4096, 4096, 1808])])
def test_on_a_quiet_interactive_lane_a_call_rides_rounds_of_the_rounds_own_cap(call, want):
    eng = GatedEngine()
    b = CheckBatcher(eng, batch_size=4096, window_ms=0.0, batch_sub_slice=1024)
    res = {}
    tuples = [_q(i) for i in range(call)]
    t = _call(b, res, "a", tuples, lane=BATCH, timeout=60)
    wait_for(lambda: b.lane_depths[BATCH] == call, msg="queued")
    b.start()
    try:
        t.join(timeout=60)
    finally:
        b.stop()
    assert res["a"][0] == [_want(x) for x in tuples]
    assert [len(eng.launched[n]) for n in range(len(want))] == want
    assert (b.clock.rounds, b.clock.round_tuples) == (len(want), call)
    assert b.clock.overlapped == len(want) - 1


def test_the_first_single_after_a_quiet_spell_rides_the_next_round_and_narrows_the_two_after():
    """Quiet -> busy -> quiet, with the collector running: two wide rounds, a
    single arrives while they are open, and from the next round taken batch-lane
    work is cut at the sub-slice until a round and the one before it carried no
    interactive item."""
    eng = GatedEngine(hold=True)
    b = _batcher(eng, batch_size=32)
    res = {}
    tuples = [_q(i) for i in range(96)]
    bulk = _call(b, res, "bulk", tuples, lane=BATCH, timeout=30)
    wait_for(lambda: b.lane_depths[BATCH] == 96, msg="queued")
    b.start()
    try:
        # round 0 is out, round 1 launched behind it: the collector waits for round 0
        wait_for(lambda: eng.n_launched() == 2, msg="two wide rounds open")
        single = _call(b, res, "single", [_q(3)], lane=INTERACTIVE, timeout=30)
        wait_for(lambda: b.lane_depths == {INTERACTIVE: 1, BATCH: 32}, msg="single queued")
        for n in range(5):
            eng.gate(n).set()
        bulk.join(timeout=30)
        single.join(timeout=30)
    finally:
        b.stop()
    assert [len(eng.launched[n]) for n in range(5)] == [32, 32, 1 + 8, 8, 16]
    assert eng.launched[2][0] == _q(3)  # at the head of the very next round taken
    assert res["single"] == ([True], 102)
    assert res["bulk"][0] == [_want(x) for x in tuples]
    assert (b.clock.rounds, b.clock.round_tuples) == (5, 97)


# -- failure and shutdown, per round ---------------------------------------------------


@pytest.mark.parametrize("fails", ["land of the older", "launch of the newer", "both"])
def test_a_fault_with_two_rounds_open_is_the_failed_rounds_own(fails):
    eng = GatedEngine(
        fail_land={0} if fails != "launch of the newer" else (),
        fail_launch={1} if fails != "land of the older" else (),
    )
    b = _batcher(eng)
    res = {}
    first = [_q(i) for i in range(8)]
    second = [_q(i) for i in range(8, 16)]
    t1 = _call(b, res, "first", first, lane=BATCH, timeout=30)
    wait_for(lambda: b.lane_depths[BATCH] == 8, msg="first queued")
    t2 = _call(b, res, "second", second, lane=BATCH, timeout=30)
    wait_for(lambda: b.lane_depths[BATCH] == 16, msg="second queued")
    b.start()
    try:
        t1.join(timeout=30)
        t2.join(timeout=30)
        assert not t1.is_alive() and not t2.is_alive()
    finally:
        b.stop()
    # every caller has its answers; a failed round went through the one retry
    # with its own tuples, the other round was left alone
    assert res["first"][0] == [_want(t) for t in first]
    assert res["second"][0] == [_want(t) for t in second]
    want_retried = {
        "land of the older": [first], "launch of the newer": [second], "both": [second, first],
    }[fails]
    assert eng.retried == want_retried
    assert res["first"][1] == (999 if fails != "launch of the newer" else 100)
    assert res["second"][1] == (999 if fails != "land of the older" else 101)


def test_stop_with_two_rounds_open_fails_both_promptly():
    eng = GatedEngine(hold=True)  # nothing ever lands: a wedged device
    b = _batcher(eng)
    res = {}
    t1 = _call(b, res, "first", [_q(i) for i in range(8)], lane=BATCH, timeout=60)
    wait_for(lambda: b.lane_depths[BATCH] == 8, msg="first queued")
    t2 = _call(b, res, "second", [_q(i) for i in range(8, 16)], lane=BATCH, timeout=60)
    wait_for(lambda: b.lane_depths[BATCH] == 16, msg="second queued")
    b.start()
    wait_for(lambda: eng.n_launched() == 2, msg="two rounds open")
    t0 = time.monotonic()
    try:
        b.stop()  # gives the collector 5 s, then fails what it holds
        t1.join(timeout=5)
        t2.join(timeout=5)
        assert time.monotonic() - t0 < 10
        for key in ("first", "second"):
            assert isinstance(res[key], RuntimeError) and "stopped" in str(res[key])
    finally:
        eng.gate(0).set()
        eng.gate(1).set()


def test_a_deadline_that_passes_before_its_segment_is_reached_still_expires_it():
    eng = GatedEngine(hold=True)
    b = _batcher(eng)
    res = {}
    t1 = _call(b, res, "first", [_q(i) for i in range(8)], lane=BATCH, timeout=30)
    wait_for(lambda: b.lane_depths[BATCH] == 8, msg="first queued")
    b.start()
    try:
        wait_for(lambda: eng.n_launched() == 1, msg="round 0 out")
        # queued behind a round that is out and not looked at: by the time the
        # collector reaches it, its time is up
        t2 = _call(b, res, "late", [_q(i) for i in range(20, 28)], lane=BATCH,
                   timeout=None, deadline=time.monotonic() + 0.05)
        t2.join(timeout=30)
        assert isinstance(res["late"], ErrDeadlineExceeded)
        eng.gate(0).set()
        t1.join(timeout=30)
        wait_for(lambda: b.deadline_drop_count == 1, msg="dropped at dispatch")
    finally:
        b.stop()
    assert [what for what, _ in eng.events] == ["launch", "land"]  # the late one never reached the engine


# -- each round its own snapshot --------------------------------------------------------


def test_each_round_asks_for_the_consistency_of_its_own_riders():
    eng = GatedEngine()
    b = _batcher(eng)
    res = {}
    t1 = _call(b, res, "serving", [_q(i) for i in range(8)], lane=BATCH, timeout=30)
    wait_for(lambda: b.lane_depths[BATCH] == 8, msg="first queued")
    t2 = _call(b, res, "latest", [_q(i) for i in range(8, 16)], lane=BATCH, timeout=30, latest=True)
    wait_for(lambda: b.lane_depths[BATCH] == 16, msg="second queued")
    t3 = _call(b, res, "pinned", [_q(i) for i in range(16, 24)], lane=BATCH, timeout=30, at_least=41)
    wait_for(lambda: b.lane_depths[BATCH] == 24, msg="third queued")
    b.start()
    try:
        for t in (t1, t2, t3):
            t.join(timeout=30)
    finally:
        b.stop()
    assert eng.kw == [
        {"at_least": None, "mode": "serving"}, {"mode": "latest"}, {"at_least": 41, "mode": "pinned"},
    ]
    assert b.clock.overlapped == 2
    assert [res[k][1] for k in ("serving", "latest", "pinned")] == [100, 101, 102]


@pytest.fixture
def world():
    """A store, its device engine and queries with known answers: docs d<i>
    are viewable by members of group g<i % 5>, group g<j> has users u<j>_0..3."""
    manager = namespace_pkg.MemoryManager(NAMESPACES)
    p = MemoryPersister(manager)
    rows = [T("groups", f"g{j}", "member", SubjectID(f"u{j}_{k}")) for j in range(5) for k in range(4)]
    rows += [T("docs", f"d{i}", "view", SubjectSet("groups", f"g{i % 5}", "member")) for i in range(40)]
    p.write_relation_tuples(*rows)
    tpu = TpuCheckEngine(p, manager)

    class World:
        pass

    w = World()
    w.manager, w.store, w.tpu = manager, p, tpu

    def queries(n, rng):
        out = []
        for _ in range(n):
            i, j, k = rng.randrange(40), rng.randrange(5), rng.randrange(4)
            out.append(T("docs", f"d{i}", "view", SubjectID(f"u{j}_{k}")))
        return out

    w.queries = queries
    w.expected = lambda tuples: [
        int(t.object[1:]) % 5 == int(t.subject.id[1:].split("_")[0]) for t in tuples
    ]
    yield w
    tpu.close()


def test_a_latest_round_behind_a_serving_round_each_decides_on_its_own_snapshot(world):
    """Round 0 rides the serving snapshot; a write commits once it is
    launched; round 1 asks for ``latest`` and is launched before round 0
    lands. Round 0 answers without the write and names the older snapshot,
    round 1 with it and names a newer one."""
    eng = world.tpu
    eng.batch_check([T("docs", "d0", "view", SubjectID("u0_0"))])  # the snapshot is built
    newcomer = T("docs", "d0", "view", SubjectID("newcomer"))
    real = eng.batch_check_stream_with_token
    calls = []

    def spying(source, **kw):
        got = real(source, **kw)
        calls.append(kw.get("mode"))
        if len(calls) == 1:
            # round 0 holds its snapshot: now the write
            world.store.write_relation_tuples(T("groups", "g0", "member", SubjectID("newcomer")))
        return got

    eng.batch_check_stream_with_token = spying
    b = _batcher(eng)
    res = {}
    t1 = _call(b, res, "serving", [newcomer] * 8, lane=BATCH, timeout=30)
    wait_for(lambda: b.lane_depths[BATCH] == 8, msg="first queued")
    t2 = _call(b, res, "latest", [newcomer] * 8, lane=BATCH, timeout=30, latest=True)
    wait_for(lambda: b.lane_depths[BATCH] == 16, msg="second queued")
    b.start()
    try:
        t1.join(timeout=60)
        t2.join(timeout=60)
    finally:
        b.stop()
    assert calls == ["serving", "latest"]
    assert (b.clock.rounds, b.clock.overlapped) == (2, 1)
    assert res["serving"][0] == [False] * 8
    assert res["latest"][0] == [True] * 8
    assert res["latest"][1] > res["serving"][1]
    assert res["latest"][1] == world.store.watermark()


# -- the real engine: the mark, and the same answers as the serial loop ----------------


def test_the_engines_stream_gives_its_mark_once_between_launch_and_land(world):
    eng = world.tpu
    rng = random.Random(3)
    tuples = world.queries(24, rng)
    eng.batch_check(tuples[:1])
    seen_ready = []
    real_ready = CheckDispatch._slice_ready
    eng.dispatch._slice_ready = lambda dev: seen_ready.append(dev is not None) or real_ready(dev)
    gen, token = eng.batch_check_stream_with_token(iter(tuples), ordered=False, launch_mark=True)
    recs = list(gen)
    assert recs[0] is None and all(r is not None for r in recs[1:])
    out = np.concatenate([r[1] for r in sorted(recs[1:], key=lambda r: r[0])])
    assert out.tolist() == world.expected(tuples)
    assert token == eng.snapshot().snapshot_id
    # nobody asked for the mark: none is given
    gen, _ = eng.batch_check_stream_with_token(iter(tuples), ordered=False)
    assert all(r is not None for r in gen)
    with pytest.raises(ValueError):
        eng.batch_check_stream_with_token(iter(tuples), launch_mark=True)  # ordered=True


def make_frame(manager, tuples) -> QueryFrame:
    body = json.dumps({"tuples": [t.to_json() for t in tuples]}).encode()
    table = FrameTable.build(manager)
    if table is None:
        pytest.skip("native library not built")
    got = table.frame(body, 65536)
    assert not isinstance(got, str), got
    return QueryFrame(*got, body, manager)


class _CountingFuture(batch_mod.Future):
    """A future that remembers how often it was resolved or asked to be."""

    def __init__(self):
        super().__init__()
        self.resolutions = 0

    def set_result(self, result):
        self.resolutions += 1
        super().set_result(result)

    def set_exception(self, exc):
        self.resolutions += 1
        super().set_exception(exc)


def _fuzz_requests(world, seed):
    """A seeded mix: singles, lists and framed bodies, interactive and batch
    lane, some with a deadline that has passed by the time the collector
    starts, some pinned to a snaptoken."""
    rng = random.Random(seed)
    reqs = []
    for k in range(rng.randrange(18, 30)):
        kind = rng.choice(["single", "single", "list", "list", "frame"])
        n = 1 if kind == "single" else rng.choice([3, 8, 13, 24, 40])
        tuples = world.queries(n, rng)
        hopeless = rng.random() < 0.2
        reqs.append({
            "key": k, "kind": kind, "tuples": tuples, "hopeless": hopeless,
            "lane": INTERACTIVE if kind == "single" else rng.choice([BATCH, BATCH, None]),
            "latest": rng.random() < 0.15,
            "at_least": 1 if rng.random() < 0.2 else None,
        })
    return reqs


def _serve(world, reqs, monkeypatch, look_ahead: bool):
    """Queue every request, then start the collector: rounds are cut the same
    way whatever the timing. Returns what each caller saw and the futures."""
    futures = []

    def counting():
        f = _CountingFuture()
        futures.append(f)
        return f

    monkeypatch.setattr(batch_mod, "Future", counting)
    monkeypatch.setattr(world.tpu, "STREAM_LAUNCH_MARK", look_ahead, raising=False)
    b = CheckBatcher(world.tpu, batch_size=32, window_ms=0.0, batch_sub_slice=8,
                     interactive_max_tuples=4, max_pending=4096)
    res, threads = {}, []
    for r in reqs:
        payload = make_frame(world.manager, r["tuples"]) if r["kind"] == "frame" else r["tuples"]
        kw = {"lane": r["lane"], "latest": r["latest"], "at_least": r["at_least"], "timeout": None,
              "deadline": time.monotonic() + (0.15 if r["hopeless"] else 120.0)}
        threads.append(_call(b, res, r["key"], payload, **kw))
        wait_for(lambda n=len(threads): len(futures) == n and b.inflight == n, msg="request queued")
    time.sleep(0.2)  # the hopeless deadlines pass while everything is still queued
    b.start()
    try:
        for t in threads:
            t.join(timeout=120)
            assert not t.is_alive()
    finally:
        b.stop()
    return res, futures, b


@pytest.mark.parametrize("seed", [29, 290, 2900, 29000, 290000])
def test_fuzz_same_decisions_and_snaptokens_as_the_serial_loop(world, monkeypatch, seed):
    reqs = _fuzz_requests(world, seed)
    world.tpu.batch_check(reqs[0]["tuples"])  # the snapshot both loops will serve from
    serial, serial_futs, b0 = _serve(world, reqs, monkeypatch, look_ahead=False)
    ahead, ahead_futs, b1 = _serve(world, reqs, monkeypatch, look_ahead=True)
    assert b0.clock.overlapped == 0
    assert b1.clock.overlapped > 0 and b1.clock.rounds == b0.clock.rounds
    for r in reqs:
        got, want = ahead[r["key"]], serial[r["key"]]
        if r["hopeless"]:
            assert isinstance(got, ErrDeadlineExceeded) and isinstance(want, ErrDeadlineExceeded)
        else:
            assert got == want == (world.expected(r["tuples"]), world.tpu.snapshot().snapshot_id)
    # each future resolved once: by its last fill, or by its expiry, never both
    for f in serial_futs + ahead_futs:
        assert f.done() and f.resolutions == 1


def test_a_device_fault_with_two_rounds_open_reaches_no_caller(world):
    """The real engine: round 1's launch hits an injected device fault while
    round 0 is out. Round 1 is answered by the one retry (the engine's plain
    path, which falls back to the CPU on the fault); round 0 lands as it was."""
    eng = world.tpu
    rng = random.Random(5)
    first, second = world.queries(8, rng), world.queries(8, rng)
    eng.batch_check(first[:1])
    b = _batcher(eng)
    res = {}
    t1 = _call(b, res, "first", first, lane=BATCH, timeout=60)
    wait_for(lambda: b.lane_depths[BATCH] == 8, msg="first queued")
    t2 = _call(b, res, "second", second, lane=BATCH, timeout=60)
    wait_for(lambda: b.lane_depths[BATCH] == 16, msg="second queued")
    faults.inject("device-exec", skip=1)  # round 0's launch passes, every later one fails
    b.start()
    try:
        t1.join(timeout=60)
        t2.join(timeout=60)
        assert not t1.is_alive() and not t2.is_alive()
    finally:
        b.stop()
    assert faults.hits("device-exec") >= 1
    assert res["first"][0] == world.expected(first)
    assert res["second"][0] == world.expected(second)
    assert (b.clock.rounds, b.clock.overlapped) == (2, 1)
    assert eng.dispatch.staging_snapshot().get("leased", 0) == 0  # nothing left leased behind


# -- the clocks: round n+1's host work is not round n's service time --------------------


class ScriptClock:
    """``time`` as ``driver/batch.py`` reads it, advanced by the script alone."""

    def __init__(self):
        self.now = 1000.0

    def monotonic(self):
        return self.now


class ScriptedEngine(GatedEngine):
    """A round costs the host ``host_s`` and ``per_tuple_s`` a tuple to launch,
    and the device ``device_s`` from then until it is ready; landing waits for
    what is left of that."""

    def __init__(self, clock, host_s, device_s, look_ahead, per_tuple_s=0.0):
        super().__init__()
        self.clock, self.host_s, self.device_s = clock, host_s, device_s
        self.per_tuple_s = per_tuple_s
        self.STREAM_LAUNCH_MARK = look_ahead

    def batch_check_stream_with_token(self, source, ordered=False, launch_mark=False, **kw):
        clock = self.clock

        def gen():
            tuples = list(source)
            clock.now += self.host_s + self.per_tuple_s * len(tuples)
            ready_at = clock.now + self.device_s
            if launch_mark:
                yield None
            clock.now = max(clock.now, ready_at)
            yield 0, np.ones(len(tuples), dtype=bool)

        return gen(), 1


def _scripted_rate(monkeypatch, look_ahead, host_s=0.0040, device_s=0.0004, rounds=40,
                   batch_size=1024, per_tuple_s=0.0):
    """The admission controller's rate after one batch-lane call of
    ``1024 * rounds`` tuples on a quiet interactive lane: rounds of
    ``batch_size``, so of the sub-slice unless a test asks for wider ones."""
    clock = ScriptClock()
    monkeypatch.setattr(batch_mod, "time", clock)
    ctrl = AdmissionController(target_ms=40.0, min_window=64, max_window=1 << 20)
    eng = ScriptedEngine(clock, host_s, device_s, look_ahead, per_tuple_s)
    b = CheckBatcher(eng, batch_size=batch_size, window_ms=0.0, batch_sub_slice=1024,
                     max_pending=1 << 20, admission=ctrl)
    res = {}
    t = _call(b, res, "a", [_q(i) for i in range(1024)] * rounds, lane=BATCH, timeout=None)
    wait_for(lambda: b.lane_depths[BATCH] == 1024 * rounds, msg="queued")
    b.start()
    try:
        t.join(timeout=60)
        assert not t.is_alive()
    finally:
        b.stop()
    n_rounds = -(-1024 * rounds // batch_size)
    assert (b.clock.rounds, b.clock.round_tuples) == (n_rounds, 1024 * rounds)
    assert b.clock.overlapped == (n_rounds - 1 if look_ahead else 0)
    return ctrl.rate_tuples_per_s


def test_admission_rate_is_tuples_over_the_threads_time_with_two_rounds_open(monkeypatch):
    """The label cells' shape: 4.0 ms of host work a round of 1,024 and 0.4 ms
    of device behind it. The serial loop reads 1,024 / 4.4 ms; with look-ahead
    the wait is covered and the rate is 1,024 / 4.0 ms, 10% above it. Were a
    round still timed from its take to its last fill it would read 1,024 / 8.0
    ms: half, and the controller would refuse at twice the backlog."""
    serial = _scripted_rate(monkeypatch, look_ahead=False)
    ahead = _scripted_rate(monkeypatch, look_ahead=True)
    assert serial == pytest.approx(1024 / 0.0044, rel=1e-6)
    assert ahead == pytest.approx(1024 / 0.0040, rel=0.02)  # the last round of the run sits its own wait out
    assert abs(ahead - serial) / serial < 0.15


@pytest.mark.parametrize("device_s", [0.0002, 0.0030])
def test_admission_rate_with_the_wait_covered_is_tuples_over_host_time(monkeypatch, device_s):
    host_s = 0.0040
    ahead = _scripted_rate(monkeypatch, look_ahead=True, device_s=device_s)
    # the controller's EWMA (0.8 / 0.2) after a long run of covered rounds
    # and the last one, which has no round behind it and sits its wait out
    want = 0.8 * 1024 / host_s + 0.2 * 1024 / (host_s + device_s)
    assert ahead == pytest.approx(want, rel=0.01)


def test_admission_rate_counts_a_wait_the_host_could_not_cover(monkeypatch):
    """A device slower (10 ms) than the next round's launch (4 ms): what is
    left of the wait is the waiting round's own time, as it always was. Rounds
    then alternate between one that waits (10 ms) and one found ready (4 ms),
    two every 14 ms."""
    host_s, device_s = 0.0040, 0.0100
    ahead = _scripted_rate(monkeypatch, look_ahead=True, device_s=device_s)
    assert 1024 / device_s < ahead < 1024 / host_s
    assert ahead == pytest.approx(2 * 1024 / (host_s + device_s), rel=0.25)


def test_admission_rate_of_wide_rounds_is_their_tuples_over_the_threads_time(monkeypatch):
    """A round that costs the thread 1.6 ms and 0.7 ms per 1,024 tuples: 2.3 ms
    at the sub-slice's width, 4.4 ms at 4,096. On a quiet interactive lane the
    batcher takes the wide ones, and the controller's rate is their tuples over
    their time like any round's: it reads 2.1 times higher because it is."""
    a, b = 0.0016, 0.0007 / 1024
    device_s = 0.0004
    narrow = _scripted_rate(monkeypatch, True, a, device_s, per_tuple_s=b)
    wide = _scripted_rate(monkeypatch, True, a, device_s, per_tuple_s=b, batch_size=4096)
    assert narrow == pytest.approx(0.8 * 1024 / 0.0023 + 0.2 * 1024 / 0.0027, rel=0.01)
    assert wide == pytest.approx(0.8 * 4096 / 0.0044 + 0.2 * 4096 / 0.0048, rel=0.01)
    assert wide / narrow == pytest.approx((4096 / 0.0044) / (1024 / 0.0023), rel=0.03)


class SteppingClock(DispatchClock):
    """A dispatch clock on scripted time: every transition and poll site costs
    the thread ``step`` seconds of work before it reads the time."""

    __slots__ = ("script", "step")

    def enter(self, state, note=None):
        self.script[0] += self.step
        super().enter(state, note)

    def poll(self):
        self.script[0] += self.step
        super().poll()


def _scripted_slice_ms(world, monkeypatch, look_ahead, device_s, step_s, rounds=12):
    """Serve ``rounds`` rounds of 8 through the real engine with the time
    scripted: a slice is ready ``device_s`` after its launch, whatever the
    thread does meanwhile. Returns the slice service times the engine noted."""
    eng = world.tpu
    rng = random.Random(9)
    tuples = world.queries(8 * rounds, rng)
    eng.batch_check(tuples[:8])  # snapshot built, programs compiled: outside the script
    # the label index comes up behind the first batch; until then a slice of
    # this tiny graph is answered on the host and has no device time to script
    wait_for(lambda: eng.dispatch._labels_usable(eng.snapshot()), msg="label route up")
    eng.batch_check(tuples[:8])
    script = [5000.0]
    monkeypatch.setattr(time, "perf_counter", lambda: script[0])
    ready_at = {}
    real_dispatch = eng.dispatch._dispatch_slices
    real_unpack = eng.dispatch._unpack_slice

    def dispatching(snap, batch, it_cap=None):
        for rec in real_dispatch(snap, batch, it_cap):
            if rec[0] is not None:
                ready_at[id(rec[0])] = script[0] + device_s
            yield rec

    def unpacking(dev, host_ans, nq):
        # the blocking fetch: the thread sleeps until the slice is ready
        script[0] = max(script[0], ready_at.get(id(dev), 0.0))
        return real_unpack(dev, host_ans, nq)

    monkeypatch.setattr(eng.dispatch, "_dispatch_slices", dispatching)
    monkeypatch.setattr(eng.dispatch, "_unpack_slice", unpacking)
    monkeypatch.setattr(eng.dispatch, "_slice_ready", lambda dev: dev is None or script[0] >= ready_at[id(dev)])
    monkeypatch.setattr(eng, "STREAM_LAUNCH_MARK", look_ahead, raising=False)
    _, before = eng.stream_slice_stats.tail(0)
    b = _batcher(eng)
    b.clock = SteppingClock()  # born on the script's time
    b.clock.script, b.clock.step = script, step_s
    born = script[0]
    res = {}
    t = _call(b, res, "a", tuples, lane=BATCH, timeout=120)
    wait_for(lambda: b.lane_depths[BATCH] == len(tuples), msg="queued")
    b.start()
    try:
        t.join(timeout=120)
        assert not t.is_alive()
    finally:
        b.stop()
    # the states stay exclusive with two rounds open: one thread, one state,
    # and they sum to the scripted time that went by since the clock was made
    seconds, n_rounds = b.clock.snapshot()
    assert sum(seconds) == pytest.approx(script[0] - born, abs=1e-9)
    assert n_rounds == rounds
    monkeypatch.undo()
    assert res["a"][0] == world.expected(tuples)
    assert b.clock.overlapped == (rounds - 1 if look_ahead else 0)
    samples, count = eng.stream_slice_stats.tail(rounds)
    assert count - before == rounds
    return samples


@pytest.mark.parametrize("device_ms", [1.0, 2.5])
def test_slice_service_time_under_look_ahead_is_what_the_serial_loop_reads(world, monkeypatch, device_ms):
    """A slice ready 1.0 ms (2.5 ms) after its launch while the next round's
    launch costs the thread ~1.5 ms in steps of 0.1 ms: the serial loop reads
    the wait it sat through; with look-ahead the slice is found ready when the
    thread comes back, and gets the time a transition site first saw it so -
    within a step of the truth, not the ~1.5 ms the thread was away."""
    step = 0.0001
    serial = _scripted_slice_ms(world, monkeypatch, False, device_ms / 1e3, step)
    ahead = _scripted_slice_ms(world, monkeypatch, True, device_ms / 1e3, step)
    m_serial = sorted(serial)[len(serial) // 2]
    m_ahead = sorted(ahead)[len(ahead) // 2]
    assert m_serial == pytest.approx(device_ms, rel=0.15)
    assert abs(m_ahead - m_serial) / m_serial < 0.15
