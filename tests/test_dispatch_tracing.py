"""One clock for the dispatch thread, the REST pool and the admission
controller (keto_tpu/x/timeline.py ``DispatchClock``, x/profiling.py's
trace hook, servers/{rest,async_rest}.py's listener stages,
driver/admission.py's signals, driver/compile_cache.py's listener).

The cost rule is tested by counting: nothing here may read the clock once
per tuple, and no annotation object may exist while no profiler session
is open."""

import json
import random
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

from keto_tpu.driver.admission import SIGNALS
from keto_tpu.driver.batch import CheckBatcher
from keto_tpu.relationtuple.model import RelationTuple, SubjectID
from keto_tpu.x import profiling
from keto_tpu.x.metrics import parse_exposition
from keto_tpu.x.timeline import (
    DEVICE_WAIT, DISPATCH_STATES, FILL, LAUNCH, LISTENER_STAGES, PACK, RESOLVE, STAGES,
    TAKE, WAIT_WORK, DispatchClock, TimelineRecorder, dispatch_clock,
)


def _tuples(n):
    return [
        RelationTuple(namespace="docs", object=f"o{i}", relation="view",
                      subject=SubjectID(f"u{i}"))
        for i in range(n)
    ]


class StreamStub:
    """The engine's streaming contract with the engine's transition sites:
    one slice per round, six transitions per slice, whatever its width."""

    def batch_check_stream_with_token(self, tuples_iter, ordered=False, **_kw):
        def gen():
            clk = dispatch_clock()
            clk.enter(RESOLVE)
            n = len(list(tuples_iter))
            clk.enter(RESOLVE)
            clk.enter(PACK)
            clk.enter(LAUNCH)
            clk.enter(DEVICE_WAIT)
            clk.enter(FILL)
            yield 0, np.ones(n, dtype=bool)

        return gen(), 7


class FakeSession:
    """A ``ProfilerSession`` whose annotations are counted, not traced."""

    def __init__(self):
        self.open = False
        self.made = []
        self.events = []

    def annotation(self, name, **args):
        self.made.append((name, args))
        session = self

        class Span:
            def __enter__(self):
                session.events.append(("enter", name))

            def __exit__(self, *exc):
                session.events.append(("exit", name))

        return Span()


class CountingClock(DispatchClock):
    __slots__ = ("idles",)

    def __init__(self, session):
        super().__init__(session)
        self.idles = 0

    def idle(self):
        self.idles += 1
        super().idle()


def _count_clock_reads(monkeypatch):
    reads = {}
    real = time.perf_counter

    def counting():
        name = threading.current_thread().name
        reads[name] = reads.get(name, 0) + 1
        return real()

    monkeypatch.setattr(time, "perf_counter", counting)
    return reads


def _drive(n_tuples, monkeypatch, requests=3):
    """``requests`` sequential batch calls of ``n_tuples``, one round each.
    Returns clock reads per round on the dispatch thread (loop passes
    taken out: an idle pass is one read, however long the test sleeps),
    clock reads per request on the caller's thread, and the session."""
    session = FakeSession()
    rec = TimelineRecorder()
    b = CheckBatcher(StreamStub(), batch_size=8192, batch_sub_slice=8192, window_ms=0.1)
    b.clock = CountingClock(session)
    tuples = _tuples(n_tuples)
    reads = _count_clock_reads(monkeypatch)
    b.start()
    try:
        me = threading.current_thread().name
        before = reads.get(me, 0)
        for _ in range(requests):
            tl = rec.begin("POST /check/batch")
            with rec.activate(tl):
                out = b.check_batch(tuples, lane="batch")
            rec.finish(tl, status=200)
            assert out == [True] * n_tuples
        caller = reads.get(me, 0) - before
    finally:
        b.stop()
    monkeypatch.undo()
    assert b.clock.rounds == requests
    per_round = (reads["check-batcher"] - b.clock.idles) / requests
    return per_round, caller / requests, session


# -- (a) the cost rule -----------------------------------------------------------


def test_clock_reads_per_round_and_request_do_not_grow_with_batch_size(monkeypatch):
    small = _drive(64, monkeypatch)
    large = _drive(4096, monkeypatch)
    assert small[0] == large[0], "clock reads per dispatch round depend on its size"
    assert small[1] == large[1], "clock reads per request depend on its size"
    # take + resolve + the stub's six, and the timeline's pack/dispatch/land
    assert small[0] == 2 + 6 + 3


@pytest.mark.parametrize("n_tuples", [64, 4096])
def test_no_annotation_is_built_while_no_session_is_open(monkeypatch, n_tuples):
    _, _, session = _drive(n_tuples, monkeypatch)
    assert session.made == []


def test_annotations_follow_the_session_flag(monkeypatch):
    session = FakeSession()
    b = CheckBatcher(StreamStub(), batch_size=8192, batch_sub_slice=8192, window_ms=0.1)
    b.clock = DispatchClock(session)
    b.start()
    try:
        b.check_batch(_tuples(32), lane="batch")
        assert session.made == []
        session.open = True
        time.sleep(0.3)  # the collector re-reads the flag at its next loop pass
        b.check_batch(_tuples(32), lane="batch")
        time.sleep(0.05)
        names = [name for name, _ in session.made]
        for state in DISPATCH_STATES:
            assert f"keto.dispatch.{state}" in names
        launch = next(args for name, args in session.made if name.endswith(".launch"))
        assert launch == {
            "tuples": 32, "slices": 1, "lane_depth": 0, "cap": 8192, "cap_by": "batch_size",
        }
        session.open = False
        time.sleep(0.3)
        made = len(session.made)
        b.check_batch(_tuples(32), lane="batch")
        assert len(session.made) == made
    finally:
        b.stop()
    # every span was closed before the next opened, and none is left open
    assert session.events[0][0] == "enter"
    for (a, _), (b_, _) in zip(session.events, session.events[1:]):
        assert a != b_
    assert session.events[-1][0] == "exit"


# -- (b) the state clock ----------------------------------------------------------


def test_clock_unit_accumulates_by_state():
    clock = DispatchClock(FakeSession())
    t0 = time.perf_counter()
    clock.enter(TAKE)
    time.sleep(0.02)
    clock.enter(PACK)
    time.sleep(0.01)
    seconds, rounds = clock.snapshot()
    wall = time.perf_counter() - t0
    assert rounds == 0
    # a sleep may overshoot on a loaded host: each state holds its own sleep
    # at least, and the sum below leaves neither room for the other's
    assert seconds[TAKE] >= 0.02
    assert seconds[PACK] >= 0.01  # the state in progress is counted up to now
    assert sum(seconds) == pytest.approx(wall, rel=0.01, abs=2e-4)
    clock.round(10, 3)
    assert clock.snapshot()[1] == 1


def test_the_two_launches_of_a_hybrid_slice_differ_in_their_spans_kernel():
    """A hybrid slice launches the label kernel, then ``check_step`` for the
    riders: both spans say ``route=hybrid``, and ``kernel`` tells them apart."""
    session = FakeSession()
    session.open = True
    clock = DispatchClock(session)
    clock.round(4096, 0)
    clock.enter(PACK)
    clock.enter(LAUNCH, ("hybrid", "label_step", (16384, 8192), "compiled"))
    clock.enter(PACK)
    clock.enter(LAUNCH, ("hybrid", "check_step", (2048, 8192, 8192, 2048), "padded_up"))
    clock.enter(DEVICE_WAIT)
    launches = [args for name, args in session.made if name == "keto.dispatch.launch"]
    assert [a["kernel"] for a in launches] == ["label_step", "check_step"]
    assert {a["route"] for a in launches} == {"hybrid"}
    assert [a["slices"] for a in launches] == [1, 2]
    assert launches[1]["geometry"] == "check_step 2048x8192x8192x2048 padded_up"
    assert all("kernel" not in args for name, args in session.made if not name.endswith(".launch"))


def test_a_rounds_spans_carry_its_room_and_what_set_it():
    """``round()`` takes the round's ``cap`` and ``cap_by``: every span of the
    round says them, so an idle gap of the device under a narrowed round is
    seen to be one."""
    session = FakeSession()
    session.open = True
    clock = DispatchClock(session)
    clock.round(2048, 6000, cap=2048, cap_by="controller")
    clock.enter(RESOLVE)
    clock.enter(PACK)
    clock.enter(LAUNCH, ("hybrid", "check_step", (2048, 8192, 8192, 2048), "compiled"))
    clock.enter(DEVICE_WAIT)
    clock.round(4096, 0, cap=4096, cap_by="batch_size")
    clock.enter(RESOLVE)
    rooms = [(name, args["cap"], args["cap_by"]) for name, args in session.made]
    assert rooms == [
        ("keto.dispatch.resolve", 2048, "controller"),
        ("keto.dispatch.pack", 2048, "controller"),
        ("keto.dispatch.launch", 2048, "controller"),
        ("keto.dispatch.device_wait", 2048, "controller"),
        ("keto.dispatch.resolve", 4096, "batch_size"),
    ]
    assert clock.round_cap == {"batch_size": 1, "sub_slice": 0, "controller": 1}


@pytest.mark.parametrize("paths", [("native",), ("numpy",), ("native", "numpy")])
def test_the_resolve_span_that_holds_the_call_carries_its_path(paths):
    """``resolving(path)`` opens the ``resolve`` span anew before the call
    that makes the chunk's rows: that span says ``path``, the spans before it
    and after it do not, and no clock is read (the state stays ``resolve``).
    A chunk the native pass hands back has a span of each. Without a session
    nothing is made."""
    session = FakeSession()
    session.open = True
    clock = DispatchClock(session)
    clock.round(4096, 0)
    clock.enter(RESOLVE)
    before = list(clock.seconds)
    for path in paths:
        clock.resolving(path)
    assert [b for a, b in zip(clock.seconds, before) if a != b] == []
    clock.enter(PACK)
    made = [(name.rsplit(".", 1)[1], args.get("path")) for name, args in session.made]
    assert made == [("resolve", None), *(("resolve", p) for p in paths), ("pack", None)]
    assert session.events.count(("exit", "keto.dispatch.resolve")) == 1 + len(paths)
    quiet = FakeSession()
    clock = DispatchClock(quiet)
    clock.enter(RESOLVE)
    clock.resolving("native")
    assert quiet.made == []


def test_a_controller_event_is_a_zero_length_annotation_on_the_dispatch_thread():
    """``StreamSliceController.observe`` marks what moved it through the
    calling thread's clock: written while a session is open, on the dispatch
    thread only, closed before the next span opens."""
    from keto_tpu.check.slice_ctrl import StreamSliceController
    from keto_tpu.x.timeline import bind_dispatch_clock

    session = FakeSession()
    clock = DispatchClock(session)
    ctrl = StreamSliceController(target_ms=40.0)
    ctrl.observe(4096, 14.0, route="hybrid", bfs_steps=12, entries=50_000)
    bind_dispatch_clock(clock)
    try:
        ctrl.observe(4096, 60.0, route="hybrid", bfs_steps=12, entries=50_000)
        assert session.made == []  # no session: counted, not written
        assert ctrl.snapshot()["events"]["narrow"] == {"hybrid": 1}
        session.open = True
        clock.round(4096, 0)
        clock.enter(FILL)
        ctrl.observe(2048, 75.0, route="bfs", bfs_steps=12, entries=25_000)
        clock.enter(WAIT_WORK)
    finally:
        bind_dispatch_clock(None)
    name, args = session.made[1]
    assert name == "keto.ctrl.narrow"
    assert args == {"route": "bfs", "nq": 2048, "ms": 75.0, "rung_before": 2048, "rung_after": 2048}
    assert session.events == [
        ("enter", "keto.dispatch.fill"),
        ("enter", "keto.ctrl.narrow"), ("exit", "keto.ctrl.narrow"),
        ("exit", "keto.dispatch.fill"), ("enter", "keto.dispatch.wait_work"),
    ]
    made = len(session.made)
    ctrl.observe(2048, 75.0, route="bfs")  # off the dispatch thread: the no-op clock
    assert len(session.made) == made
    assert ctrl.snapshot()["events"]["narrow"] == {"hybrid": 1, "bfs": 2}


def test_the_geometry_workers_compile_is_named_while_a_session_is_open(monkeypatch):
    """A padded-up slice asks the worker for a program of its own width: under
    an open session the compile is a ``keto.geometry.compile`` span on the
    worker's thread that says which program; none is built otherwise."""
    from keto_tpu.check import geometry

    session = FakeSession()
    monkeypatch.setattr(geometry, "SESSION", session)
    threads = []

    def compile_fn(kernel, shape, fixed, sizes):
        threads.append(threading.current_thread().name)
        return True

    def ask(sizes):
        geoms = geometry.KernelGeometries(compile_fn)
        geoms.add("check", (7, 3), ("fixed",), (1024, 8192))
        geoms.mark_warmed("check", (7, 3))
        assert geoms.meet("check", (7, 3), ("fixed",), sizes) == ((1024, 8192), geometry.PADDED_UP)
        geoms.close(timeout=10)
        assert geoms.pending() == 0
        assert geoms.meet("check", (7, 3), ("fixed",), sizes)[1] == geometry.COMPILED

    ask((512, 2048))
    assert session.made == [] and threads == ["keto-tpu-geometry-compile"]
    session.open = True
    ask((256, 2048))
    assert session.made == [
        ("keto.geometry.compile", {"kernel": "check", "shape": "(7, 3)", "sizes": "256x2048"})
    ]
    assert session.events == [("enter", "keto.geometry.compile"), ("exit", "keto.geometry.compile")]
    assert threads == ["keto-tpu-geometry-compile"] * 2


def test_states_sum_to_the_threads_wall_time_and_wait_work_grows_when_idle():
    t0 = time.perf_counter()
    b = CheckBatcher(StreamStub(), batch_size=8192, batch_sub_slice=8192, window_ms=0.1)
    b.start()
    try:
        for _ in range(5):
            b.check_batch(_tuples(512), lane="batch")
        first, _ = b.clock.snapshot()
        time.sleep(0.3)
        second, rounds = b.clock.snapshot()
        wall = time.perf_counter() - t0
    finally:
        b.stop()
    assert rounds == 5
    assert sum(second) == pytest.approx(wall, rel=0.01)
    assert second[WAIT_WORK] - first[WAIT_WORK] == pytest.approx(0.3, abs=0.03)
    for state in (TAKE, RESOLVE, PACK, LAUNCH, DEVICE_WAIT, FILL):
        assert second[state] == first[state], DISPATCH_STATES[state]
        assert second[state] > 0


def test_threads_other_than_the_collector_get_the_noop_clock():
    clk = dispatch_clock()
    clk.enter(PACK)  # must not raise, must not be a DispatchClock
    assert not isinstance(clk, DispatchClock)


def test_snapshot_under_a_racing_writer_never_counts_an_interval_twice():
    """More writers' transitions than the scraper can see apart: the sum
    of a snapshot may miss the interval in flight, it never exceeds the
    clock's age."""
    import sys

    clock = DispatchClock(FakeSession())
    born = time.perf_counter()
    stop = threading.Event()

    def writer():
        i = 0
        while not stop.is_set():
            clock.enter(i % len(DISPATCH_STATES))
            i += 1

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    t = threading.Thread(target=writer, daemon=True)
    t.start()
    try:
        deadline = time.monotonic() + 0.5
        last = 0.0
        while time.monotonic() < deadline:
            total = sum(clock.snapshot()[0])
            age = time.perf_counter() - born
            assert total <= age + 1e-4
            assert total >= last - 1e-4 or total >= age - 0.05
            last = total
    finally:
        stop.set()
        t.join(timeout=5)
        sys.setswitchinterval(old)
    assert not t.is_alive()


# -- a live daemon: /metrics, the REST stages, the engine's transition sites -----

NAMESPACES = [{"id": 0, "name": "docs"}, {"id": 1, "name": "groups"}]


def _boot(**overrides):
    from keto_tpu.config.provider import Config
    from keto_tpu.driver.daemon import Daemon
    from keto_tpu.driver.registry import Registry

    cfg = Config(overrides={
        "namespaces": NAMESPACES, "dsn": "memory",
        "serve.read.port": 0, "serve.write.port": 0, **overrides,
    })
    d = Daemon(Registry(cfg))
    d.serve_all(block=False)
    for body in (
        {"namespace": "groups", "object": "g", "relation": "member", "subject_id": "ann"},
        {"namespace": "docs", "object": "readme", "relation": "view",
         "subject_set": {"namespace": "groups", "object": "g", "relation": "member"}},
    ):
        urllib.request.urlopen(urllib.request.Request(
            f"http://127.0.0.1:{d.write_port}/relation-tuples",
            data=json.dumps(body).encode(), method="PUT",
            headers={"Content-Type": "application/json"},
        ), timeout=10)
    return d


@pytest.fixture(scope="module")
def daemon():
    d = _boot()
    yield d
    d.shutdown()


@pytest.fixture(scope="module")
def threading_daemon():
    d = _boot(**{"serve.http_backend": "threading"})
    yield d
    d.shutdown()


def _request(port, path, body=None):
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}{path}",
        data=None if body is None else json.dumps(body).encode(),
        method="GET" if body is None else "POST",
        headers={"Content-Type": "application/json"},
    )
    try:
        with urllib.request.urlopen(req, timeout=30) as resp:
            return resp.status, resp.read(), dict(resp.headers)
    except urllib.error.HTTPError as e:
        return e.code, e.read(), dict(e.headers)


def _scrape(d):
    _, raw, _ = _request(d.read_port, "/metrics")
    return parse_exposition(raw.decode())


def _value(families, family, sample=None, **labels):
    sample = sample or family
    return sum(
        v for name, have, v in families[family]["samples"]
        if name == sample and all(have.get(k) == want for k, want in labels.items())
    )


def _batch(d, n=40):
    tuples = [
        {"namespace": "docs", "object": "readme", "relation": "view",
         "subject_id": "ann" if i % 2 else f"nobody-{i}"}
        for i in range(n)
    ]
    status, raw, headers = _request(d.read_port, "/check/batch", {"tuples": tuples})
    assert status == 200
    assert json.loads(raw)["results"] == [bool(i % 2) for i in range(n)]
    return headers


def _stage_counts(d, stages, deadline_s=5.0):
    """``encode_write`` is observed after the response is flushed: poll."""
    deadline = time.monotonic() + deadline_s
    while True:
        fams = _scrape(d)
        counts = {
            s: _value(fams, "keto_timeline_stage_duration_seconds",
                      "keto_timeline_stage_duration_seconds_count", stage=s)
            for s in stages
        }
        if all(counts.values()) or time.monotonic() > deadline:
            return counts
        time.sleep(0.05)


def _server_timing(headers):
    entries = [e.strip().split(";dur=") for e in headers["Server-Timing"].split(",")]
    return {name: float(ms) for name, ms in entries}


# -- (d) the REST stages ------------------------------------------------------------


def test_batch_call_over_async_rest_feeds_the_three_new_stages(daemon):
    before = _stage_counts(daemon, (), 0)
    assert before == {}
    headers = _batch(daemon)
    counts = _stage_counts(daemon, ("pool_wait", "decode", "encode_write"))
    assert all(c >= 1 for c in counts.values()), counts
    timing = _server_timing(headers)
    total = timing.pop("total")
    # the listener's stages lie outside the timeline; decode is inside it
    assert "decode" in timing and not set(LISTENER_STAGES) & set(timing)
    for stage in ("admit", "pack", "dispatch", "device", "land", "deliver"):
        assert stage in timing
    # each entry is rounded to 0.01 ms
    assert sum(timing.values()) == pytest.approx(total, abs=0.005 * (len(timing) + 1))


def test_single_get_check_stamps_decode(daemon):
    status, _, headers = _request(
        daemon.read_port, "/check?namespace=docs&object=readme&relation=view&subject_id=ann"
    )
    assert status == 200
    timing = _server_timing(headers)
    assert list(timing)[:2] == ["decode", "admit"]


def test_threading_backend_has_no_pool_and_observes_no_pool_wait(threading_daemon):
    _batch(threading_daemon)
    counts = _stage_counts(threading_daemon, ("decode", "encode_write"))
    assert all(c >= 1 for c in counts.values()), counts
    assert _stage_counts(threading_daemon, ("pool_wait",), 0) == {"pool_wait": 0}


def test_scrapes_and_health_checks_are_not_observed_as_stages(daemon):
    _batch(daemon)
    first = _stage_counts(daemon, ("pool_wait", "encode_write"))
    for _ in range(3):
        _request(daemon.read_port, "/health/ready")
        _scrape(daemon)
    time.sleep(0.1)
    assert _stage_counts(daemon, ("pool_wait", "encode_write")) == first


def test_stage_names_are_declared():
    assert "decode" in STAGES and STAGES.index("decode") == STAGES.index("admit") - 1
    assert LISTENER_STAGES == ("pool_wait", "encode_write")
    assert not set(LISTENER_STAGES) & set(STAGES)


# -- (b) on /metrics, with the real engine's transition sites ------------------------


@pytest.mark.parametrize("state", DISPATCH_STATES)
def test_every_dispatch_state_is_on_metrics_and_accrues_under_traffic(daemon, state):
    _batch(daemon)
    fams = _scrape(daemon)
    assert fams["keto_dispatch_thread_seconds_total"]["type"] == "counter"
    assert _value(fams, "keto_dispatch_thread_seconds_total", state=state) > 0
    assert _value(fams, "keto_dispatch_rounds_total") >= 1


def _states_sum_to_the_window(d, traffic):
    """The dispatch states' seconds between two scrapes against the wall time
    between them. A scrape reads the clock somewhere between its request and
    its reply, so each is bracketed and the window is known to that."""
    def read():
        a = time.perf_counter()
        fams = _scrape(d)
        return a, time.perf_counter(), fams

    a0, b0, f0 = read()
    traffic()
    time.sleep(0.5)
    a1, b1, f1 = read()
    states = (
        _value(f1, "keto_dispatch_thread_seconds_total")
        - _value(f0, "keto_dispatch_thread_seconds_total")
    )
    assert a1 - b0 - 0.005 <= states <= b1 - a0 + 0.005
    return f0, f1


def test_dispatch_states_of_a_window_sum_to_its_length(daemon):
    _states_sum_to_the_window(daemon, lambda: [_batch(daemon, 200) for _ in range(5)])


def test_dispatch_states_sum_to_the_window_with_two_rounds_open(daemon):
    """A body of 10,000 on a quiet interactive lane is three rounds, 4,096 +
    4,096 + 1,808: every round but the first is launched while the one before
    it is out. The states stay exclusive (one thread, one state), the round
    counter says which rounds were overlapped and the tuples counter how wide
    they were."""
    f0, f1 = _states_sum_to_the_window(
        daemon, lambda: [_batch(daemon, 10000) for _ in range(4)]
    )

    def rounds(fams, **labels):
        return _value(fams, "keto_dispatch_rounds_total", **labels)

    assert {have["overlapped"] for _n, have, _v in f1["keto_dispatch_rounds_total"]["samples"]} == {
        "true", "false"
    }
    taken = rounds(f1) - rounds(f0)
    overlapped = rounds(f1, overlapped="true") - rounds(f0, overlapped="true")
    # (one more if the round before the window carried a single: the first
    # call's first round is then cut at the sub-slice)
    assert taken in (4 * 3, 4 * 3 + 1)
    assert overlapped >= 4 * 1  # a call's first round finds nothing out; its last may not either
    assert f1["keto_dispatch_round_tuples_total"]["type"] == "counter"
    tuples = _value(f1, "keto_dispatch_round_tuples_total") - _value(f0, "keto_dispatch_round_tuples_total")
    assert tuples == 4 * 10000
    for state in DISPATCH_STATES:
        assert _value(f1, "keto_dispatch_thread_seconds_total", state=state) >= _value(
            f0, "keto_dispatch_thread_seconds_total", state=state
        )


def test_singles_alone_never_overlap_a_round(daemon):
    before = _value(_scrape(daemon), "keto_dispatch_rounds_total", overlapped="true")
    for i in range(20):
        status, _raw, _h = _request(
            daemon.read_port,
            f"/check?namespace=docs&object=readme&relation=view&subject_id={'ann' if i % 2 else 'bob'}",
        )
        assert status in (200, 403)
    assert _value(_scrape(daemon), "keto_dispatch_rounds_total", overlapped="true") == before


# -- (c) a real profiler session on the CPU backend -------------------------------


def test_profiler_capture_holds_contiguous_dispatch_spans_on_one_thread(daemon, tmp_path):
    import jax

    assert profiling.install_trace_hook() is profiling.SESSION
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    assert not profiling.SESSION.open
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        assert profiling.SESSION.open
        time.sleep(0.3)  # an idle loop pass reads the flag
        for _ in range(4):
            _batch(daemon, 100)
        time.sleep(0.3)
    finally:
        jax.profiler.stop_trace()
    assert not profiling.SESSION.open
    _batch(daemon)  # closes the last span; opens none

    found = sorted(tmp_path.glob("plugins/profile/*/*.xplane.pb"))
    data = jax.profiler.ProfileData.from_file(str(found[-1]))
    threads = []  # one line per thread; their names need not differ
    for plane in data.planes:
        for line in plane.lines:
            spans = sorted(
                (ev.start_ns, ev.start_ns + ev.duration_ns, ev.name, dict(ev.stats))
                for ev in line.events if ev.name.startswith("keto.dispatch.")
            )
            if spans:
                threads.append(spans)
    for spans in threads:
        covered = spans[-1][1] - spans[0][0]
        gaps = 0.0
        for (_, end, _, _), (start, _, _, _) in zip(spans, spans[1:]):
            assert start >= end - 1000, "dispatch spans overlap"  # 1 us of rounding
            gaps += max(0.0, start - end)
        assert gaps < 0.02 * covered, "dispatch spans are not contiguous"
    # another daemon of this module idles beside this one: its collector
    # waits for work on a thread of its own, and only waits
    busy = [spans for spans in threads if any(n.endswith(".launch") for _, _, n, _ in spans)]
    assert len(busy) == 1, "the rounds' spans lie on more than one thread"
    spans = busy[0]
    assert {name for _, _, name, _ in spans} == {f"keto.dispatch.{s}" for s in DISPATCH_STATES}
    launch = next(stats for _, _, name, stats in spans if name.endswith(".launch"))
    assert launch["tuples"] == 100 and launch["slices"] >= 1 and "lane_depth" in launch
    # the round's room on every span: the first round after another test's
    # singles is held to the sub-slice, the others have the round's own size,
    # or the controller's floor if this module's bodies of 10,000 narrowed it
    # (``wait_work`` and ``take`` come before ``round()``: they say what the
    # round before them said, as they do for ``tuples``)
    rooms = {
        (stats["cap"], stats["cap_by"])
        for _, _, name, stats in spans if not name.endswith((".wait_work", ".take"))
    }
    assert rooms and rooms <= {(4096, "batch_size"), (1024, "sub_slice"), (2048, "controller")}


def test_trace_hook_is_installed_once_and_wraps_both_functions():
    import jax

    profiling.install_trace_hook()
    start, stop = jax.profiler.start_trace, jax.profiler.stop_trace
    profiling.install_trace_hook()
    assert jax.profiler.start_trace is start and jax.profiler.stop_trace is stop
    assert hasattr(start, "__wrapped__") and hasattr(stop, "__wrapped__")
    assert profiling.SESSION.annotation is jax.profiler.TraceAnnotation


def test_a_start_trace_that_fails_leaves_the_session_closed(tmp_path):
    import jax

    profiling.install_trace_hook()
    jax.profiler.start_trace(str(tmp_path))
    try:
        with pytest.raises(Exception):
            jax.profiler.start_trace(str(tmp_path))  # one session at a time
        assert profiling.SESSION.open  # the first one still is
    finally:
        jax.profiler.stop_trace()
    assert not profiling.SESSION.open


# -- admission and compiles on /metrics ((e)'s replay: tests/test_admission_replay.py) --


def test_admission_families_on_metrics(daemon):
    _batch(daemon)
    fams = _scrape(daemon)
    signals = {have["signal"] for _, have, _ in
               fams["keto_admission_decreases_total"]["samples"]}
    assert signals == set(SIGNALS)
    assert fams["keto_admission_queue_delay_seconds"]["type"] == "histogram"
    assert fams["keto_admission_increases_total"]["type"] == "counter"
    assert _value(fams, "keto_admission_rate_tuples_per_second") > 0


# -- (f) compiles and device memory, from the runtime --------------------------------


def test_compile_listener_counts_a_fresh_compile_and_nothing_on_a_second_call():
    import jax
    import jax.numpy as jnp

    from keto_tpu.driver import compile_cache

    counts = compile_cache.install_listener()
    assert compile_cache.install_listener() is counts  # registered once

    salt = random.random()  # a program this process has not compiled

    @jax.jit
    def fresh(x):
        return x * salt + 25.0

    x = jnp.ones(7)  # an eager op is a program of its own: compiled before the count
    s0, n0, _ = counts.snapshot()
    fresh(x).block_until_ready()
    s1, n1, _ = counts.snapshot()
    assert n1 == n0 + 1 and s1 > s0
    fresh(x).block_until_ready()
    assert counts.snapshot()[:2] == (s1, n1)


def test_compile_and_memory_families_on_metrics(daemon):
    _batch(daemon)
    fams = _scrape(daemon)
    assert _value(fams, "keto_compiles_total") >= 1  # the daemon's own kernels
    assert _value(fams, "keto_compile_seconds_total") > 0
    assert fams["keto_compile_cache_hits_total"]["type"] == "counter"
    # the CPU backend keeps no memory stats: the family is there, valued 0
    rows = fams["keto_device_memory_bytes"]["samples"]
    assert rows and all(set(have) == {"device", "kind"} for _, have, _ in rows)


def test_device_memory_rows_read_every_local_device(monkeypatch):
    import jax

    from keto_tpu.driver import hbm

    class Dev:
        def __init__(self, id_, stats):
            self.id, self._stats = id_, stats

        def memory_stats(self):
            return self._stats

    monkeypatch.setattr(jax, "local_devices", lambda: [
        Dev(0, {"bytes_in_use": 5, "peak_bytes_in_use": 9, "bytes_limit": 100, "other": 1}),
        Dev(1, {"bytes_in_use": 2}),
        Dev(2, None),
    ])
    assert hbm.device_memory_rows() == [
        (("0", "in_use"), 5.0), (("0", "peak"), 9.0), (("0", "limit"), 100.0),
        (("1", "in_use"), 2.0),
    ]


# -- the order check_step sweeps its buckets in (PR 42) ---------------------------


def test_sweep_families_count_landed_slices_under_the_settled_order_and_hold_the_probe(monkeypatch):
    """``keto_check_sweep_slices_total{order}`` moves once a landed
    ``check_step`` slice, under the order the warm-up's probe kept, and
    ``keto_check_sweep_probe_pulls{order}`` holds both of its readings; a pull
    of ``keto_check_bfs_steps_total`` is one sweep, and the three older
    families keep their names and what they are worth to each other."""
    from keto_tpu.check.dispatch import check_sweep_metrics
    from keto_tpu.check.kernels import SWEEPS
    from keto_tpu.config.provider import Config
    from keto_tpu.driver.registry import Registry
    from keto_tpu.x.metrics import MetricsRegistry
    from tests.test_check_step_sweep import NSS, nested_rows

    def rows(fams, family):
        return {have["order"]: v for _, have, v in fams[family]["samples"]}

    # without an engine: the whole label sets at 0
    m = MetricsRegistry()
    check_sweep_metrics(m, lambda: ({}, {}, {}))
    fams = parse_exposition(m.render())
    assert rows(fams, "keto_check_sweep_slices_total") == dict.fromkeys(SWEEPS, 0.0)
    assert rows(fams, "keto_check_sweep_probe_pulls") == {"up": 0.0, "down": 0.0}
    assert fams["keto_check_sweep_slices_total"]["type"] == "counter"
    assert fams["keto_check_sweep_probe_pulls"]["type"] == "gauge"

    reg = Registry(Config(overrides={
        "namespaces": [{"id": n.id, "name": n.name} for n in NSS], "serve.labels_enabled": False,
    }))
    try:
        stored, queries = nested_rows(42)
        reg.relation_tuple_manager().write_relation_tuples(*stored)
        engine = reg.permission_engine()
        monkeypatch.setattr(engine.dispatch, "stream_widths", lambda snap: [32])
        assert engine.warm_compile() == 1
        order = engine.dispatch._sweep
        fams = parse_exposition(reg.metrics().render())
        probe = rows(fams, "keto_check_sweep_probe_pulls")
        assert set(probe) == {"up", "down"} and min(probe.values()) >= 2
        assert order == min(probe, key=probe.get)
        assert rows(fams, "keto_check_sweep_slices_total") == dict.fromkeys(SWEEPS, 0.0)

        engine.batch_check(queries[:64])
        fams = parse_exposition(reg.metrics().render())
        slices = _value(fams, "keto_check_bfs_slices_total")
        steps = _value(fams, "keto_check_bfs_steps_total")
        assert slices >= 1 and steps > 2 * slices  # deeper than the floor of either scheme
        assert rows(fams, "keto_check_sweep_slices_total") == {**dict.fromkeys(SWEEPS, 0.0), order: slices}
        # 64 queries ride a bitmap of 8 words (the ladder's 256 rung)
        assert _value(fams, "keto_check_pull_words_total") == steps * 8
        assert rows(fams, "keto_check_sweep_probe_pulls") == probe
    finally:
        reg.close()
