"""What the slice controller decided, counted where it is decided
(keto_tpu/check/slice_ctrl.py ``count_take`` / ``observe``, check/dispatch.py
``_slices`` / ``_dispatch_slices``, driver/batch.py ``_take_locked``,
x/timeline.py ``DispatchClock``) and put on ``/metrics`` by the modules that
count it. No sleeps: the controller is fed readings, the batcher's take is
scripted, the clock's long stay is a patched ``perf_counter``."""

from concurrent.futures import Future

import numpy as np
import pytest

from keto_tpu.check.dispatch import CHUNK_CUTS, stream_chunk_metrics
from keto_tpu.check.slice_ctrl import (
    CTRL_EVENTS, ROUTES, TAKE_CAP_BY, StreamSliceController, stream_ctrl_metrics,
)
from keto_tpu.check.tpu_engine import TpuCheckEngine
from keto_tpu.driver.batch import BATCH, INTERACTIVE, CheckBatcher, _Item
from keto_tpu.relationtuple.model import RelationTuple, SubjectID
from keto_tpu.x import timeline
from keto_tpu.x.metrics import MetricsRegistry, NullMetricsRegistry, parse_exposition
from keto_tpu.x.timeline import (
    DISPATCH_STATES, LONG_STAY_S, PACK, ROUND_CAP_BY, TAKE, DispatchClock,
    dispatch_clock_metrics,
)

from test_slice_tail import _mixed_depth_store

#: the served path's numbers (PERF.md §7, First): ``engine.batch_size`` bounds a
#: round at 4,096 and the memory-derived slice cap is wider than any rung met
ROUND, BOUND = 4096, 1 << 17


def _take(ctrl, bound):
    """A take as ``_slices`` cuts and counts it."""
    rung = ctrl.cap()
    ctrl.count_take(rung, bound)
    return min(bound, rung)


def _serve(ctrl, ms, route="hybrid", takes=1):
    """``takes`` takes as ``_slices`` cuts them off a round of ``ROUND``, each
    landing as one pulling slice of ``ms``. Returns the caps offered."""
    caps = []
    for _ in range(takes):
        cap = _take(ctrl, BOUND)
        nq = min(cap, ROUND)
        ctrl.observe(nq, ms, route=route, bfs_steps=12, entries=12 * nq, full_take=nq >= cap)
        caps.append(cap)
    return caps


def _families(m):
    return parse_exposition(m.render())


def _rows(fams, family, sample=None):
    sample = sample or family
    return {
        tuple(sorted(labels.items())): v
        for name, labels, v in fams[family]["samples"] if name == sample
    }


# -- the controller: takes, what set them, what moved it ------------------------


def test_replay_of_the_narrow_regime_one_stall_then_the_model_holds_2048():
    """PERF.md §7, First, on the class: wide pulling slices at ~14 ms, one
    reading over 1.25 x 40 ms, then slices of 2,048 that cost the pulls of a
    wide one. One ``narrow`` event; every later take is offered rung 2,048, by
    the model once the reactive index has recovered; ``futile`` never widens."""
    ctrl = StreamSliceController(target_ms=40.0)
    wide = _serve(ctrl, 14.0, takes=20)
    assert set(wide[1:]) == {8192}  # 4,096 at 14 ms predicts 8,192 within 40
    before = ctrl.snapshot()
    assert before["events"] == {"narrow": {}, "widen": before["events"]["widen"], "guard_down": {}}
    assert before["takes"][2048] == 0

    _serve(ctrl, 1.25 * 40.0 + 1.0)  # the stall
    stalled = ctrl.snapshot()
    assert stalled["events"]["narrow"] == {"hybrid": 1}
    assert stalled["cap"] == 2048  # the reactive rung went to the floor

    later = _serve(ctrl, 14.0, takes=200)
    after = ctrl.snapshot()
    assert set(later) == {2048}
    assert after["takes"][2048] - stalled["takes"][2048] == 200
    assert after["events"]["narrow"] == {"hybrid": 1}
    assert after["take_cap"]["futile"] == 0
    # two good readings (``patience``) and the reactive index is above the
    # floor again: from then on the model alone holds the narrow rung
    assert after["cap"] > 2048 and after["model_cap"] == 2048
    assert after["take_cap"]["reactive"] - stalled["take_cap"]["reactive"] == 2
    assert after["take_cap"]["model"] - stalled["take_cap"]["model"] == 198
    assert sum(after["takes"].values()) == sum(after["take_cap"].values()) == 221


def test_a_take_under_the_callers_bound_counts_bound_and_keeps_the_rung():
    ctrl = StreamSliceController(target_ms=40.0)
    rung = ctrl.cap()
    assert _take(ctrl, 64) == 64
    assert _take(ctrl, rung) == rung  # not lower: the controller's own
    snap = ctrl.snapshot()
    assert snap["take_cap"] == {"reactive": 1, "model": 0, "futile": 0, "bound": 1}
    assert snap["takes"][rung] == 2


def test_the_batchers_own_cap_call_is_not_a_take():
    ctrl = StreamSliceController()
    for _ in range(5):
        ctrl.cap()
    assert sum(ctrl.snapshot()["takes"].values()) == 0


def test_futile_is_counted_where_the_wider_rung_is_served():
    """tests/test_slice_tail.py ``_narrowed(30, 30)``: a pulling slice of the
    2,048 rung took as long as one of the 8,192 rung."""
    ctrl = StreamSliceController(target_ms=40.0)
    ctrl.observe(4096, 30.0, route="hybrid", bfs_steps=10, entries=70_000)
    ctrl.observe(2048, 30.0, route="bfs", bfs_steps=10, entries=35_000, full_take=True)
    assert ctrl.snapshot()["model_cap"] == 2048
    assert _take(ctrl, BOUND) == 8192
    snap = ctrl.snapshot()
    assert snap["take_cap"] == {"reactive": 0, "model": 0, "futile": 1, "bound": 0}
    assert snap["takes"][8192] == 1 and snap["takes"][2048] == 0


def test_widen_and_guard_down_carry_the_observed_slices_route():
    ctrl = StreamSliceController(target_ms=40.0, floor=32, patience=1)
    ctrl.observe(ctrl.cap(), 400.0, route="bfs")
    low = ctrl.snapshot()["cap"]
    ctrl.observe(low, 1.0, route="label")
    snap = ctrl.snapshot()
    assert snap["cap"] > low
    assert snap["events"]["narrow"] == {"bfs": 1} and snap["events"]["widen"] == {"label": 1}
    # a blown tail: 31 fast readings and a monster, retuned at the 32nd
    ctrl = StreamSliceController(target_ms=40.0, floor=32)
    for _ in range(StreamSliceController.TAIL_EVERY - 1):
        ctrl.observe(64, 1.0, route="label")
    ctrl.observe(64, 500.0, route="hybrid")
    snap = ctrl.snapshot()
    assert snap["tail_guard"] == 0.5
    assert snap["events"]["guard_down"] == {"hybrid": 1}


def test_the_unread_ewma_is_gone():
    ctrl = StreamSliceController()
    ctrl.observe(64, 1.0)
    assert "ewma_ms_per_query" not in ctrl.snapshot()
    assert not hasattr(ctrl, "_ewma_ms_per_q")


# -- on /metrics: declared by the module, whole label sets at 0 from boot ---------


def test_controller_families_read_0_over_their_whole_label_sets_without_a_controller():
    m = MetricsRegistry()
    stream_ctrl_metrics(m, lambda: None)
    fams = _families(m)
    takes = _rows(fams, "keto_stream_takes_total")
    assert [dict(k)["rung"] for k in takes] == ["131072", "2048", "32768", "65536", "8192"]
    assert len(takes) <= 7 and set(takes.values()) == {0.0}
    assert {dict(k)["by"] for k in _rows(fams, "keto_stream_take_cap_total")} == set(TAKE_CAP_BY)
    events = _rows(fams, "keto_stream_ctrl_events_total")
    assert len(events) == len(CTRL_EVENTS) * len(ROUTES) and set(events.values()) == {0.0}
    assert {dict(k)["route"] for k in _rows(fams, "keto_stream_ctrl_route_ms_per_query")} == set(ROUTES)
    assert fams["keto_stream_takes_total"]["type"] == "counter"
    for gauge in ("rung", "model_cap", "tail_guard", "entry_budget"):
        assert fams[f"keto_stream_ctrl_{gauge}"]["type"] == "gauge"


def test_snapshot_is_the_one_source_of_the_gauges_and_the_counters():
    ctrl = StreamSliceController(target_ms=40.0)
    _serve(ctrl, 14.0, takes=20)
    _serve(ctrl, 60.0)
    _serve(ctrl, 14.0, takes=10)
    m = MetricsRegistry()
    stream_ctrl_metrics(m, ctrl.snapshot)
    fams, snap = _families(m), ctrl.snapshot()

    def one(family, **labels):
        return _rows(fams, family)[tuple(sorted(labels.items()))]

    assert one("keto_stream_ctrl_rung") == snap["cap"]
    assert one("keto_stream_ctrl_model_cap") == snap["model_cap"] == 2048
    assert one("keto_stream_ctrl_tail_guard") == snap["tail_guard"]
    assert one("keto_stream_ctrl_entry_budget") == snap["entry_budget"] > 0
    assert one("keto_stream_ctrl_route_ms_per_query", route="hybrid") == pytest.approx(
        snap["routes"]["hybrid"]["per_q_ms"]
    )
    assert one("keto_stream_ctrl_route_ms_per_query", route="label") == 0.0
    for rung, n in snap["takes"].items():
        assert one("keto_stream_takes_total", rung=str(rung)) == n
    for by, n in snap["take_cap"].items():
        assert one("keto_stream_take_cap_total", by=by) == n
    assert one("keto_stream_ctrl_events_total", event="narrow", route="hybrid") == 1
    assert one("keto_stream_ctrl_events_total", event="narrow", route="bfs") == 0


def test_the_null_registry_takes_the_declarations():
    m = NullMetricsRegistry()
    stream_ctrl_metrics(m, lambda: None)
    stream_chunk_metrics(m, dict)
    assert dispatch_clock_metrics(m, lambda: None) is not None


# -- what cut a chunk -----------------------------------------------------------------


def _make_persister(namespaces):
    from keto_tpu import namespace as namespace_pkg
    from keto_tpu.persistence.memory import MemoryPersister

    nss = [namespace_pkg.Namespace(id=i, name=name) for name, i in namespaces]
    return MemoryPersister(namespace_pkg.MemoryManager(nss), network_id="default")


@pytest.fixture(scope="module")
def engine():
    p, queries = _mixed_depth_store(_make_persister, seed=7)
    engine = TpuCheckEngine(p, p.namespaces, labels_enabled=False)
    yield engine, queries[:128]
    engine.close()


def _chunks(engine):
    counters = engine.maintenance.raw()[0]
    return (
        {cut: counters.get(f"stream_chunks_{cut}", 0) for cut in CHUNK_CUTS},
        counters.get("stream_chunk_pieces", 0),
    )


def _dispatch(engine, batch):
    d = engine.dispatch
    before, pieces = _chunks(engine)
    recs = list(d._dispatch_slices(engine.snapshot(), batch))
    for rec in recs:
        d._stage_release(rec[4])
    after, pieces_after = _chunks(engine)
    return {cut: after[cut] - before[cut] for cut in CHUNK_CUTS}, pieces_after - pieces, len(recs)


def test_a_chunk_cut_by_the_entry_budget_alone_counts_budget_and_its_pieces(engine, monkeypatch):
    engine, batch = engine
    monkeypatch.setattr(engine.dispatch.stream_ctrl, "entry_budget", lambda: None)
    assert _dispatch(engine, batch) == ({"none": 1, "geometry": 0, "budget": 0}, 1, 1)
    monkeypatch.setattr(engine.dispatch.stream_ctrl, "entry_budget", lambda: 64)
    cuts, pieces, slices = _dispatch(engine, batch)
    assert cuts == {"none": 0, "geometry": 0, "budget": 1}
    assert pieces == slices > 1


@pytest.mark.parametrize("path", ["native", "numpy"])
def test_a_chunk_over_the_geometric_bound_counts_geometry_with_or_without_a_budget(engine, monkeypatch, path):
    """Whichever path resolved the chunk (``resolve``'s one native pass hands
    the entry counts over as running sums; a chunk it declines takes
    ``_entry_counts``), at least 20 entries a query are over the bound."""
    from keto_tpu.check import native_pack

    engine, batch = engine
    d = engine.dispatch
    # 128 queries pad to B = 256: the geometric bound is 4 x B = 1,024 entries
    if path == "numpy":
        real = d._entry_counts
        monkeypatch.setattr(d, "_resolve_decline", lambda snap, raw: "no_library")
        monkeypatch.setattr(d, "_entry_counts", lambda *a: np.maximum(real(*a), 20))
    else:
        if not native_pack.available():
            pytest.skip("native pack library not built")
        # the pass takes no chunk with a pattern in it
        patterns = set(d._raw_ids(engine.snapshot(), batch)[1][2])
        batch = [q for i, q in enumerate(batch) if i not in patterns]
        real = native_pack.resolve_chunk

        def at_least_20(*a, **kw):
            got = real(*a, **kw)
            sums = np.zeros_like(got.sums)
            np.cumsum(np.maximum(np.diff(got.sums), 20), axis=1, out=sums[:, 1:])
            return got._replace(sums=sums)

        monkeypatch.setattr(native_pack, "resolve_chunk", at_least_20)
    for budget in (None, 64):
        monkeypatch.setattr(d.stream_ctrl, "entry_budget", lambda: budget)
        cuts, pieces, slices = _dispatch(engine, batch)
        assert cuts == {"none": 0, "geometry": 1, "budget": 0}
        assert pieces == slices > 1


def test_chunk_families_are_declared_by_dispatch_and_read_the_engines_counters(engine):
    engine, _ = engine
    m = MetricsRegistry()
    stream_chunk_metrics(m, lambda: {})
    fams = _families(m)
    assert {dict(k)["cut"]: v for k, v in _rows(fams, "keto_stream_chunks_total").items()} == dict.fromkeys(
        CHUNK_CUTS, 0.0
    )
    assert _rows(fams, "keto_stream_chunk_pieces_total") == {(): 0.0}
    m = MetricsRegistry()
    stream_chunk_metrics(m, lambda: engine.maintenance.raw()[0])
    cuts, pieces = _chunks(engine)
    fams = _families(m)
    assert {dict(k)["cut"]: v for k, v in _rows(fams, "keto_stream_chunks_total").items()} == cuts
    assert _rows(fams, "keto_stream_chunk_pieces_total") == {(): pieces}
    assert pieces >= sum(cuts.values()) > 0


def test_a_stream_counts_one_take_a_cut_of_the_source(engine, monkeypatch):
    engine, batch = engine
    ctrl = engine.dispatch.stream_ctrl
    before = ctrl.snapshot()
    monkeypatch.setattr(ctrl, "cap", lambda: 64)  # as tests/test_slice_tail.py patches it
    assert len(engine.batch_check(batch)) == 128
    after = ctrl.snapshot()
    # 128 queries at 64 a take: two takes; the one that found the source dry is none
    assert after["takes"].get(64, 0) - before["takes"].get(64, 0) == 2
    assert sum(after["take_cap"].values()) - sum(before["take_cap"].values()) == 2


# -- what set a round's room -------------------------------------------------------


class PlainEngine:
    """An engine without the stream API: ``_launch`` makes one plain call."""

    def batch_check_with_token(self, tuples, **_kw):
        return [True] * len(list(tuples)), 7


def _queue(b, n, lane):
    tuples = [
        RelationTuple(namespace="acl", object=f"q-{i}", relation="access", subject=SubjectID("u"))
        for i in range(n)
    ]
    b._enqueue(_Item(tuples, Future(), None, False, None, lane))


def _round(b):
    """One round as the collector makes it: taken, launched, its room counted."""
    with b._cond:
        segments = b._take_locked()
    b._launch(segments, 0, False)
    return sum(count for item, _, count in segments if item.lane == BATCH)


def test_a_rounds_room_is_counted_by_what_set_it():
    class Ctrl:
        value = 1 << 20

        def cap(self):
            return self.value

    eng = PlainEngine()
    eng.stream_ctrl = Ctrl()
    b = CheckBatcher(eng, batch_size=4096, batch_sub_slice=1024)
    _queue(b, 20000, BATCH)
    assert _round(b) == 4096
    assert b.clock.round_cap == {"batch_size": 1, "sub_slice": 0, "controller": 0}
    _queue(b, 1, INTERACTIVE)
    assert _round(b) == 1024  # an interactive item rides
    assert _round(b) == 1024  # and one rode the round before
    assert b.clock.round_cap == {"batch_size": 1, "sub_slice": 2, "controller": 0}
    eng.stream_ctrl.value = 2048
    assert _round(b) == 2048
    assert b.clock.round_cap == {"batch_size": 1, "sub_slice": 2, "controller": 1}
    eng.stream_ctrl.value = 4096  # not lower than the round's own size: not the controller's
    assert _round(b) == 4096
    eng.stream_ctrl.value = 600
    _queue(b, 1, INTERACTIVE)
    assert _round(b) == 600  # the least of the three
    assert b.clock.round_cap == {"batch_size": 2, "sub_slice": 2, "controller": 2}
    assert sum(b.clock.round_cap.values()) == b.clock.rounds == 6


def test_round_cap_family_sums_to_the_rounds_and_reads_0_without_a_batcher():
    m = MetricsRegistry()
    clock = DispatchClock()
    holder = [None]
    dispatch_clock_metrics(m, lambda: holder[0])
    fams = _families(m)
    assert {dict(k)["by"]: v for k, v in _rows(fams, "keto_dispatch_round_cap_total").items()} == dict.fromkeys(
        ROUND_CAP_BY, 0.0
    )
    holder[0] = clock
    clock.round(4096, 0)
    clock.round(2048, 0, cap=2048, cap_by="controller")
    got = {dict(k)["by"]: v for k, v in _rows(_families(m), "keto_dispatch_round_cap_total").items()}
    assert got == {"batch_size": 1.0, "sub_slice": 0.0, "controller": 1.0}


# -- long stays of the dispatch thread ----------------------------------------------


def test_a_stay_of_16_ms_is_observed_by_state_and_a_shorter_one_is_not(monkeypatch):
    now = [100.0]
    monkeypatch.setattr(timeline.time, "perf_counter", lambda: now[0])
    m = MetricsRegistry()
    clock = DispatchClock()
    clock.long_stays = dispatch_clock_metrics(m, lambda: clock)
    fams = _families(m)
    counts = _rows(fams, "keto_dispatch_long_stay_seconds", "keto_dispatch_long_stay_seconds_count")
    assert {dict(k)["state"] for k in counts} == set(DISPATCH_STATES)
    assert set(counts.values()) == {0.0}
    buckets = {dict(k)["le"] for k in _rows(
        fams, "keto_dispatch_long_stay_seconds", "keto_dispatch_long_stay_seconds_bucket")}
    assert buckets == {"0.064", "0.256", "1.024", "+Inf"}

    clock.enter(TAKE)  # wait_work since the clock was built: no time at all
    now[0] += LONG_STAY_S - 0.001
    clock.enter(PACK)  # take: just under
    now[0] += 0.300
    clock.enter(TAKE)  # pack: a long stay
    now[0] += LONG_STAY_S
    clock.enter(PACK)  # take: at the threshold
    fams = _families(m)
    counts = _rows(fams, "keto_dispatch_long_stay_seconds", "keto_dispatch_long_stay_seconds_count")
    sums = _rows(fams, "keto_dispatch_long_stay_seconds", "keto_dispatch_long_stay_seconds_sum")
    by_state = {dict(k)["state"]: v for k, v in counts.items()}
    assert by_state == {**dict.fromkeys(DISPATCH_STATES, 0.0), "pack": 1.0, "take": 1.0}
    assert {dict(k)["state"]: v for k, v in sums.items()}["pack"] == pytest.approx(0.300)
    le = _rows(fams, "keto_dispatch_long_stay_seconds", "keto_dispatch_long_stay_seconds_bucket")
    assert le[(("le", "0.256"), ("state", "pack"))] == 0.0
    assert le[(("le", "1.024"), ("state", "pack"))] == 1.0


def test_a_clock_nobody_attached_a_histogram_to_keeps_no_long_stays(monkeypatch):
    now = [5.0]
    monkeypatch.setattr(timeline.time, "perf_counter", lambda: now[0])
    clock = DispatchClock()
    now[0] += 1.0
    clock.enter(PACK)
    assert clock.long_stays is None and clock.snapshot()[0][0] == pytest.approx(1.0)


# -- a booted daemon ---------------------------------------------------------------------

FAMILIES = {
    "keto_stream_takes_total": 5,
    "keto_stream_take_cap_total": len(TAKE_CAP_BY),
    "keto_stream_ctrl_events_total": len(CTRL_EVENTS) * len(ROUTES),
    "keto_stream_chunks_total": len(CHUNK_CUTS),
    "keto_stream_chunk_pieces_total": 1,
    "keto_dispatch_round_cap_total": len(ROUND_CAP_BY),
    "keto_stream_ctrl_rung": 1,
    "keto_stream_ctrl_model_cap": 1,
    "keto_stream_ctrl_tail_guard": 1,
    "keto_stream_ctrl_entry_budget": 1,
    "keto_stream_ctrl_route_ms_per_query": len(ROUTES),
}


@pytest.fixture(scope="module")
def daemon():
    from test_dispatch_tracing import _boot

    d = _boot()
    yield d
    d.shutdown()


@pytest.mark.parametrize("family", sorted(FAMILIES) + ["keto_dispatch_long_stay_seconds"])
def test_every_family_is_on_metrics_of_a_fresh_daemon_with_its_label_set(daemon, family):
    from test_dispatch_tracing import _scrape

    fams = _scrape(daemon)
    if family == "keto_dispatch_long_stay_seconds":
        assert fams[family]["type"] == "histogram"
        counts = _rows(fams, family, family + "_count")
        assert {dict(k)["state"] for k in counts} == set(DISPATCH_STATES)
        return
    rows = _rows(fams, family)
    assert len(rows) == FAMILIES[family], rows
    if fams[family]["type"] == "counter":
        # nothing was checked yet: a window's delta is defined on the first scrape
        assert set(rows.values()) == {0.0}


def test_a_batch_call_moves_takes_chunks_and_the_rounds_room_together(daemon):
    from test_dispatch_tracing import _batch, _scrape, _value

    f0 = _scrape(daemon)
    _batch(daemon, 200)
    f1 = _scrape(daemon)

    def moved(family, **labels):
        return _value(f1, family, **labels) - _value(f0, family, **labels)

    rounds = moved("keto_dispatch_rounds_total")
    assert rounds >= 1 and moved("keto_dispatch_round_cap_total") == rounds
    assert moved("keto_dispatch_round_cap_total", by="controller") == 0
    assert moved("keto_stream_takes_total") == moved("keto_stream_take_cap_total") == rounds
    assert moved("keto_stream_chunks_total") >= rounds
    assert moved("keto_stream_chunk_pieces_total") >= moved("keto_stream_chunks_total")
    assert _value(f1, "keto_stream_ctrl_rung") >= 2048
