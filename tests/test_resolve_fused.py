"""``resolve`` as one native pass (``native_pack.resolve_chunk``,
native/pack.cpp ``keto_resolve_chunk``; check/dispatch.py ``_resolve_chunk``)
against what it replaces on the chunks it takes: ``_resolve_records``,
``_entry_counts``, the reach mask of ``_dispatch_piece`` and
``_rewrite_split``'s closure bytes.

The contract under test, on seeded worlds from the benchmark's own generators
(plain groups; a Kronecker directory with hub sinks and relay rows; a
union-only rewrite plan; a plan with gates) and chunk widths 0, 1, 31 and
4,096, with ``dead`` and ``no_target`` marks drawn over real raw ids:

- equal ``sd`` / ``tg``, equal per-query entry counts with and without the
  reach mask (the pass hands over their running sums), equal closure bytes and
  count of ``REWRITTEN``, equal count of misses;
- a stream's slices, their entry counts and what cut their chunks are the
  same whichever path resolved them, and ``gates.split`` is handed the same
  bytes;
- each reason the pass declines for is taken, counted once, and gives the
  numpy path's rows; a snapshot with overlay nodes declines only on a miss;
- a library of another ABI version, or none, resolves with numpy;
- inputs out of range raise as numpy does; under a profiler session the
  ``resolve`` span open while either path's call runs carries ``path``.
"""

import json
import random
from pathlib import Path

import numpy as np
import pytest

from keto_tpu import namespace as namespace_pkg
from keto_tpu.check import gates, native_pack
from keto_tpu.check.dispatch import RESOLVE_DECLINES, RESOLVE_PATHS
from keto_tpu.check.frame import QueryBatch
from keto_tpu.check.pack import hub_usable
from keto_tpu.check.tpu_engine import TpuCheckEngine
from keto_tpu.driver.registry import Registry
from keto_tpu.config.provider import Config
from keto_tpu.namespace.rewrites import REWRITTEN
from keto_tpu.persistence.memory import MemoryPersister
from keto_tpu.relationtuple import SubjectID, SubjectSet

from rewrites_util import as_tuple, manager_of
from test_door_resolve import MANAGER, T, World, make_frame
from test_pack_fused import _generator, _queries

ROOT = Path(__file__).resolve().parents[1]

pytestmark = pytest.mark.skipif(
    not native_pack.available(), reason="native pack library not built"
)

#: world -> configuration of benchmarks/configs
WORLDS = {
    "plain groups": "rbac-groups",
    "hub sinks": "nested-groups",  # relay rows in the count
    "union-only plan": "github-rewrites",
    "plan with gates": "github-blocklist",
}
WIDTHS = (0, 1, 31, 4096)
N_TUPLES = 20_000


@pytest.fixture(scope="module")
def built():
    """world -> (engine, snapshot, queries), built when first asked for."""
    cache, engines = {}, []

    def get(world: str):
        if world not in cache:
            config = json.loads((ROOT / "benchmarks" / "configs" / f"{WORLDS[world]}.json").read_text())
            gen = _generator(config["generator"])
            rng = random.Random(44_000 + len(cache))
            graph = gen.build(random.Random(rng.getrandbits(32)), N_TUPLES)
            nm = manager_of(config)
            p = MemoryPersister(nm)
            p.write_relation_tuples(*[as_tuple(r) for r in graph.rows])
            engine = TpuCheckEngine(p, nm)
            engines.append(engine)
            queries = _queries(gen, graph, graph.rows, rng)
            # one served batch first: the engine uploads what it serves from
            # (a hub snapshot's relay rows among it)
            engine.batch_check(queries[:64])
            engine.labels_settled()
            cache[world] = (engine, engine.snapshot(), queries)
        return cache[world]

    yield get
    for engine in engines:
        engine.close()


def _numpy_side(d, snap, queries, raw):
    """What the pass must equal, from the functions it stands in for."""
    sd, tg, multi = d._resolve_records(snap, queries, *raw)
    assert not multi
    sums = d._entry_sums(snap, sd, tg, multi)
    flags = None
    if snap.rewrites is not None:
        flags = d._closure_bytes(snap, queries, sd, multi)
    done = set(raw[3])
    misses = sum(1 for i in np.flatnonzero((sd == -1) | (tg == -1)).tolist() if i not in done)
    assert sums.shape == (2, len(queries) + 1) and not sums[:, 0].any()
    return sd, tg, np.diff(sums[0]), np.diff(sums[1]), flags, misses


def _compare(d, snap, queries, rng, marks: bool):
    queries, raw = d._raw_ids(snap, queries)
    assert raw is not None and not raw[2]
    n = len(queries)
    if marks and n:
        at = rng.sample(range(n), min(n, 1 + n // 16))
        raw = (*raw[:3], sorted(raw[3] + at[::2]), sorted(raw[4] + at[1::2]))
    want = _numpy_side(d, snap, queries, raw)
    got = native_pack.resolve_chunk(d._resolve_view_of(snap), raw[0], raw[1], raw[3], raw[4])
    assert got.sd.dtype == got.tg.dtype == np.int64
    assert np.array_equal(got.sd, want[0]) and np.array_equal(got.tg, want[1])
    assert got.sums.dtype == np.int64 and got.sums.shape == (2, n + 1) and not got.sums[:, 0].any()
    assert np.array_equal(np.diff(got.sums[0]), want[2])
    assert np.array_equal(np.diff(got.sums[1]), want[3])
    if want[4] is None:
        assert got.flags is None and got.rewritten == 0
    else:
        assert got.flags.dtype == np.uint8 and np.array_equal(got.flags, want[4])
        assert got.rewritten == int(np.count_nonzero(want[4] & REWRITTEN))
    assert (got.misses, got.overlay_starts, got.bad_inputs) == (want[5], 0, 0)
    # the two halves by themselves, as a chunk under a plan with gates takes
    # them: its rows and bytes first, the count once the split has made its
    # positions
    rows = native_pack.resolve_chunk(
        d._resolve_view_of(snap), raw[0], raw[1], raw[3], raw[4], count=False
    )
    assert rows.sums is None and rows[5:] == got[5:]
    assert np.array_equal(rows.sd, got.sd) and np.array_equal(rows.tg, got.tg)
    assert (rows.flags is None) == (got.flags is None)
    assert rows.flags is None or np.array_equal(rows.flags, got.flags)
    assert np.array_equal(native_pack.entry_sums(d._resolve_view_of(snap), got.sd, got.tg), got.sums)
    return want


@pytest.mark.parametrize("width", WIDTHS)
@pytest.mark.parametrize("world", list(WORLDS))
def test_the_pass_equals_the_numpy_functions_array_for_array(built, world, width):
    engine, snap, queries = built(world)
    d = engine.dispatch
    rng = random.Random(width)
    starts = [0, len(queries) - width] + [
        rng.randrange(len(queries) - width) for _ in range(4 if width > 64 else 24)
    ]
    misses = zeroed = relays = rewritten = 0
    for k, i0 in enumerate(starts):
        sd, tg, cnt, cnt_reach, flags, miss = _compare(
            d, snap, queries[i0 : i0 + width], rng, marks=k % 2 == 1
        )
        misses += miss
        zeroed += int((cnt != cnt_reach).sum())
        if flags is not None:
            rewritten += int(np.count_nonzero(flags & REWRITTEN))
        if snap.hub_ptr is not None:
            sink = tg[(tg >= snap.sink_base) & (tg < snap.num_live) & (sd >= 0)] - snap.sink_base
            relays += int(np.count_nonzero(snap.hub_ptr[sink + 1] > snap.hub_ptr[sink]))
    # the worlds bring what the comparison is for
    if width == 4096:
        assert misses
        if world == "hub sinks":
            assert hub_usable(snap) and relays, "no relay row was counted: vacuous"
        if world == "plain groups":
            assert d._device_reach(snap) is not None and zeroed
        if snap.rewrites is not None:
            assert rewritten and snap.rewrites.has_gated == (world == "plan with gates")


def _stream(d, snap, batch):
    """A chunk through ``_dispatch_slices``: what a caller of the stream
    sees of each slice, and what cut the chunk."""
    keys = ("stream_chunks_none", "stream_chunks_geometry", "stream_chunks_budget", "stream_chunk_pieces")
    before = d.maintenance.snapshot()
    out = []
    for dev, host_ans, nq, chunk, leases, n_entries, gate in d._dispatch_slices(snap, batch):
        out.append((nq, n_entries, host_ans[:nq].tolist(), len(chunk), gate is not None))
        d._stage_release(leases)
    after = d.maintenance.snapshot()
    return out, {k: after.get(k, 0) - before.get(k, 0) for k in keys}


@pytest.mark.parametrize("world", list(WORLDS))
def test_a_streams_slices_and_cut_points_do_not_depend_on_the_path(built, world, monkeypatch):
    engine, snap, queries = built(world)
    d = engine.dispatch
    monkeypatch.setattr(d.stream_ctrl, "entry_budget", lambda: None)
    handed = []
    real_split = gates.split

    def split(dispatch, snap, queries, sd, tg, multi, flags, cap_q):
        handed.append(flags.copy())
        return real_split(dispatch, snap, queries, sd, tg, multi, flags, cap_q)

    monkeypatch.setattr(gates, "split", split)
    if world == "hub sinks":
        # the BFS route's bound, 4 x B entries, where the label route's is the
        # pair cap x B: relay rows by the dozen a query pass it
        monkeypatch.setattr(d, "_labels_usable", lambda snap: False)
    cuts = 0
    for i0 in range(0, len(queries), 4096):
        batch = queries[i0 : i0 + 4096]
        paths = d.maintenance.snapshot()
        native, native_cut = _stream(d, snap, batch)
        assert d.maintenance.snapshot().get("resolve_chunks_native", 0) == paths.get("resolve_chunks_native", 0) + 1
        with monkeypatch.context() as m:
            m.setattr(d, "_resolve_decline", lambda snap, raw: "no_library")
            numpy_, numpy_cut = _stream(d, snap, batch)
        assert native == numpy_ and native_cut == numpy_cut
        cuts += native_cut["stream_chunks_geometry"]
        if world == "plan with gates":
            a, b = handed[-2:]
            assert a.dtype == b.dtype == np.uint8 and np.array_equal(a, b)
    if world in ("hub sinks", "plan with gates"):
        assert cuts, "no chunk passed the geometric bound: the cut points were not compared"
    assert bool(handed) == (world == "plan with gates")


@pytest.mark.parametrize("budget", [700, 2500])
def test_a_cut_under_a_lowered_budget_is_the_same_bisect(built, monkeypatch, budget):
    """The controller's budget cuts a chunk the geometric bound leaves whole:
    the bisect runs over the sums the pass handed over."""
    engine, snap, queries = built("plain groups")
    d = engine.dispatch
    monkeypatch.setattr(d.stream_ctrl, "entry_budget", lambda: budget)
    native, native_cut = _stream(d, snap, queries[:4096])
    monkeypatch.setattr(d, "_resolve_decline", lambda snap, raw: "no_library")
    numpy_, numpy_cut = _stream(d, snap, queries[:4096])
    assert native == numpy_ and native_cut == numpy_cut
    assert native_cut["stream_chunks_budget"] == 1 and len(native) > 1


def test_inputs_out_of_range_raise_as_numpy_does(built):
    engine, snap, queries = built("plain groups")
    d = engine.dispatch
    queries, raw = d._raw_ids(snap, queries[:64])
    bad = raw[0].copy()
    bad[7] = snap.raw2dev.shape[0] + 3
    with pytest.raises(IndexError):
        d._resolve_records(snap, queries, bad, *raw[1:])
    got = native_pack.resolve_chunk(d._resolve_view_of(snap), bad, raw[1])
    assert got.bad_inputs == 1
    before = engine.maintenance.snapshot()
    monkey = pytest.MonkeyPatch()
    try:
        monkey.setattr(d, "_raw_ids", lambda snap, tuples: (tuples, (bad, *raw[1:])))
        with pytest.raises(IndexError):
            d._resolve_chunk(snap, queries)
    finally:
        monkey.undo()
    assert _moved(engine, before) == {}  # neither path resolved it


def _moved(engine, before) -> dict:
    after = engine.maintenance.snapshot()
    keys = [f"resolve_chunks_{p}" for p in RESOLVE_PATHS] + [f"resolve_declines_{r}" for r in RESOLVE_DECLINES]
    return {k: after.get(k, 0) - before.get(k, 0) for k in keys if after.get(k, 0) != before.get(k, 0)}


def _truncate_flags(snap):
    """The plan's closure bytes stop short of the snapshot's rows: a start
    past them is what an overlay start looks like to the pass."""
    plan = snap.rewrites
    flags = plan.flags_of(snap)
    snap.__dict__["_rewrite_flags"] = (plan.flags, flags[: snap.num_int // 2].copy())


@pytest.mark.parametrize("reason", ["none", "overlay nodes, no miss", *RESOLVE_DECLINES])
def test_a_declined_chunk_takes_the_numpy_path_and_is_counted_once(built, monkeypatch, reason):
    if reason == "overlay_start":
        engine, snap, queries = built("union-only plan")
        oracle, queries = None, queries[:256]
        monkeypatch.setitem(snap.__dict__, "_rewrite_flags", snap.__dict__.get("_rewrite_flags"))
        _truncate_flags(snap)
        want = engine.dispatch._resolve_bulk(snap, queries)
        assert (want[0] >= snap.rewrites.flags_of(snap).shape[0]).any()
    else:
        w = World()
        engine, oracle = w.tpu, w.oracle
        queries = w.literal(40)
        engine.batch_check(queries[:4])
    try:
        if reason == "no_library":
            monkeypatch.setattr(native_pack, "available", lambda: False)
        if reason == "special":
            queries = queries + [T("docs", "", "view", SubjectID("u1_1"))]
        if reason in ("overlay", "overlay nodes, no miss"):
            # a user and a group the base tables never held
            w.store.write_relation_tuples(
                T("groups", "g2", "member", SubjectID("hired-today")),
                T("docs", "d3", "view", SubjectSet("groups", "made-today", "member")),
            )
            snap = engine.snapshot()
            assert snap.ov_leaf_ids and snap.ov_set_ids
            if reason == "overlay":
                queries = queries + [T("docs", "d2", "view", SubjectID("hired-today"))]
        before = engine.maintenance.snapshot()
        if oracle is not None:
            assert engine.batch_check(queries) == [oracle.subject_is_allowed(q) for q in queries]
        else:
            rows = engine.dispatch._resolve_chunk(snap, queries)
            assert np.array_equal(rows[0], want[0]) and np.array_equal(rows[1], want[1])
            assert rows[3:] == (None, None, None)  # the caller makes the bytes and the sums
        if reason in ("none", "overlay nodes, no miss"):
            assert _moved(engine, before) == {"resolve_chunks_native": 1}
        else:
            assert _moved(engine, before) == {"resolve_chunks_numpy": 1, f"resolve_declines_{reason}": 1}
    finally:
        if oracle is not None:
            engine.close()


@pytest.mark.parametrize("path", RESOLVE_PATHS)
def test_under_a_profiler_session_the_span_that_holds_the_call_says_the_path(built, monkeypatch, path):
    from keto_tpu.x import timeline
    from test_dispatch_tracing import FakeSession

    engine, snap, queries = built("plain groups")
    d = engine.dispatch
    session = FakeSession()
    session.open = True
    clock = timeline.DispatchClock(session)
    if path == "numpy":
        monkeypatch.setattr(d, "_resolve_decline", lambda snap, raw: "no_library")
    order = []
    real = native_pack.resolve_chunk if path == "native" else d._resolve_records

    def called(*a, **kw):
        order.append(len(session.made))  # the spans opened before the call
        return real(*a, **kw)

    if path == "native":
        monkeypatch.setattr(native_pack, "resolve_chunk", called)
    else:
        monkeypatch.setattr(d, "_resolve_records", called)
    timeline.bind_dispatch_clock(clock)
    try:
        clock.round(256, 0)
        _stream(d, snap, queries[:256])
    finally:
        timeline.bind_dispatch_clock(None)
    assert len(order) == 1
    name, args = session.made[order[0] - 1]  # the span open while the call ran
    assert name == "keto.dispatch.resolve" and args["path"] == path
    said = [args.get("path") for name, args in session.made if name == "keto.dispatch.resolve"]
    assert said == [None, path]


def test_a_frame_that_a_reload_made_stale_is_decoded_before_the_pass_and_still_takes_it():
    """``_frame_blocker`` comes first: the frame's namespace ids are another
    manager's, so it is turned into objects; their records are framed on the
    thread, and the pass resolves those."""
    swapped = namespace_pkg.MemoryManager(
        [namespace_pkg.Namespace(id=2, name="docs"), namespace_pkg.Namespace(id=1, name="groups")]
    )
    store = MemoryPersister(swapped)
    store.write_relation_tuples(
        T("groups", "g1", "member", SubjectID("u1_0")),
        T("docs", "d1", "view", SubjectSet("groups", "g1", "member")),
    )
    eng = TpuCheckEngine(store, swapped)
    try:
        snap = eng.snapshot()
        queries = [T("docs", "d1", "view", SubjectID("u1_0")), T("docs", "d1", "view", SubjectID("u2_0"))]
        stale = make_frame(queries, snap)
        assert stale.manager is MANAGER
        batch = QueryBatch([(stale, 0, 2)])
        assert eng.dispatch._frame_blocker(snap, batch) == "reload"
        before = eng.maintenance.snapshot()
        sd, tg, multi, closure, sums, view = eng.dispatch._resolve_chunk(snap, batch)
        assert _moved(eng, before) == {"resolve_chunks_native": 1} and view is not None
        want = eng.dispatch._resolve_bulk(snap, queries)
        assert np.array_equal(sd, want[0]) and np.array_equal(tg, want[1]) and not multi
        assert eng.batch_check(queries) == [True, False]
    finally:
        eng.close()


@pytest.mark.parametrize("library", ["another ABI version", "absent"])
def test_without_a_library_of_this_abi_every_chunk_resolves_with_numpy(monkeypatch, library):
    """``load_library`` takes a library only at the binding's own ABI number
    (4 since the resolve pass; the parent's build was 3): a stale build, or
    none, is passed over and the chunk takes the numpy functions."""
    assert native_pack.load_library().keto_pack_version() == native_pack._ABI_VERSION == 4
    if library == "absent":
        monkeypatch.setenv("KETO_TPU_PACK_LIB", "/nonexistent/libketopack.so")
        monkeypatch.setattr(native_pack, "_candidate_paths", lambda: iter(()))
    else:
        monkeypatch.setattr(native_pack, "_ABI_VERSION", 3)
    monkeypatch.setattr(native_pack, "_lib", None)
    monkeypatch.setattr(native_pack, "_lib_checked", False)
    assert native_pack.load_library() is None
    w = World()
    try:
        queries = w.literal(24)
        before = w.tpu.maintenance.snapshot()
        assert w.tpu.batch_check(queries) == [w.oracle.subject_is_allowed(q) for q in queries]
        assert _moved(w.tpu, before) == {"resolve_chunks_numpy": 1, "resolve_declines_no_library": 1}
    finally:
        w.tpu.close()


def test_the_two_families_are_on_metrics_with_their_whole_label_sets():
    reg = Registry(Config(overrides={"namespaces": [{"id": 1, "name": "docs"}, {"id": 2, "name": "groups"}]}))
    try:
        reg.relation_tuple_manager().write_relation_tuples(T("docs", "d1", "view", SubjectID("u")))
        text = reg.metrics().render()
        for family, labels in (
            ("keto_check_resolve_chunks_total", [f'path="{p}"' for p in RESOLVE_PATHS]),
            ("keto_check_resolve_declines_total", [f'reason="{r}"' for r in RESOLVE_DECLINES]),
        ):
            for label in labels:  # at 0 on a daemon nobody asked yet
                assert f"{family}{{{label}}} 0" in text
        engine = reg.permission_engine()
        assert engine.batch_check(
            [T("docs", "d1", "view", SubjectID("u")), T("docs", "", "view", SubjectID("u"))]
        ) == [True, True]
        assert engine.batch_check([T("docs", "d1", "view", SubjectID("u"))]) == [True]
        text = reg.metrics().render()
        assert 'keto_check_resolve_declines_total{reason="special"} 1' in text
        assert 'keto_check_resolve_chunks_total{path="numpy"} 1' in text
        assert 'keto_check_resolve_chunks_total{path="native"} 1' in text
    finally:
        reg.close()
