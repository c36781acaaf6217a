"""drive-docs (benchmarks/generators/drive_docs.py: folders and files of Keto's
own guide, access inherited through subject sets) at a small size on the CPU:
the engine through each of its routes against the CPU oracle, the benchmark's
plain reference and the generator's analytic expectation; the same through
``RestApp`` with a framed body; and the slice geometries - once
``warm_compile`` has run, a slice whose own program is not compiled pads up
to one that is, answers bit-identically, and compiles nothing on the thread
that launched it (keto_tpu/check/geometry.py)."""

import random
import sys
import threading
import time
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

from benchmarks import traffic  # noqa: E402
from benchmarks.reference import Reference  # noqa: E402
from benchmarks.run import load_module  # noqa: E402
from keto_tpu import namespace as namespace_pkg  # noqa: E402
from keto_tpu.check import CheckEngine  # noqa: E402
from keto_tpu.check import kernels, pack  # noqa: E402
from keto_tpu.check.geometry import COMPILED, INLINE, PADDED_UP, KernelGeometries  # noqa: E402
from keto_tpu.check.tpu_engine import TpuCheckEngine  # noqa: E402
from keto_tpu.config.provider import Config  # noqa: E402
from keto_tpu.driver.registry import Registry  # noqa: E402
from keto_tpu.persistence.memory import MemoryPersister  # noqa: E402
from keto_tpu.relationtuple.model import RelationTuple, SubjectID, SubjectSet  # noqa: E402
from keto_tpu.servers.rest import READ, RestApp  # noqa: E402

GEN = load_module("generators", "drive_docs")
NSS = [namespace_pkg.Namespace(id=i, name=n) for i, n in enumerate(GEN.NAMESPACES)]
TUPLES = 8_000
POOL = 4_096
SEEDS = (11, 12, 2_700_000_013)
SKEW = {"kind": "zipf", "theta": 0.99}


def as_tuple(row):
    ns, obj, rel, sid, sns, sobj, srel = row
    sub = SubjectID(sid) if sid is not None else SubjectSet(sns, sobj, srel)
    return RelationTuple(namespace=ns, object=obj, relation=rel, subject=sub)


def as_query(q):
    ns, obj, rel, sid = q
    return RelationTuple(namespace=ns, object=obj, relation=rel, subject=SubjectID(sid))


class Deployment:
    """The generator's graph in a store, with a pool of queries as the bulk
    traffic builds them and what the reference and the generator say of each.

    ``ring``: the top-level groups that hold no folder are also members of
    one another in a ring. The generator's device part is the all-staff
    groups and their leaves (the host walk and ``device_part`` answer every
    query that does not touch them); the ring puts every group on the
    device, and changes no answer, since membership of a group that holds
    no folder grants nothing."""

    def __init__(self, seed: int, ring: bool = False):
        self.graph = GEN.build(random.Random(seed), TUPLES)
        rows = list(self.graph.rows)
        if ring:
            holds = {r[5] for r in rows if r[0] == "directories" and r[4] == "groups"}
            tops = sorted({r[1] for r in rows if r[0] == "groups" and r[4] == "groups"} - holds,
                          key=lambda g: int(g.split("-")[1]))
            rows += [("groups", a, "member", None, "groups", b, "member")
                     for a, b in zip(tops, tops[1:] + tops[:1])]
        self.ring, self.rows = ring, rows
        self.store = MemoryPersister(namespace_pkg.MemoryManager(NSS))
        self.store.write_relation_tuples(*map(as_tuple, rows))
        objects = traffic.skewed_objects(seed, self.graph.n_objects, POOL, SKEW)
        self.queries, self.analytic = GEN.queries(self.graph, random.Random(seed + 1), objects)
        reference = Reference(rows)
        self.reference = [reference.allowed(*q) for q in self.queries]
        self.tuples = [as_query(q) for q in self.queries]

    def engine(self, **kw):
        kw.setdefault("compact_after_s", 3600.0)
        eng = TpuCheckEngine(self.store, self.store.namespaces, **kw)
        eng.labels_settled()
        return eng


@pytest.fixture(scope="module", params=SEEDS + ("ring",), ids=str)
def deployment(request):
    if request.param == "ring":
        return Deployment(SEEDS[0], ring=True)
    return Deployment(request.param)


@pytest.fixture(scope="module")
def one():
    return Deployment(SEEDS[0])


@pytest.fixture(scope="module")
def ringed():
    return Deployment(SEEDS[0], ring=True)


def stream(engine, tuples, width=1024):
    """The answers of ``tuples`` sent as the batcher sends a call: slices of
    ``width`` through ``batch_check_stream``."""
    out = []
    for i in range(0, len(tuples), width):
        for part in engine.batch_check_stream(tuples[i:i + width]):
            out.extend(np.asarray(part).tolist())
    return out


# -- answers, through each route -------------------------------------------------


def test_reference_and_analytic_expectation_agree(deployment):
    assert deployment.reference == deployment.analytic
    share = sum(deployment.analytic) / POOL
    assert 0.5 < share < 0.95, "the pool should hold grants and denials"


def over_the_pair_cap(engine, tuples):
    """Per query of ``tuples``: do its seed rows times its target-side rows
    pass the label route's pair cap (what ``_device_batch_labeled`` counts)."""
    snap = engine.snapshot()
    sd, tg, multi = engine.dispatch._resolve_bulk(snap, tuples)
    n = len(tuples)
    W = next(w for w in pack._WORD_WIDTHS if 32 * w >= n)
    packed = pack.pack_chunk(snap, sd, tg, multi, 0, n, W)[0]
    e1r, e1q, e2r, e2q, ar, aq, targets = packed
    ni = snap.num_int
    ns = (np.bincount(e1q[e1r != ni + 1], minlength=n)[:n]
          + np.bincount(e2q[e2r != ni + 1], minlength=n)[:n])
    nr = np.bincount(aq[ar != ni], minlength=n)[:n] + (targets[:n] < ni)
    return ns * nr > engine.dispatch._LABEL_PAIR_CAP


@pytest.mark.parametrize("route", ["labels_on", "labels_off", "oracle"])
def test_engine_equals_reference_and_expectation(deployment, route):
    if route == "oracle":
        oracle = CheckEngine(deployment.store)
        got = [oracle.subject_is_allowed(t) for t in deployment.tuples]
    else:
        engine = deployment.engine(labels_enabled=route == "labels_on")
        try:
            got = stream(engine, deployment.tuples)
            counters = engine.maintenance.snapshot()
            routes = engine.route_slice_counts()
            assert routes.get("bfs", 0) + routes.get("hybrid", 0) > 0, (
                f"no slice reached check_step: the test is vacuous ({routes})"
            )
            if route == "labels_on":
                assert counters.get("label_fallbacks_pair_cap", 0) > 0, (
                    "no check left the label kernel: the test is vacuous"
                )
            else:
                assert not routes.get("hybrid", 0) and not routes.get("label", 0)
            if route == "labels_on" and deployment.ring:
                # a slice with a few such queries among ones the label
                # kernel takes is a hybrid, whatever its sub-chunks
                over = over_the_pair_cap(engine, deployment.tuples)
                under = list(np.nonzero(~over)[0][:200])
                assert len(under) >= 40, "the pool has too few queries under the cap"
                few = list(np.nonzero(over)[0][:20]) + under  # 20 of 60..220: a sub-batch of one word
                engine.dispatch.reset_route_stats()
                mixed = stream(engine, [deployment.tuples[i] for i in few])
                assert mixed == [deployment.reference[i] for i in few]
                assert engine.route_slice_counts().get("hybrid", 0) >= 1, (
                    "no hybrid slice landed: the test is vacuous"
                )
        finally:
            engine.close()
    assert got == deployment.reference
    assert got == deployment.analytic


def test_a_slice_that_mostly_falls_back_rides_check_step_whole(ringed):
    """A sub-batch as wide as the slice holds the slice: the label kernel is
    not launched, and the queries it could have taken are counted as handed
    over, by that cause."""
    engine = ringed.engine()
    try:
        engine.dispatch.stream_ctrl.entry_budget = lambda: None  # one sub-chunk a slice, as on a fast device
        seen = launched_sizes(engine)
        # 512 queries: a slice of the 2,048 width, most of them over the cap
        assert stream(engine, ringed.tuples[:512], 512) == ringed.reference[:512]
        assert seen and all(kernel == "check" for kernel, _ in seen), seen
        counters = engine.maintenance.snapshot()
        assert counters["label_fallbacks"] == 512 and not counters.get("label_checks")
        assert counters["label_fallbacks_whole_slice"] == 512 - counters["label_fallbacks_pair_cap"] > 0
        assert set(engine.route_slice_counts()) == {"bfs"}
    finally:
        engine.close()


def test_framed_batch_through_rest_gives_the_same_answers(one):
    reg = Registry(Config(overrides={
        "namespaces": [{"id": n.id, "name": n.name} for n in NSS],
    }))
    try:
        reg.relation_tuple_manager().write_relation_tuples(*map(as_tuple, one.graph.rows))
        app = RestApp(reg, READ)
        framed = lambda: sum(
            v for _n, _ln, labels, v, _e in app._batch_tuples.samples() if labels == ("framed",))
        before = framed()
        status, payload, _ = app.handle(
            "POST", "/check/batch", {}, traffic.batch_body(one.queries), {})
        assert status == 200
        assert framed() - before == POOL, "the body was not framed"
        assert [bool(r) for r in payload["results"]] == one.reference
    finally:
        reg.close()


# -- the geometry set, by itself -------------------------------------------------


def test_a_slice_pads_up_only_once_warmed_and_its_own_program_compiles_behind_it():
    compiled_on = []
    done = threading.Event()

    def compile_fn(kernel, shape, fixed, sizes):
        compiled_on.append((threading.current_thread().name, kernel, sizes))
        done.set()
        return True

    g = KernelGeometries(compile_fn)
    shape, fixed = ("shape",), ("fixed",)
    try:
        for width in (32, 256, 2048):
            g.add("check", shape, fixed, (width,) * 4)
        own = (256, 512, 256, 256)
        assert g.meet("check", shape, fixed, own) == (own, INLINE)  # nobody warmed it
        g.mark_warmed("check", shape)
        assert g.meet("check", shape, fixed, (256,) * 4) == ((256,) * 4, COMPILED)
        # the narrowest program that holds every size; its own is asked for
        assert g.meet("check", shape, fixed, own) == ((2048,) * 4, PADDED_UP)
        assert done.wait(10)
        deadline = time.monotonic() + 10
        while g.pending() and time.monotonic() < deadline:
            time.sleep(0.01)
        assert compiled_on == [("keto-tpu-geometry-compile", "check", own)]
        assert g.meet("check", shape, fixed, own) == (own, COMPILED)
        # a slice of other sizes, while its own compiles, rides the smallest that holds it
        other = (256, 256, 512, 256)
        assert g.meet("check", shape, fixed, other) == ((2048,) * 4, PADDED_UP)
        assert g.meet("check", shape, fixed, other)[1] in (PADDED_UP, COMPILED)
        deadline = time.monotonic() + 10
        while g.pending() and time.monotonic() < deadline:
            time.sleep(0.01)
        assert len(compiled_on) == 2, "sizes are asked for once"
        # more entries than anything compiled holds: the launch compiles, and is counted
        huge = (256, 65536, 256, 256)
        assert g.meet("check", shape, fixed, huge) == (huge, INLINE)
        # another snapshot shape starts an empty, unwarmed set
        assert g.meet("check", ("other",), fixed, (256,) * 4) == ((256,) * 4, INLINE)
        # and the first, met again, is as it was left
        assert g.meet("check", shape, fixed, own) == (own, COMPILED)
        counts = g.counts()
        assert counts[("check", INLINE)] == 3 and counts[("check", COMPILED)] >= 3
        assert counts[("check", PADDED_UP)] >= 2
    finally:
        g.close()


def test_a_compile_that_ends_after_its_shape_has_gone_leaves_the_new_shape_alone():
    """The snapshot changes while the worker compiles: what it compiled is
    entered for the shape it was asked for, and the shape served meanwhile
    keeps its sizes and stays warmed."""
    started, release = threading.Event(), threading.Event()

    def compile_fn(kernel, shape, fixed, sizes):
        started.set()
        assert release.wait(10)
        return True

    g = KernelGeometries(compile_fn)
    old, new, fixed = ("old",), ("new",), ("fixed",)
    own = (256, 512, 256, 256)
    try:
        for shape in (old, new):
            g.add("check", shape, fixed, (2048,) * 4)
            g.mark_warmed("check", shape)
        assert g.meet("check", old, fixed, own) == ((2048,) * 4, PADDED_UP)
        assert started.wait(10)
        # the refresh: slices of the new shape are served while the worker compiles
        g.add("check", new, fixed, (256,) * 4)
        assert g.meet("check", new, fixed, (256,) * 4) == ((256,) * 4, COMPILED)
        release.set()
        deadline = time.monotonic() + 10
        while g.pending() and time.monotonic() < deadline:
            time.sleep(0.01)
        assert g.meet("check", new, fixed, (256,) * 4) == ((256,) * 4, COMPILED)
        assert g.meet("check", new, fixed, (512,) * 4) == ((2048,) * 4, PADDED_UP), "no longer warmed"
        assert g.meet("check", new, fixed, own)[1] == PADDED_UP, "the old shape's program is not the new one's"
        assert g.meet("check", old, fixed, own) == (own, COMPILED)
        assert not g.counts().get(("check", INLINE))
    finally:
        release.set()
        g.close()


# -- the geometry set, in the engine ---------------------------------------------


def backend_compiles_by_thread():
    """``{thread name: backend compiles}``, counted from here on."""
    from jax import monitoring

    counts = {}

    def on_duration(event, duration, **_kw):
        if event == "/jax/core/compile/backend_compile_duration":
            name = threading.current_thread().name
            counts[name] = counts.get(name, 0) + 1

    monitoring.register_event_duration_secs_listener(on_duration)
    return counts


COMPILES = backend_compiles_by_thread()


def wait_for_worker(engine, timeout=120.0):
    deadline = time.monotonic() + timeout
    while engine.dispatch.geoms.pending():
        assert time.monotonic() < deadline, "the geometry worker did not finish"
        time.sleep(0.02)


def test_after_warm_compile_no_pass_compiles_on_the_calling_thread(ringed):
    one = ringed
    """The pin: on the parent (4203fa9) the same passes compile 7 programs on
    the calling thread, ``check_step`` and ``label_step`` at entry pads that
    are not the rung ``warm_compile`` warmed."""
    engine = one.engine()
    try:
        engine.warm_compile()
        settled = engine.dispatch._block_iters
        me = threading.current_thread().name
        before = COMPILES.get(me, 0)
        for width in (1024, 256, 1024):
            assert stream(engine, one.tuples, width) == one.reference
        assert COMPILES.get(me, 0) == before, "a slice compiled on the thread that launched it"
        counts = engine.kernel_geometry_counts()
        assert not counts.get(("check", INLINE)) and not counts.get(("label", INLINE))
        assert counts.get(("check", PADDED_UP), 0) > 0, "no slice left its warmed rung: vacuous"
        wait_for_worker(engine)
        # what the worker compiled is ridden from here on, still without a compile here
        assert stream(engine, one.tuples) == one.reference
        assert COMPILES.get(me, 0) == before
        assert engine.dispatch._block_iters == settled, "block_iters moved after the warm-up"
    finally:
        engine.close()


def test_block_iters_is_left_alone_on_the_warmed_shape_only(ringed):
    one = ringed
    engine = one.engine()
    try:
        engine.warm_compile()
        snap, settled = engine.snapshot(), engine.dispatch._block_iters
        engine.dispatch._after_batch(30, snap)
        assert engine.dispatch._block_iters == settled, "grew on the shape the ladder was warmed for"
        # a refresh that changes the shape: its programs compile anyway
        engine.dispatch._block_iters_shape = ("another shape",)
        engine.dispatch._after_batch(30, snap)
        assert engine.dispatch._block_iters == 32
    finally:
        engine.close()


def test_an_engine_nobody_warmed_compiles_inline_and_says_so(ringed):
    one = ringed
    engine = one.engine()
    try:
        assert stream(engine, one.tuples[:1024]) == one.reference[:1024]
        counts = engine.kernel_geometry_counts()
        assert counts.get(("check", INLINE), 0) >= 1
        assert not counts.get(("check", PADDED_UP)) and not counts.get(("label", PADDED_UP))
        assert engine.dispatch.geoms.pending() == 0 and engine.dispatch.geoms._worker is None
    finally:
        engine.close()


def launched_sizes(engine):
    """Record the sizes every kernel launch of ``engine`` from this thread
    runs at (the geometry worker's compiles go through the same kernels)."""
    seen = []
    check, label = engine.dispatch._entry_kernels()
    me = threading.current_thread()

    def spy(name, kern):
        def call(*args, **kw):
            if threading.current_thread() is me:
                seen.append((name, kw.get("sizes") or (kw["n_pairs"], kw["B"])))
            return kern(*args, **kw)

        return call

    engine.dispatch._entry_kernels = lambda: (spy("check", check), spy("label", label))
    return seen


def test_padded_geometry_answers_bit_identically_on_a_rung_boundary(ringed):
    """Two slices cut on either side of a rung boundary - the longest whose
    device part still has the entry pads of the one before it, and one query
    more - through the exact programs of an engine nobody warmed and the
    padded ones of a warmed engine: the same bits."""
    one = ringed
    exact = one.engine(labels_enabled=False)
    padded = one.engine(labels_enabled=False)
    try:
        snap = exact.snapshot()
        sd, tg, multi = exact.dispatch._resolve_bulk(snap, one.tuples)

        def sizes(n):
            """The sizes the first ``n`` queries launch at, as one slice."""
            W = next(w for w in pack._WORD_WIDTHS if 32 * w >= n)
            packed, host_ans = pack.pack_chunk(snap, sd, tg, multi, 0, n, W)
            sub = None if packed is None else pack.device_part(snap, packed, host_ans)[0]
            return None if sub is None else tuple(sub[i].shape[0] for i in (0, 2, 4, 6))

        above = lambda sz: sz is not None and max(sz[:3]) > sz[3]  # off the warmed minimum rung
        n = next(n for n in range(64, 1024) if above(sizes(n - 1)) and sizes(n) != sizes(n - 1))
        def answer(engine, cut):
            """The first ``cut`` queries as one chunk, not split by entries."""
            snap = engine.snapshot()
            resolved = engine.dispatch._resolve_bulk(snap, one.tuples)
            W = next(w for w in pack._WORD_WIDTHS if 32 * w >= cut)
            dev, host_ans, leases = engine.dispatch._device_batch(snap, *resolved, 0, cut, W)
            try:
                return engine.dispatch._unpack_slice(dev, host_ans, cut)[0].tolist()
            finally:
                engine.dispatch._stage_release(leases)

        padded.warm_compile()
        own, ran = launched_sizes(exact), launched_sizes(padded)
        for cut in (n - 1, n):
            assert answer(padded, cut) == answer(exact, cut) == one.reference[:cut]
        assert [sz for _, sz in own] == [sizes(n - 1), sizes(n)]
        assert all(r != o and all(a >= b for a, b in zip(r, o))
                   for (_, r), (_, o) in zip(ran, own)), (
            f"the warmed engine ran the slices at their own sizes: {ran}")
        assert padded.kernel_geometry_counts().get(("check", PADDED_UP), 0) == 2
        assert exact.kernel_geometry_counts() == {("check", INLINE): 2}
    finally:
        exact.close()
        padded.close()


@pytest.mark.parametrize("where", ["ringed", "cycle"])
def test_device_part_grants_what_the_seeds_hold_and_keeps_what_an_active_row_can_change(ringed, where):
    """``device_part`` against the kernel itself: a chunk run whole through
    ``check_step`` gives the bits that the host's direct grants and the
    sub-batch's bits give together. On the ringed deployment every grant is
    direct and the device part confirms the denials; on a membership cycle
    the grants come through rows that the pulls change."""
    if where == "ringed":
        store, tuples, want = ringed.store, ringed.tuples[:1024], ringed.reference[:1024]
    else:
        store = small_store()
        tuples = [T("files", f"doc-{c}", "access", SubjectID(u))
                  for c in "gh" for u in ("ann", "bob", "cyd", "dee")] * 8
        want = [CheckEngine(store).subject_is_allowed(q) for q in tuples]
    engine = TpuCheckEngine(store, store.namespaces, compact_after_s=3600.0, labels_enabled=False)
    try:
        snap = engine.snapshot()
        n = len(tuples)
        W = next(w for w in pack._WORD_WIDTHS if 32 * w >= n)
        sd, tg, multi = engine.dispatch._resolve_bulk(snap, tuples)
        packed, host_ans = pack.pack_chunk(snap, sd, tg, multi, 0, n, W)
        kw = dict(n_active=snap.num_active, n_int=snap.num_int,
                  valid_rows=tuple(b.n for b in snap.buckets), it_cap=64)
        run = lambda pk: np.asarray(kernels.check_step(
            snap.device_buckets, pack.pack_entries(pk)[0],
            sizes=tuple(pk[i].shape[0] for i in (0, 2, 4, 6)), **kw))
        whole = engine.dispatch._decode_packed(run(packed), host_ans.copy(), n)[0]
        granted = host_ans.copy()
        sub, pos = pack.device_part(snap, packed, granted)
        assert sub is not None and 0 < pos.size < n and not granted[pos].any()
        bits = engine.dispatch._decode_packed(run(sub), np.zeros(pos.size, bool), pos.size)[0]
        if where == "ringed":
            assert granted.sum() > host_ans.sum(), "no direct grant: vacuous"
        else:
            assert bits.any(), "the device part granted nothing: vacuous"
        got = granted.copy()
        got[pos] |= bits
        assert got.tolist() == whole.tolist() == want
    finally:
        engine.close()


# -- why a check leaves the label kernel -----------------------------------------


def T(ns, obj, rel, sub):
    return RelationTuple(namespace=ns, object=obj, relation=rel, subject=sub)


def reasons_of(engine):
    return {k[len("label_fallbacks_"):]: v for k, v in engine.maintenance.snapshot().items()
            if k.startswith("label_fallbacks_")}


def test_pair_cap_is_what_fires_on_the_deployment(one):
    engine = one.engine()
    try:
        assert stream(engine, one.tuples) == one.reference
        reasons = reasons_of(engine)
        assert reasons.get("pair_cap", 0) > 0
        assert sum(reasons.values()) == engine.maintenance.snapshot()["label_fallbacks"]
    finally:
        engine.close()


def small_store():
    """Two components, each g0 <- g1 <- ... <- g5 <- g0 (a cycle keeps the
    chain on the device) with users under g5 and a file reading g0."""
    store = MemoryPersister(namespace_pkg.MemoryManager(NSS))
    rows = []
    for c, users in (("g", ("ann", "bob")), ("h", ("cyd",))):
        rows += [T("groups", f"{c}{i}", "member", SubjectSet("groups", f"{c}{(i + 1) % 6}", "member"))
                 for i in range(6)]
        rows += [T("groups", f"{c}5", "member", SubjectID(u)) for u in users]
        rows.append(T("files", f"doc-{c}", "access", SubjectSet("groups", f"{c}0", "member")))
    store.write_relation_tuples(*rows)
    return store


@pytest.mark.parametrize("reason, query, kw", [
    # a start row that is the target: reach0 would count the path of no edge
    ("self_hit", T("groups", "g2", "member", SubjectSet("groups", "g2", "member")), {}),
    # a pattern (the empty object) resolves to several start rows
    ("multi", T("groups", "", "member", SubjectID("ann")), {}),
    # one landmark processed: the pairs of one component cannot be certified
    ("uncertifiable", T("files", "doc-h", "access", SubjectID("cyd")), {"labels_landmarks": 1}),
])
def test_each_fallback_reason_is_reached_by_a_query_built_for_it(reason, query, kw):
    store = small_store()
    engine = TpuCheckEngine(store, store.namespaces, compact_after_s=3600.0, **kw)
    try:
        engine.labels_settled()
        queries = [query, T("files", "doc-g", "access", SubjectID("bob"))]
        want = [CheckEngine(store).subject_is_allowed(q) for q in queries]
        assert engine.batch_check(queries) == want
        assert reasons_of(engine).get(reason, 0) >= 1, reasons_of(engine)
    finally:
        engine.close()


def test_new_families_are_on_metrics(ringed):
    one = ringed
    reg = Registry(Config(overrides={
        "namespaces": [{"id": n.id, "name": n.name} for n in NSS],
    }))
    try:
        reg.relation_tuple_manager().write_relation_tuples(*map(as_tuple, one.rows))
        app = RestApp(reg, READ)
        engine = reg.permission_engine()
        engine.labels_settled()  # the build overlaps: the label route has to be live
        status, payload, _ = app.handle(
            "POST", "/check/batch", {}, traffic.batch_body(one.queries[:1024]), {})
        assert status == 200 and [bool(r) for r in payload["results"]] == one.reference[:1024]
        text = reg.metrics().render()
        value = lambda line: float(next(
            ln for ln in text.splitlines() if ln.startswith(line)).rsplit(" ", 1)[1])
        assert value('keto_label_fallbacks_total{reason="pair_cap"}') > 0
        assert value('keto_kernel_geometry_total{kernel="check",met="inline_compile"}') >= 1
        assert value('keto_kernel_geometry_total{kernel="check",met="padded_up"}') == 0
        # at this size the device part has no row that can change, so a
        # slice may converge in 0 pulls
        assert value("keto_check_bfs_slices_total") == engine.dispatch.bfs_steps_stats.snapshot()["count"] >= 1
        assert value("keto_check_bfs_steps_total") == sum(engine.dispatch.bfs_steps_stats.tail(4096)[0])
    finally:
        reg.close()
