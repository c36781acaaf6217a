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
from keto_tpu.check import tpu_engine as te  # noqa: E402
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
    traffic builds them and what the reference and the generator say of each."""

    def __init__(self, seed: int):
        self.graph = GEN.build(random.Random(seed), TUPLES)
        self.store = MemoryPersister(namespace_pkg.MemoryManager(NSS))
        self.store.write_relation_tuples(*map(as_tuple, self.graph.rows))
        objects = traffic.skewed_objects(seed, self.graph.n_objects, POOL, SKEW)
        self.queries, self.analytic = GEN.queries(self.graph, random.Random(seed + 1), objects)
        reference = Reference(self.graph.rows)
        self.reference = [reference.allowed(*q) for q in self.queries]
        self.tuples = [as_query(q) for q in self.queries]

    def engine(self, **kw):
        kw.setdefault("compact_after_s", 3600.0)
        eng = TpuCheckEngine(self.store, self.store.namespaces, **kw)
        eng.labels_settled()
        return eng


@pytest.fixture(scope="module", params=SEEDS)
def deployment(request):
    return Deployment(request.param)


@pytest.fixture(scope="module")
def one():
    return Deployment(SEEDS[0])


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
        finally:
            engine.close()
        if route == "labels_on":
            assert counters.get("label_fallbacks", 0) > 0 and routes.get("hybrid", 0) > 0, (
                "no check left the label kernel: the test is vacuous"
            )
        else:
            assert routes.get("bfs", 0) > 0 and not routes.get("hybrid", 0)
    assert got == deployment.reference
    assert got == deployment.analytic


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
        counts = g.counts()
        assert counts[("check", INLINE)] == 3 and counts[("check", COMPILED)] >= 2
        assert counts[("check", PADDED_UP)] >= 2
    finally:
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
    while engine._geoms.pending():
        assert time.monotonic() < deadline, "the geometry worker did not finish"
        time.sleep(0.02)


def test_after_warm_compile_no_pass_compiles_on_the_calling_thread(one):
    """The pin: on the parent (4203fa9) the same passes compile 7 programs on
    the calling thread, ``check_step`` and ``label_step`` at entry pads that
    are not the rung ``warm_compile`` warmed."""
    engine = one.engine()
    try:
        engine.warm_compile()
        settled = engine._block_iters
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
        assert engine._block_iters == settled, "block_iters moved after the warm-up"
    finally:
        engine.close()


def test_an_engine_nobody_warmed_compiles_inline_and_says_so(one):
    engine = one.engine()
    try:
        assert stream(engine, one.tuples[:1024]) == one.reference[:1024]
        counts = engine.kernel_geometry_counts()
        assert counts.get(("check", INLINE), 0) >= 1
        assert not counts.get(("check", PADDED_UP)) and not counts.get(("label", PADDED_UP))
        assert engine._geoms.pending() == 0 and engine._geoms._worker is None
    finally:
        engine.close()


def launched_sizes(engine):
    """Record the sizes every kernel launch of ``engine`` from this thread
    runs at (the geometry worker's compiles go through the same kernels)."""
    seen = []
    check, label = engine._entry_kernels()
    me = threading.current_thread()

    def spy(name, kern):
        def call(*args, **kw):
            if threading.current_thread() is me:
                seen.append((name, kw.get("sizes") or (kw["n_pairs"], kw["B"])))
            return kern(*args, **kw)

        return call

    engine._entry_kernels = lambda: (spy("check", check), spy("label", label))
    return seen


def test_padded_geometry_answers_bit_identically_on_a_rung_boundary(one):
    """Two slices cut on either side of a rung boundary - the longest whose
    seed entries still fit 2 x B, and one query more - through the exact
    programs of an engine nobody warmed and the padded ones of a warmed
    engine: the same bits."""
    exact = one.engine(labels_enabled=False)
    padded = one.engine(labels_enabled=False)
    try:
        snap = exact.snapshot()
        sd, tg, multi = exact._resolve_bulk(snap, one.tuples)
        B = 256

        def seeds(n):
            packed = te.pack_chunk(snap, sd, tg, multi, 0, n, B // 32)[0]
            return 0 if packed is None else int(np.count_nonzero(packed[2] != snap.num_int + 1))

        n = next(n for n in range(2, B + 1) if seeds(n) > 2 * B)
        assert seeds(n - 1) <= 2 * B < seeds(n)
        padded.warm_compile()
        seen = launched_sizes(padded)
        for cut in (n - 1, n):
            want = exact.batch_check(one.tuples[:cut])
            assert padded.batch_check(one.tuples[:cut]) == want == one.reference[:cut]
        assert len(seen) == 2 and all(kernel == "check" for kernel, _ in seen)
        assert all(sizes[3] > B or sizes[1] > 2 * B for _, sizes in seen), (
            f"the warmed engine ran the slices at their own sizes: {seen}")
        assert padded.kernel_geometry_counts().get(("check", PADDED_UP), 0) == 2
        assert exact.kernel_geometry_counts() == {("check", INLINE): 2}
    finally:
        exact.close()
        padded.close()


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


def test_new_families_are_on_metrics(one):
    reg = Registry(Config(overrides={
        "namespaces": [{"id": n.id, "name": n.name} for n in NSS],
    }))
    try:
        reg.relation_tuple_manager().write_relation_tuples(*map(as_tuple, one.graph.rows))
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
        assert value("keto_check_bfs_slices_total") == engine.bfs_steps_stats.snapshot()["count"] >= 1
        assert value("keto_check_bfs_steps_total") == sum(engine.bfs_steps_stats.tail(4096)[0])
    finally:
        reg.close()
