"""nested-groups (benchmarks/generators/nested_groups.py: a company directory
whose group sizes, memberships, nestings and grants are drawn from Graph500's
Kronecker generator) at a small size on the CPU: the engine with labels on
(the ``hybrid`` route: the label kernel for what it certifies, ``check_step``
for the riders), with labels off, and the CPU oracle, each against the
benchmark's plain reference and the generator's own expectation; the same
pool through ``RestApp`` as a framed body; a hub row past the pull's degree
chunk; the generator's determinism, scaling and skew; and no compile on the
calling thread once ``warm_compile`` has run.

The graphs here stay far below ``serve.labels_device_min_edges`` interior ELL
slots (579 interior rows at 20,000 tuples), so the host builds their labels:
on the CPU backend the device builder took 131 s at a quarter of the
deployment (ISSUE 34), and tier-1 runs in under four minutes."""

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
from keto_tpu.check import CheckEngine, kernels  # noqa: E402
from keto_tpu.check.geometry import INLINE, PADDED_UP, KernelGeometries  # noqa: E402
from keto_tpu.check.tpu_engine import TpuCheckEngine  # noqa: E402
from keto_tpu.config.provider import Config  # noqa: E402
from keto_tpu.driver.registry import Registry  # noqa: E402
from keto_tpu.persistence.memory import MemoryPersister  # noqa: E402
from keto_tpu.servers.rest import READ, RestApp  # noqa: E402
from tests.test_drive_docs import COMPILES, as_query, as_tuple, stream, wait_for_worker  # noqa: E402

GEN = load_module("generators", "nested_groups")
NSS = [namespace_pkg.Namespace(id=i, name=n) for i, n in enumerate(GEN.NAMESPACES)]
TUPLES = 20_000
POOL = 4_096
ORACLE_POOL = 512
SEEDS = (21, 22, 2_800_000_023)
SKEW = {"kind": "zipf", "theta": 0.99}


def store_of(rows):
    store = MemoryPersister(namespace_pkg.MemoryManager(NSS))
    store.write_relation_tuples(*map(as_tuple, rows))
    return store


def engine_on(store, **kw):
    engine = TpuCheckEngine(store, store.namespaces, compact_after_s=3600.0, **kw)
    engine.labels_settled()
    return engine


class Deployment:
    """The generator's graph in a store, with a pool of queries as the bulk
    traffic builds them and what the reference and the generator say of each."""

    def __init__(self, seed: int):
        self.graph = GEN.build(random.Random(seed), TUPLES)
        self.store = store_of(self.graph.rows)
        objects = traffic.skewed_objects(seed, self.graph.n_objects, POOL, SKEW)
        self.queries, self.analytic = GEN.queries(self.graph, random.Random(seed + 1), objects)
        reference = Reference(self.graph.rows)
        self.reference = [reference.allowed(*q) for q in self.queries]
        self.tuples = [as_query(q) for q in self.queries]


@pytest.fixture(scope="module", params=SEEDS, ids=str)
def deployment(request):
    return Deployment(request.param)


@pytest.fixture(scope="module")
def one():
    return Deployment(SEEDS[0])


# -- answers, through each route -------------------------------------------------


def test_reference_and_analytic_expectation_agree(deployment):
    assert deployment.reference == deployment.analytic
    share = sum(deployment.analytic) / POOL
    assert 0.5 < share < 0.97, "the pool should hold grants and denials"
    # the uniform holders of the odd positions are not all denials here: a
    # document's descendant set is most of the nested groups
    assert 0.1 < sum(deployment.analytic[1::2]) / (POOL // 2) < 0.9


@pytest.mark.parametrize("route", ["labels_on", "labels_off", "oracle"])
def test_engine_equals_reference_and_expectation(deployment, route):
    if route == "oracle":
        # the host recursion walks a descendant set a query: a part of the pool
        oracle = CheckEngine(deployment.store)
        got = [oracle.subject_is_allowed(t) for t in deployment.tuples[:ORACLE_POOL]]
        assert got == deployment.reference[:ORACLE_POOL] == deployment.analytic[:ORACLE_POOL]
        return
    else:
        engine = engine_on(deployment.store, labels_enabled=route == "labels_on")
        try:
            # a call of 4,096 as the batcher takes it: a third of it rides
            # check_step, and a narrower slice would ride it whole
            got = stream(engine, deployment.tuples, 4096)
            counters = engine.maintenance.snapshot()
            routes = engine.route_slice_counts()
            pulled = routes.get("bfs", 0) + routes.get("hybrid", 0)
            assert pulled > 0, f"no slice reached check_step: the test is vacuous ({routes})"
            assert counters["bfs_steps"] / counters["bfs_slices"] > 2, (
                "check_step converged at once: the nesting is not on the device"
            )
            if route == "labels_on":
                assert routes.get("hybrid", 0) > 0, f"no hybrid slice landed ({routes})"
                assert counters.get("label_fallbacks_pair_cap", 0) > 0, (
                    "no check left the label kernel: the test is vacuous"
                )
                assert counters.get("label_checks", 0) > 0, "the label kernel answered nothing"
            else:
                assert not routes.get("hybrid", 0) and not routes.get("label", 0)
            # what pack_chunk saw: every check once, and more than one row a side
            assert counters["packed_checks"] == POOL
            assert counters["pack_rows_seed"] > POOL and counters["pack_rows_target"] > POOL
        finally:
            engine.close()
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


def test_pack_rows_and_hybrid_launches_as_a_scrape_and_a_profiler_session_see_them(one):
    """The counters behind ``target_rows_mean.bulk`` on ``/metrics``, the
    ELL gauges, and - with a session open on the calling thread's clock -
    the two launches of a hybrid slice as two ``keto.dispatch.launch`` spans
    that differ in ``kernel``."""
    from keto_tpu.x.timeline import DispatchClock, bind_dispatch_clock
    from tests.test_dispatch_tracing import FakeSession

    reg = Registry(Config(overrides={
        "namespaces": [{"id": n.id, "name": n.name} for n in NSS],
    }))
    session = FakeSession()
    session.open = True
    clock = DispatchClock(session)
    try:
        reg.relation_tuple_manager().write_relation_tuples(*map(as_tuple, one.graph.rows))
        engine = reg.permission_engine()
        engine.labels_settled()
        clock.round(1024, 0)
        bind_dispatch_clock(clock)
        assert stream(engine, one.tuples[:1024]) == one.reference[:1024]
        bind_dispatch_clock(None)
        launches = [a for name, a in session.made if name == "keto.dispatch.launch"]
        hybrid = [a["kernel"] for a in launches if a["route"] == "hybrid"]
        assert "label_step" in hybrid and "check_step" in hybrid, launches
        text = reg.metrics().render()
        value = lambda line: float(next(
            ln for ln in text.splitlines() if ln.startswith(line)).rsplit(" ", 1)[1])
        counters = engine.maintenance.snapshot()
        assert value("keto_check_packed_total") == 1024
        assert value('keto_check_pack_rows_total{side="seed"}') == counters["pack_rows_seed"] > 1024
        assert value('keto_check_pack_rows_total{side="target"}') == counters["pack_rows_target"] > 1024
        snap = engine.snapshot()
        slots = sum(b.n * b.nbrs.shape[1] for b in snap.buckets)  # what one pull gathers
        edges = value('keto_snapshot_ell_slots{kind="edge"}')
        assert edges + value('keto_snapshot_ell_slots{kind="pad"}') == slots and 0 < edges < slots
        # every pull of the slices landed, times the words of the riders' program
        words = value("keto_check_pull_words_total")
        assert words == counters["bfs_pull_words"] > 0
        assert words % counters["bfs_steps"] == 0 or counters["bfs_slices"] > 1
        widest = snap.buckets[-1].nbrs.shape[1]
        assert widest // 2 < value("keto_snapshot_max_in_degree") <= widest
    finally:
        bind_dispatch_clock(None)
        reg.close()


# -- a hub row past the pull's degree chunk ---------------------------------------

PARENTS = kernels._DEGREE_CHUNK + 76


def hub_rows():
    """One group nested in ``PARENTS`` groups, each granted on a document of
    its own; under the hub three levels of groups wide enough for the engine
    to keep them on the device."""
    rows = []
    for i in range(PARENTS):
        rows.append(("docs", f"doc-{i}", "view", None, "groups", f"p-{i}", "member"))
        rows.append(("groups", f"p-{i}", "member", None, "groups", "hub", "member"))
        rows.append(("groups", f"p-{i}", "member", f"user-p{i}", None, None, None))
    for j in range(8):
        rows.append(("groups", "hub", "member", None, "groups", f"c-{j}", "member"))
        for k in range(6):
            rows.append(("groups", f"c-{j}", "member", f"user-c{j}-{k}", None, None, None))
            rows.append(("groups", f"c-{j}", "member", None, "groups", f"d-{j}-{k}", "member"))
            rows.extend(("groups", f"d-{j}-{k}", "member", f"user-d{j}-{k}-{m}", None, None, None)
                        for m in range(5))
    return rows


@pytest.mark.parametrize("labels", [True, False], ids=["labels_on", "labels_off"])
def test_a_row_with_more_parents_than_the_degree_chunk_answers_as_the_reference(labels):
    rows = hub_rows()
    queries = (
        [("docs", f"doc-{i}", "view", f"user-d{i % 8}-{i % 6}-{i % 5}") for i in range(0, PARENTS, 7)]
        + [("docs", f"doc-{i}", "view", f"user-c{i % 8}-{i % 6}") for i in range(0, PARENTS, 13)]
        + [("docs", f"doc-{i}", "view", f"user-p{i + 1}") for i in range(0, PARENTS - 1, 11)]
    )
    reference = Reference(rows)
    want = [reference.allowed(*q) for q in queries]
    assert 0 < sum(want) < len(want)
    engine = engine_on(store_of(rows), labels_enabled=labels)
    try:
        snap = engine.snapshot()
        widest = snap.buckets[-1].nbrs
        assert widest.shape == (1, 2 * kernels._DEGREE_CHUNK), (
            "the hub's bucket is not past the degree chunk: the chunk loop would not iterate"
        )
        assert stream(engine, [as_query(q) for q in queries]) == want
        gauges = engine.maintenance.snapshot()
        assert gauges["max_in_degree"] == PARENTS
        slots = sum(b.n * b.nbrs.shape[1] for b in snap.buckets)
        assert gauges["ell_slots_edge"] + gauges["ell_slots_pad"] == slots
        assert gauges["ell_slots_pad"] >= widest.size - PARENTS
        if not labels:
            assert engine.route_slice_counts().get("bfs", 0) > 0, "check_step did not run"
    finally:
        engine.close()


# -- hub sinks: a user in groups by the hundred ------------------------------------


def test_check_step_reads_a_hub_sinks_relay_rows_as_it_reads_its_rows():
    """An answer entry that names relay row k (``n_int + 1 + k``) is worth the
    entries of the rows the relay row holds: the packed output, pulls
    included, is the same either way."""
    import jax.numpy as jnp

    rng = np.random.default_rng(34)
    ni, na, B, S, SA, C = 300, 200, 64, 64, 2048, 8
    nbrs = rng.integers(0, ni, (256, 4)).astype(np.int32)
    nbrs[na:] = ni
    pad = lambda a, n, fill: np.concatenate([np.asarray(a, np.int32), np.full(n - len(a), fill, np.int32)])
    for _ in range(6):
        key = np.unique(rng.integers(0, B, S) * 1000 + rng.integers(0, ni, S))
        seeds = [pad(key % 1000, S, ni + 1), pad(key // 1000, S, 0), pad([], S, ni + 1), pad([], S, 0)]
        rows, rows_q, short, short_q, relays = [], [], [], [], []
        for q in range(B):
            mine = rng.choice(ni, rng.integers(1, 40), replace=False)
            rows += mine.tolist()
            rows_q += [q] * mine.size
            if mine.size <= C:
                short += mine.tolist()
                short_q += [q] * mine.size
                continue
            for c0 in range(0, mine.size, C):
                short.append(ni + 1 + len(relays))
                short_q.append(q)
                relays.append(pad(mine[c0:c0 + C], C, ni))

        def run(a_rows, a_q, hub):
            entries = np.concatenate(seeds + [pad(a_rows, SA, ni), pad(a_q, SA, 0), np.full(B, ni, np.int32)])
            return np.asarray(kernels.check_step(
                (jnp.asarray(nbrs),), jnp.asarray(entries), hub_nbrs=hub, sizes=(S, S, SA, B),
                n_active=na, n_int=ni, valid_rows=(na,), it_cap=64))

        plain, relayed = run(rows, rows_q, None), run(short, short_q, jnp.asarray(np.stack(relays)))
        assert np.array_equal(plain, relayed)
        assert plain[:2].any() and plain[2] > 2, "nothing granted, or no pull: vacuous"


@pytest.mark.parametrize("labels", [True, False], ids=["labels_on", "labels_off"])
def test_a_hub_sink_is_answered_from_relay_rows_and_counts_its_rows(one, labels):
    """A user in more groups than ``HUB_RELAY_ROWS`` sends ``check_step`` a
    relay row where it would have sent 128 rows; the answers and the rows
    ``target_rows_mean.bulk`` counts are those of an engine without relay rows."""
    from keto_tpu.graph.snapshot import HUB_RELAY_ROWS

    relayed = engine_on(one.store, labels_enabled=labels)
    plain = engine_on(one.store, labels_enabled=labels)
    try:
        snap = plain.snapshot()
        snap.hub_ptr = snap.hub_rows = snap.device_hub = None
        snap = relayed.snapshot()
        degrees = np.diff(snap.sink_indptr)
        hubs = degrees > HUB_RELAY_ROWS
        assert hubs.any(), "no sink is gathered from rows by the hundred: vacuous"
        assert np.array_equal(np.diff(snap.hub_ptr), np.where(hubs, -(-degrees // HUB_RELAY_ROWS), 0))
        assert snap.hub_rows.sum() == degrees[hubs].sum()
        table = np.asarray(snap.device_hub)
        first = int(np.flatnonzero(hubs)[0])
        mine = table[snap.hub_ptr[first]:snap.hub_ptr[first + 1]].ravel()
        assert sorted(mine[mine != snap.num_int]) == sorted(
            snap.sink_indices[snap.sink_indptr[first]:snap.sink_indptr[first + 1]])
        assert stream(relayed, one.tuples, 4096) == stream(plain, one.tuples, 4096) == one.reference
        ours, theirs = relayed.maintenance.snapshot(), plain.maintenance.snapshot()
        assert ours["pack_rows_target"] == theirs["pack_rows_target"]
        sd, tg, multi = relayed.dispatch._resolve_bulk(snap, one.tuples)
        sent = lambda engine: int(engine.dispatch._entry_counts(engine.snapshot(), sd, tg, multi).sum())
        assert sent(relayed) < sent(plain) - 1024, "the relay rows saved no entries: vacuous"
    finally:
        relayed.close()
        plain.close()


def test_a_write_that_reaches_a_sink_takes_the_relay_rows_out_of_use(one):
    """The relay rows are the base's: once a tombstone or an overlay edge
    reaches a sink its rows are gathered one by one again, and a membership
    taken from a hub user is gone from the very next answer."""
    from keto_tpu.check.pack import hub_usable

    store = store_of(one.graph.rows)
    engine = engine_on(store)
    try:
        snap = engine.snapshot()
        assert hub_usable(snap)
        groups_of = {}
        for r in one.graph.rows:
            if r[0] == "groups" and r[3] is not None:
                groups_of.setdefault(r[3], []).append(r)
        user, rows = max(groups_of.items(), key=lambda kv: len(kv[1]))
        held = [q for q, ok in zip(one.queries, one.reference) if ok and q[3] == user]
        assert held, "the pool holds no grant of the widest user"
        store.delete_relation_tuples(*map(as_tuple, rows))
        after = engine.snapshot()
        assert after is not snap
        if after.ov_removed is not None and after.ov_removed.size:
            assert not hub_usable(after), "a tombstone under relay rows of the base"
        left = Reference([r for r in one.graph.rows if r not in set(rows)])
        want = [left.allowed(*q) for q in held]
        assert not any(want)
        assert stream(engine, [as_query(q) for q in held]) == want
    finally:
        engine.close()


def test_the_entry_budget_cuts_a_chunk_to_a_quarter_of_its_bound_and_no_further(one):
    """What a hybrid slice costs whatever it carries - its pulls - every
    piece pays again, so a slice controller that has seen slow slices may
    not cut a take into dozens: on a snapshot of hub sinks the bound is the
    pair cap times B entries and the floor a quarter of it."""
    engine = engine_on(one.store)
    try:
        engine.dispatch.stream_ctrl.entry_budget = lambda: 256
        assert stream(engine, one.tuples, 4096) == one.reference
        assert sum(engine.route_slice_counts().values()) <= 4
    finally:
        engine.close()


# -- the generator ----------------------------------------------------------------


def top_share(degrees: np.ndarray) -> float:
    """The share of all edges that the top 1% of the rows, by degree, hold."""
    ordered = np.sort(degrees)[::-1]
    return ordered[: max(1, ordered.size // 100)].sum() / ordered.sum()


def test_generator_is_deterministic_in_the_seed_and_scales():
    a = GEN.build(random.Random(5), TUPLES)
    b = GEN.build(random.Random(5), TUPLES)
    c = GEN.build(random.Random(6), TUPLES)
    assert a.rows == b.rows and a.rows != c.rows
    assert len(set(a.rows)) == len(a.rows), "Keto stores a tuple once"
    objects = traffic.skewed_objects(5, a.n_objects, 512, SKEW)
    assert GEN.queries(a, random.Random(6), objects) == GEN.queries(b, random.Random(6), objects)
    # twice the tuples: twice the edges drawn, one bit more on every axis
    big = GEN.build(random.Random(5), 2 * TUPLES)
    assert big.n_groups == 2 * a.n_groups and big.n_users == 2 * a.n_users
    assert 1.8 < len(big.rows) / len(a.rows) < 2.2
    assert 0.8 * TUPLES < len(a.rows) <= TUPLES
    # every document asked about holds a grant, every user asked about a membership
    granted = {r[1] for r in a.rows if r[0] == "docs"}
    holders = {r[3] for r in a.rows if r[3] is not None}
    queries, _ = GEN.queries(a, random.Random(6), objects)
    assert all(q[1] in granted and q[3] in holders for q in queries)


def test_nesting_has_no_cycle_and_no_self_pair():
    g = GEN.build(random.Random(7), TUPLES)
    parent, child = g.degrees["nesting"]
    assert (parent < child).all(), "the lower label is the parent"


@pytest.mark.parametrize("seed", SEEDS)
def test_the_three_relations_show_the_initiators_skew(seed):
    """Rows of a Kronecker matrix with the initiator (0.57, 0.19, 0.19, 0.05)
    draw a row bit of 1 with probability 0.24 a level, so a hundredth of the
    rows holds a fifth to a half of the edges where uniform rows would hold
    about a fiftieth. Read on seeds 1-24 and these three at 100,000 tuples:
    membership by group 0.299-0.307, by user 0.320-0.326, nesting by child
    0.301-0.357, grants by document 0.152-0.170 (the documents counted are
    those that hold a grant)."""
    g = GEN.build(random.Random(seed), 100_000)
    group, user = g.degrees["membership"]
    parent, child = g.degrees["nesting"]
    granted, doc = g.degrees["grants"]
    assert 0.25 < top_share(np.bincount(group, minlength=g.n_groups)) < 0.40
    assert 0.25 < top_share(np.bincount(user, minlength=g.n_users)) < 0.40
    assert 0.22 < top_share(np.bincount(child, minlength=g.n_groups)) < 0.45
    assert 0.10 < top_share(np.bincount(doc)[np.unique(doc)]) < 0.25
    uniform = np.bincount(np.random.default_rng(seed).integers(0, g.n_groups, group.size),
                          minlength=g.n_groups)
    assert top_share(uniform) < 0.05


# -- compiles ----------------------------------------------------------------------


def test_the_device_label_build_compiles_by_size_class_and_not_by_batch(one):
    """The covered kernel and the store scatter see a batch's own sizes (the
    union of its landmarks' labels, the entries it stored); padded to powers
    of two they are a handful of programs. The parent compiled ~10 a batch,
    and at 24,954 landmarks was not ready after 1,100 s on the chip."""
    from keto_tpu.graph.label_build import device_build_labels
    from keto_tpu.graph.labels import build_labels

    engine = engine_on(one.store, labels_enabled=False)
    try:
        snap = engine.snapshot()
        me = threading.current_thread().name
        before = COMPILES.get(me, 0)
        idx, info = device_build_labels(snap, max_width=64, batch=32)
        compiled = COMPILES.get(me, 0) - before
        assert info.batches >= 16, "too few batches to tell a batch from a size class"
        assert compiled <= 2 + 4 * (info.batches.bit_length() + 8), (
            f"{compiled} programs for {info.batches} batches"
        )
        host = build_labels(snap, max_width=64)
        for name in ("out_lab", "in_lab", "out_ok", "in_ok", "processed"):
            assert np.array_equal(getattr(idx, name), getattr(host, name)), name
    finally:
        engine.close()


def test_a_slice_that_rides_a_program_of_its_own_width_asks_for_none_of_its_own():
    """Padded entries cost a few dropped scatters; only a wider bitmap is
    worth a compile behind the slice (keto_tpu/check/geometry.py)."""
    asked = []
    g = KernelGeometries(lambda *job: asked.append(job) or True)
    shape, fixed = ("shape",), ("fixed",)
    try:
        for sizes in ((32,) * 4, (256,) * 4, (65536, 65536, 65536, 32)):
            g.add("check", shape, fixed, sizes)
        g.mark_warmed("check", shape)
        riders = (2048, 2048, 2048, 32)
        assert g.meet("check", shape, fixed, riders) == ((65536, 65536, 65536, 32), PADDED_UP)
        assert g.pending() == 0 and not asked
        # no rung of its width holds it: it rides a wider one, and its own is asked for
        assert g.meet("check", shape, fixed, (128, 128, 128, 64))[1] == PADDED_UP
        deadline = time.monotonic() + 10
        while g.pending() and time.monotonic() < deadline:
            time.sleep(0.01)
        assert [job[3] for job in asked] == [(128, 128, 128, 64)]
    finally:
        g.close()


def test_warm_compile_adds_the_hub_rungs_only_where_a_sink_is_gathered_from_rows_by_the_hundred(one):
    hub = engine_on(one.store)
    flat = engine_on(store_of([r for r in hub_rows() if r[1] != "hub" and r[5] != "hub"]))
    try:
        widths = hub.dispatch.stream_widths(hub.snapshot())
        rungs = hub.dispatch._hub_rungs(widths)
        assert hub.dispatch._riders_expected(hub.snapshot())
        assert hub.warm_compile() == 2 * len(widths) + len(rungs)
        assert not flat.dispatch._riders_expected(flat.snapshot())
        assert flat.warm_compile() == 2 * len(flat.dispatch.stream_widths(flat.snapshot()))
    finally:
        hub.close()
        flat.close()


def test_after_warm_compile_a_pass_over_the_pool_compiles_nothing_on_the_calling_thread(one):
    engine = engine_on(one.store)
    try:
        engine.warm_compile()
        me = threading.current_thread().name
        before = COMPILES.get(me, 0)
        for width in (4096, 1024):
            assert stream(engine, one.tuples, width) == one.reference
        assert COMPILES.get(me, 0) == before, "a slice compiled on the thread that launched it"
        counts = engine.kernel_geometry_counts()
        assert not counts.get(("check", INLINE)) and not counts.get(("label", INLINE))
        routes = engine.route_slice_counts()
        assert routes.get("hybrid", 0) > 0, "no hybrid slice: vacuous"
        # a call of 4,096 is one hybrid slice (two launches), not one per 4 x B entries
        assert sum(routes.values()) <= 2 + POOL // 1024, routes
        assert not counts.get(("check", PADDED_UP)) or engine.dispatch.geoms.pending() == 0
        wait_for_worker(engine)
        assert stream(engine, one.tuples, 4096) == one.reference
        assert COMPILES.get(me, 0) == before
    finally:
        engine.close()
