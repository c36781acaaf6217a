"""Slice-tail machinery: donated/pooled staging buffers, the staging
eviction rung, and the service-time-aware slice scheduler.

The safety contract under fuzz (the one that makes buffer reuse legal):
a staging buffer is only re-leased after the slice that shipped it has
LANDED — across the ordered and ``ordered=False`` offset fast paths,
across a mid-stream width switch, and across an HBM eviction of the
staging rung mid-stream, every decision must still match the CPU
reference oracle. The donated kernel variants are forced on (the test
patches ``kernels._donation_default``) so the donation call path executes
even on backends where XLA ignores the donation.
"""

import random
import warnings

import numpy as np
import pytest

from keto_tpu.check.engine import CheckEngine
from keto_tpu.check import kernels
from keto_tpu.check.pack import _StagingPool
from keto_tpu.check.slice_ctrl import StreamSliceController
from keto_tpu.check.tpu_engine import TpuCheckEngine
from keto_tpu.relationtuple.model import RelationTuple, SubjectID, SubjectSet


def T(ns, obj, rel, sub):
    return RelationTuple(namespace=ns, object=obj, relation=rel, subject=sub)


def _mixed_depth_store(make_persister, seed=3, n_groups=24, n_users=60, depth=8):
    """Direct grants next to chains of increasing depth — the workload
    shape whose route mix (label/hybrid/bfs/host) exercises the slice
    scheduler."""
    rng = random.Random(seed)
    p = make_persister([("docs", 1), ("groups", 2)])
    rows = []
    for g in range(n_groups):
        for _ in range(4):
            rows.append(
                T("groups", f"g{g}", "member", SubjectID(f"user-{rng.randrange(n_users)}"))
            )
    for d in range(40):
        rows.append(
            T("docs", f"doc-{d}", "view",
              SubjectSet("groups", f"g{rng.randrange(n_groups)}", "member"))
        )
    # chains c<k>-0 -> c<k>-1 -> ... of depth k for k in 2..depth
    for k in range(2, depth + 1):
        for i in range(k):
            rows.append(
                T("groups", f"c{k}-{i}", "member",
                  SubjectSet("groups", f"c{k}-{i+1}", "member"))
            )
        rows.append(T("groups", f"c{k}-{k}", "member", SubjectID(f"deep-{k}")))
        rows.append(
            T("docs", f"chain-doc-{k}", "view",
              SubjectSet("groups", f"c{k}-0", "member"))
        )
    p.write_relation_tuples(*rows)
    queries = []
    for _ in range(400):
        r = rng.random()
        if r < 0.75:
            queries.append(
                T("docs", f"doc-{rng.randrange(40)}", "view",
                  SubjectID(f"user-{rng.randrange(n_users)}"))
            )
        elif r < 0.9:
            k = rng.randrange(2, depth + 1)
            queries.append(
                T("docs", f"chain-doc-{k}", "view",
                  SubjectID(f"deep-{k}" if rng.random() < 0.5 else "nobody"))
            )
        else:
            queries.append(T("", "", "", SubjectID(f"user-{rng.randrange(n_users)}")))
    return p, queries


def _hooked(queries, hooks):
    """Yield queries, firing hooks[i] just before query i."""
    for i, q in enumerate(queries):
        if i in hooks:
            hooks[i]()
        yield q


@pytest.mark.parametrize("ordered", [True, False])
@pytest.mark.parametrize("seed", [0, 1, 2])
def test_staging_reuse_never_corrupts_decisions(
    make_persister, monkeypatch, ordered, seed
):
    """The donation-aliasing fuzz: donated kernels + pooled staging,
    a forced mid-stream width switch, and a mid-stream eviction (then
    restore) of the staging rung — every decision matches the oracle
    and no lease leaks."""
    monkeypatch.setattr(kernels, "_donation_default", lambda: True)
    # the CPU backend ignores the donation and says so once a geometry
    warnings.filterwarnings("ignore", message="Some donated buffers were not usable")
    p, queries = _mixed_depth_store(make_persister, seed=seed)
    engine = TpuCheckEngine(p, p.namespaces, max_batch=64)
    oracle = CheckEngine(p)
    try:
        assert engine.dispatch._donate_entries
        expected = [oracle.subject_is_allowed(q) for q in queries]
        n = len(queries)
        hooks = {
            # mid-stream width switch: one fake monster-slow observation
            # narrows the controller's next planned width immediately
            n // 4: lambda: engine.dispatch.stream_ctrl.observe(
                engine.dispatch.stream_ctrl.cap(), 100_000.0
            ),
            # mid-stream staging eviction: rung 0 drops the pool; later
            # slices fall back to per-slice buffers
            n // 2: lambda: engine.hbm.evict_one(reason="test"),
            # and recovery: the pool refills from the NEXT slice on
            3 * n // 4: lambda: engine.hbm.maybe_restore(),
        }
        if ordered:
            outs = list(
                engine.batch_check_stream(_hooked(queries, hooks), ordered=True)
            )
            got = np.concatenate(outs).tolist()
        else:
            got = [None] * n
            gen, _tok = engine.batch_check_stream_with_token(
                _hooked(queries, hooks), ordered=False
            )
            for off, out in gen:
                got[off : off + len(out)] = out.tolist()
        assert got == expected
        st = engine.dispatch.staging_snapshot()
        assert st["leased"] == 0, "a staging lease outlived its slice"
        # the ledger's staging tag reconciles with the pool's accounting
        assert engine.hbm.ledger().get("staging", 0) == engine.dispatch._staging.bytes()
    finally:
        engine.close()


def test_abandoned_stream_releases_leases(make_persister):
    """Closing a stream mid-flight (the batcher's error path does this)
    sweeps the un-landed slices' staging leases back to the pool — no
    leak, no double release."""
    p, queries = _mixed_depth_store(make_persister, seed=4)
    engine = TpuCheckEngine(p, p.namespaces, max_batch=32)
    try:
        gen, _tok = engine.batch_check_stream_with_token(
            iter(queries), ordered=False
        )
        next(gen)  # at least one slice landed, several more in flight
        gen.close()
        assert engine.dispatch.staging_snapshot()["leased"] == 0
        assert engine.hbm.ledger().get("staging", 0) == engine.dispatch._staging.bytes()
        # and the engine still serves correctly afterwards
        oracle = CheckEngine(p)
        assert engine.batch_check(queries[:32]) == [
            oracle.subject_is_allowed(q) for q in queries[:32]
        ]
    finally:
        engine.close()


def test_staging_pool_accounting_and_reuse():
    ledger = {}
    pool = _StagingPool(on_change=lambda b: ledger.__setitem__("staging", b))
    a = pool.acquire(128)
    assert a is not None and a.shape == (128,) and a.dtype == np.int32
    assert ledger["staging"] == 512
    pool.release(a)
    b = pool.acquire(128)
    assert b is a, "freed buffer must be re-leased, not re-allocated"
    # a planned refusal returns None instead of growing the pool
    assert pool.acquire(256, plan=lambda nbytes: False) is None
    assert ledger["staging"] == 512
    assert pool.acquire(256, plan=lambda nbytes: True) is not None
    assert ledger["staging"] == 512 + 1024
    freed = pool.drop()
    assert freed == 512 + 1024  # all accounted bytes (free + leased) go
    assert ledger["staging"] == 0


def test_staging_rung_evicts_and_restores(make_persister):
    """The governor's first rung drops the staging pool (ledger tag to
    zero, engine falls back to per-slice buffers) and answers hold;
    restore re-enables pooling."""
    p, queries = _mixed_depth_store(make_persister, seed=5)
    engine = TpuCheckEngine(p, p.namespaces)
    oracle = CheckEngine(p)
    try:
        # the label kernel's pairs are what is staged here: this store has
        # no row a pull can change, so the BFS route alone launches nothing
        engine.labels_settled()
        expected = [oracle.subject_is_allowed(q) for q in queries[:64]]
        assert engine.batch_check(queries[:64]) == expected
        assert engine.hbm.ledger().get("staging", 0) > 0
        assert engine.hbm.evict_one(reason="test") == "staging"
        assert engine.dispatch._staging_suspended
        assert engine.hbm.ledger().get("staging", 0) == 0
        assert engine.batch_check(queries[:64]) == expected
        # suspended: the pool must not refill
        assert engine.hbm.ledger().get("staging", 0) == 0
        engine.hbm.maybe_restore()
        assert not engine.dispatch._staging_suspended
        assert engine.batch_check(queries[:64]) == expected
        assert engine.hbm.ledger().get("staging", 0) > 0
    finally:
        engine.close()


def test_staging_suspended_engine_uses_no_pool(make_persister):
    """An engine whose staging rung was shed before its first slice ships
    every slice from a buffer of its own: no pool, nothing on the ledger."""
    p, queries = _mixed_depth_store(make_persister, seed=6)
    engine = TpuCheckEngine(p, p.namespaces)
    try:
        engine.labels_settled()  # a refresh that lands walks the ladder back up
        assert engine.hbm.evict_one(reason="test") == "staging"
        engine.batch_check(queries[:64])
        assert engine.hbm.ledger().get("staging", 0) == 0
        assert engine.dispatch.staging_snapshot()["bytes"] == 0
    finally:
        engine.close()


# -- the service-time model ----------------------------------------------------


def test_model_narrows_after_one_slow_route_observation():
    ctrl = StreamSliceController(target_ms=40.0, floor=32)
    wide = ctrl.cap()
    # a label slice is fast at full width: no narrowing
    ctrl.observe(wide, 2.0, route="label", entries=wide)
    assert ctrl.cap() >= wide
    # ONE slow bfs slice: the model's pessimistic per-query cost binds
    # the next planned width immediately
    ctrl.observe(wide, 400.0, route="bfs", bfs_steps=64, entries=4 * wide)
    narrowed = ctrl.cap()
    assert narrowed < wide
    assert narrowed * (400.0 / wide) <= ctrl.target_ms * 1.01 or narrowed == 32


def test_entry_budget_tracks_slow_route():
    ctrl = StreamSliceController(target_ms=40.0, floor=32)
    assert ctrl.entry_budget() is None  # no data yet
    ctrl.observe(1024, 10.0, route="bfs", entries=4096)  # ~0.0024 ms/entry
    budget = ctrl.entry_budget()
    assert budget is not None
    assert 256 <= budget <= int(40.0 / (10.0 / 4096)) + 1
    # a much slower per-entry slice shrinks the budget hard
    ctrl.observe(1024, 400.0, route="bfs", entries=4096)
    assert ctrl.entry_budget() < budget


def test_tail_guard_engages_on_blown_ratio():
    ctrl = StreamSliceController(target_ms=10.0, floor=32, tail_ratio=5.0)
    # 31 fast + 1 huge straggler per 32-slice window -> ratio >> 5
    for _ in range(3):
        for _ in range(31):
            ctrl.observe(64, 1.0, route="label", entries=64)
        ctrl.observe(64, 500.0, route="bfs", entries=4096)
    snap = ctrl.snapshot()
    assert snap["tail_guard"] < 1.0
    assert snap["tail_p99_ms"] > 5.0 * snap["tail_p50_ms"]
    # recovery: flat windows decay the guard back toward 1.0
    for _ in range(8 * 32):
        ctrl.observe(64, 1.0, route="label", entries=64)
    assert ctrl.snapshot()["tail_guard"] > snap["tail_guard"]


def test_sub_chunks_of_a_full_take_speak_for_the_caps_width():
    """One slow slice takes the reactive rung to the floor. A graph whose
    takes at that rung are always cut by the entry budget hands the controller
    only slices narrower than the cap: they widen it again when they come
    from a take that filled it, and only then."""
    ctrl = StreamSliceController(target_ms=40.0)
    ctrl.observe(4096, 2000.0, route="label", entries=16384)  # a compile, a pause
    assert ctrl.cap() == 2048
    for _ in range(8):
        ctrl.observe(1024, 1.5, route="label", entries=4096)  # a narrow take: says nothing
    assert ctrl.cap() == 2048
    for _ in range(2):
        ctrl.observe(1024, 1.5, route="label", entries=4096, full_take=True)
    assert ctrl.cap() == 8192
    # a slow sub-chunk of a full take narrows like any slow slice
    ctrl.observe(1024, 2000.0, route="label", entries=4096, full_take=True)
    assert ctrl.cap() == 2048


def _narrowed(wide_ms: float, narrow_ms: float, steps: int = 10) -> StreamSliceController:
    """A controller that saw one slice of the 8,192 rung and then, narrowed by
    the model or not, one of the 2,048 rung."""
    ctrl = StreamSliceController(target_ms=40.0)
    ctrl.observe(4096, wide_ms, route="hybrid", bfs_steps=steps, entries=70_000)
    ctrl.observe(2048, narrow_ms, route="bfs", bfs_steps=steps, entries=35_000, full_take=True)
    return ctrl


@pytest.mark.parametrize(
    "wide_ms, narrow_ms, steps, cap",
    [
        (30.0, 30.0, 10, 8192),  # the narrower rung bought nothing: the wider is served
        (30.0, 16.0, 10, 8192),  # over half the time for half a take: still nothing
        (30.0, 12.0, 10, 2048),  # narrowing bought time: the model's rung is kept
        (48.0, 40.0, 10, 2048),  # the wider rung is over the target: never served
        (30.0, 30.0, 0, 2048),  # slices that do not pull leave no reading
    ],
)
def test_a_rung_whose_pulling_slices_are_no_faster_than_the_wider_rungs_is_not_served(
    wide_ms, narrow_ms, steps, cap
):
    """A ``check_step`` of ten pulls costs its pulls whatever it carries: the
    model, which reads time as proportional to width, narrows on the bfs
    route's cost per query, sees the narrow slices take as long as the wide
    ones, and goes back. Where narrowing does buy time, or the wider rung is
    over the target, the model's rung is kept."""
    assert _narrowed(wide_ms, narrow_ms, steps).cap() == cap


def test_a_rungs_reading_lapses_with_the_routes():
    ctrl = _narrowed(30.0, 30.0)
    assert ctrl.cap() == 8192
    for _ in range(StreamSliceController.ROUTE_RECENCY + 1):
        ctrl.observe(2048, 30.0, route="bfs", bfs_steps=10, entries=35_000, full_take=True)
    assert ctrl.cap() == 2048  # nothing recent says what the wider rung takes


def test_the_stream_tells_the_controller_which_slices_came_from_a_full_take(make_persister, monkeypatch):
    """``_stream`` cuts up to the controller's cap off its source; whatever the
    entry budget then makes of a take, each launched slice carries whether the
    take filled the cap."""
    p, queries = _mixed_depth_store(make_persister, seed=9)
    engine = TpuCheckEngine(p, p.namespaces, labels_enabled=False)
    oracle = CheckEngine(p)
    try:
        d = engine.dispatch
        batch = (queries * 3)[:160]
        monkeypatch.setattr(d.stream_ctrl, "cap", lambda: 64)
        monkeypatch.setattr(d.stream_ctrl, "entry_budget", lambda: 64)
        seen = []
        real = d.stream_ctrl.observe
        monkeypatch.setattr(
            d.stream_ctrl, "observe",
            lambda nq, ms, **kw: seen.append((nq, kw["full_take"])) or real(nq, ms, **kw),
        )
        out = engine.batch_check(batch)
        assert out == [oracle.subject_is_allowed(q) for q in batch]
        # 160 queries at a cap of 64: two full takes and a rest of 32
        full = sum(nq for nq, was_full in seen if was_full)
        rest = sum(nq for nq, was_full in seen if not was_full)
        assert (full, rest) == (128, 32)
        assert sum(1 for _nq, was_full in seen if was_full) > 2  # and the budget cut them
    finally:
        engine.close()


def test_predicted_slow_chunks_split_before_dispatch(make_persister, monkeypatch):
    """A tiny entry budget splits a resolved chunk into many sub-slices
    (the pre-dispatch half of the tail control), decisions unchanged."""
    p, queries = _mixed_depth_store(make_persister, seed=7)
    engine = TpuCheckEngine(p, p.namespaces, labels_enabled=False)
    oracle = CheckEngine(p)
    try:
        snap = engine.snapshot()
        batch = queries[:128]
        n_default = sum(1 for _ in engine.dispatch._dispatch_slices(snap, batch))
        monkeypatch.setattr(
            engine.dispatch.stream_ctrl, "entry_budget", lambda: 64
        )
        recs = list(engine.dispatch._dispatch_slices(snap, batch))
        assert len(recs) > n_default, "entry budget did not split the chunk"
        for rec in recs:
            engine.dispatch._stage_release(rec[4])
        # every sub-slice stayed within ~the budget floor geometry and
        # the decisions of the drained stream still match the oracle
        before = sum(engine.route_slice_counts().values())
        out, _iters = engine.dispatch._run_exact(snap, batch)
        assert sum(engine.route_slice_counts().values()) - before == len(recs)
        assert out.tolist() == [oracle.subject_is_allowed(q) for q in batch]
    finally:
        engine.close()


def test_batcher_consults_planned_slice_width(make_persister):
    """The batch lane's sub-slice sizing is bounded by the controller's
    predicted slice width, so a monster chunk drains in rounds the
    engine would not re-split anyway."""
    from keto_tpu.driver.batch import BATCH, CheckBatcher, _Item
    from concurrent.futures import Future

    p, queries = _mixed_depth_store(make_persister, seed=8)
    engine = TpuCheckEngine(p, p.namespaces)
    try:
        b = CheckBatcher(engine, batch_size=8192, batch_sub_slice=4096)
        # narrow the planned width to the controller floor (2048): one
        # huge observation — now narrower than the configured sub-slice
        engine.dispatch.stream_ctrl.observe(engine.dispatch.stream_ctrl.cap(), 1_000_000.0)
        cap = engine.dispatch.stream_ctrl.cap()
        assert cap < 4096
        big = (queries * 20)[: cap + 1000]
        item = _Item(big, Future(), None, False, None, BATCH)
        with b._cond:
            b._lanes[BATCH].append(item)
            b._lane_tuples[BATCH] += item.n
            segments = b._take_locked()
        took = sum(count for _, _, count in segments)
        assert took == cap, "sub-slice not bounded by the planned width"
    finally:
        engine.close()
