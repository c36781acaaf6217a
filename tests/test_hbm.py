"""HBM budget governor: the eviction ladder, OOM containment, and the
shadow-parity auditor (keto_tpu/driver/hbm.py + the engine seams).

The contract under test, end to end:

- a budget forced below the device footprint walks the DETERMINISTIC
  eviction ladder (drop the entry-staging pool -> drop labels -> drop
  reverse layouts -> trim the warm width ladder -> shrink the overlay
  budget -> refuse the refresh and serve stale +
  DEGRADED(memory_pressure)) with decision parity vs the CPU oracle
  after EVERY rung — coverage and throughput degrade, answers never;
- pressure clearing walks back UP the ladder (labels rebuilt, widths
  restored, overlay budget back to configured);
- an injected RESOURCE_EXHAUSTED (the ``device-alloc`` ``oom`` fault) at
  every registered allocation site evicts one rung, retries once, and
  otherwise escalates through the bit-identical CPU fallback — the
  process NEVER exits;
- the ledger reconciles: per-tag bytes sum to the governor's total;
- the sampled auditor re-verifies live decisions against the CPU oracle
  and flips DEGRADED on any divergence.
"""

import random
import time

import pytest

from keto_tpu.check.engine import CheckEngine
from keto_tpu.check.tpu_engine import TpuCheckEngine
from keto_tpu.driver.health import HealthMonitor, HealthState
from keto_tpu.driver.hbm import (
    FALLBACK_BUDGET_BYTES,
    HbmGovernor,
    MemoryPressure,
    device_budget_bytes,
    is_resource_exhausted,
)
from keto_tpu.relationtuple.model import RelationTuple, SubjectID, SubjectSet
from keto_tpu.x import faults


def T(ns, obj, rel, sub):
    return RelationTuple(namespace=ns, object=obj, relation=rel, subject=sub)


@pytest.fixture(autouse=True)
def _clean_faults():
    faults.clear()
    faults.reset_hits()
    yield
    faults.clear()
    faults.reset_hits()


def wait_for(cond, timeout=10.0, interval=0.02, msg="condition"):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if cond():
            return
        time.sleep(interval)
    raise AssertionError(f"timed out waiting for {msg}")


def _store_and_queries(make_persister, seed=3, n_tuples=120, n_queries=96):
    rng = random.Random(seed)
    namespaces = [("ns0", 0), ("ns1", 1)]
    p = make_persister(namespaces)
    ns_names = [n for n, _ in namespaces]
    objects = [f"o{i}" for i in range(8)]
    relations = ["r0", "r1"]
    users = [f"u{i}" for i in range(6)]

    def rand_set():
        return SubjectSet(rng.choice(ns_names), rng.choice(objects), rng.choice(relations))

    tuples = []
    for _ in range(n_tuples):
        sub = SubjectID(rng.choice(users)) if rng.random() < 0.5 else rand_set()
        tuples.append(T(rng.choice(ns_names), rng.choice(objects), rng.choice(relations), sub))
    p.write_relation_tuples(*tuples)
    queries = []
    for _ in range(n_queries):
        sub = SubjectID(rng.choice(users + ["ghost"])) if rng.random() < 0.5 else rand_set()
        queries.append(T(rng.choice(ns_names), rng.choice(objects), rng.choice(relations), sub))
    return p, queries


def _oracle_expect(p, queries):
    oracle = CheckEngine(p)
    return [oracle.subject_is_allowed(q) for q in queries]


# -- governor unit surface ----------------------------------------------------


def test_ledger_register_add_release_reconciles():
    g = HbmGovernor(budget_bytes=1000)
    g.register("snapshot", 400)
    g.add("warmup", 100)
    g.add("warmup", 50)
    g.register("labels", 200)
    led = g.ledger()
    assert led == {"snapshot": 400, "warmup": 150, "labels": 200}
    assert g.resident_bytes() == sum(led.values()) == 750
    assert g.release("warmup") == 150
    assert g.resident_bytes() == 600
    # register replaces, never accumulates (a snapshot swap)
    g.register("snapshot", 100)
    assert g.resident_bytes() == 300


def test_plan_walks_rungs_in_order_then_refuses():
    g = HbmGovernor(budget_bytes=100)
    walked = []
    g.attach_rungs([
        ("labels", lambda: walked.append("labels") or g.release("labels"), lambda: None),
        ("warm-ladder", lambda: walked.append("warm") or g.release("warmup"), lambda: None),
        ("overlay-budget", lambda: walked.append("overlay") or 0, lambda: None),
    ])
    g.register("snapshot", 40)
    g.register("labels", 40)
    g.register("warmup", 15)
    # fits without eviction
    assert g.plan(5) and walked == []
    # needs the labels rung only
    assert g.plan(30) and walked == ["labels"]
    assert g.rung_depth == 1
    # needs everything, still over -> False (and evict=False never walks)
    assert not g.plan(1000, evict=False)
    assert g.rung_depth == 1
    assert not g.plan(1000)
    assert walked == ["labels", "warm", "overlay"]
    assert g.rung_depth == 3


def test_restore_walks_back_up_with_hysteresis():
    g = HbmGovernor(budget_bytes=100)
    restored = []
    g.attach_rungs([
        ("labels", lambda: 0, lambda: restored.append("labels")),
        ("warm-ladder", lambda: 0, lambda: restored.append("warm")),
        ("overlay-budget", lambda: 0, lambda: restored.append("overlay")),
    ])
    g.register("snapshot", 120)
    assert not g.plan(0)
    assert g.rung_depth == 3
    # still over the restore threshold: nothing comes back
    assert g.maybe_restore() == 0
    g.register("snapshot", 80)
    # resident 80 > 0.7 * 100: hysteresis holds the ladder down
    assert g.maybe_restore() == 0
    g.register("snapshot", 30)
    assert g.maybe_restore() == 3
    assert restored == ["overlay", "warm", "labels"]  # reverse order
    assert g.rung_depth == 0
    # planned margin blocks a restore that would immediately re-evict
    assert not g.plan(1000)
    g.register("snapshot", 10)
    assert g.maybe_restore(planned=200) == 0


def test_deterministic_mode_pins_fallback_budget_and_blocks_reactive_eviction():
    assert device_budget_bytes(deterministic=True) == FALLBACK_BUDGET_BYTES
    g = HbmGovernor(deterministic=True)
    g.attach_rungs([("labels", lambda: g.release("labels"), lambda: None)])
    assert g.evict_one("oom") is None  # lockstep meshes never evict on OOM
    # planned eviction (replicated state) still works
    g.register("labels", 2)
    g.register("snapshot", FALLBACK_BUDGET_BYTES - 2)
    assert g.plan(1)
    assert g.rung_depth == 1


def test_is_resource_exhausted_classifier():
    assert is_resource_exhausted(faults.OomInjected("device-alloc"))
    assert is_resource_exhausted(RuntimeError("RESOURCE_EXHAUSTED: out of HBM"))
    assert is_resource_exhausted(RuntimeError("Resource exhausted: oom"))
    assert not is_resource_exhausted(ValueError("boom"))
    assert not is_resource_exhausted(MemoryError())  # host OOM is not ours


def test_oom_fault_spec_parses_from_env():
    faults.load_env("device-alloc:oom:1")
    with pytest.raises(faults.OomInjected) as ei:
        faults.check("device-alloc")
    assert "RESOURCE_EXHAUSTED" in str(ei.value)
    faults.check("device-alloc")  # count exhausted: no fire


# -- the ladder, end to end ----------------------------------------------------


def test_tiny_budget_walks_every_rung_with_decision_parity(make_persister):
    p, queries = _store_and_queries(make_persister)
    expected = _oracle_expect(p, queries)

    engine = TpuCheckEngine(p, p.namespaces, hbm_budget_bytes=1)
    try:
        # cold boot under an impossible budget: every rung walks, the
        # base snapshot force-allocates (nothing to serve stale from),
        # and every decision still matches the oracle
        assert engine.batch_check(queries) == expected
        snap = engine.hbm.snapshot()
        assert snap["evicted"] == [
            "staging", "labels", "reverse", "warm-ladder", "overlay-budget",
        ]
        assert snap["forced_allocs"] >= 1
        assert engine.dispatch._staging_suspended
        assert engine._labels_suspended
        assert engine._snapshot.labels is None
        # rung 2 trimmed the compile-width ladder
        assert len(engine.dispatch._word_widths()) < 7
        # rung 3 shrank the overlay budget below the configured value
        assert engine._max_overlay_edges < engine._configured_overlay_budget
        # ladder decisions changed no answers (again, post-eviction)
        assert engine.batch_check(queries) == expected
    finally:
        engine.close()


def test_rungs_walk_stepwise_and_recover_when_pressure_clears(make_persister):
    p, queries = _store_and_queries(make_persister, seed=11)
    expected = _oracle_expect(p, queries)
    engine = TpuCheckEngine(p, p.namespaces)
    try:
        assert engine.batch_check(queries) == expected
        led = engine.hbm.ledger()
        assert led.get("labels", 0) > 0, "labels should be resident at a sane budget"
        resident = engine.hbm.resident_bytes()

        # budget just below residency minus what the staging rung could
        # free: planning the next (identical) snapshot swap must shed
        # staging AND labels — and answers hold
        engine.hbm.set_budget_bytes(resident - led.get("staging", 0) - 1)
        assert engine.hbm.plan(led["snapshot"], what="test swap")
        assert engine.hbm.rung_depth >= 2
        assert engine.dispatch._staging_suspended
        assert engine._labels_suspended
        assert engine.batch_check(queries) == expected

        # pressure clears: a refresh pass restores the ladder and
        # rebuilds + re-uploads the labels
        engine.hbm.set_budget_bytes(64 << 20)
        engine._kick_background_refresh()
        wait_for(
            lambda: engine.hbm.rung_depth == 0
            and engine._snapshot.labels is not None
            and engine._snapshot.device_labels is not None,
            msg="ladder restore + label rebuild",
        )
        assert not engine._labels_suspended
        assert engine.hbm.ledger().get("labels", 0) > 0
        assert engine.hbm.snapshot()["restores"] >= 1
        assert engine.batch_check(queries) == expected
    finally:
        engine.close()


def test_refusal_serves_stale_with_memory_pressure_degraded(make_persister):
    # a chain store: every set node is interior, so the delta below adds
    # an interior->interior (overlay-ELL) edge whose upload the governor
    # must actually plan — a host-only delta (new sink edge) consumes no
    # device memory and would sail through any budget
    p = make_persister([("ns0", 0)])
    chain = [
        T("ns0", f"o{i}", "r0", SubjectSet("ns0", f"o{(i + 1) % 10}", "r0"))
        for i in range(10)
    ]
    p.write_relation_tuples(*chain, T("ns0", "o0", "r0", SubjectID("u0")))
    queries = [T("ns0", f"o{i}", "r0", SubjectID("u0")) for i in range(10)]

    engine = TpuCheckEngine(p, p.namespaces)
    monitor = HealthMonitor(engine, staleness_budget_s=3600.0)
    try:
        baseline = engine.batch_check(queries)
        token = engine._snapshot.snapshot_id
        assert monitor.status()[0] is HealthState.SERVING

        # pin the budget below residency, then add an interior edge: the
        # overlay-ELL upload cannot fit, every rung is spent, and the
        # refresh is REFUSED — stale serving, not a crash
        engine.hbm.set_budget_bytes(1)
        p.write_relation_tuples(
            T("ns0", "o3", "r0", SubjectSet("ns0", "o7", "r0"))
        )
        got, got_token = engine.batch_check_with_token(queries, mode="serving")
        assert got == baseline
        assert got_token == token, "refused refresh must serve the STALE snapshot"
        wait_for(lambda: engine.health()["memory_pressure"], msg="memory_pressure flag")
        state, reason = monitor.status()
        assert state is HealthState.DEGRADED
        assert "memory_pressure" in reason
        assert engine.hbm.snapshot()["refusals"] >= 1

        # budget returns: the supervised refresh catches up, pressure
        # clears, and the new write becomes visible
        engine.hbm.set_budget_bytes(64 << 20)
        engine._kick_background_refresh()
        wait_for(
            lambda: not engine.health()["memory_pressure"]
            and engine._snapshot.snapshot_id == p.watermark(),
            msg="refresh recovery after pressure cleared",
        )
        assert monitor.status()[0] in (HealthState.SERVING, HealthState.DEGRADED)
        oracle = CheckEngine(p)
        fresh = engine.batch_check(queries)
        assert fresh == [oracle.subject_is_allowed(q) for q in queries]
    finally:
        engine.close()


# -- OOM containment at every registered site ---------------------------------


def _arm_oom(count=1):
    faults.inject("device-alloc", exc=faults.OomInjected, count=count)


def test_oom_on_check_path_evicts_retries_and_stays_correct(make_persister):
    p, queries = _store_and_queries(make_persister, seed=7)
    expected = _oracle_expect(p, queries)
    engine = TpuCheckEngine(p, p.namespaces)
    try:
        assert engine.batch_check(queries) == expected
        # one OOM: the seam evicts a rung and retries once — the caller
        # sees correct answers either way
        _arm_oom(count=1)
        assert engine.batch_check(queries) == expected
        snap = engine.hbm.snapshot()
        assert snap["oom_events"] >= 1
        assert snap["oom_recoveries"] >= 1
        # persistent OOM at every allocation: after the ladder is spent
        # the device path escalates to the bit-identical CPU fallback
        faults.clear("device-alloc")
        faults.inject("device-alloc", exc=faults.OomInjected)
        assert engine.batch_check(queries) == expected
        assert engine.maintenance.snapshot().get("fallback_checks", 0) >= len(queries)
        faults.clear("device-alloc")
        assert engine.batch_check(queries) == expected
    finally:
        engine.close()


def test_oom_at_refresh_upload_sites_recovers_without_exit(make_persister):
    p, queries = _store_and_queries(make_persister, seed=9)
    expected = _oracle_expect(p, queries)

    # site: snapshot-upload during the cold build
    _arm_oom(count=1)
    engine = TpuCheckEngine(p, p.namespaces)
    try:
        assert engine.batch_check(queries) == expected
        assert engine.hbm.snapshot()["oom_events"] >= 1

        # site: overlay-upload during a delta refresh
        _arm_oom(count=1)
        p.write_relation_tuples(T("ns0", "o1", "r0", SubjectID("oom-user")))
        oracle = CheckEngine(p)
        got = engine.batch_check(queries)
        assert got == [oracle.subject_is_allowed(q) for q in queries]

        # site: warm-compile (plus the label kernel when labels live)
        _arm_oom(count=1)
        engine.warm_compile()

        # site: compaction re-upload — force a fold of the overlay
        _arm_oom(count=1)
        engine._kick_background_refresh(force_full=True)
        wait_for(
            lambda: not engine._snapshot.has_overlay,
            msg="compaction under oom injection",
        )
        assert engine.batch_check(queries) == [
            oracle.subject_is_allowed(q) for q in queries
        ]
    finally:
        faults.clear()
        engine.close()


def test_multiprocess_mode_never_evicts_on_oom(make_persister):
    p, _ = _store_and_queries(make_persister, seed=1)
    engine = TpuCheckEngine(p, p.namespaces)
    try:
        engine.hbm.deterministic = True  # what a lockstep mesh constructs
        assert engine.hbm.evict_one("oom") is None
        assert engine.hbm.rung_depth == 0
    finally:
        engine.close()


# -- warm-ladder budget skipping ----------------------------------------------


def test_warm_compile_skips_widths_over_budget(make_persister):
    p, queries = _store_and_queries(make_persister, seed=13)
    engine = TpuCheckEngine(p, p.namespaces)
    try:
        engine.batch_check(queries[:8])
        snap = engine._snapshot
        all_widths = engine.dispatch.stream_widths(snap)
        assert len(all_widths) > 1
        # budget: residency plus the SMALLEST width's workspace only —
        # warming must stop there and count the skipped rungs
        smallest = engine.dispatch._warm_width_bytes(snap, all_widths[0])
        engine.hbm.set_budget_bytes(engine.hbm.resident_bytes() + smallest)
        warmed = engine.warm_compile()
        assert warmed >= 1
        skipped = engine.maintenance.snapshot().get("warm_widths_skipped", 0)
        assert skipped >= len(all_widths) - 1
        assert engine.hbm.rung_depth == 0, "warming is optional: it must never evict"
        assert engine.hbm.ledger().get("warmup", 0) == smallest
    finally:
        engine.close()


# -- ledger reconciliation ------------------------------------------------------


def test_resident_bytes_reconcile_with_engine_state(make_persister):
    p, queries = _store_and_queries(make_persister, seed=17)
    engine = TpuCheckEngine(p, p.namespaces)
    try:
        engine.batch_check(queries)
        led = engine.hbm.ledger()
        snap = engine._snapshot
        assert led["snapshot"] == snap.bucket_device_bytes()
        assert led["labels"] == snap.labels.device_bytes()
        assert sum(led.values()) == engine.hbm.resident_bytes()
        h = engine.health()
        assert h["hbm_resident_bytes"] == engine.hbm.resident_bytes()
        assert h["hbm_budget_bytes"] == engine.hbm.budget_bytes
    finally:
        engine.close()


# -- sampled shadow-parity auditor --------------------------------------------


def test_auditor_confirms_parity_on_live_decisions(make_persister):
    p, queries = _store_and_queries(make_persister, seed=19)
    engine = TpuCheckEngine(p, p.namespaces, audit_sample_rate=1.0)
    try:
        engine.batch_check(queries)
        wait_for(
            lambda: engine.health()["audit_checks"] >= 1,
            msg="audit worker drained samples",
        )
        assert engine.health()["audit_mismatches"] == 0
        monitor = HealthMonitor(engine)
        assert monitor.status()[0] is HealthState.SERVING
    finally:
        engine.close()


def test_auditor_divergence_flips_degraded(make_persister, monkeypatch):
    p, queries = _store_and_queries(make_persister, seed=23)
    engine = TpuCheckEngine(p, p.namespaces, audit_sample_rate=1.0)
    try:
        # poison the oracle: every audited decision now "diverges" —
        # the auditor must count mismatches and flip DEGRADED
        monkeypatch.setattr(
            CheckEngine, "subject_is_allowed", lambda self, rt: None
        )
        engine.batch_check(queries[:16])
        wait_for(
            lambda: engine.health()["audit_mismatches"] >= 1,
            msg="audit mismatch detection",
        )
        monitor = HealthMonitor(engine)
        state, reason = monitor.status()
        assert state is HealthState.DEGRADED
        assert "audit" in reason
        assert engine.maintenance.snapshot().get("audit_mismatches", 0) >= 1
    finally:
        engine.close()


def test_auditor_skips_samples_the_store_moved_past(make_persister, monkeypatch):
    p, queries = _store_and_queries(make_persister, seed=29)
    engine = TpuCheckEngine(p, p.namespaces, audit_sample_rate=1.0)
    try:
        # stall the worker so samples queue, then move the store: every
        # queued sample's snaptoken is stale and must be SKIPPED, not
        # compared against the newer store state
        monkeypatch.setattr(engine._audit_task, "kick", lambda: None)
        engine.batch_check(queries[:8])
        assert len(engine._audit_pending) > 0
        p.write_relation_tuples(T("ns0", "o2", "r1", SubjectID("mover")))
        engine._audit_pass()
        assert engine.health()["audit_mismatches"] == 0
        assert engine.maintenance.snapshot().get("audit_skipped_stale", 0) >= 1
    finally:
        engine.close()
