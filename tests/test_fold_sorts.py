"""A fold never compiles, and never keeps a reader from a write it was told of.

The build's and the fold's stable argsorts go through padded rungs
(keto_tpu/graph/device_build.py): one program a rung and arity, never one a
length; while a snapshot serves, a batch whose program is not compiled sorts
on the host and the program compiles behind. The serving path waits out a fold
or a delta apply and is handed the old snapshot only while a full rebuild
stands in the way (keto_tpu/check/tpu_engine.py ``snapshot_serving``); a round
with unpinned requests catches up as an unpinned round would, whatever floor
rides beside it (keto_tpu/driver/batch.py ``_consistency_kw``). CPU jax, small
sizes; nothing here is a device number."""

import random
import sys
import threading
import time
from pathlib import Path

import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from benchmarks.reference import Reference  # noqa: E402
from keto_tpu.check import CheckEngine  # noqa: E402
from keto_tpu.check.tpu_engine import TpuCheckEngine  # noqa: E402
from keto_tpu.driver.batch import CheckBatcher  # noqa: E402
from keto_tpu.driver.compile_cache import install_listener  # noqa: E402
from keto_tpu.driver.hbm import HbmGovernor  # noqa: E402
from keto_tpu.graph import device_build  # noqa: E402
from keto_tpu.graph.device_build import (  # noqa: E402
    SENTINEL, DeviceSorter, GovernedSorter, SentinelCollision, pad_keys, rung_of,
)
from keto_tpu.relationtuple import SubjectID, SubjectSet  # noqa: E402
from keto_tpu.x.telemetry import MaintenanceStats  # noqa: E402
from test_compaction import T, make_store, rand_tuple, universe_queries  # noqa: E402

EDGES = (65_536, 131_072)  # the ladder's first two rungs: what a CPU run affords


def compiles() -> int:
    """Backend compiles of this process so far, as ``jax.monitoring`` reports
    them to ``driver/compile_cache.py`` ``_on_duration``."""
    return install_listener().snapshot()[1]


def keys_of(rng, n: int) -> np.ndarray:
    """``n`` keys with many duplicates, as a bucket key or a CSR endpoint has."""
    return rng.integers(0, max(1, n // 7 + 1), size=n).astype(np.int64)


def batch_of(rng, n: int, arity: int) -> list:
    """``arity`` arrays of unequal lengths, the longest ``n``."""
    return [keys_of(rng, m) for m in (n, max(1, n // 3), 7)[:arity]]


# -- the padded sort is numpy's, bit for bit -----------------------------------


def test_the_ladder_is_powers_of_two_from_the_threshold():
    assert [rung_of(n) for n in (0, 1, 65_535, 65_536, 65_537, 1_000_000, 1_000_114)] == [
        65_536, 65_536, 65_536, 65_536, 131_072, 1_048_576, 1_048_576]
    assert rung_of(2**30 + 1) == 2**31
    assert device_build.DEFAULT_MIN_EDGES == rung_of(0)


@pytest.mark.parametrize("arity", [1, 2, 3])
@pytest.mark.parametrize("n", [e + d for e in EDGES for d in (-1, 0, 1)])
def test_padded_device_sort_equals_numpy_around_a_rung_edge(n, arity):
    arrays = batch_of(np.random.default_rng(n * 4 + arity), n, arity)
    got = DeviceSorter().argsort_many(arrays)
    for a, perm in zip(arrays, got):
        assert perm.dtype == np.int64 and perm.shape == a.shape
        assert (perm == np.argsort(a, kind="stable")).all()
    assert tuple(rung_of(a.size) for a in arrays) in device_build.compiled_rungs()


@pytest.mark.parametrize("dtype", [np.int32, np.int64, np.uint32])
def test_pad_keys_fills_the_tail_and_keeps_the_head(dtype):
    a = np.arange(70_000, dtype=dtype)[::-1]  # over one block of the pass, and a strided view
    out = pad_keys(a, 131_072)
    assert out.dtype == np.int32 and out.shape == (131_072,)
    assert (out[:70_000] == a).all() and (out[70_000:] == SENTINEL).all()


@pytest.mark.parametrize("key, error", [
    (SENTINEL, SentinelCollision), (SENTINEL + 1, OverflowError), (-SENTINEL - 2, OverflowError)],
    ids=["int32-max", "over-int32", "under-int32"])
def test_a_key_the_padding_cannot_sort_behind_is_refused_not_corrupted(key, error):
    keys = np.array([5, key, 3, key, 5], np.int64)
    with pytest.raises(error) as raised:
        DeviceSorter().argsort(keys)
    assert type(raised.value) is error
    # the policy falls back to the host, explicitly, and says so
    stats = MaintenanceStats()
    sorter = GovernedSorter(min_size=1, stats=stats)
    assert (sorter.argsort(keys) == np.argsort(keys, kind="stable")).all()
    seen = stats.snapshot()
    assert seen["build_sort_host_error"] == 1 and seen["device_build_errors"] == 1
    assert "build_sort_device_ok" not in seen and seen["build_sort_host_count"] == 1


# -- one program a rung and arity ----------------------------------------------


@pytest.mark.parametrize("n", [70_000, 140_000])
def test_lengths_a_fold_apart_share_one_compiled_program(n):
    """n, n + 57, n + 114: PR 46 saw the chip compile 18-20 s for each. Arity
    4 is no other test's, so the first length compiles here and is counted."""
    rng = np.random.default_rng(n)
    dev, rungs = DeviceSorter(), (rung_of(n),) * 4
    assert rungs not in device_build.compiled_rungs()
    spent = []
    for m in (n, n + 57, n + 114):
        arrays = [keys_of(rng, m) for _ in range(4)]
        before = compiles()
        got = dev.argsort_many(arrays)
        spent.append(compiles() - before)
        assert all((p == np.argsort(a, kind="stable")).all() for a, p in zip(arrays, got))
    assert spent[0] >= 1 and spent[1:] == [0, 0]
    assert rungs in device_build.compiled_rungs()


# -- the policy: where a batch sorts, and why ----------------------------------


def _wait_compiled(sorter, timeout=120.0):
    deadline = time.monotonic() + timeout
    while sorter.pending() and time.monotonic() < deadline:
        time.sleep(0.01)
    assert sorter.pending() == 0


@pytest.mark.parametrize("arity", [5, 6])
def test_a_cold_rung_while_serving_sorts_on_the_host_and_compiles_behind(arity):
    """Arities 5 and 6 are no other test's: their programs are cold here."""
    rng = np.random.default_rng(arity)
    stats, serving = MaintenanceStats(), [True]
    sorter = GovernedSorter(min_size=1, stats=stats, serving=lambda: serving[0])
    arrays = [keys_of(rng, 900 + k) for k in range(arity)]
    rungs = (65_536,) * arity
    assert rungs not in device_build.compiled_rungs()
    want = [np.argsort(a, kind="stable") for a in arrays]

    before = compiles()
    got = sorter.argsort_many(arrays)  # as a fold asks: under the engine's lock
    assert all((g == w).all() for g, w in zip(got, want))
    assert stats.snapshot()["build_sort_host_cold"] == 1
    assert "build_sort_device_ok" not in stats.snapshot()
    _wait_compiled(sorter)  # the worker thread, off every lock
    assert rungs in device_build.compiled_rungs() and compiles() > before
    assert stats.snapshot()["build_sort_rungs"] == len(device_build.compiled_rungs())

    before = compiles()
    got = sorter.argsort_many(arrays)  # the next fold of that size
    assert all((g == w).all() for g, w in zip(got, want))
    assert compiles() == before, "a sort compiled while a snapshot was serving"
    seen = stats.snapshot()
    assert seen["build_sort_device_ok"] == 1 and seen["build_sort_host_cold"] == 1
    assert seen["build_sort_device_count"] == 1 and seen["build_sort_host_count"] == 1
    assert seen["device_build_dispatches"] == 1
    sorter.close()


@pytest.mark.parametrize("case, why", [
    ("boot", "device_ok"), ("small", "host_small"), ("pressure", "host_pressure")])
def test_every_other_batch_says_where_it_sorted_and_why(case, why):
    stats = MaintenanceStats()
    gov = HbmGovernor(budget_bytes=1, stats=stats) if case == "pressure" else None
    sorter = GovernedSorter(
        hbm=gov, stats=stats, min_size=1 if case != "small" else 65_536,
        # nothing serves during the boot build: it may compile as it goes
        serving=lambda: False,
    )
    keys = keys_of(np.random.default_rng(3), 5_000)
    assert (sorter.argsort(keys) == np.argsort(keys, kind="stable")).all()
    seen = stats.snapshot()
    assert {k: v for k, v in seen.items() if k.startswith("build_sort_") and k.count("_") == 3
            and not k.endswith(("_ms", "_count"))} == {f"build_sort_{why}": 1}
    assert seen.get("device_build_skipped", 0) == (case == "pressure")
    if gov is not None:
        assert gov.ledger().get("build", 0) == 0  # the transient never leaked


# -- an engine written to across three folds, a reader beside the writer --------


def _row(t):
    s = t.subject
    if isinstance(s, SubjectID):
        return (t.namespace, t.object, t.relation, s.id, None, None, None)
    return (t.namespace, t.object, t.relation, None, s.namespace, s.object, s.relation)


@pytest.mark.parametrize("seed", range(3))
def test_reads_sent_after_an_acknowledgement_see_the_write_across_folds(seed):
    rng = random.Random(1000 + seed)  # the seeded worlds of tests/test_compaction.py
    objects, relations = [f"o{i}" for i in range(6)], ["m", "v"]
    users = [f"u{i}" for i in range(6)] + ["ghost"]
    p = make_store()
    first = list(dict.fromkeys(rand_tuple(rng, objects, relations, users) for _ in range(30)))
    # every group has a member before the engine boots: a group's first member
    # changes its node's class, which only a rebuild can lay out, and the
    # serving path answers a rebuild's readers from the old snapshot by design
    first += [t for o in objects if (t := T("g", o, "m", SubjectID(f"founder-{o}"))) not in first]
    p.write_relation_tuples(*first)
    reference = Reference([_row(t) for t in first])
    engine = TpuCheckEngine(p, p.namespaces, compact_after_s=0.02, overlay_edge_budget=1 << 20)
    engine.snapshot()
    # a deployment's graph takes seconds to build: the serving path then
    # never rebuilds inline, and waits or serves stale by the holder
    engine._last_full_build_s = 10.0

    acknowledged, stop, failures, read = [], threading.Event(), [], [0]

    def reader():
        while not stop.is_set() or read[0] < len(acknowledged):
            if read[0] == len(acknowledged):
                time.sleep(0.001)
                continue
            query, want = acknowledged[read[0]]  # sent after the acknowledgement
            got = engine.batch_check([query], mode="serving")[0]
            if got != want:
                failures.append((query, want, got))
            read[0] += 1

    thread = threading.Thread(target=reader, daemon=True)
    thread.start()
    log, folds, k = [], lambda: engine.maintenance.snapshot().get("compactions", 0), 0
    deadline = time.monotonic() + 60.0
    while folds() < 3 and time.monotonic() < deadline:
        k += 1
        joiner = T("g", rng.choice(objects), "m", SubjectID(f"fresh-{seed}-{k}"))
        p.write_relation_tuples(joiner)  # returns: acknowledged
        log.append(("insert", _row(joiner)))
        acknowledged.append((joiner, True))
        if k % 3 == 0:
            # someone the reader has seen join leaves: from its
            # acknowledgement on, denied
            at = len(acknowledged) - 3
            while read[0] <= at and time.monotonic() < deadline:
                time.sleep(0.001)
            leaver = acknowledged[at][0]
            p.delete_relation_tuples(leaver)
            log.append(("delete", _row(leaver)))
            acknowledged.append((leaver, False))
        time.sleep(0.01)
    stop.set()
    thread.join(timeout=60.0)
    assert not thread.is_alive() and folds() >= 3
    assert failures == []
    seen = engine.maintenance.snapshot()
    assert [seen.get(f"stale_serves_{h}", 0) for h in ("fold", "delta", "rebuild")] == [0, 0, 0]

    # and the whole universe against both oracles, after the same log
    for op, row in log:
        reference.apply(op, row)
    oracle = CheckEngine(p)
    queries = [q for q in universe_queries(objects, relations, users + [f"fresh-{seed}-1"])
               if isinstance(q.subject, SubjectID)]
    for q, got in zip(queries, engine.batch_check(queries, mode="serving")):
        assert got == oracle.subject_is_allowed(q), q
        assert got == reference.allowed(q.namespace, q.object, q.relation, q.subject.id), q
    engine.close()


@pytest.mark.parametrize("holder", ["fold", "delta", "rebuild"])
def test_what_the_serving_path_does_while_the_lock_is_held(holder):
    """A fold or a delta apply is waited for, and the round then holds the
    write; a rebuild is not, and the round is counted as served stale."""
    p = make_store()
    p.write_relation_tuples(T("d", "doc", "view", SubjectSet("g", "team", "m")),
                            T("g", "team", "m", SubjectID("alice")))
    engine = TpuCheckEngine(p, p.namespaces, compact_after_s=3600.0)
    old = engine.snapshot()
    engine._last_full_build_s = 10.0
    held, release = threading.Event(), threading.Event()

    def pass_():
        with engine._engine_lock():
            engine._lock_holder = holder
            held.set()
            release.wait(timeout=30.0)

    thread = threading.Thread(target=pass_, daemon=True)
    thread.start()
    assert held.wait(timeout=30.0)
    p.write_relation_tuples(T("g", "team", "m", SubjectID("bob")))  # acknowledged
    threading.Timer(0.5, release.set).start()
    snap = engine.snapshot_serving()
    returned_early = not release.is_set()
    seen = engine.maintenance.snapshot()
    if holder == "rebuild":
        assert snap is old and returned_early
        assert seen["stale_serves_rebuild"] == 1 and "lock_wait_serving_count" in seen
    else:
        assert snap.snapshot_id == p.watermark() > old.snapshot_id and not returned_early
        assert seen["lock_wait_serving_count"] == 1 and seen["lock_wait_serving_total_ms"] >= 300
        assert not [k for k in seen if k.startswith("stale_serves_")]
        assert engine.batch_check([T("d", "doc", "view", SubjectID("bob"))], mode="serving") == [True]
    release.set()
    thread.join(timeout=30.0)
    assert engine._lock_holder is None
    engine.close()


@pytest.mark.parametrize("at_leasts, latests, want", [
    ([None, None], [False, False], {"at_least": None, "mode": "serving"}),
    ([7, None, 9], [False] * 3, {"at_least": 9, "mode": "serving"}),
    ([7, 9], [False, False], {"at_least": 9, "mode": "pinned"}),
    ([7, None], [False, True], {"mode": "latest"}),
], ids=["unpinned", "mixed", "all-pinned", "latest"])
def test_a_round_is_served_at_the_strongest_consistency_it_holds(at_leasts, latests, want):
    assert CheckBatcher._consistency_kw(at_leasts, latests) == want


@pytest.mark.parametrize("mode, fresh", [("serving", True), ("pinned", False)])
def test_an_unpinned_request_beside_a_pinned_one_still_sees_every_acknowledged_write(mode, fresh):
    p = make_store()
    p.write_relation_tuples(T("g", "team", "m", SubjectID("alice")))
    engine = TpuCheckEngine(p, p.namespaces, compact_after_s=3600.0)
    floor = engine.snapshot().snapshot_id
    engine._last_full_build_s = 10.0
    p.write_relation_tuples(T("g", "team", "m", SubjectID("bob")))  # acknowledged, unpinned reader's
    snap = engine._snapshot_for(floor, mode)
    assert (snap.snapshot_id == p.watermark()) is fresh and snap.snapshot_id >= floor
    engine.close()
