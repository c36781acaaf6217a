"""Device label build parity: batched sweeps == host PLL, entry for entry.

The batched landmark sweeps (keto_tpu/graph/label_build.py) are only
allowed to be FAST — the resulting index must be entry-set-identical to
the serial host walk (keto_tpu/graph/labels.py), per row, per side,
including width-overflow ok flags and the processed set. These suites
fuzz that equivalence across random engine-built snapshots (wildcard
keys, sink bursts, tombstoned rows), across 2- and 4-shard meshes vs the
single-device sweeper, and across the incremental patch path including
its budget-abort outcome; plus the engine-level story: device-built
labels serving checks against the CPU oracle, riding the snapshot cache,
and quarantining on a corrupted segment.
"""

import random

import jax
import numpy as np
import pytest

from keto_tpu import namespace as namespace_pkg
from keto_tpu.check import CheckEngine
from keto_tpu.check.tpu_engine import TpuCheckEngine
from keto_tpu.graph.label_build import (
    DEFAULT_BATCH,
    build_ell_groups,
    device_build_labels,
    device_patch_labels,
    estimate_build_bytes,
)
from keto_tpu.graph.labels import IN_PAD, OUT_PAD, build_labels, patch_labels
from keto_tpu.graph.snapshot import build_snapshot
from keto_tpu.parallel.mesh import make_mesh
from keto_tpu.persistence.memory import MemoryPersister
from keto_tpu.relationtuple import RelationTuple, SubjectID, SubjectSet


def T(ns, obj, rel, sub):
    return RelationTuple(namespace=ns, object=obj, relation=rel, subject=sub)


NSS = [namespace_pkg.Namespace(id=1, name="g"), namespace_pkg.Namespace(id=2, name="d")]


def make_store():
    return MemoryPersister(namespace_pkg.MemoryManager(NSS))


def quiet_engine(p, **kw):
    kw.setdefault("compact_after_s", 3600.0)
    kw.setdefault("overlay_edge_budget", 1 << 20)
    return TpuCheckEngine(p, p.namespaces, **kw)


def rand_tuple(rng, objects, relations, users):
    sub = (
        SubjectID(rng.choice(users))
        if rng.random() < 0.5
        else SubjectSet("g", rng.choice(objects), rng.choice(relations))
    )
    return T(rng.choice(["g", "d"]), rng.choice(objects), rng.choice(relations), sub)


def fuzz_store(rng, n_objects=10, n_rows=70):
    """A store exercising every row class the labels must survive:
    interior chains, sink bursts, wildcard keys, and tombstoned rows."""
    objects = [f"o{i}" for i in range(n_objects)]
    relations = ["m", "v"]
    users = [f"u{i}" for i in range(4)]
    p = make_store()
    rows = [rand_tuple(rng, objects, relations, users) for _ in range(n_rows)]
    if rng.random() < 0.5:  # wildcard-relation key rows
        rows.append(T("g", rng.choice(objects), "", SubjectID("seed")))
    p.write_relation_tuples(*rows)
    if rng.random() < 0.6:  # tombstones: deletes applied before the build
        from keto_tpu.relationtuple.model import RelationQuery

        existing, _ = p.get_relation_tuples(RelationQuery())
        p.delete_relation_tuples(
            *rng.sample(existing, min(rng.randrange(1, 6), len(existing)))
        )
    return p


def snap_of(p):
    rows, wm = p.snapshot_rows()
    return build_snapshot(rows, wm)


def entry_sets(lab, pad):
    return [
        frozenset(int(x) for x in row if x != pad) for row in np.asarray(lab)
    ]


def assert_same_index(dev, host):
    """Entry-set identity, row by row, both sides — plus the flag/meta
    surface the router's certifiability rules read."""
    assert dev.n == host.n and dev.n_landmarks == host.n_landmarks
    assert entry_sets(dev.out_lab, OUT_PAD) == entry_sets(host.out_lab, OUT_PAD)
    assert entry_sets(dev.in_lab, IN_PAD) == entry_sets(host.in_lab, IN_PAD)
    np.testing.assert_array_equal(np.asarray(dev.processed), np.asarray(host.processed))
    np.testing.assert_array_equal(np.asarray(dev.out_ok), np.asarray(host.out_ok))
    np.testing.assert_array_equal(np.asarray(dev.in_ok), np.asarray(host.in_ok))
    assert dev.n_entries == host.n_entries


# -- single-device build parity ------------------------------------------------


@pytest.mark.parametrize("seed", range(5))
def test_device_build_matches_host_fuzz(seed):
    """Full builds over random wildcard/sink/tombstone graphs: the
    batched sweeps reproduce the host walk entry for entry, including
    tight widths where overflow flags and prune order interact."""
    rng = random.Random(4100 + seed)
    snap = snap_of(fuzz_store(rng))
    for max_width in (3, 64):
        host = build_labels(snap, max_width)
        dev, info = device_build_labels(snap, max_width, batch=32)
        assert_same_index(dev, host)
        assert dev.backend == "device"
        assert info.landmarks == snap.num_int and not info.truncated


def test_device_build_landmark_cap_matches_host():
    rng = random.Random(77)
    snap = snap_of(fuzz_store(rng))
    k = max(1, snap.num_int // 2)
    host = build_labels(snap, 64, landmarks=k)
    dev, info = device_build_labels(snap, 64, landmarks=k, batch=32)
    assert_same_index(dev, host)
    assert info.truncated == "cap" and info.landmarks == k


def test_min_gain_exits_early_and_reports():
    """A high min_gain threshold stops the landmark stream after the
    first batch; the result is a sound prefix build (identical to the
    host build capped at the processed count)."""
    rng = random.Random(78)
    snap = snap_of(fuzz_store(rng, n_objects=14, n_rows=90))
    dev, info = device_build_labels(snap, 64, min_gain=1e9, batch=32)
    assert info.truncated == "min_gain"
    assert 0 < info.landmarks < snap.num_int
    assert_same_index(dev, build_labels(snap, 64, landmarks=info.landmarks))
    assert dev.coverage < 1.0


def test_estimate_build_bytes_monotone():
    assert estimate_build_bytes(10, 4) < estimate_build_bytes(1000, 4)
    assert estimate_build_bytes(100, 4) < estimate_build_bytes(100, 64)
    assert estimate_build_bytes(100, 4, batch=32) < estimate_build_bytes(
        100, 4, batch=256
    )


def test_ell_groups_cover_csr():
    rng = random.Random(5)
    snap = snap_of(fuzz_store(rng))
    from keto_tpu.graph.labels import interior_adjacency

    out_ip, out_ix, _, _ = interior_adjacency(snap)
    n = snap.num_int
    got = set()
    for nbrs, dst in build_ell_groups(out_ip, out_ix, n):
        for r in range(dst.size):
            for x in nbrs[r]:
                if x != n:
                    got.add((int(dst[r]), int(x)))
    want = {
        (u, int(out_ix[e]))
        for u in range(n)
        for e in range(int(out_ip[u]), int(out_ip[u + 1]))
    }
    assert got == want


# -- sharded build parity ------------------------------------------------------


@pytest.mark.parametrize("shards", [2, 4])
def test_sharded_build_matches_single(shards):
    """The shard_map sweeps (frontier all_gather per wave, locally routed
    edge gathers) produce the identical index on 2- and 4-shard meshes."""
    mesh = make_mesh(devices=jax.devices()[:shards], graph=shards, data=1)
    for seed in (4200, 4201):
        rng = random.Random(seed)
        snap = snap_of(fuzz_store(rng))
        host = build_labels(snap, 64)
        dev, _ = device_build_labels(
            snap, 64, batch=32, mesh=mesh, shard_count=shards
        )
        assert_same_index(dev, host)
        assert dev.backend == "sharded"


# -- incremental patch parity --------------------------------------------------


def interior_edge_candidates(rng, snap, k=3):
    """Random (a, b) pairs over interior rows — the patch path's input
    shape (compaction hands it folded overlay ELL inserts)."""
    n = snap.num_int
    if n < 2:
        return []
    return [
        (rng.randrange(n), rng.randrange(n))
        for _ in range(k)
    ]


@pytest.mark.parametrize("seed", range(5))
def test_device_patch_matches_host_fuzz(seed):
    """Edge-insert patches through the lane sweeps == the host per-edge
    landmark resumption — including the None (must-rebuild) outcome on
    truncated endpoints, and under tight widths."""
    rng = random.Random(4300 + seed)
    snap = snap_of(fuzz_store(rng))
    for max_width in (3, 64):
        base = build_labels(snap, max_width)
        edges = interior_edge_candidates(rng, snap)
        if not edges:
            pytest.skip("degenerate graph: no interior rows")
        host = patch_labels(build_labels(snap, max_width), snap, edges)
        dev = device_patch_labels(base, snap, edges, batch=32)
        assert (host is None) == (dev is None), "rebuild outcome diverged"
        if host is not None:
            assert_same_index(dev, host)
            assert dev.backend == "device"


@pytest.mark.parametrize("budget", [2, 40, 65536])
def test_patch_budget_abort_outcome_parity(budget):
    """The visit budget counts the same newly-visited pairs on both
    paths, so the abort OUTCOME (None vs patched) must agree at any
    budget even though the device path aborts between sweeps."""
    outcomes = set()
    for seed in range(6):
        rng = random.Random(4400 + seed)
        snap = snap_of(fuzz_store(rng))
        edges = interior_edge_candidates(rng, snap, k=4)
        if not edges:
            continue
        host = patch_labels(
            build_labels(snap, 64), snap, edges, visit_budget=budget
        )
        dev = device_patch_labels(
            build_labels(snap, 64), snap, edges, visit_budget=budget, batch=32
        )
        assert (host is None) == (dev is None), f"seed={seed} budget={budget}"
        outcomes.add(host is None)
        if host is not None:
            assert_same_index(dev, host)
    assert outcomes, "every fuzz graph degenerated — the suite is vacuous"


# -- engine integration --------------------------------------------------------


def deep_store(depth=8, users=("alice", "bob")):
    p = make_store()
    rows = [T("d", "doc", "view", SubjectSet("g", "c0", "m"))]
    for i in range(depth - 1):
        rows.append(T("g", f"c{i}", "m", SubjectSet("g", f"c{i+1}", "m")))
    rows.append(T("g", f"c{depth-1}", "m", SubjectSet("g", "c0", "m")))
    for u in users:
        rows.append(T("g", f"c{depth-1}", "m", SubjectID(u)))
    p.write_relation_tuples(*rows)
    return p


def test_engine_device_path_vs_oracle():
    """labels_device_min_edges=0 forces the device build inside the real
    engine: decisions match the CPU oracle, the build overlaps serving
    (labels_settled pins the install), and the fast path engages."""
    p = deep_store(depth=12)
    eng = quiet_engine(p, labels_device_min_edges=0)
    assert eng.labels_settled()
    oracle = CheckEngine(p)
    qs = [
        T("d", "doc", "view", SubjectID("alice")),
        T("d", "doc", "view", SubjectID("ghost")),
        T("g", "c2", "m", SubjectSet("g", "c9", "m")),
        T("g", "c9", "m", SubjectID("bob")),
    ]
    assert eng.batch_check(qs) == [oracle.subject_is_allowed(q) for q in qs]
    m = eng.maintenance.snapshot()
    assert m.get("label_device_builds", 0) >= 1
    assert m.get("label_checks", 0) > 0
    assert eng._snapshot.labels.backend == "device"
    assert eng._snapshot.labels.coverage == 1.0
    eng.close()


def test_engine_patch_after_compaction_uses_device_path():
    """An interior ELL overlay insert → compaction patches through the
    device sweeps; decisions stay oracle-identical before and after."""
    p = deep_store(depth=6)
    eng = quiet_engine(p, labels_device_min_edges=0)
    assert eng.labels_settled()
    p.write_relation_tuples(T("g", "c1", "m", SubjectSet("g", "c4", "m")))
    snap = eng.snapshot()
    assert snap.has_overlay and snap.lab_dirty
    compacted = eng._compact_locked(snap)
    assert compacted is not None and not compacted.has_overlay
    eng._snapshot = compacted
    m = eng.maintenance.snapshot()
    assert m.get("label_patches", 0) + m.get("label_rebuilds", 0) >= 1
    oracle = CheckEngine(p)
    qs = [
        T("d", "doc", "view", SubjectID("alice")),
        T("g", "c4", "m", SubjectID("ghost")),
    ]
    assert eng.batch_check(qs) == [oracle.subject_is_allowed(q) for q in qs]
    assert compacted.labels is not None and not compacted.lab_dirty
    eng.close()


def test_engine_tiny_graph_stays_on_host_path():
    """Below labels_device_min_edges the host walk runs directly — no
    device dispatch for graphs where one compile costs more than the
    whole build."""
    p = deep_store(depth=4)
    eng = quiet_engine(p)  # default min_edges=65536 >> this graph
    assert eng.labels_settled()
    m = eng.maintenance.snapshot()
    assert m.get("label_device_builds", 0) == 0
    assert m.get("label_builds", 0) >= 1
    assert eng._snapshot.labels.backend == "host"
    eng.close()


@pytest.mark.parametrize("floor_s, backend, event", [
    (60.0, "host", "label_host_first_builds"),
    (0.0, "device", "label_host_first_timeouts"),
])
def test_above_the_gate_the_host_walk_goes_first(monkeypatch, floor_s, backend, event):
    """Above ``labels_device_min_edges`` the host walk gets as long as the
    device's batches would take at the least: a graph it indexes in that
    time never waits for the device; one it does not gets the device build.
    The same entries either way."""
    monkeypatch.setattr(TpuCheckEngine, "LABEL_BATCH_FLOOR_S", floor_s)
    p = deep_store(depth=12)
    eng = quiet_engine(p, labels_device_min_edges=1)
    want = quiet_engine(p)  # under the default gate: the host walk, no deadline
    try:
        assert eng.labels_settled() and want.labels_settled()
        m = eng.maintenance.snapshot()
        assert m.get(event, 0) == 1
        assert m.get("label_device_builds", 0) == (backend == "device")
        got, ref = eng._snapshot.labels, want._snapshot.labels
        assert got.backend == backend and ref.backend == "host"
        assert np.array_equal(got.out_lab, ref.out_lab) and np.array_equal(got.in_lab, ref.in_lab)
        assert not any(k.startswith("label_host_first") for k in want.maintenance.snapshot())
    finally:
        eng.close()
        want.close()


def test_snapcache_roundtrip_carries_device_built_labels(tmp_path):
    """save → cold reload of a device-built index: the arrays and the
    backend tag ride the cache, construction is skipped, decisions
    match, and the fast path engages."""
    cache = str(tmp_path / "snapcache")
    p = deep_store(depth=8)
    a = TpuCheckEngine(
        p, p.namespaces, snapshot_cache_dir=cache, labels_device_min_edges=0
    )
    assert a.labels_settled()
    assert a._snapshot.labels.backend == "device"
    assert a.save_snapshot_cache() is not None

    b = TpuCheckEngine(
        p, p.namespaces, snapshot_cache_dir=cache, labels_device_min_edges=0
    )
    snap_b = b.snapshot()
    assert b.maintenance.snapshot().get("cache_loads", 0) == 1
    assert b.maintenance.snapshot().get("label_builds", 0) == 0, (
        "cold start rebuilt labels despite the cache carrying them"
    )
    assert snap_b.labels is not None and snap_b.labels.backend == "device"
    qs = [
        T("d", "doc", "view", SubjectID("alice")),
        T("d", "doc", "view", SubjectID("ghost")),
    ]
    assert b.batch_check(qs) == a.batch_check(qs)
    assert b.maintenance.snapshot().get("label_checks", 0) > 0
    a.close()
    b.close()


def test_corrupt_device_label_segment_quarantined(tmp_path):
    """A flipped byte in device-built label arrays quarantines the cache
    (crc mismatch) — the cold start rebuilds from the store and serves
    the oracle answer, never the torn index."""
    cache = tmp_path / "snapcache"
    p = deep_store(depth=6)
    a = TpuCheckEngine(
        p, p.namespaces, snapshot_cache_dir=str(cache), labels_device_min_edges=0
    )
    assert a.labels_settled()
    path = a.save_snapshot_cache()
    assert path is not None
    lab = next(
        d
        for d in cache.iterdir()
        if not d.name.startswith(".") and (d / "lab_out.npy").exists()
    ) / "lab_out.npy"
    raw = bytearray(lab.read_bytes())
    raw[-1] ^= 0xFF
    lab.write_bytes(bytes(raw))

    b = TpuCheckEngine(
        p, p.namespaces, snapshot_cache_dir=str(cache), labels_device_min_edges=0
    )
    b.snapshot()
    assert b.maintenance.snapshot().get("cache_quarantined", 0) >= 1
    oracle = CheckEngine(p)
    q = T("d", "doc", "view", SubjectID("alice"))
    assert b.subject_is_allowed(q) == oracle.subject_is_allowed(q)
    a.close()
    b.close()
