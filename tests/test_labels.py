"""2-hop reachability label parity: label path == BFS path == CPU oracle.

The label fast path (keto_tpu/graph/labels.py + the engine's
label-intersection kernel) is only allowed to be FAST — never different.
These suites assert bit-identical decisions between a labels-on engine, a
labels-off (pure BFS) engine, and the CPU reference CheckEngine across
random graphs with overlay inserts, tombstones, wildcards, sink-class
rows, and stacked compactions — the same shape as tests/test_compaction.py
— plus the snapshot-cache round trip of the label arrays and quarantine
of a corrupted label segment.
"""

import random

import numpy as np
import pytest

from keto_tpu import namespace as namespace_pkg
from keto_tpu.check import CheckEngine
from keto_tpu.check.tpu_engine import TpuCheckEngine
from keto_tpu.graph.labels import build_labels, patch_labels
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


def universe_queries(objects, relations, users):
    qs = []
    for ns in ("g", "d"):
        for obj in objects:
            for rel in relations:
                for u in users:
                    qs.append(T(ns, obj, rel, SubjectID(u)))
                for sobj in objects:
                    qs.append(T(ns, obj, rel, SubjectSet("g", sobj, relations[0])))
    return qs


def rand_tuple(rng, objects, relations, users):
    sub = (
        SubjectID(rng.choice(users))
        if rng.random() < 0.55
        else SubjectSet("g", rng.choice(objects), rng.choice(relations))
    )
    return T(rng.choice(["g", "d"]), rng.choice(objects), rng.choice(relations), sub)


def deep_store(depth=8, users=("alice", "bob")):
    """doc → c0 → … → c{depth-1} → users, with a back-edge cycle so the
    chain stays active-interior (the label path's target shape)."""
    p = make_store()
    rows = [T("d", "doc", "view", SubjectSet("g", "c0", "m"))]
    for i in range(depth - 1):
        rows.append(T("g", f"c{i}", "m", SubjectSet("g", f"c{i+1}", "m")))
    rows.append(T("g", f"c{depth-1}", "m", SubjectSet("g", "c0", "m")))
    for u in users:
        rows.append(T("g", f"c{depth-1}", "m", SubjectID(u)))
    p.write_relation_tuples(*rows)
    return p


def assert_three_way(p, queries, *, expect_label_use=True, **engine_kw):
    """labels-on == labels-off == CPU oracle on ``queries``; returns the
    labels-on engine for follow-up assertions."""
    on = quiet_engine(p, **engine_kw)
    off = quiet_engine(p, labels_enabled=False)
    oracle = CheckEngine(p)
    on.labels_settled()  # join the overlapped build: parity must be non-vacuous
    got_on = on.batch_check(queries)
    got_off = off.batch_check(queries)
    want = [oracle.subject_is_allowed(q) for q in queries]
    assert got_on == got_off, "label path diverged from the BFS path"
    assert got_on == want, "device paths diverged from the CPU oracle"
    if expect_label_use:
        assert on.maintenance.snapshot().get("label_checks", 0) > 0, (
            "label path never engaged — the parity test is vacuous"
        )
    return on


# -- index-level unit coverage -------------------------------------------------


def test_label_index_matches_bfs_closure():
    """Full build on a real snapshot: label query == interior-subgraph
    transitive closure, and every pair is certifiable."""
    from keto_tpu.graph.labels import interior_adjacency
    from keto_tpu.graph.snapshot import build_snapshot

    rng = random.Random(11)
    p = make_store()
    objects = [f"o{i}" for i in range(8)]
    p.write_relation_tuples(
        *[rand_tuple(rng, objects, ["m", "v"], ["u1", "u2"]) for _ in range(60)]
    )
    rows, wm = p.snapshot_rows()
    snap = build_snapshot(rows, wm)
    idx = build_labels(snap)
    n = snap.num_int
    oi, ov, _, _ = interior_adjacency(snap)
    reach = np.zeros((n, n), bool)
    for s in range(n):
        seen = {s}
        frontier = [s]
        while frontier:
            nxt = []
            for u in frontier:
                for w in ov[oi[u] : oi[u + 1]]:
                    if int(w) not in seen:
                        seen.add(int(w))
                        nxt.append(int(w))
            frontier = nxt
        reach[s, list(seen)] = True
    for a in range(n):
        for b in range(n):
            assert idx.query(a, b) == reach[a, b], (a, b)
            assert idx.certifiable(np.asarray([a]), np.asarray([b]))[0]


def test_label_width_and_landmark_caps_stay_sound():
    """Truncated / partially-built indexes lose coverage, never
    soundness: hits witness real paths, certified misses are real."""
    from keto_tpu.graph.snapshot import build_snapshot

    rng = random.Random(13)
    p = make_store()
    objects = [f"o{i}" for i in range(8)]
    p.write_relation_tuples(
        *[rand_tuple(rng, objects, ["m", "v"], ["u1"]) for _ in range(70)]
    )
    rows, wm = p.snapshot_rows()
    snap = build_snapshot(rows, wm)
    full = build_labels(snap)
    for kw in ({"max_width": 1}, {"landmarks": 2}, {"max_width": 2, "landmarks": 3}):
        idx = build_labels(snap, **kw)
        for a in range(snap.num_int):
            for b in range(snap.num_int):
                hit = idx.query(a, b)
                truth = full.query(a, b)
                if hit:
                    assert truth, f"unsound hit {a}->{b} under {kw}"
                elif idx.certifiable(np.asarray([a]), np.asarray([b]))[0]:
                    assert not truth, f"unsound certified miss {a}->{b} under {kw}"


class _FakeBucketSnap:
    """Minimal bucket-bearing snapshot stand-in: an edge list over n
    interior ids, laid out the way interior_adjacency reads buckets.
    Device ids are STABLE across instances by construction — exactly the
    id-stability contract compaction gives the real patch path (two
    independent build_snapshot runs renumber, so they cannot be compared
    edge-for-edge; this harness can)."""

    def __init__(self, n, edges):
        from keto_tpu.graph.snapshot import Bucket

        self.num_int = n
        indeg: dict = {}
        for s, d in edges:
            indeg.setdefault(d, []).append(s)
        cap = max((len(v) for v in indeg.values()), default=1)
        nbrs = np.full((max(n, 1), max(cap, 1)), n, np.int32)
        for d, ss in indeg.items():
            for j, s in enumerate(ss):
                nbrs[d, j] = s
        self.buckets = [Bucket(offset=0, n=n, nbrs=nbrs)]


def _closure(n, edges):
    R = np.zeros((n, n), bool)
    for s, d in edges:
        R[s, d] = True
    np.fill_diagonal(R, True)
    for k in range(n):
        R |= np.outer(R[:, k], R[k, :])
    return R


def test_patch_labels_matches_closure():
    """Incremental insertion vs the brute-force transitive closure:
    after patching in new edges, every certifiable pair answers exactly
    and every hit is sound."""
    rng = random.Random(17)
    exercised = 0
    for trial in range(120):
        n = rng.randrange(2, 12)
        m = rng.randrange(0, 2 * n)
        edges = list({(rng.randrange(n), rng.randrange(n)) for _ in range(m)})
        idx = build_labels(_FakeBucketSnap(n, edges))
        new = list(
            {(rng.randrange(n), rng.randrange(n)) for _ in range(rng.randrange(1, 4))}
            - set(edges)
        )
        all_edges = edges + new
        patched = patch_labels(idx, _FakeBucketSnap(n, all_edges), new)
        if patched is None:
            continue
        exercised += 1
        R = _closure(n, all_edges)
        for a in range(n):
            for b in range(n):
                hit = patched.query(a, b)
                cert = bool(patched.certifiable(np.asarray([a]), np.asarray([b]))[0])
                assert not (hit and not R[a, b]), (
                    f"trial={trial}: unsound hit {a}->{b} base={edges} new={new}"
                )
                assert not (cert and not hit and R[a, b]), (
                    f"trial={trial}: unsound miss {a}->{b} base={edges} new={new}"
                )
    assert exercised >= 50, "patch path barely exercised — harness too hostile"


# -- engine-level parity -------------------------------------------------------


def test_deep_chain_served_by_labels():
    p = deep_store(depth=10)
    qs = [
        T("d", "doc", "view", SubjectID("alice")),
        T("d", "doc", "view", SubjectID("ghost")),
        T("g", "c0", "m", SubjectID("bob")),
        T("g", "c9", "m", SubjectSet("g", "c2", "m")),
    ]
    on = assert_three_way(p, qs)
    m = on.maintenance.snapshot()
    assert m["label_builds"] == 1
    assert m.get("label_fallbacks", 0) == 0


def test_router_fallbacks_stay_bit_identical():
    """Wildcards, self-queries, and unknown nodes route to BFS — and the
    answers still agree everywhere."""
    p = deep_store(depth=6)
    qs = [
        T("g", "", "", SubjectID("alice")),              # full wildcard
        T("g", "c0", "", SubjectID("alice")),            # relation wildcard
        T("g", "c3", "m", SubjectSet("g", "c3", "m")),   # self through cycle
        T("g", "loner", "m", SubjectID("alice")),        # unknown object
        T("x", "c0", "m", SubjectID("alice")),           # unknown namespace
        T("d", "doc", "view", SubjectID("alice")),       # plain deep grant
    ]
    # wider than one word of queries: a sub-batch as wide as the slice
    # would take the whole slice to the BFS kernel, the label kernel unused
    qs += [T("d", "doc", "view", SubjectID("alice"))] * 32
    on = assert_three_way(p, qs)
    assert on.maintenance.snapshot().get("label_fallbacks", 0) > 0


def test_stream_parity_and_hits():
    p = deep_store(depth=8, users=tuple(f"u{i}" for i in range(6)))
    rng = random.Random(3)
    qs = [
        T("d", "doc", "view", SubjectID(rng.choice(["u0", "u3", "ghost", "nope"])))
        for _ in range(500)
    ]
    on = quiet_engine(p)
    off = quiet_engine(p, labels_enabled=False)
    on.labels_settled()
    got_on = np.concatenate(list(on.batch_check_stream(iter(qs))))
    got_off = np.concatenate(list(off.batch_check_stream(iter(qs))))
    np.testing.assert_array_equal(got_on, got_off)
    assert on.maintenance.snapshot().get("label_checks", 0) > 0


@pytest.mark.parametrize("seed", range(6))
def test_label_fuzz_parity(seed):
    """Randomized mutation rounds (inserts incl. new sinks and wildcard
    graphs, tombstone deletes, stacked compactions): label-on decisions
    must match labels-off AND the CPU oracle at every step, overlay
    pending or folded."""
    rng = random.Random(7000 + seed)
    objects = [f"o{i}" for i in range(6)]
    relations = ["m", "v"]
    users = [f"u{i}" for i in range(5)] + ["ghost"]
    p = make_store()
    p.write_relation_tuples(
        *[rand_tuple(rng, objects, relations, users) for _ in range(30)]
    )
    on = quiet_engine(p)
    off = quiet_engine(p, labels_enabled=False)
    oracle = CheckEngine(p)
    queries = universe_queries(objects, relations, users)
    from keto_tpu.relationtuple.model import RelationQuery

    for round_ in range(6):
        n_ins = rng.randrange(1, 5)
        n_del = rng.randrange(0, 3)
        existing, _ = p.get_relation_tuples(RelationQuery())
        p.write_relation_tuples(
            *[rand_tuple(rng, objects, relations, users) for _ in range(n_ins)]
        )
        if existing and n_del:
            p.delete_relation_tuples(*rng.sample(existing, min(n_del, len(existing))))
        got_on = on.batch_check(queries)
        got_off = off.batch_check(queries)
        assert got_on == got_off, f"seed={seed} round={round_}: label/BFS divergence"
        sample = rng.sample(range(len(queries)), 60)
        for i in sample:
            assert got_on[i] == oracle.subject_is_allowed(queries[i]), (
                f"seed={seed} round={round_}: {queries[i]}"
            )
        if round_ % 2 == 1:
            # fold the overlay (when compactable) so later rounds stack
            # label patches/rebuilds on compacted bases
            snap = on.snapshot()
            if snap.has_overlay:
                compacted = on._compact_locked(snap)
                if compacted is not None:
                    on._snapshot = compacted
                    assert compacted.labels is None or not compacted.lab_dirty
    assert on.maintenance.snapshot().get("label_checks", 0) > 0


def test_overlay_ell_insert_blocks_then_compaction_restores():
    """An interior→interior overlay edge disables the label path (every
    check falls back, counted as an invalidation); compaction patches the
    labels and the fast path resumes — bit-identically throughout."""
    p = deep_store(depth=6)
    on = quiet_engine(p)
    # the overlapped label build has to have installed its index before the
    # write: a snapshot without one blocks nothing and counts no invalidation
    assert on.labels_settled()
    q = T("d", "doc", "view", SubjectID("alice"))
    assert on.subject_is_allowed(q)
    # new edge between existing active-interior rows → overlay ELL
    p.write_relation_tuples(T("g", "c1", "m", SubjectSet("g", "c4", "m")))
    snap = on.snapshot()
    assert snap.has_overlay and snap.ov_ell is not None
    assert snap.lab_dirty, "ELL insert must dirty the label set"
    m0 = on.maintenance.snapshot()
    oracle = CheckEngine(p)
    qs = [q, T("g", "c4", "m", SubjectID("alice")), T("g", "c5", "m", SubjectID("ghost"))]
    got = on.batch_check(qs)
    assert got == [oracle.subject_is_allowed(x) for x in qs]
    m1 = on.maintenance.snapshot()
    assert m1.get("label_invalidations", 0) >= 1
    assert m1.get("label_checks", 0) == m0.get("label_checks", 0), (
        "label path served checks while the interior subgraph was dirty"
    )
    compacted = on._compact_locked(on.snapshot())
    assert compacted is not None and not compacted.has_overlay
    assert compacted.labels is not None and not compacted.lab_dirty
    on._snapshot = compacted
    got2 = on.batch_check(qs)
    assert got2 == got
    m2 = on.maintenance.snapshot()
    assert m2.get("label_patches", 0) + m2.get("label_rebuilds", 0) >= 1
    assert m2.get("label_checks", 0) > m1.get("label_checks", 0)


def test_sink_burst_keeps_labels_live():
    """The common burst — new users on existing groups (interior→sink
    overlay edges) — must NOT invalidate labels: the interior subgraph
    is untouched."""
    p = deep_store(depth=6)
    on = quiet_engine(p)
    on.labels_settled()
    p.write_relation_tuples(
        *[T("g", "c5", "m", SubjectID(f"burst-{i}")) for i in range(10)]
    )
    snap = on.snapshot()
    assert snap.has_overlay
    assert not snap.lab_dirty
    oracle = CheckEngine(p)
    qs = [T("d", "doc", "view", SubjectID(f"burst-{i}")) for i in range(10)]
    qs.append(T("d", "doc", "view", SubjectID("ghost")))
    m0 = on.maintenance.snapshot().get("label_checks", 0)
    got = on.batch_check(qs)
    assert got == [oracle.subject_is_allowed(x) for x in qs]
    assert on.maintenance.snapshot().get("label_checks", 0) > m0


def test_tombstoned_ell_edge_blocks_labels():
    """Deleting an iterated interior edge must disable the label path
    until the fold: a label hit through the dead edge would over-grant."""
    p = deep_store(depth=5)
    on = quiet_engine(p)
    on.snapshot()
    p.delete_relation_tuples(T("g", "c1", "m", SubjectSet("g", "c2", "m")))
    snap = on.snapshot()
    assert snap.has_overlay and snap.lab_dirty
    oracle = CheckEngine(p)
    q = T("d", "doc", "view", SubjectID("alice"))
    assert on.subject_is_allowed(q) == oracle.subject_is_allowed(q) == False  # noqa: E712


@pytest.mark.parametrize("kw", [{"labels_max_width": 1}, {"labels_landmarks": 1}])
def test_coverage_gaps_fall_back_not_lie(kw):
    p = deep_store(depth=8)
    qs = [
        T("d", "doc", "view", SubjectID("alice")),
        T("d", "doc", "view", SubjectID("ghost")),
        T("g", "c2", "m", SubjectSet("g", "c6", "m")),
        T("g", "c6", "m", SubjectSet("g", "c2", "m")),
    ]
    assert_three_way(p, qs, expect_label_use=False, **kw)


# -- snapshot cache ------------------------------------------------------------


def test_snapcache_roundtrip_carries_labels(tmp_path):
    """save → cold reload: the label arrays ride the cache, construction
    is skipped, decisions match, and the fast path engages."""
    cache = str(tmp_path / "snapcache")
    p = deep_store(depth=8)
    a = TpuCheckEngine(p, p.namespaces, snapshot_cache_dir=cache)
    a.snapshot()
    assert a.save_snapshot_cache() is not None

    b = TpuCheckEngine(p, p.namespaces, snapshot_cache_dir=cache)
    snap_b = b.snapshot()
    assert b.maintenance.snapshot().get("cache_loads", 0) == 1
    assert b.maintenance.snapshot().get("label_builds", 0) == 0, (
        "cold start rebuilt labels despite the cache carrying them"
    )
    assert snap_b.labels is not None
    qs = [
        T("d", "doc", "view", SubjectID("alice")),
        T("d", "doc", "view", SubjectID("ghost")),
        T("g", "c3", "m", SubjectID("bob")),
    ]
    assert b.batch_check(qs) == a.batch_check(qs)
    assert b.maintenance.snapshot().get("label_checks", 0) > 0


def test_snapcache_corrupt_label_segment_quarantined(tmp_path):
    """A flipped byte in the label arrays must quarantine the cache (crc
    mismatch), never serve wrong reachability."""
    from keto_tpu.graph import snapcache

    cache = tmp_path / "snapcache"
    p = deep_store(depth=6)
    a = TpuCheckEngine(p, p.namespaces, snapshot_cache_dir=str(cache))
    a.snapshot()
    path = a.save_snapshot_cache()
    assert path is not None
    # published caches only: engine a's background save worker may still
    # hold an in-flight .tmp- dir (corrupting that would test nothing)
    lab = next(
        d for d in cache.iterdir()
        if not d.name.startswith(".") and (d / "lab_out.npy").exists()
    ) / "lab_out.npy"
    raw = bytearray(lab.read_bytes())
    raw[-1] ^= 0xFF
    lab.write_bytes(bytes(raw))

    b = TpuCheckEngine(p, p.namespaces, snapshot_cache_dir=str(cache))
    snap = b.snapshot()  # quarantines, rebuilds from the store
    assert b.maintenance.snapshot().get("cache_quarantined", 0) >= 1
    oracle = CheckEngine(p)
    q = T("d", "doc", "view", SubjectID("alice"))
    assert b.subject_is_allowed(q) == oracle.subject_is_allowed(q)
    assert any(x.name.startswith(".quarantine-") for x in cache.iterdir())


def test_labels_disabled_engine_ignores_cached_labels(tmp_path):
    cache = str(tmp_path / "snapcache")
    p = deep_store(depth=5)
    a = TpuCheckEngine(p, p.namespaces, snapshot_cache_dir=cache)
    a.snapshot()
    assert a.save_snapshot_cache() is not None
    b = TpuCheckEngine(
        p, p.namespaces, snapshot_cache_dir=cache, labels_enabled=False
    )
    snap = b.snapshot()
    assert snap.labels is None
    q = T("d", "doc", "view", SubjectID("alice"))
    assert b.subject_is_allowed(q)
    assert b.maintenance.snapshot().get("label_checks", 0) == 0
