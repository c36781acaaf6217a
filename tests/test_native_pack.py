"""Native pack walk (native/pack.cpp) vs the numpy reference, and the
amortized seen-set fix.

The contract under test:

- the native walk's output — seed arrays, host-decided grants, and the
  final seven packed kernel arrays — is BYTE-identical to the numpy
  path's across fuzzed graphs (wildcards, deep chains, sink targets,
  multi-start patterns);
- snapshots carrying host-visible overlay state (tombstones, overlay
  adjacency, overlay sink in-edges) are ineligible and route to numpy —
  with decisions still matching the CPU oracle;
- the numpy fallback's visited set (``_SortedSeen``) does O(n log n)
  total merge work where the old ``np.insert`` scheme did O(n^2) — a
  long stream of chunks can no longer go superlinear.
"""

import math
import random

import numpy as np
import pytest

from keto_tpu.check import native_pack
from keto_tpu.check.engine import CheckEngine
from keto_tpu.check.pack import _SortedSeen, pack_chunk
from keto_tpu.check.tpu_engine import TpuCheckEngine
from keto_tpu.relationtuple.model import RelationTuple, SubjectID, SubjectSet


def T(ns, obj, rel, sub):
    return RelationTuple(namespace=ns, object=obj, relation=rel, subject=sub)


def _fuzz_store(make_persister, seed, n_tuples=300, chain=40):
    rng = random.Random(seed)
    names = ["a", "b"]
    p = make_persister([("a", 1), ("b", 2)])
    objs = [f"o{i}" for i in range(12)]
    rels = ["r0", "r1", "r2"]
    users = [f"u{i}" for i in range(10)]
    rows = []
    for _ in range(n_tuples):
        sub = (
            SubjectID(rng.choice(users))
            if rng.random() < 0.5
            else SubjectSet(rng.choice(names), rng.choice(objs), rng.choice(rels))
        )
        rows.append(T(rng.choice(names), rng.choice(objs), rng.choice(rels), sub))
    # deep chain so the walk actually iterates many hops
    for i in range(chain):
        rows.append(T("a", f"c{i}", "r0", SubjectSet("a", f"c{i+1}", "r0")))
    rows.append(T("a", f"c{chain}", "r0", SubjectID("deep-user")))
    p.write_relation_tuples(*rows)
    queries = []
    for _ in range(200):
        r = rng.random()
        if r < 0.1:
            queries.append(T("", "", "", SubjectID(rng.choice(users))))
        elif r < 0.2:
            queries.append(
                T(rng.choice(names), "", rng.choice(rels),
                  SubjectSet(rng.choice(names), rng.choice(objs), rng.choice(rels)))
            )
        else:
            sub = (
                SubjectID(rng.choice(users))
                if rng.random() < 0.6
                else SubjectSet(rng.choice(names), rng.choice(objs), rng.choice(rels))
            )
            queries.append(
                T(rng.choice(names), rng.choice(objs), rng.choice(rels), sub)
            )
    queries.append(T("a", "c0", "r0", SubjectID("deep-user")))
    return p, queries


needs_native = pytest.mark.skipif(
    not native_pack.available(), reason="native pack library not built"
)


@needs_native
@pytest.mark.parametrize("seed", [0, 1, 2, 3, 4, 5])
def test_native_pack_byte_parity_fuzz(make_persister, seed):
    """Every packed array and every host-decided grant is byte-identical
    between the native and numpy walks, over full chunks and interior
    sub-chunks."""
    p, queries = _fuzz_store(make_persister, seed)
    engine = TpuCheckEngine(p, p.namespaces, labels_enabled=False)
    try:
        snap = engine.snapshot()
        assert native_pack.walk_eligible(snap)
        sd, tg, multi = engine.dispatch._resolve_bulk(snap, queries)
        for i0, i1 in [(0, len(queries)), (17, 130), (60, 61)]:
            pn, hn = pack_chunk(snap, sd, tg, multi, i0, i1, native=True)
            pp, hp = pack_chunk(snap, sd, tg, multi, i0, i1, native=False)
            assert (hn == hp).all()
            assert (pn is None) == (pp is None)
            if pn is not None:
                for k, (a, b) in enumerate(zip(pn, pp)):
                    assert a.dtype == b.dtype, f"arr {k} dtype"
                    assert a.shape == b.shape, f"arr {k} shape"
                    assert (a == b).all(), f"arr {k} contents"
    finally:
        engine.close()


@needs_native
def test_native_pack_decisions_match_oracle(make_persister):
    """End-to-end: an engine on the native pack path answers every fuzzed
    query exactly like the CPU reference engine."""
    p, queries = _fuzz_store(make_persister, seed=9)
    engine = TpuCheckEngine(p, p.namespaces)
    oracle = CheckEngine(p)
    try:
        before = native_pack.COUNTERS["native"]
        got = engine.batch_check(queries)
        assert native_pack.COUNTERS["native"] > before, "native path not taken"
        assert got == [oracle.subject_is_allowed(q) for q in queries]
    finally:
        engine.close()


@needs_native
def test_overlay_state_routes_to_numpy(make_persister):
    """A tombstone (host-visible overlay state) makes the snapshot
    ineligible: chunks route to the numpy walk and decisions still match
    the oracle."""
    p, queries = _fuzz_store(make_persister, seed=4, n_tuples=120, chain=10)
    engine = TpuCheckEngine(p, p.namespaces)
    oracle = CheckEngine(p)
    try:
        engine.batch_check(queries[:8])  # build the base snapshot
        # delete one known chain edge -> delta tombstone, no rebuild
        p.delete_relation_tuples(T("a", "c5", "r0", SubjectSet("a", "c6", "r0")))
        snap = engine.snapshot()
        if snap.ov_removed is None or snap.ov_removed.size == 0:
            pytest.skip("store rebuilt instead of tombstoning")
        assert not native_pack.walk_eligible(snap)
        before = native_pack.COUNTERS["numpy"]
        got = engine.batch_check(queries)
        assert native_pack.COUNTERS["numpy"] > before
        assert got == [oracle.subject_is_allowed(q) for q in queries]
    finally:
        engine.close()


@needs_native
def test_native_pack_unavailable_pins_numpy(make_persister, monkeypatch):
    """With no library to load (what ``KETO_TPU_NATIVE=0`` or a box without
    a compiler leaves) the engine packs with numpy, and answers the same."""
    p, queries = _fuzz_store(make_persister, seed=2, n_tuples=80, chain=5)
    monkeypatch.setattr(native_pack, "available", lambda: False)
    engine = TpuCheckEngine(p, p.namespaces)
    oracle = CheckEngine(p)
    try:
        before = native_pack.COUNTERS["native"]
        got = engine.batch_check(queries)
        assert native_pack.COUNTERS["native"] == before
        assert got == [oracle.subject_is_allowed(q) for q in queries]
    finally:
        engine.close()


@needs_native
def test_sink_gather_parity(make_persister):
    """The native sink answer gather equals sink_in_rows_bulk's
    overlay-free arm on every sink target."""
    p, _ = _fuzz_store(make_persister, seed=7)
    engine = TpuCheckEngine(p, p.namespaces, labels_enabled=False)
    try:
        snap = engine.snapshot()
        sb, nl = snap.sink_base, snap.num_live
        if nl <= sb:
            pytest.skip("no sink nodes in this store")
        sinks = np.arange(sb, nl, dtype=np.int64)
        rn, cn = native_pack.sink_gather(snap, sinks)
        rp, cp = snap.sink_in_rows_bulk(sinks)
        assert (cn == cp).all()
        assert rn.dtype == rp.dtype and (rn == rp).all()
    finally:
        engine.close()


@needs_native
@pytest.mark.parametrize("seed", range(4))
def test_pairs_member_parity(seed, monkeypatch):
    """The hash set behind ``device_part``'s direct grants equals the sorted
    keys of the numpy arm: empty sets, empty probes, repeated pairs."""
    rng = np.random.default_rng(seed)
    for _ in range(40):
        n, m = (int(x) for x in rng.integers(0, 3000, 2))
        rows_hi, q_hi = int(rng.choice([4, 300, 2_000_000])), int(rng.choice([32, 2048]))
        sr, sq = rng.integers(0, rows_hi, n), rng.integers(0, q_hi, n)
        r, q = rng.integers(0, rows_hi, m), rng.integers(0, q_hi, m)
        native = native_pack.pairs_member(sr, sq, r, q)
        with monkeypatch.context() as mp:
            mp.setattr(native_pack, "load_library", lambda: None)
            plain = native_pack.pairs_member(sr, sq, r, q)
        want = np.array([(a, b) in set(zip(sr.tolist(), sq.tolist()))
                         for a, b in zip(r.tolist(), q.tolist())], bool)
        assert native.dtype == plain.dtype == bool
        assert (native == want).all() and (plain == want).all()


# -- the amortized seen set ----------------------------------------------------


def test_sorted_seen_matches_python_set():
    rng = random.Random(5)
    seen = _SortedSeen()
    ref: set = set()
    for _ in range(200):
        batch = np.array(
            sorted({rng.randrange(4096) for _ in range(rng.randrange(1, 40))}),
            dtype=np.int64,
        )
        got = seen.contains(batch)
        want = np.array([int(k) in ref for k in batch])
        assert (got == want).all()
        fresh = batch[~got]
        seen.add(fresh)
        ref.update(int(k) for k in fresh)
    # final full-membership sweep
    allk = np.arange(4096, dtype=np.int64)
    assert (seen.contains(allk) == np.array([k in ref for k in range(4096)])).all()


def test_sorted_seen_merge_work_is_loglinear():
    """10k insert batches (one per simulated chunk/hop) stay within the
    O(n log n) merge-work bound — the regression test for the quadratic
    ``np.insert`` accumulation this structure replaced (an O(n^2) scheme
    would do ~5e9 units here; the bound allows ~3e6)."""
    seen = _SortedSeen()
    n_batches = 10_000
    per = 10
    base = 0
    for _ in range(n_batches):
        seen.add(np.arange(base, base + per, dtype=np.int64))
        base += per
    n = n_batches * per
    assert seen.work <= 2 * n * math.log2(n), (
        f"merge work {seen.work} exceeds the loglinear bound"
    )
    # and membership still answers correctly at full size
    probe = np.array([0, 1, n - 1, n, n + 7], dtype=np.int64)
    assert seen.contains(probe).tolist() == [True, True, True, False, False]


def test_deep_chain_pack_completes(make_persister):
    """A 4k-hop chain packs through the numpy fallback in one call —
    the walk that used to pay a quadratic seen-set rebuild per hop."""
    p = make_persister([("a", 1)])
    depth = 4000
    rows = [
        T("a", f"c{i}", "r0", SubjectSet("a", f"c{i+1}", "r0"))
        for i in range(depth)
    ]
    rows.append(T("a", f"c{depth}", "r0", SubjectID("u")))
    p.write_relation_tuples(*rows)
    engine = TpuCheckEngine(p, p.namespaces, labels_enabled=False)
    try:
        snap = engine.snapshot()
        q = [T("a", "c0", "r0", SubjectID("u"))]
        sd, tg, multi = engine.dispatch._resolve_bulk(snap, q)
        packed, host_ans = pack_chunk(snap, sd, tg, multi, 0, 1, native=False)
        # the chain is peeled/static-heavy: the walk decides it on host
        # or seeds the bitmap — either way it must agree with native
        if native_pack.available():
            packed_n, host_n = pack_chunk(snap, sd, tg, multi, 0, 1, native=True)
            assert (host_ans == host_n).all()
            assert (packed is None) == (packed_n is None)
            if packed is not None:
                for a, b in zip(packed, packed_n):
                    assert (a == b).all()
    finally:
        engine.close()
