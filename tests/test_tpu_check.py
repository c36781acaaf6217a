"""TPU check engine: scenario parity + randomized differential testing.

Every reference engine scenario (tests/test_check_engine.py, from reference
internal/check/engine_test.go) must produce identical decisions from the
recursive oracle and the device BFS kernel; fuzzed random graphs then sweep
the long tail (cycles, multi-namespace edges, empty relations, unknown
nodes). This is the "same cases × every engine" analog of the reference's
same-cases-×-every-client e2e pattern (internal/e2e/full_suit_test.go:40-78).
"""

import random

import pytest

from keto_tpu.check import CheckEngine
from keto_tpu.check.tpu_engine import TpuCheckEngine
from keto_tpu.relationtuple import RelationTuple, SubjectID, SubjectSet


def T(ns, obj, rel, sub):
    return RelationTuple(namespace=ns, object=obj, relation=rel, subject=sub)


def both_engines(p):
    return CheckEngine(p), TpuCheckEngine(p, p.namespaces)


def assert_same(p, requested, expected=None):
    oracle, tpu = both_engines(p)
    o = oracle.subject_is_allowed(requested)
    t = tpu.subject_is_allowed(requested)
    assert o == t, f"oracle={o} tpu={t} for {requested}"
    if expected is not None:
        assert o == expected
    return o


# -- reference scenarios through the device engine ---------------------------


def test_direct_inclusion(make_persister):
    p = make_persister([("test", 1)])
    rel = T("test", "object", "access", SubjectID("user"))
    p.write_relation_tuples(rel)
    assert_same(p, rel, True)


def test_indirect_inclusion_level_2(make_persister):
    sn, on = "some namespace", "all organizations"
    p = make_persister([(sn, 1), (on, 2)])
    user = SubjectID("some user")
    p.write_relation_tuples(
        T(sn, "some object", "write", SubjectSet(sn, "some object", "owner")),
        T(sn, "some object", "owner", SubjectSet(on, "some organization", "member")),
        T(on, "some organization", "member", user),
    )
    assert_same(p, T(sn, "some object", "write", user), True)
    assert_same(p, T(on, "some organization", "member", user), True)
    assert_same(p, T(sn, "some object", "owner", user), True)
    assert_same(p, T(sn, "some object", "write", SubjectID("other")), False)


def test_rejects_transitive_relation(make_persister):
    # empty relation is a real edge but grants nothing transitively
    # (reference engine_test.go:257-295)
    p = make_persister([("", 2)])
    p.write_relation_tuples(
        T("", "file", "parent", SubjectSet("", "directory", "")),
        T("", "directory", "access", SubjectID("user")),
    )
    assert_same(p, T("", "file", "access", SubjectID("user")), False)
    assert_same(p, T("", "file", "parent", SubjectSet("", "directory", "")), True)


def test_circular_tuples_terminate(make_persister):
    p = make_persister([("m", 0)])
    stations = ["a", "b", "c"]
    for x, y in zip(stations, stations[1:] + stations[:1]):
        p.write_relation_tuples(T("m", x, "connected", SubjectSet("m", y, "connected")))
    assert_same(p, T("m", "a", "connected", SubjectID("c")), False)
    # the cycle makes every station's set reachable from every other
    assert_same(p, T("m", "a", "connected", SubjectSet("m", "c", "connected")), True)
    assert_same(p, T("m", "a", "connected", SubjectSet("m", "a", "connected")), True)


def test_unknown_namespace_is_denied(make_persister):
    p = make_persister([("known", 1)])
    p.write_relation_tuples(T("known", "o", "r", SubjectID("u")))
    assert_same(p, T("unknown", "o", "r", SubjectID("u")), False)
    assert_same(p, T("known", "o", "r", SubjectSet("unknown", "o", "r")), False)


def test_wide_graph(make_persister):
    p = make_persister([("n", 1)])
    users, orgs = ["u1", "u2", "u3", "u4"], ["o1", "o2"]
    for org in orgs:
        p.write_relation_tuples(T("n", "obj", "access", SubjectSet("n", org, "member")))
    for i, u in enumerate(users):
        p.write_relation_tuples(T("n", orgs[i % 2], "member", SubjectID(u)))
    for u in users:
        assert_same(p, T("n", "obj", "access", SubjectID(u)), True)
    assert_same(p, T("n", "obj", "access", SubjectID("u5")), False)


def test_requested_set_not_matched_without_tuple(make_persister):
    p = make_persister([("n", 1)])
    p.write_relation_tuples(T("n", "obj", "read", SubjectSet("n", "group", "member")))
    assert_same(p, T("n", "obj", "read", SubjectSet("n", "group", "member")), True)
    assert_same(p, T("n", "obj", "read", SubjectSet("n", "group", "other")), False)
    # the queried set itself never matches without an edge
    assert_same(p, T("n", "obj", "read", SubjectSet("n", "obj", "read")), False)


def test_snapshot_refreshes_after_writes(make_persister):
    p = make_persister([("n", 1)])
    p.write_relation_tuples(T("n", "obj", "access", SubjectID("u1")))
    tpu = TpuCheckEngine(p, p.namespaces)
    assert tpu.subject_is_allowed(T("n", "obj", "access", SubjectID("u1")))
    snap1 = tpu.snapshot()

    p.write_relation_tuples(T("n", "obj", "access", SubjectID("u2")))
    assert tpu.subject_is_allowed(T("n", "obj", "access", SubjectID("u2")))
    assert tpu.snapshot().snapshot_id != snap1.snapshot_id

    p.delete_relation_tuples(T("n", "obj", "access", SubjectID("u1")))
    assert not tpu.subject_is_allowed(T("n", "obj", "access", SubjectID("u1")))
    assert tpu.subject_is_allowed(T("n", "obj", "access", SubjectID("u2")))


def test_empty_store(make_persister):
    p = make_persister([("n", 1)])
    _, tpu = both_engines(p)
    assert tpu.batch_check([T("n", "o", "r", SubjectID("u"))]) == [False]
    assert tpu.batch_check([]) == []


def test_batch_mixed_queries(make_persister):
    p = make_persister([("n", 1), ("m", 2)])
    p.write_relation_tuples(
        T("n", "doc", "view", SubjectSet("n", "doc", "own")),
        T("n", "doc", "own", SubjectID("alice")),
        T("m", "repo", "push", SubjectSet("n", "doc", "own")),
    )
    oracle, tpu = both_engines(p)
    queries = [
        T("n", "doc", "view", SubjectID("alice")),
        T("n", "doc", "view", SubjectID("bob")),
        T("m", "repo", "push", SubjectID("alice")),
        T("bogus", "doc", "view", SubjectID("alice")),
        T("n", "doc", "own", SubjectSet("n", "doc", "own")),
    ]
    got = tpu.batch_check(queries)
    want = [oracle.subject_is_allowed(q) for q in queries]
    assert got == want == [True, False, True, False, False]


# -- fuzzing -----------------------------------------------------------------


def test_wildcard_expansion(make_persister):
    # empty fields wildcard the expansion (reference
    # relationtuples.go:218-235) but matching stays literal
    p = make_persister([("n", 1), ("", 2)])
    p.write_relation_tuples(
        T("n", "folder", "access", SubjectID("adam")),
        T("n", "folder", "edit", SubjectID("eve")),
        T("n", "file", "parent", SubjectSet("n", "folder", "")),
        T("", "x", "r", SubjectID("zed")),
    )
    # subject set with empty relation expands every relation on the object
    assert_same(p, T("n", "file", "parent", SubjectID("adam")), True)
    assert_same(p, T("n", "file", "parent", SubjectID("eve")), True)
    # requested relation "" wildcards the start expansion
    assert_same(p, T("n", "folder", "", SubjectID("adam")), True)
    # requested object "" wildcards objects
    assert_same(p, T("n", "", "edit", SubjectID("eve")), True)
    assert_same(p, T("n", "", "edit", SubjectID("adam")), False)
    # requested namespace "" wildcards namespaces (configured or not)
    assert_same(p, T("", "x", "r", SubjectID("zed")), True)
    assert_same(p, T("", "", "", SubjectID("zed")), True)
    assert_same(p, T("", "", "", SubjectID("nobody")), False)


@pytest.mark.parametrize("seed", range(12))
def test_fuzz_differential(make_persister, seed):
    rng = random.Random(seed)
    namespaces = [("ns0", 0), ("ns1", 1), ("ns2", 7), ("", 3)]
    p = make_persister(namespaces)
    ns_names = [n for n, _ in namespaces]
    objects = [f"o{i}" for i in range(6)]
    relations = ["r0", "r1", ""]
    users = [f"u{i}" for i in range(5)]

    def rand_set():
        return SubjectSet(rng.choice(ns_names), rng.choice(objects), rng.choice(relations))

    tuples = []
    for _ in range(rng.randrange(5, 60)):
        sub = SubjectID(rng.choice(users)) if rng.random() < 0.4 else rand_set()
        tuples.append(T(rng.choice(ns_names), rng.choice(objects), rng.choice(relations), sub))
    p.write_relation_tuples(*tuples)

    oracle, tpu = both_engines(p)
    queries = []
    for _ in range(64):
        sub = SubjectID(rng.choice(users + ["ghost"])) if rng.random() < 0.5 else rand_set()
        ns = rng.choice(ns_names + ["nope"])
        queries.append(T(ns, rng.choice(objects), rng.choice(relations), sub))

    got = tpu.batch_check(queries)
    for q, g in zip(queries, got):
        w = oracle.subject_is_allowed(q)
        assert g == w, f"divergence on {q}: tpu={g} oracle={w} (seed={seed})"


@pytest.mark.parametrize("seed", range(4))
def test_bulk_resolve_native_parity(make_persister, seed):
    # the C++ bulk resolver and the Python host loop must agree entry for
    # entry, including wildcard patterns, unknown namespaces, and subject
    # sets routed through the special path
    import numpy as np

    rng = random.Random(seed)
    p = make_persister([("ns0", 0), ("ns1", 1), ("", 3)])
    ns_names = ["ns0", "ns1", ""]
    objects = [f"o{i}" for i in range(6)]
    relations = ["r0", "r1", ""]
    users = [f"u{i}" for i in range(5)]

    def rand_set():
        return SubjectSet(rng.choice(ns_names), rng.choice(objects), rng.choice(relations))

    tuples = []
    for _ in range(rng.randrange(10, 80)):
        sub = SubjectID(rng.choice(users)) if rng.random() < 0.4 else rand_set()
        tuples.append(T(rng.choice(ns_names), rng.choice(objects), rng.choice(relations), sub))
    p.write_relation_tuples(*tuples)

    tpu = TpuCheckEngine(p, p.namespaces)
    snap = tpu.snapshot()
    if not hasattr(snap.interned, "resolve_queries"):
        pytest.skip("native library not built")

    queries = []
    for _ in range(128):
        sub = SubjectID(rng.choice(users + ["ghost"])) if rng.random() < 0.5 else rand_set()
        queries.append(
            T(rng.choice(ns_names + ["nope"]), rng.choice(objects), rng.choice(relations), sub)
        )
    assert tpu.dispatch._raw_of_tuples(snap, queries) is not None  # the native resolver takes them
    got_n = tpu.dispatch._resolve_bulk(snap, queries)
    sd_n, tg_n, multi_n = got_n
    sd_p, tg_p, multi_p = tpu.dispatch._resolve_bulk_py(snap, queries)
    assert np.array_equal(sd_n, sd_p)
    assert np.array_equal(tg_n, tg_p)
    assert multi_n.keys() == multi_p.keys()
    for i in multi_n:
        assert np.array_equal(multi_n[i][0], multi_p[i][0])
        assert np.array_equal(multi_n[i][1], multi_p[i][1])


def test_bulk_resolve_wild_subject_namespace_parity(make_persister):
    # regression (tier-1 bulk-resolve parity failure): a LITERAL start
    # with an empty-namespace subject set routed to the pattern path in
    # the native resolver but resolved literally in the Python host loop
    # (_subject_target), so sd diverged (-2 multi vs the start row).
    # Subjects match literally — an empty subject namespace can only
    # equal a stored subject in a namespace named "" — and both
    # resolvers must agree entry for entry.
    import numpy as np

    p = make_persister([("ns0", 0), ("", 3)])
    p.write_relation_tuples(
        T("ns0", "o0", "r1", SubjectSet("", "o5", "r0")),
        T("", "o5", "r0", SubjectID("u1")),
    )
    tpu = TpuCheckEngine(p, p.namespaces)
    snap = tpu.snapshot()
    queries = [
        T("ns0", "o0", "r1", SubjectSet("", "o5", "r0")),  # divergent shape
        T("ns0", "o0", "r1", SubjectID("u1")),
    ]
    sd_p, tg_p, multi_p = tpu.dispatch._resolve_bulk_py(snap, queries)
    # the pure-Python contract: literal start resolves to a single row
    # (never the -2 multi sentinel) with a reachable target
    assert sd_p[0] >= 0 and tg_p[0] >= 0 and 0 not in multi_p
    if hasattr(snap.interned, "resolve_queries"):
        assert tpu.dispatch._raw_of_tuples(snap, queries) is not None
        got = tpu.dispatch._resolve_bulk(snap, queries)
        sd_n, tg_n, multi_n = got
        assert np.array_equal(sd_n, sd_p)
        assert np.array_equal(tg_n, tg_p)
        assert multi_n.keys() == multi_p.keys()
    # decisions through the full engine stay correct either way
    assert tpu.subject_is_allowed(queries[0]) is True
    assert tpu.subject_is_allowed(queries[1]) is True


def test_bulk_resolve_wild_subject_no_empty_namespace(make_persister):
    # the other half of the contract: with NO namespace named "", an
    # empty-namespace subject set can never match — the start still
    # resolves, the target is unreachable, decision is deny, and the
    # native path agrees with the host loop entry for entry
    import numpy as np

    p = make_persister([("ns0", 0)])
    p.write_relation_tuples(T("ns0", "o0", "r1", SubjectID("u1")))
    tpu = TpuCheckEngine(p, p.namespaces)
    snap = tpu.snapshot()
    queries = [T("ns0", "o0", "r1", SubjectSet("", "o5", "r0"))]
    sd_p, tg_p, _ = tpu.dispatch._resolve_bulk_py(snap, queries)
    assert sd_p[0] >= 0 and tg_p[0] == -1
    if hasattr(snap.interned, "resolve_queries"):
        assert tpu.dispatch._raw_of_tuples(snap, queries) is not None
        got = tpu.dispatch._resolve_bulk(snap, queries)
        sd_n, tg_n, _ = got
        assert np.array_equal(sd_n, sd_p)
        assert np.array_equal(tg_n, tg_p)
    assert tpu.subject_is_allowed(queries[0]) is False


def test_deep_chain(make_persister):
    # depth beyond anything the fuzzer hits; exercises many BFS iterations
    p = make_persister([("n", 1)])
    depth = 64
    for i in range(depth):
        p.write_relation_tuples(T("n", f"o{i}", "r", SubjectSet("n", f"o{i+1}", "r")))
    p.write_relation_tuples(T("n", f"o{depth}", "r", SubjectID("u")))
    assert_same(p, T("n", "o0", "r", SubjectID("u")), True)
    assert_same(p, T("n", "o1", "r", SubjectID("zzz")), False)


def test_high_degree_node(make_persister):
    # >1024 in-edges on one node (1200 objects sharing one subject set)
    # crosses the kernel's degree-chunk boundary
    p = make_persister([("n", 1)])
    fans = [T("n", f"o{i}", "r", SubjectSet("n", "hub", "member")) for i in range(1200)]
    members = [T("n", "hub", "member", SubjectID(f"u{i}")) for i in range(40)]
    p.write_relation_tuples(*(fans + members))
    assert_same(p, T("n", "o700", "r", SubjectID("u13")), True)
    assert_same(p, T("n", "o700", "r", SubjectID("nope")), False)
    assert_same(p, T("n", "o700", "r", SubjectSet("n", "hub", "member")), True)


@pytest.mark.parametrize("depth", [1, 3])
def test_stream_matches_batch(make_persister, depth):
    # the streaming API must produce bit-identical decisions to batch_check
    # across slice boundaries; max_batch=32 forces many slices
    import numpy as np

    rng = random.Random(99)
    p = make_persister([("ns0", 0), ("ns1", 1)])
    objects = [f"o{i}" for i in range(8)]
    users = [f"u{i}" for i in range(6)]
    tuples = []
    for _ in range(120):
        sub = (
            SubjectID(rng.choice(users))
            if rng.random() < 0.5
            else SubjectSet(rng.choice(["ns0", "ns1"]), rng.choice(objects), "r")
        )
        tuples.append(T(rng.choice(["ns0", "ns1"]), rng.choice(objects), "r", sub))
    p.write_relation_tuples(*tuples)

    queries = []
    for _ in range(200):
        sub = (
            SubjectID(rng.choice(users + ["ghost"]))
            if rng.random() < 0.6
            else SubjectSet("ns0", rng.choice(objects), "r")
        )
        queries.append(T(rng.choice(["ns0", "ns1", "nope"]), rng.choice(objects), "r", sub))

    tpu = TpuCheckEngine(p, p.namespaces, max_batch=32)
    want = tpu.batch_check(queries)
    slices = list(tpu.batch_check_stream(iter(queries), depth=depth))
    assert len(slices) > 1  # actually exercised slice boundaries
    got = np.concatenate(slices).tolist()
    assert got == want


# -- exactness under it_cap ---------------------------------------------------


def _deep_chain_store(make_persister, depth=24):
    """doc#view → c0 → c1 → … → c{depth-1} → user, closed into a CYCLE
    (c{depth-1} → c0): cycle members have interior in-edges from never-
    peelable nodes, so the whole chain stays in the iterated device kernel
    (a plain chain would peel into host propagation and never truncate)."""
    p = make_persister([("g", 1), ("d", 2)])
    rows = [T("d", "doc", "view", SubjectSet("g", "c0", "m"))]
    for i in range(depth - 1):
        rows.append(T("g", f"c{i}", "m", SubjectSet("g", f"c{i+1}", "m")))
    rows.append(T("g", f"c{depth-1}", "m", SubjectSet("g", "c0", "m")))
    rows.append(T("g", f"c{depth-1}", "m", SubjectID("user")))
    p.write_relation_tuples(*rows)
    return p


def test_it_cap_truncation_rerun_exact(make_persister):
    """it_cap=1 on a deep chain: the first kernel truncates, but NO decision
    may come from the truncated frontier — the engine re-runs with an
    escalating cap and must match the oracle on grants AND denies
    (the reference is always exact via its visited set)."""
    p = _deep_chain_store(make_persister)
    oracle = CheckEngine(p)
    # labels off: the 2-hop fast path answers deep chains in one step,
    # and this test exists to exercise the BFS truncation retry ladder
    engine = TpuCheckEngine(p, p.namespaces, it_cap=1, labels_enabled=False)
    rungs = []
    orig = engine.dispatch._run_exact
    engine.dispatch._run_exact = lambda s, t, it_cap=None: (
        rungs.append(it_cap), orig(s, t, it_cap=it_cap)
    )[1]
    queries = [
        T("d", "doc", "view", SubjectID("user")),   # deep grant
        T("d", "doc", "view", SubjectID("ghost")),  # deep deny
        T("g", "c0", "m", SubjectID("user")),       # grant, one shorter
        T("g", "c5", "m", SubjectID("ghost")),      # deny mid-chain
    ]
    got = engine.batch_check(queries)
    want = [oracle.subject_is_allowed(q) for q in queries]
    assert got == want == [True, False, True, False]
    assert len(rungs) >= 2, "truncation retry ladder never engaged"


def test_it_cap_truncation_rerun_exact_stream(make_persister):
    p = _deep_chain_store(make_persister)
    oracle = CheckEngine(p)
    engine = TpuCheckEngine(p, p.namespaces, it_cap=1, labels_enabled=False)
    queries = [
        T("d", "doc", "view", SubjectID("user")),
        T("d", "doc", "view", SubjectID("ghost")),
    ] * 5
    import numpy as np

    got = np.concatenate(list(engine.batch_check_stream(iter(queries)))).tolist()
    want = [oracle.subject_is_allowed(q) for q in queries]
    assert got == want


def test_bulk_wildcard_batch_resolves_indexed(make_persister):
    """A wildcard-heavy batch must resolve through the snapshot's sorted
    pattern indexes (binary searches), matching the oracle on every
    pattern family — the old path re-scanned all set keys per pattern."""
    import random as _random

    import numpy as np

    rng = _random.Random(77)
    p = make_persister([("g", 1), ("d", 2), ("", 3)])
    objs = [f"o{i}" for i in range(40)]
    rels = ["r0", "r1", "r2"]
    rows = []
    for i in range(3000):
        sub = (
            SubjectID(f"u{i % 50}")
            if rng.random() < 0.6
            else SubjectSet("g", rng.choice(objs), rng.choice(rels))
        )
        rows.append(T(rng.choice(["g", "d"]), rng.choice(objs), rng.choice(rels), sub))
    p.write_relation_tuples(*rows)
    oracle, engine = both_engines(p)
    snap = engine.snapshot()

    # every pattern family hits its index; parity vs the direct key scan
    interned = snap.interned
    kn = np.asarray(interned.key_ns)
    ko = np.asarray(interned.key_obj)
    kr = np.asarray(interned.key_rel)
    for ns_id, obj, rel in [
        (1, "o1", ""), (1, "", "r0"), (1, "", ""),
        (-1, "o2", "r1"), (-1, "o3", ""), (-1, "", "r2"), (-1, "", ""),
        (1, "absent-obj", ""), (-1, "", "absent-rel"),
    ]:
        got = np.sort(engine.snapshot().resolve_starts(ns_id, obj, rel))
        m = np.ones(kn.shape[0], bool)
        if ns_id != -1:
            m &= kn == ns_id
        if obj != "":
            c = interned.obj_code(obj)
            m = (m & (ko == c)) if c >= 0 else np.zeros_like(m)
        if rel != "":
            c = interned.rel_code(rel)
            m = (m & (kr == c)) if c >= 0 else np.zeros_like(m)
        want = np.sort(snap.raw2dev[np.nonzero(m)[0]])
        assert got.tolist() == want.tolist(), (ns_id, obj, rel)

    # a wildcard-heavy check batch end-to-end vs oracle
    queries = []
    for _ in range(300):
        pattern = rng.randrange(4)
        o = rng.choice(objs) if pattern in (0, 2) else ""
        r = rng.choice(rels) if pattern in (0, 1) else ""
        ns = rng.choice(["g", "d", ""])
        queries.append(T(ns, o, r, SubjectID(f"u{rng.randrange(60)}")))
    got = engine.batch_check(queries)
    for q, g in zip(queries, got):
        w = oracle.subject_is_allowed(q)
        assert g == w, f"{q}: tpu={g} oracle={w}"


# -- latency-adaptive ready-order streaming pipeline --------------------------


def _skewed_stream_store(make_persister):
    rng = random.Random(123)
    p = make_persister([("ns0", 0), ("ns1", 1)])
    objects = [f"o{i}" for i in range(10)]
    users = [f"u{i}" for i in range(8)]
    rows = []
    for _ in range(150):
        sub = (
            SubjectID(rng.choice(users))
            if rng.random() < 0.5
            else SubjectSet(rng.choice(["ns0", "ns1"]), rng.choice(objects), "r")
        )
        rows.append(T(rng.choice(["ns0", "ns1"]), rng.choice(objects), "r", sub))
    p.write_relation_tuples(*rows)
    queries = []
    for _ in range(300):
        sub = (
            SubjectID(rng.choice(users + ["ghost"]))
            if rng.random() < 0.6
            else SubjectSet("ns0", rng.choice(objects), "r")
        )
        queries.append(T(rng.choice(["ns0", "ns1", "nope"]), rng.choice(objects), "r", sub))
    return p, queries


@pytest.mark.parametrize("pattern", ["never", "random", "always"])
def test_stream_ready_order_preserves_order_under_skew(make_persister, pattern):
    """Ready-order landing with artificially skewed slice readiness: some
    slices are declared "finished" early (unpacked out of order into the
    delivery buffer), others never poll ready and land via the blocking
    path — the ordered yield contract must hold regardless."""
    import numpy as np

    p, queries = _skewed_stream_store(make_persister)
    engine = TpuCheckEngine(p, p.namespaces, max_batch=32)
    want = engine.batch_check(queries)

    rng = random.Random(5)
    ready = {"never": lambda dev: False, "always": lambda dev: True,
             "random": lambda dev: rng.random() < 0.5}[pattern]
    engine.dispatch._slice_ready = ready  # instance seam shadows the staticmethod
    slices = list(engine.batch_check_stream(iter(queries), depth=3))
    assert len(slices) > 3
    assert np.concatenate(slices).tolist() == want


def test_stream_unordered_reassociates_by_offset(make_persister):
    """ordered=False yields (offset, decisions) the moment a slice lands;
    re-assembling by offset must reproduce the ordered decisions exactly
    (the CheckBatcher fast path)."""
    import numpy as np

    p, queries = _skewed_stream_store(make_persister)
    engine = TpuCheckEngine(p, p.namespaces, max_batch=32)
    want = engine.batch_check(queries)
    rng = random.Random(9)
    engine.dispatch._slice_ready = lambda dev: rng.random() < 0.5
    got = np.zeros(len(queries), dtype=bool)
    seen = 0
    for off, out in engine.batch_check_stream(iter(queries), depth=3, ordered=False):
        got[off : off + out.shape[0]] = out
        seen += out.shape[0]
    assert seen == len(queries)
    assert got.tolist() == want


def test_stream_with_token_matches_snapshot(make_persister):
    p, queries = _skewed_stream_store(make_persister)
    engine = TpuCheckEngine(p, p.namespaces, max_batch=32)
    gen, token = engine.batch_check_stream_with_token(iter(queries))
    import numpy as np

    got = np.concatenate(list(gen)).tolist()
    assert token == engine.snapshot().snapshot_id
    assert got == engine.batch_check(queries)


def test_stream_adaptive_controller_converges():
    """The width controller narrows under slow slices (multiplicatively,
    to the rung its per-query cost predicts) and re-widens rung by rung
    once full-width slices show headroom again."""
    from keto_tpu.check.slice_ctrl import StreamSliceController

    ctrl = StreamSliceController(target_ms=40.0, floor=32, patience=1)
    top = 32 * 4096
    start = ctrl.cap()
    assert 32 <= start <= top

    # slow transfers: one overshoot jumps straight to a fitting width
    ctrl.observe(start, 400.0)  # 400 ms for `start` queries
    narrowed = ctrl.cap()
    assert narrowed < start
    assert narrowed * (400.0 / start) <= 40.0 or narrowed == 32
    # keep overshooting → collapses to the floor, never below
    for _ in range(6):
        ctrl.observe(ctrl.cap(), 400.0)
    assert ctrl.cap() == 32

    # headroom returns: re-widens one rung per good full-width slice
    caps = []
    for _ in range(16):
        ctrl.observe(ctrl.cap(), 1.0)
        caps.append(ctrl.cap())
    assert caps[-1] == top
    assert caps == sorted(caps)  # monotone climb, no oscillation

    # partial (non-full-width) fast slices must NOT widen
    ctrl2 = StreamSliceController(target_ms=40.0, floor=32, patience=1)
    ctrl2.observe(ctrl2.cap(), 400.0)
    low = ctrl2.cap()
    ctrl2.observe(low // 2, 1.0)
    assert ctrl2.cap() == low


def test_stream_slice_stats_recorded(make_persister):
    p, queries = _skewed_stream_store(make_persister)
    engine = TpuCheckEngine(p, p.namespaces, max_batch=32)
    engine.stream_slice_stats.reset()
    list(engine.batch_check_stream(iter(queries)))
    snap_stats = engine.stream_slice_stats.snapshot()
    assert snap_stats["count"] >= len(queries) // 32
    assert snap_stats["p50_ms"] >= 0.0


def test_check_batcher_streams_tpu_engine(make_persister):
    """CheckBatcher routes coalesced batches through the unordered stream
    fast path against the TPU engine: every caller's future resolves with
    the correct decision + snaptoken."""
    from concurrent.futures import ThreadPoolExecutor

    from keto_tpu.driver.batch import CheckBatcher

    p, queries = _skewed_stream_store(make_persister)
    engine = TpuCheckEngine(p, p.namespaces, max_batch=32)
    want = engine.batch_check(queries)
    b = CheckBatcher(engine, batch_size=64, window_ms=5.0)
    b.start()
    try:
        with ThreadPoolExecutor(max_workers=16) as pool:
            got = list(pool.map(lambda q: b.check(q, timeout=30.0), queries))
    finally:
        b.stop()
    assert got == want


# -- bulk pattern resolution --------------------------------------------------


def test_bulk_allwildcard_10k_batch(make_persister):
    """10k all-wildcard queries (every field empty) resolve through ONE
    bulk pass — and an all-wildcard check grants exactly the users that
    are the subject of at least one tuple ("reached via >= 1 edge" from
    the universal start set)."""
    import numpy as np

    rng = random.Random(31)
    p = make_persister([("g", 1), ("d", 2)])
    n_users = 400
    rows = []
    for i in range(2000):
        if rng.random() < 0.7:
            rows.append(T("g", f"o{rng.randrange(60)}", "r", SubjectID(f"u{rng.randrange(n_users)}")))
        else:
            rows.append(
                T(rng.choice(["g", "d"]), f"o{rng.randrange(60)}", "r",
                  SubjectSet("g", f"o{rng.randrange(60)}", "r"))
            )
    p.write_relation_tuples(*rows)
    subjects = {r.subject.id for r in rows if isinstance(r.subject, SubjectID)}

    engine = TpuCheckEngine(p, p.namespaces)
    queries, expected = [], []
    for i in range(10_000):
        u = f"u{rng.randrange(2 * n_users)}"  # half the id space never granted
        queries.append(T("", "", "", SubjectID(u)))
        expected.append(u in subjects)
    got = engine.batch_check(queries)
    assert got == expected
    # spot-check parity vs the oracle on a sample
    oracle = CheckEngine(p)
    sample = rng.sample(range(10_000), 40)
    assert [got[i] for i in sample] == [
        oracle.subject_is_allowed(queries[i]) for i in sample
    ]


def test_resolve_starts_bulk_matches_scalar(make_persister):
    """resolve_starts_bulk == resolve_starts for every pattern family,
    probed on a FRESH snapshot each way so the bulk path cannot ride the
    scalar path's cache."""
    rng = random.Random(44)
    p = make_persister([("g", 1), ("d", 2), ("", 3)])
    rows = []
    for i in range(1500):
        sub = (
            SubjectID(f"u{i % 40}")
            if rng.random() < 0.6
            else SubjectSet("g", f"o{rng.randrange(30)}", rng.choice(["r0", "r1"]))
        )
        rows.append(
            T(rng.choice(["g", "d"]), f"o{rng.randrange(30)}", rng.choice(["r0", "r1"]), sub)
        )
    p.write_relation_tuples(*rows)
    engine = TpuCheckEngine(p, p.namespaces)
    pats = [
        (1, "o1", ""), (1, "", "r0"), (1, "", ""), (2, "o2", "r1"),
        (-1, "o2", "r1"), (-1, "o3", ""), (-1, "", "r1"), (-1, "", ""),
        (1, "absent", ""), (-1, "", "absent"), (1, "o1", ""),  # dup on purpose
    ]
    bulk = engine.snapshot().resolve_starts_bulk(pats)
    fresh = TpuCheckEngine(p, p.namespaces).snapshot()
    for pat, got in zip(pats, bulk):
        want = fresh.resolve_starts(*pat)
        assert got.tolist() == want.tolist(), pat
