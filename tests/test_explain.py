"""Decision provenance: witness paths, the explain engine, the durable
decision log, and the serving surface (keto_tpu/explain/).

The contract under test:

- **Witness soundness**: every grant's witness path verifies edge-by-edge
  against the Manager (each edge exists; each intermediate subject is the
  subject-set the next edge expands; the terminal subject is the
  requested subject). Forged/stale witnesses are rejected.
- **Decision parity**: `ExplainEngine.explain` agrees with the CPU
  reference oracle on every decision, across every serving route —
  label / hybrid / bfs (TPU engine), sharded mesh, host, cpu — including
  overlay churn, tombstones, wildcards, and stacked compactions.
- **Deny certificates**: a denied check carries a frontier-exhaustion
  certificate (the closure sizes the BFS exhausted without reaching the
  subject) — checkable against the brute-force closure.
- **Durable decision log**: fsync-then-rename segment rotation (sealed
  segments are never torn), bounded retention, per-tenant scoping, and a
  reader that tolerates torn/corrupt lines.
- **Shadow-audit witness diff**: an injected `audit-flip` fault forces a
  device/oracle divergence and the auditor captures BOTH witnesses for
  the flight recorder.
- **Serving wiring**: REST `GET /check/explain` (200/400/404/412,
  tenant routing, snaptoken echo), hot-path sampling into the decision
  log, and the explain-disabled zero-work guarantee.
"""

import json
import random
import time

import pytest

from keto_tpu import namespace as namespace_pkg
from keto_tpu.check.engine import CheckEngine
from keto_tpu.check.tpu_engine import TpuCheckEngine
from keto_tpu.explain import (
    DecisionLog,
    ExplainEngine,
    build_witness,
    oracle_witness,
    verify_witness,
)
from keto_tpu.persistence.memory import MemoryPersister
from keto_tpu.relationtuple.model import RelationTuple, SubjectID, SubjectSet
from keto_tpu.x import faults


def T(ns, obj, rel, sub):
    return RelationTuple(namespace=ns, object=obj, relation=rel, subject=sub)


NSS = [namespace_pkg.Namespace(id=1, name="g"), namespace_pkg.Namespace(id=2, name="d")]


def make_store(tuples=()):
    p = MemoryPersister(namespace_pkg.MemoryManager(NSS))
    if tuples:
        p.write_relation_tuples(*tuples)
    return p


def quiet_engine(p, **kw):
    kw.setdefault("compact_after_s", 3600.0)
    kw.setdefault("overlay_edge_budget", 1 << 20)
    return TpuCheckEngine(p, p.namespaces, **kw)


def wait_for(cond, timeout=10.0, interval=0.02, msg="condition"):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if cond():
            return
        time.sleep(interval)
    raise AssertionError(f"timed out waiting for {msg}")


def fuzz_store(seed, n_tuples=120):
    """A random subject-set graph plus a query mix that exercises hits,
    misses, unknown namespaces, and subject-set subjects."""
    rng = random.Random(seed)
    objects = [f"o{i}" for i in range(10)]
    relations = ["r0", "r1"]
    users = [f"u{i}" for i in range(6)]

    def rand_set():
        return SubjectSet("g", rng.choice(objects), rng.choice(relations))

    tuples = []
    for _ in range(n_tuples):
        sub = SubjectID(rng.choice(users)) if rng.random() < 0.5 else rand_set()
        tuples.append(T(rng.choice(["g", "d"]), rng.choice(objects), rng.choice(relations), sub))
    p = make_store(tuples)
    queries = []
    for _ in range(60):
        sub = SubjectID(rng.choice(users + ["ghost"])) if rng.random() < 0.5 else rand_set()
        queries.append(T(rng.choice(["g", "d"]), rng.choice(objects), rng.choice(relations), sub))
    return p, queries


def deep_store(depth=8, users=("alice", "bob")):
    """doc → c0 → … → c{depth-1} → users with a back-edge so the chain
    stays active-interior — the 2-hop label fast path's target shape."""
    rows = [T("d", "doc", "view", SubjectSet("g", "c0", "m"))]
    for i in range(depth - 1):
        rows.append(T("g", f"c{i}", "m", SubjectSet("g", f"c{i + 1}", "m")))
    rows.append(T("g", f"c{depth - 1}", "m", SubjectSet("g", "c0", "m")))
    for u in users:
        rows.append(T("g", f"c{depth - 1}", "m", SubjectID(u)))
    return make_store(rows)


def assert_explained(ex, oracle, queries, *, routes_seen=None):
    """Every query: explain decision == oracle decision; grants carry a
    verified witness, denies a certificate; no divergence flags."""
    for q in queries:
        want = oracle.subject_is_allowed(q)
        got = ex.explain(q)
        assert got["allowed"] == want, f"decision drift on {q}: {got}"
        assert "decision_divergence" not in got, f"divergence flagged on {q}: {got}"
        if routes_seen is not None:
            routes_seen.add(got["route"])
        if want:
            assert got["verified"], f"unverified grant witness on {q}: {got}"
            assert got["witness"], got
            path = [RelationTuple.from_json(w) for w in got["witness"]]
            ok, reason = verify_witness(ex._manager, q, path)
            assert ok, f"re-verification failed on {q}: {reason}"
        else:
            assert got["witness"] is None
            assert got["certificate"] is not None
            assert got["certificate"]["type"] == "frontier-exhaustion"


# -- witness core --------------------------------------------------------------


def test_witness_grant_path_verifies():
    p = make_store([
        T("d", "doc", "view", SubjectSet("g", "eng", "m")),
        T("g", "eng", "m", SubjectSet("g", "core", "m")),
        T("g", "core", "m", SubjectID("alice")),
    ])
    rt = T("d", "doc", "view", SubjectID("alice"))
    found, path, cert = build_witness(p, rt)
    assert found and cert is None
    assert [str(t) for t in path] == [
        "d:doc#view@g:eng#m",
        "g:eng#m@g:core#m",
        "g:core#m@alice",
    ]
    ok, reason = verify_witness(p, rt, path)
    assert ok, reason


def test_witness_deny_certificate_counts_the_closure():
    p = make_store([
        T("d", "doc", "view", SubjectSet("g", "eng", "m")),
        T("g", "eng", "m", SubjectID("alice")),
    ])
    found, path, cert = build_witness(p, T("d", "doc", "view", SubjectID("mallory")))
    assert not found and path is None
    assert cert["type"] == "frontier-exhaustion"
    # the closure is {doc#view, eng#m}: both expanded, neither grants
    assert cert["subject_sets_expanded"] == 2
    assert cert["edges_scanned"] == 2
    assert cert["hops"] >= 1 and not cert["truncated"]
    assert sum(cert["frontier_sizes"]) >= 1


def test_oracle_witness_matches_oracle_decision_fuzz():
    p, queries = fuzz_store(seed=7)
    oracle = CheckEngine(p)
    for q in queries:
        path = oracle_witness(p, q)
        assert (path is not None) == oracle.subject_is_allowed(q), q
        if path is not None:
            ok, reason = verify_witness(p, q, path)
            assert ok, reason


def test_verify_rejects_forged_witnesses():
    p = make_store([
        T("d", "doc", "view", SubjectSet("g", "eng", "m")),
        T("g", "eng", "m", SubjectID("alice")),
    ])
    rt = T("d", "doc", "view", SubjectID("alice"))
    _, path, _ = build_witness(p, rt)

    # an edge that is not in the store
    forged = [path[0], T("g", "eng", "m", SubjectID("mallory"))]
    ok, reason = verify_witness(p, T("d", "doc", "view", SubjectID("mallory")), forged)
    assert not ok and "store" in reason

    # a chain whose intermediate subject doesn't name the next head
    broken = [T("d", "doc", "view", SubjectSet("g", "other", "m")), path[1]]
    ok, _ = verify_witness(p, rt, broken)
    assert not ok

    # terminal subject differs from the requested subject
    ok, _ = verify_witness(p, T("d", "doc", "view", SubjectID("bob")), path)
    assert not ok

    ok, _ = verify_witness(p, rt, [])
    assert not ok


# -- decision parity across routes ---------------------------------------------


@pytest.mark.parametrize("seed", [3, 11])
def test_explain_parity_tpu_engine_fuzz(seed):
    p, queries = fuzz_store(seed)
    eng = quiet_engine(p)
    try:
        ex = ExplainEngine(eng, p)
        routes = set()
        assert_explained(ex, CheckEngine(p), queries, routes_seen=routes)
        # the TPU engine decided: every route label is a device/host one
        assert routes <= {"label", "hybrid", "bfs", "host", "cpu"}
        assert sum(ex.requests_by_route.values()) == len(queries)
        assert ex.verify_failures == 0
    finally:
        eng.close()


def test_explain_parity_labels_off_pure_bfs():
    p, queries = fuzz_store(seed=19)
    eng = quiet_engine(p, labels_enabled=False)
    try:
        ex = ExplainEngine(eng, p)
        routes = set()
        assert_explained(ex, CheckEngine(p), queries, routes_seen=routes)
        assert "label" not in routes and "hybrid" not in routes
    finally:
        eng.close()


def test_explain_parity_deep_chain_label_shape():
    p = deep_store(depth=8)
    eng = quiet_engine(p)
    try:
        ex = ExplainEngine(eng, p)
        queries = [
            T("d", "doc", "view", SubjectID("alice")),
            T("d", "doc", "view", SubjectID("bob")),
            T("d", "doc", "view", SubjectID("mallory")),
            T("g", "c0", "m", SubjectID("alice")),
            T("g", "c3", "m", SubjectSet("g", "c5", "m")),
        ]
        assert_explained(ex, CheckEngine(p), queries)
        # a deep-chain grant's witness threads the whole chain
        got = ex.explain(T("d", "doc", "view", SubjectID("alice")))
        assert got["allowed"] and len(got["witness"]) >= 3
    finally:
        eng.close()


def test_explain_parity_sharded_mesh():
    import jax

    if len(jax.devices()) < 8:
        pytest.skip("needs the 8-device CPU mesh")
    from keto_tpu.parallel import make_mesh

    p, queries = fuzz_store(seed=29)
    eng = TpuCheckEngine(p, p.namespaces, mesh=make_mesh(graph=2), sharded=True)
    try:
        ex = ExplainEngine(eng, p)
        assert_explained(ex, CheckEngine(p), queries[:30])
    finally:
        eng.close()


def test_explain_parity_overlay_churn_and_tombstones():
    p, _ = fuzz_store(seed=31, n_tuples=60)
    eng = quiet_engine(p)
    try:
        ex = ExplainEngine(eng, p)
        oracle = CheckEngine(p)
        # overlay insert: a fresh grant chain lands without a rebuild
        p.write_relation_tuples(
            T("d", "o9", "r0", SubjectSet("g", "o1", "r1")),
            T("g", "o1", "r1", SubjectID("newcomer")),
        )
        q = T("d", "o9", "r0", SubjectID("newcomer"))
        assert_explained(ex, oracle, [q])
        assert ex.explain(q)["allowed"]
        # tombstone: deleting the terminal edge flips the decision and
        # the deny carries a certificate over the post-delete closure
        p.delete_relation_tuples(T("g", "o1", "r1", SubjectID("newcomer")))
        assert_explained(ex, oracle, [q])
        assert not ex.explain(q)["allowed"]
    finally:
        eng.close()


def test_explain_parity_wildcards():
    # an empty relation is the reference's wildcard key: the tuple's
    # subject-set pattern matches every relation on that object
    p = make_store([
        T("d", "doc", "view", SubjectSet("g", "grp", "m")),
        T("g", "grp", "", SubjectID("seed")),
        T("g", "grp", "m", SubjectID("alice")),
        T("d", "sec", "view", SubjectID("alice")),
    ])
    eng = quiet_engine(p)
    try:
        ex = ExplainEngine(eng, p)
        oracle = CheckEngine(p)
        queries = [
            T("d", "doc", "view", SubjectID("alice")),
            T("d", "doc", "view", SubjectID("seed")),
            T("g", "grp", "m", SubjectID("seed")),
            T("d", "sec", "view", SubjectID("alice")),
            T("d", "sec", "view", SubjectID("anyone")),
        ]
        for q in queries:
            want = oracle.subject_is_allowed(q)
            got = ex.explain(q)
            assert got["allowed"] == want, (q, got)
            assert "decision_divergence" not in got
    finally:
        eng.close()


def test_explain_parity_across_stacked_compactions():
    p, queries = fuzz_store(seed=37, n_tuples=60)
    eng = TpuCheckEngine(
        p, p.namespaces, compact_after_s=0.05, overlay_edge_budget=1 << 20
    )
    try:
        ex = ExplainEngine(eng, p)
        oracle = CheckEngine(p)
        for round_i in range(3):
            p.write_relation_tuples(
                T("d", "o0", "r0", SubjectID(f"round{round_i}"))
            )
            wait_for(
                lambda: not eng.snapshot().has_overlay,
                msg=f"compaction round {round_i}",
            )
            assert_explained(ex, oracle, queries[:20])
    finally:
        eng.close()


# -- explain engine unit -------------------------------------------------------


def test_explain_cpu_route_threads_the_oracle_traversal():
    p = make_store([
        T("d", "doc", "view", SubjectSet("g", "eng", "m")),
        T("g", "eng", "m", SubjectID("alice")),
    ])
    ex = ExplainEngine(CheckEngine(p), p)
    got = ex.explain(T("d", "doc", "view", SubjectID("alice")))
    assert got["route"] == "cpu" and got["allowed"] and got["verified"]
    assert got["witness_source"] == "oracle"
    assert ex.requests_by_route == {"cpu": 1}


def test_explain_counts_divergence_when_decision_is_wrong():
    p = make_store([T("d", "doc", "view", SubjectID("alice"))])
    notes = []
    # a decide hook that lies: grants a check the closure denies
    ex = ExplainEngine(
        None,
        p,
        decide=lambda rt, at_least: (True, "label", 1),
        on_verify_failure=notes.append,
    )
    got = ex.explain(T("d", "doc", "view", SubjectID("mallory")))
    assert got["allowed"] is True  # the engine's (wrong) decision is reported
    assert got["decision_divergence"] is True
    assert not got["verified"] and got["witness"] is None
    assert ex.verify_failures == 1
    assert notes and "no witness path" in notes[0]["reason"]
    # ...and the inverse lie: denied while the closure grants
    ex2 = ExplainEngine(None, p, decide=lambda rt, at_least: (False, "label", 1))
    got = ex2.explain(T("d", "doc", "view", SubjectID("alice")))
    assert got["allowed"] is False and got["decision_divergence"] is True
    assert ex2.verify_failures == 1


def test_label_witness_info_names_the_landmark():
    p = deep_store(depth=6)
    eng = quiet_engine(p)
    try:
        eng.labels_settled()  # join the overlapped label build
        snap = eng.snapshot()
        if snap.labels is None:
            pytest.skip("label index not built at this shape")
        # interior → interior: exactly the decided label probe
        info = eng.label_witness_info(T("g", "c0", "m", SubjectSet("g", "c4", "m")))
        assert info is not None
        assert info["kind"] == "2-hop-label"
        assert isinstance(info["landmark_dev"], int)
        # the winning landmark names a real subject-set on the chain
        assert info["landmark"].startswith("g:c")
    finally:
        eng.close()


def test_explain_records_to_decision_log(tmp_path):
    p = make_store([T("d", "doc", "view", SubjectID("alice"))])
    dl = DecisionLog(str(tmp_path / "dlog"))
    ex = ExplainEngine(CheckEngine(p), p, decision_log=dl)
    ex.explain(T("d", "doc", "view", SubjectID("alice")), trace_id="t-1")
    ex.explain(T("d", "doc", "view", SubjectID("mallory")), tenant="acme")
    recs, corrupt = dl.read_all("default")
    assert corrupt == 0 and len(recs) == 1
    assert recs[0]["kind"] == "explain" and recs[0]["decision"] is True
    assert recs[0]["witness"] and recs[0]["trace_id"] == "t-1"
    acme, _ = dl.read_all("acme")
    assert len(acme) == 1 and acme[0]["decision"] is False
    assert acme[0]["certificate"]["type"] == "frontier-exhaustion"
    assert sorted(dl.tenants()) == ["acme", "default"]


# -- durable decision log ------------------------------------------------------


def test_decision_log_rotation_and_retention(tmp_path):
    dl = DecisionLog(str(tmp_path), segment_bytes=256, retention=3)
    for i in range(60):
        dl.record("default", {"kind": "check", "i": i})
    segs = dl.segments("default")
    sealed = [s for s in segs if "seg-" in s.name]
    assert sealed, "rotation never sealed a segment"
    assert len(sealed) <= 3, "retention did not prune"
    assert dl.rotations_total >= len(sealed)
    # the reader sees only what retention kept, newest records last
    recs, corrupt = dl.read_all("default")
    assert corrupt == 0
    assert [r["i"] for r in recs] == sorted(r["i"] for r in recs)
    assert recs[-1]["i"] == 59
    # every record carries the stamped envelope
    assert all("ts" in r and r["tenant"] == "default" for r in recs)


def test_decision_log_tolerates_torn_and_corrupt_lines(tmp_path):
    dl = DecisionLog(str(tmp_path), segment_bytes=1 << 20)
    for i in range(5):
        dl.record("default", {"kind": "check", "i": i})
    dl.close()
    active = [s for s in dl.segments("default") if s.name.endswith(".tmp")]
    assert active
    # a SIGKILL mid-append tears the last line; earlier garbage happens
    # only through corruption — both must be skipped, counted, non-fatal
    with open(active[0], "a") as f:
        f.write('{"kind": "check", "i": 99')  # torn tail
    with open(active[0], "r+") as f:
        lines = f.readlines()
        lines[1] = "NOT JSON AT ALL\n"
        f.seek(0)
        f.writelines(lines)
        f.truncate()
    recs, corrupt = dl.read_all("default")
    assert corrupt == 2
    assert [r["i"] for r in recs] == [0, 2, 3, 4]


def test_decision_log_sampling_bounds():
    dl0 = DecisionLog("/nonexistent-never-written", sample=0.0)
    assert not any(dl0.sampled() for _ in range(200))
    dl1 = DecisionLog("/nonexistent-never-written", sample=1.0)
    assert all(dl1.sampled() for _ in range(200))
    dl_half = DecisionLog("/nonexistent-never-written", sample=0.5, seed=42)
    hits = sum(dl_half.sampled() for _ in range(1000))
    assert 350 < hits < 650


# -- shadow-audit witness diff (audit-flip fault) ------------------------------


def test_audit_flip_fault_captures_both_witnesses():
    p = make_store([
        T("d", "doc", "view", SubjectSet("g", "eng", "m")),
        T("g", "eng", "m", SubjectID("alice")),
    ])
    eng = quiet_engine(p, audit_sample_rate=1.0)
    try:
        # stall the worker so the pass runs deterministically under the
        # armed fault (the flip corrupts the device's recorded decision)
        eng._audit_task.kick = lambda: None
        assert eng.batch_check([T("d", "doc", "view", SubjectID("alice"))]) == [True]
        assert len(eng._audit_pending) > 0
        with faults.injected("audit-flip"):
            eng._audit_pass()
        assert eng.health()["audit_mismatches"] >= 1
        d = eng.audit_divergences[-1]
        assert d["device_decision"] is False and d["oracle_decision"] is True
        # BOTH witnesses captured: what the device should have seen and
        # what the oracle traversed — the flight-recorder evidence
        assert d["device_witness"] == [
            "d:doc#view@g:eng#m",
            "g:eng#m@alice",
        ]
        assert d["oracle_witness"] == d["device_witness"]
        assert d["snaptoken"] >= 1
    finally:
        eng.close()


def test_audit_divergence_rides_into_flightrec_bundle(tmp_path):
    from keto_tpu.config.provider import Config
    from keto_tpu.driver.registry import Registry

    cfg = Config(overrides={
        "namespaces": [{"id": 1, "name": "g"}, {"id": 2, "name": "d"}],
        "serve": {"debug_bundle_dir": str(tmp_path)},
    })
    reg = Registry(cfg)
    try:
        eng = reg.permission_engine()
        eng.audit_divergences.append({"tuple": "d:doc#view@alice", "device_decision": False,
                                      "oracle_decision": True, "snaptoken": 1,
                                      "device_witness": ["x"], "oracle_witness": ["x"],
                                      "certificate": None})
        bundle = reg.flight_recorder().trigger("audit-divergence-test", detail="")
        with open(bundle) as f:
            data = json.load(f)
        assert data["sections"]["audit_divergences"][0]["tuple"] == "d:doc#view@alice"
    finally:
        reg.close()


# -- REST conformance ----------------------------------------------------------


from urllib.parse import parse_qs, urlparse  # noqa: E402


def _call(app, method, url, body=None, headers=None):
    u = urlparse(url)
    st, payload, hdrs = app.handle(
        method,
        u.path,
        parse_qs(u.query),
        json.dumps(body).encode() if body is not None else b"",
        headers or {},
    )
    if isinstance(payload, (bytes, bytearray)):
        payload = json.loads(payload) if payload else None
    return st, payload, hdrs


@pytest.fixture
def rest_registry(tmp_path):
    from keto_tpu.config.provider import Config
    from keto_tpu.driver.registry import Registry

    cfg = Config(overrides={
        "namespaces": [{"id": 1, "name": "g"}, {"id": 2, "name": "d"}],
        "serve": {
            "decision_log_dir": str(tmp_path / "dlog"),
            "decision_log_sample": 1.0,
            "tenant_enabled": True,
        },
    })
    reg = Registry(cfg)
    yield reg
    reg.close()


def test_rest_explain_contract(rest_registry):
    from keto_tpu.servers.rest import READ, WRITE, RestApp

    reg = rest_registry
    wapp, rapp = RestApp(reg, WRITE), RestApp(reg, READ)
    for t in (
        {"namespace": "d", "object": "doc", "relation": "view",
         "subject_set": {"namespace": "g", "object": "eng", "relation": "m"}},
        {"namespace": "g", "object": "eng", "relation": "m", "subject_id": "alice"},
    ):
        st, p, _ = _call(wapp, "PUT", "/relation-tuples", t)
        assert st in (200, 201), (st, p)

    # grant: 200, verified witness, snaptoken echoed in the header
    st, p, h = _call(rapp, "GET",
                     "/check/explain?namespace=d&object=doc&relation=view&subject_id=alice")
    assert st == 200 and p["allowed"] and p["verified"], p
    assert len(p["witness"]) == 2
    assert any(k.lower() == "x-keto-snaptoken" for k in h)

    # deny: 200 (the decision is in the body), certificate attached
    st, p, _ = _call(rapp, "GET",
                     "/check/explain?namespace=d&object=doc&relation=view&subject_id=bob")
    assert st == 200 and not p["allowed"]
    assert p["certificate"]["type"] == "frontier-exhaustion"

    # malformed tuple: no subject → 400 with the reference's message
    st, p, _ = _call(rapp, "GET", "/check/explain?namespace=d&object=doc&relation=view")
    assert st == 400, p

    # pinned re-explain: the same decision is re-derivable at its token
    st, p, _ = _call(rapp, "GET",
                     "/check/explain?namespace=d&object=doc&relation=view"
                     "&subject_id=alice&snaptoken=2")
    assert st == 200 and p["allowed"] and p["snaptoken"] == "2"


def test_rest_explain_disabled_404_and_zero_hot_path_work(tmp_path):
    from keto_tpu.config.provider import Config
    from keto_tpu.driver.registry import Registry
    from keto_tpu.servers.rest import READ, WRITE, RestApp

    cfg = Config(overrides={
        "namespaces": [{"id": 1, "name": "g"}, {"id": 2, "name": "d"}],
        "serve": {"explain_enabled": False},
    })
    reg = Registry(cfg)
    try:
        wapp, rapp = RestApp(reg, WRITE), RestApp(reg, READ)
        _call(wapp, "PUT", "/relation-tuples",
              {"namespace": "d", "object": "doc", "relation": "view",
               "subject_id": "alice"})
        st, _, _ = _call(rapp, "GET",
                         "/check/explain?namespace=d&object=doc&relation=view"
                         "&subject_id=alice")
        assert st == 404
        # the hot path: checks ran, yet neither the explain engine nor a
        # decision log was ever built — explain adds zero work when off
        st, _, _ = _call(rapp, "GET",
                         "/check?namespace=d&object=doc&relation=view&subject_id=alice")
        assert st == 200
        assert reg.peek("explain_engine") is None
        assert reg.decision_log() is None
    finally:
        reg.close()


def test_rest_explain_replica_412_gate(rest_registry, monkeypatch):
    from keto_tpu.servers.rest import READ, RestApp
    from keto_tpu.x.errors import ErrPreconditionFailed

    reg = rest_registry

    class GateStub:
        def gate_read(self, at_least, latest=False):
            if at_least is not None and at_least > 1:
                raise ErrPreconditionFailed(
                    "replica behind requested snaptoken",
                    details={"watermark": "1"},
                )

    monkeypatch.setattr(reg, "replica_controller", lambda: GateStub())
    rapp = RestApp(reg, READ)
    st, p, _ = _call(rapp, "GET",
                     "/check/explain?namespace=d&object=doc&relation=view"
                     "&subject_id=alice&snaptoken=99")
    assert st == 412, p


def test_rest_explain_tenant_routing(rest_registry):
    from keto_tpu.servers.rest import READ, WRITE, RestApp

    reg = rest_registry
    wapp, rapp = RestApp(reg, WRITE), RestApp(reg, READ)
    hdr = {"x-keto-tenant": "acme"}
    st, p, _ = _call(wapp, "PUT", "/relation-tuples",
                     {"namespace": "d", "object": "tdoc", "relation": "view",
                      "subject_id": "eve"}, headers=hdr)
    assert st in (200, 201), (st, p)
    # the tenant sees its tuple, verified against the tenant's store
    st, p, _ = _call(rapp, "GET",
                     "/check/explain?namespace=d&object=tdoc&relation=view&subject_id=eve",
                     headers=hdr)
    assert st == 200 and p["allowed"] and p["verified"], p
    # the default tenant does not
    st, p, _ = _call(rapp, "GET",
                     "/check/explain?namespace=d&object=tdoc&relation=view&subject_id=eve")
    assert st == 200 and not p["allowed"]
    # tenant-scoped decisions land under the tenant's log subdir
    recs, _ = reg.decision_log().read_all("acme")
    assert any(r["kind"] == "explain" for r in recs)


def test_rest_check_hot_path_sampled_into_decision_log(rest_registry):
    from keto_tpu.servers.rest import READ, WRITE, RestApp

    reg = rest_registry
    wapp, rapp = RestApp(reg, WRITE), RestApp(reg, READ)
    _call(wapp, "PUT", "/relation-tuples",
          {"namespace": "d", "object": "doc", "relation": "view", "subject_id": "alice"})
    st, _, _ = _call(rapp, "GET",
                     "/check?namespace=d&object=doc&relation=view&subject_id=alice")
    assert st == 200
    st, _, _ = _call(rapp, "GET",
                     "/check?namespace=d&object=doc&relation=view&subject_id=bob")
    assert st == 403
    recs, corrupt = reg.decision_log().read_all("default")
    checks = [r for r in recs if r["kind"] == "check"]
    assert corrupt == 0 and len(checks) == 2
    assert [c["decision"] for c in checks] == [True, False]
    for c in checks:
        assert c["route"], c  # the deciding route rode into the record
        assert c["trace_id"]
        assert c["witness"] is None  # hot-path records are witness-free
        assert c["snaptoken"]


def test_explain_metrics_exposed(rest_registry):
    from keto_tpu.servers.rest import READ, WRITE, RestApp

    reg = rest_registry
    wapp, rapp = RestApp(reg, WRITE), RestApp(reg, READ)
    _call(wapp, "PUT", "/relation-tuples",
          {"namespace": "d", "object": "doc", "relation": "view", "subject_id": "alice"})
    _call(rapp, "GET", "/check/explain?namespace=d&object=doc&relation=view&subject_id=alice")
    text = reg.metrics().render()
    assert 'keto_explain_requests_total{route="' in text
    assert "keto_witness_verify_failures_total 0" in text
    assert "keto_decision_log_records_total" in text
    assert "keto_decision_log_bytes_total" in text


def test_httpclient_explain_roundtrip(rest_registry):
    from keto_tpu.servers.rest import READ, WRITE, RestServer

    reg = rest_registry
    read = RestServer(reg, READ, port=0)
    write = RestServer(reg, WRITE, port=0)
    read.start()
    write.start()
    try:
        from keto_tpu.httpclient import KetoClient

        c = KetoClient(
            read_url=f"http://127.0.0.1:{read.port}",
            write_url=f"http://127.0.0.1:{write.port}",
        )
        c.create_relation_tuple(T("d", "doc", "view", SubjectID("alice")))
        got = c.explain(T("d", "doc", "view", SubjectID("alice")))
        assert got["allowed"] and got["verified"] and len(got["witness"]) == 1
        got = c.explain(T("d", "doc", "view", SubjectID("bob")))
        assert not got["allowed"] and got["certificate"]
    finally:
        read.stop()
        write.stop()


# -- userset rewrites: a witness step the schema states, not the store ----------


def test_a_rewrite_step_is_typed_and_verified_against_the_schema():
    from keto_tpu.explain.witness import GatedClosure, RewriteStep

    nss = [
        namespace_pkg.Namespace(id=1, name="g"),
        namespace_pkg.Namespace(id=2, name="d", config={"relations": {
            "edit": {"union": [{"this": {}}, {"computed_userset": "own"}]},
            "view": {"union": [{"computed_userset": "edit"}, {"tuple_to_userset": {
                "tupleset": "parent", "computed_userset": "view"}}]},
            "share": {"intersection": [{"this": {}}, {"computed_userset": "own"}]},
        }}),
    ]
    p = MemoryPersister(namespace_pkg.MemoryManager(nss))
    p.write_relation_tuples(
        T("d", "doc", "parent", SubjectSet("d", "dir", "...")),
        T("d", "dir", "own", SubjectID("ann")),
        T("d", "doc", "view", SubjectID("mallory")),  # view has no 'this': not a grant
    )
    rewrites = (p.namespaces(), p.namespaces().rewrites)
    requested = T("d", "doc", "view", SubjectID("ann"))
    found, path, _ = build_witness(p, requested, rewrites=rewrites)
    assert found
    assert [getattr(e, "rewrite", None) for e in path] == [
        "tuple_to_userset", "computed_userset", "computed_userset", None]
    assert isinstance(path[0], RewriteStep) and path[0].via == T(
        "d", "doc", "parent", SubjectSet("d", "dir", "..."))
    assert path[0].to_json()["rewrite"] == "tuple_to_userset"
    assert verify_witness(p, requested, path, rewrites) == (True, "")
    # a rewrite step is held to the schema: another relation, another tupleset row
    forged = [RewriteStep("d", "doc", "view", SubjectSet("d", "dir", "own"),
                          rewrite="tuple_to_userset", via=path[0].via)] + path[3:]
    ok, why = verify_witness(p, requested, forged, rewrites)
    assert not ok and "schema has no tuple_to_userset" in why
    gone = [RewriteStep("d", "doc", "view", SubjectSet("d", "dir", "view"), rewrite="tuple_to_userset",
                        via=T("d", "doc", "parent", SubjectSet("d", "other", "...")))] + path[1:]
    assert not verify_witness(p, requested, gone, rewrites)[0]
    # a stored row on a relation whose rewrite has no 'this' is no step
    ok, why = verify_witness(
        p, T("d", "doc", "view", SubjectID("mallory")),
        [T("d", "doc", "view", SubjectID("mallory"))], rewrites)
    assert not ok and "no 'this'" in why
    assert not build_witness(p, T("d", "doc", "view", SubjectID("mallory")), rewrites=rewrites)[0]
    # without the schema the same rows give the v0.7 answer, as before
    assert build_witness(p, T("d", "doc", "view", SubjectID("mallory")))[0]
    with pytest.raises(GatedClosure):
        build_witness(p, T("d", "doc", "share", SubjectID("ann")), rewrites=rewrites)

    engine = TpuCheckEngine(p, p.namespaces)
    try:
        resp = ExplainEngine(engine, p).explain(requested)
        assert resp["allowed"] and resp["verified"]
        assert [e.get("rewrite") for e in resp["witness"]][:1] == ["tuple_to_userset"]
        gated = ExplainEngine(engine, p).explain(T("d", "doc", "share", SubjectID("ann")))
        assert gated["witness"] is None and not gated["verified"]
        assert "intersection" in gated["rewrite"]
    finally:
        engine.close()
