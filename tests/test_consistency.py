"""Request-level consistency: snaptoken / latest end-to-end.

The reference documents snaptoken semantics on its proto but stubs the
implementation (reference internal/check/handler.go:162,
proto/ory/keto/acl/v1alpha1/check_service.proto:39-75). Here they are real:

- the serving default is bounded staleness that NEVER stalls on a snapshot
  rebuild (TpuCheckEngine.snapshot_serving);
- a write's snaptoken (the store watermark) pins ``at_least`` freshness;
- ``latest`` forces read-your-writes.
"""

import json
import threading
import urllib.request

import pytest

from keto_tpu import namespace as namespace_pkg
from keto_tpu.check.tpu_engine import TpuCheckEngine
from keto_tpu.driver.batch import CheckBatcher
from keto_tpu.persistence.memory import MemoryPersister
from keto_tpu.relationtuple import RelationTuple, SubjectID, SubjectSet


def T(ns, obj, rel, sub):
    return RelationTuple(namespace=ns, object=obj, relation=rel, subject=sub)


NSS = [namespace_pkg.Namespace(id=1, name="g"), namespace_pkg.Namespace(id=2, name="d")]


def make_store():
    return MemoryPersister(namespace_pkg.MemoryManager(NSS))


class _BlockedRebuild:
    """Blocks the store's full-rebuild read and disables the delta seams,
    simulating the expensive-rebuild regime (log overflow at scale)."""

    def __init__(self, store):
        self.store = store
        self.gate = threading.Event()
        self.entered = threading.Event()
        self._orig = store.snapshot_rows

    def __enter__(self):
        def blocked():
            self.entered.set()
            assert self.gate.wait(timeout=30)
            return self._orig()

        self.store.snapshot_rows = blocked
        self.store.changes_since = lambda wm: None
        self.store.rows_since = lambda wm: None
        return self

    def __exit__(self, *exc):
        self.gate.set()
        self.store.snapshot_rows = self._orig
        del self.store.changes_since
        del self.store.rows_since


def test_serving_mode_never_stalls_on_rebuild():
    p = make_store()
    p.write_relation_tuples(
        T("d", "doc", "view", SubjectSet("g", "team", "member")),
        T("g", "team", "member", SubjectID("alice")),
    )
    engine = TpuCheckEngine(p, p.namespaces)
    base = engine.snapshot()
    engine._last_full_build_s = 60.0  # pretend the base build was expensive

    with _BlockedRebuild(p) as blk:
        p.write_relation_tuples(T("g", "team", "member", SubjectID("bob")))
        # serving mode: decided immediately from the stale snapshot
        got, token = engine.batch_check_with_token(
            [
                T("d", "doc", "view", SubjectID("alice")),
                T("d", "doc", "view", SubjectID("bob")),
            ],
            mode="serving",
        )
        assert got == [True, False]  # bob not visible yet — bounded staleness
        assert token == base.snapshot_id
        # the background refresh is parked inside the blocked read
        assert blk.entered.wait(timeout=10)
    # after the rebuild completes, freshness returns
    deadline = threading.Event()
    for _ in range(100):
        if engine.snapshot_serving().snapshot_id == p.watermark():
            break
        deadline.wait(0.05)
    assert engine.batch_check([T("d", "doc", "view", SubjectID("bob"))]) == [True]


def test_serving_mode_catches_up_via_delta():
    # deltas are cheap — the serving path applies them synchronously, so
    # write→check is still read-your-writes in the common case even with an
    # expensive-rebuild history
    p = make_store()
    p.write_relation_tuples(
        T("d", "doc", "view", SubjectSet("g", "team", "member")),
        T("g", "team", "member", SubjectID("alice")),
    )
    engine = TpuCheckEngine(p, p.namespaces)
    # the first snapshot's label build installs its index under the engine's
    # lock, on a thread of its own, and the serving path never waits for
    # that lock (it serves the snapshot it has): let the build land first
    engine.labels_settled()
    engine._last_full_build_s = 60.0
    p.write_relation_tuples(T("g", "team", "member", SubjectID("bob")))
    p.delete_relation_tuples(T("g", "team", "member", SubjectID("alice")))
    got, token = engine.batch_check_with_token(
        [
            T("d", "doc", "view", SubjectID("bob")),
            T("d", "doc", "view", SubjectID("alice")),
        ],
        mode="serving",
    )
    assert got == [True, False]
    assert token == p.watermark()


def test_at_least_token_round_trip():
    p = make_store()
    p.write_relation_tuples(
        T("d", "doc", "view", SubjectSet("g", "team", "member")),
        T("g", "team", "member", SubjectID("alice")),
    )
    engine = TpuCheckEngine(p, p.namespaces)
    engine.snapshot()
    p.write_relation_tuples(T("g", "team", "member", SubjectID("bob")))
    token = p.watermark()  # what the write API returns as snaptoken
    got, used = engine.batch_check_with_token(
        [T("d", "doc", "view", SubjectID("bob"))], at_least=token
    )
    assert got == [True] and used >= token


def test_batcher_coalesces_mixed_consistency():
    p = make_store()
    p.write_relation_tuples(
        T("d", "doc", "view", SubjectSet("g", "team", "member")),
        T("g", "team", "member", SubjectID("alice")),
    )
    engine = TpuCheckEngine(p, p.namespaces)
    engine.snapshot()
    b = CheckBatcher(engine, batch_size=8, window_ms=20.0)
    b.start()
    try:
        results = {}

        def call(name, **kw):
            results[name] = b.check_with_token(T("d", "doc", "view", SubjectID("alice")), **kw)

        ts = [
            threading.Thread(target=call, args=("serving",)),
            threading.Thread(target=call, args=("latest",), kwargs={"latest": True}),
            threading.Thread(
                target=call, args=("floor",), kwargs={"at_least": p.watermark()}
            ),
        ]
        for t in ts:
            t.start()
        for t in ts:
            t.join(timeout=30)
        for name, (allowed, token) in results.items():
            assert allowed is True, name
            assert token == p.watermark(), name
    finally:
        b.stop()


def test_oracle_engine_through_batcher_has_no_token():
    from keto_tpu.check import CheckEngine

    p = make_store()
    p.write_relation_tuples(T("g", "team", "member", SubjectID("alice")))
    b = CheckBatcher(CheckEngine(p), batch_size=4, window_ms=1.0)
    b.start()
    try:
        allowed, token = b.check_with_token(T("g", "team", "member", SubjectID("alice")))
        assert allowed is True and token is None
    finally:
        b.stop()


# -- API surface ------------------------------------------------------------


@pytest.fixture
def rest_servers():
    from keto_tpu.config.provider import Config
    from keto_tpu.driver.registry import Registry
    from keto_tpu.servers.rest import READ, WRITE, RestServer

    cfg = Config(
        overrides={"namespaces": [{"id": 1, "name": "g"}, {"id": 2, "name": "d"}]}
    )
    reg = Registry(cfg)
    read = RestServer(reg, READ, port=0)
    write = RestServer(reg, WRITE, port=0)
    read.start()
    write.start()
    yield read, write, reg
    read.stop()
    write.stop()
    reg.close()


def _req(server, method, path, body=None):
    import urllib.error

    url = f"http://127.0.0.1:{server.port}{path}"
    data = json.dumps(body).encode() if body is not None else None
    r = urllib.request.Request(url, data=data, method=method)
    try:
        with urllib.request.urlopen(r) as resp:
            raw = resp.read()
            return resp.status, json.loads(raw) if raw else None, dict(resp.headers)
    except urllib.error.HTTPError as e:
        raw = e.read()
        return e.code, json.loads(raw) if raw else None, dict(e.headers)


def test_rest_snaptoken_and_latest(rest_servers):
    read, write, reg = rest_servers
    _req(
        write,
        "PUT",
        "/relation-tuples",
        {"namespace": "g", "object": "team", "relation": "member", "subject_id": "alice"},
    )
    status, body, headers = _req(
        read,
        "GET",
        "/check?namespace=g&object=team&relation=member&subject_id=alice&latest=true",
    )
    assert status == 200 and body["allowed"] is True
    token = headers.get("X-Keto-Snaptoken")
    assert token and token.isdigit()

    # the returned token is accepted as a floor
    status, body, _ = _req(
        read,
        "GET",
        f"/check?namespace=g&object=team&relation=member&subject_id=alice&snaptoken={token}",
    )
    assert status == 200 and body["allowed"] is True

    # malformed token → 400, not 403
    status, body, _ = _req(
        read,
        "GET",
        "/check?namespace=g&object=team&relation=member&subject_id=alice&snaptoken=zook",
    )
    assert status == 400


def test_grpc_snaptoken_and_latest():
    import grpc
    from ory.keto.acl.v1alpha1 import acl_pb2, check_service_pb2, write_service_pb2

    from keto_tpu.config.provider import Config
    from keto_tpu.driver.daemon import Daemon
    from keto_tpu.driver.registry import Registry

    cfg = Config(
        overrides={
            "namespaces": [{"id": 1, "name": "g"}, {"id": 2, "name": "d"}],
            "serve.read.port": 0,
            "serve.write.port": 0,
        }
    )
    d = Daemon(Registry(cfg))
    d.serve_all(block=False)
    try:
        write_ch = grpc.insecure_channel(f"127.0.0.1:{d.write_port}")
        read_ch = grpc.insecure_channel(f"127.0.0.1:{d.read_port}")

        def unary(ch, method, req, resp_cls):
            return ch.unary_unary(
                method,
                request_serializer=lambda m: m.SerializeToString(),
                response_deserializer=resp_cls.FromString,
            )(req)

        tup = acl_pb2.RelationTuple(
            namespace="g", object="team", relation="member",
            subject=acl_pb2.Subject(id="alice"),
        )
        wr = unary(
            write_ch,
            "/ory.keto.acl.v1alpha1.WriteService/TransactRelationTuples",
            write_service_pb2.TransactRelationTuplesRequest(
                relation_tuple_deltas=[
                    write_service_pb2.RelationTupleDelta(
                        action=write_service_pb2.RelationTupleDelta.INSERT,
                        relation_tuple=tup,
                    )
                ]
            ),
            write_service_pb2.TransactRelationTuplesResponse,
        )
        token = wr.snaptokens[0]
        assert token.isdigit()

        # write's snaptoken → check at_least that fresh: must see the write
        resp = unary(
            read_ch,
            "/ory.keto.acl.v1alpha1.CheckService/Check",
            check_service_pb2.CheckRequest(
                namespace="g", object="team", relation="member",
                subject=acl_pb2.Subject(id="alice"), snaptoken=token,
            ),
            check_service_pb2.CheckResponse,
        )
        assert resp.allowed is True
        assert resp.snaptoken and int(resp.snaptoken) >= int(token)

        # latest works too
        resp = unary(
            read_ch,
            "/ory.keto.acl.v1alpha1.CheckService/Check",
            check_service_pb2.CheckRequest(
                namespace="g", object="team", relation="member",
                subject=acl_pb2.Subject(id="alice"), latest=True,
            ),
            check_service_pb2.CheckResponse,
        )
        assert resp.allowed is True

        # malformed snaptoken → INVALID_ARGUMENT
        with pytest.raises(grpc.RpcError) as ei:
            unary(
                read_ch,
                "/ory.keto.acl.v1alpha1.CheckService/Check",
                check_service_pb2.CheckRequest(
                    namespace="g", object="team", relation="member",
                    subject=acl_pb2.Subject(id="alice"), snaptoken="zook",
                ),
                check_service_pb2.CheckResponse,
            )
        assert ei.value.code() == grpc.StatusCode.INVALID_ARGUMENT
        write_ch.close()
        read_ch.close()
    finally:
        d.shutdown()
