"""Native epoll mux (native/mux.cpp) vs the Python fallback.

Both implementations must serve the identical REST+gRPC-multiplexed
daemon flow; the native one adds serving-grade properties (no
per-connection threads, connection cap, sniff deadline) that the heavy
stress job exercises. CheckBatcher backpressure: a full queue blocks —
then times out — callers instead of growing an unbounded backlog.
"""

import json
import threading
import time
import urllib.error
import urllib.parse
import urllib.request

import pytest

from keto_tpu.config.provider import Config
from keto_tpu.driver.batch import CheckBatcher
from keto_tpu.driver.daemon import Daemon
from keto_tpu.driver.registry import Registry
from keto_tpu.relationtuple import RelationTuple, SubjectID
from keto_tpu.servers import native_mux


@pytest.fixture(params=["native", "python"])
def daemon(request, monkeypatch):
    if request.param == "native":
        if native_mux.load_library() is None:
            pytest.skip("libketomux.so not built (make native)")
    else:
        # force the Python fallback
        from keto_tpu.servers.mux import PortMux

        monkeypatch.setattr(
            native_mux, "make_port_mux",
            lambda host, port, rest_port, grpc_port: PortMux(
                host, port, rest_port=rest_port, grpc_port=grpc_port
            ),
        )
        import keto_tpu.driver.daemon as dmod

        monkeypatch.setattr(dmod, "make_port_mux", native_mux.make_port_mux)
    cfg = Config(
        overrides={"namespaces": [{"id": 1, "name": "g"}],
                   "serve.read.port": 0, "serve.write.port": 0}
    )
    d = Daemon(Registry(cfg))
    d.serve_all(block=False)
    yield d
    d.shutdown()


def test_mux_serves_rest_and_grpc(daemon):
    d = daemon
    # REST write through the multiplexed write port
    req = urllib.request.Request(
        f"http://127.0.0.1:{d.write_port}/relation-tuples", method="PUT",
        data=json.dumps({"namespace": "g", "object": "o", "relation": "r",
                         "subject_id": "u"}).encode())
    assert urllib.request.urlopen(req).status in (200, 201)
    # REST check through the multiplexed read port
    q = urllib.parse.urlencode({"namespace": "g", "object": "o", "relation": "r",
                                "subject_id": "u"})
    assert urllib.request.urlopen(f"http://127.0.0.1:{d.read_port}/check?{q}").status == 200
    # gRPC through the SAME port (sniffed by the HTTP/2 preface)
    import grpc

    from ory.keto.acl.v1alpha1 import acl_pb2, check_service_pb2

    ch = grpc.insecure_channel(f"127.0.0.1:{d.read_port}")
    resp = ch.unary_unary(
        "/ory.keto.acl.v1alpha1.CheckService/Check",
        request_serializer=lambda m: m.SerializeToString(),
        response_deserializer=check_service_pb2.CheckResponse.FromString,
    )(check_service_pb2.CheckRequest(
        namespace="g", object="o", relation="r",
        subject=acl_pb2.Subject(id="u")))
    assert resp.allowed is True
    ch.close()


def test_mux_concurrent_mixed_protocols(daemon):
    d = daemon
    req = urllib.request.Request(
        f"http://127.0.0.1:{d.write_port}/relation-tuples", method="PUT",
        data=json.dumps({"namespace": "g", "object": "o", "relation": "r",
                         "subject_id": "u"}).encode())
    urllib.request.urlopen(req)
    errors = []

    def rest_client():
        try:
            for i in range(20):
                q = urllib.parse.urlencode(
                    {"namespace": "g", "object": "o", "relation": "r",
                     "subject_id": "u" if i % 2 else "ghost"})
                try:
                    r = urllib.request.urlopen(
                        f"http://127.0.0.1:{d.read_port}/check?{q}", timeout=30)
                    assert r.status == 200
                except urllib.error.HTTPError as e:
                    assert e.code == 403
        except Exception as e:
            errors.append(repr(e))

    def grpc_client():
        import grpc

        from ory.keto.acl.v1alpha1 import acl_pb2, check_service_pb2

        try:
            ch = grpc.insecure_channel(f"127.0.0.1:{d.read_port}")
            call = ch.unary_unary(
                "/ory.keto.acl.v1alpha1.CheckService/Check",
                request_serializer=lambda m: m.SerializeToString(),
                response_deserializer=check_service_pb2.CheckResponse.FromString,
            )
            for i in range(20):
                resp = call(check_service_pb2.CheckRequest(
                    namespace="g", object="o", relation="r",
                    subject=acl_pb2.Subject(id="u" if i % 2 else "ghost")))
                assert resp.allowed is (i % 2 == 1)
            ch.close()
        except Exception as e:
            errors.append(repr(e))

    threads = [threading.Thread(target=rest_client) for _ in range(4)] + [
        threading.Thread(target=grpc_client) for _ in range(4)
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=120)
        assert not t.is_alive(), "mixed-protocol client hung"
    assert not errors, errors


def test_mux_stop_relays_what_the_backend_has_written():
    """The drain's last step (``mux_stop``): the REST drain waits until every
    response has been WRITTEN by the backend, the mux is stopped next, and
    what it has not relayed by then must still reach the client: a rolling
    restart drops no accepted request. Bodies of 256 KiB keep most of a
    response in the proxy's hands at the moment of the stop (without the
    final relay 23-58 of 64 arrived cut short)."""
    import socket

    if native_mux.load_library() is None:
        pytest.skip("libketomux.so not built (make native)")
    n, body = 32, b"x" * (256 * 1024) + b"ok"
    response = b"HTTP/1.1 200 OK\r\nContent-Length: %d\r\nConnection: close\r\n\r\n" % len(body) + body
    srv = socket.socket()
    srv.bind(("127.0.0.1", 0))
    srv.listen(64)
    go = threading.Event()
    got_request, written = threading.Semaphore(0), threading.Semaphore(0)

    def backend(conn):
        buf = b""
        while b"\r\n\r\n" not in buf:
            buf += conn.recv(4096) or b"\r\n\r\n"
        got_request.release()
        go.wait(10)
        conn.sendall(response)
        written.release()
        conn.close()

    def accept():
        for _ in range(n):
            conn, _ = srv.accept()
            threading.Thread(target=backend, args=(conn,), daemon=True).start()

    threading.Thread(target=accept, daemon=True).start()
    port = srv.getsockname()[1]
    mux = native_mux.NativePortMux("127.0.0.1", 0, port, port)
    got = [b""] * n

    def client(i):
        with socket.create_connection(("127.0.0.1", mux.port), timeout=10) as s:
            s.sendall(b"GET / HTTP/1.1\r\nHost: x\r\n\r\n")
            try:
                while chunk := s.recv(65536):
                    got[i] += chunk
            except OSError:
                pass

    clients = [threading.Thread(target=client, args=(i,)) for i in range(n)]
    try:
        for t in clients:
            t.start()
        for _ in range(n):
            assert got_request.acquire(timeout=10)
        go.set()
        for _ in range(n):
            assert written.acquire(timeout=10)
    finally:
        mux.stop()  # at once: the relay is still under way
        for t in clients:
            t.join(10)
        srv.close()
    assert [len(g) for g in got] == [len(response)] * n


def test_batcher_backpressure_blocks_then_times_out():
    """A device that can't keep up fills the bounded queue; callers block
    and time out instead of the queue growing without bound."""
    release = threading.Event()

    class SlowEngine:
        def batch_check(self, tuples):
            release.wait(10)
            return [False] * len(tuples)

    b = CheckBatcher(SlowEngine(), batch_size=2, window_ms=1.0, max_pending=2)
    b.start()
    t = RelationTuple(namespace="g", object="o", relation="r", subject=SubjectID("u"))
    fillers = [
        threading.Thread(target=lambda: b.check(t, timeout=10), daemon=True)
        for _ in range(6)
    ]
    for f in fillers:
        f.start()
    time.sleep(0.3)  # queue now full (collector blocked in SlowEngine)
    t0 = time.monotonic()
    with pytest.raises(TimeoutError):
        b.check(t, timeout=0.4)
    assert 0.3 <= time.monotonic() - t0 < 5, "did not block-then-timeout"
    release.set()
    for f in fillers:
        f.join(timeout=20)
    b.stop()
