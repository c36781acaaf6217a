"""Overload resilience: priority lanes, adaptive admission, retry budgets.

The serving-stack half of the overload story (bench.py's open-loop
harness is the load half):

- the CheckBatcher's priority lanes pack interactive checks into the
  next dispatch round ahead of queued batch work, and serve monster
  batch chunks in bounded sub-slices while singles are about (in rounds
  of the round's own cap while their lane is quiet);
- the AIMD admission controller shrinks the admitted batch window past
  the latency budget and sheds with growing Retry-After advice —
  interactive is never admission-limited;
- a deadline that expires while blocked on a full queue is a 504
  (ErrDeadlineExceeded), not a 429 — the double-deadline race;
- 429/503 responses carry Retry-After on REST and retry-after trailing
  metadata on gRPC, and the SDK honors both under a token-bucket retry
  budget that caps retries during a brownout;
- hedged idempotent reads amputate the tail without storming.
"""

import json
import threading
import time
import urllib.error
import urllib.request
from concurrent.futures import Future
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

import pytest

from keto_tpu import namespace as namespace_pkg
from keto_tpu.config.provider import Config
from keto_tpu.driver.admission import AdmissionController
from keto_tpu.driver.batch import BATCH, INTERACTIVE, CheckBatcher, _Item
from keto_tpu.driver.daemon import Daemon
from keto_tpu.driver.registry import Registry
from keto_tpu.httpclient import KetoClient, RetryBudget
from keto_tpu.relationtuple import RelationTuple, SubjectID
from keto_tpu.x.errors import ErrDeadlineExceeded, ErrTooManyRequests


def T(obj, user="u"):
    return RelationTuple(
        namespace="acl", object=obj, relation="access", subject=SubjectID(user)
    )


def wait_for(cond, timeout=10.0, interval=0.02, msg="condition"):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if cond():
            return
        time.sleep(interval)
    raise AssertionError(f"timed out waiting for {msg}")


class GateEngine:
    """Records every dispatch round's tuples; the first call blocks until
    released so tests can stage work behind an in-flight round."""

    def __init__(self, block_first=True):
        self.calls = []
        self.release = threading.Event()
        self._block_first = block_first
        self._first = True

    def batch_check_with_token(self, tuples, **kw):
        tuples = list(tuples)
        self.calls.append(tuples)
        if self._block_first and self._first:
            self._first = False
            assert self.release.wait(10), "gate never released"
        # allowed iff the object name ends in an even digit
        return [int(t.object.rsplit("-", 1)[1]) % 2 == 0 for t in tuples], 7


# -- priority lanes ----------------------------------------------------------


def test_interactive_packs_ahead_of_queued_batch():
    """An interactive check that arrives while a monster batch chunk is
    queued rides the NEXT dispatch round, ahead of the remaining batch
    tuples — and with singles about, batch work is taken at most one
    sub-slice per round."""
    eng = GateEngine()
    b = CheckBatcher(eng, batch_size=8, window_ms=2.0, batch_sub_slice=4)
    b.start()
    try:
        # round 1 is a single's, and the collector blocks inside it
        first = threading.Thread(target=lambda: b.check(T("i-0"), timeout=30), daemon=True)
        first.start()
        wait_for(lambda: len(eng.calls) == 1, msg="first round dispatched")
        chunk = [T(f"c-{i}") for i in range(12)]
        batch_res = {}
        bt = threading.Thread(
            target=lambda: batch_res.update(r=b.check_batch(chunk, timeout=30, lane=BATCH)),
            daemon=True,
        )
        bt.start()
        wait_for(lambda: b.lane_depths[BATCH] == 12, msg="chunk queued")
        # an interactive check arrives behind the monster chunk
        inter_res = {}
        it = threading.Thread(
            target=lambda: inter_res.update(r=b.check(T("i-2"), timeout=30)),
            daemon=True,
        )
        it.start()
        wait_for(lambda: b.lane_depths[INTERACTIVE] == 1, msg="interactive queued")
        eng.release.set()
        for t in (first, it, bt):
            t.join(timeout=10)
        assert inter_res["r"] is True  # i-2 → even → allowed
        assert batch_res["r"] == [int(t.object[2:]) % 2 == 0 for t in chunk]
        # round 2: the interactive tuple is FIRST, ahead of the chunk's
        # first sub-slice
        assert [t.object for t in eng.calls[1]] == ["i-2", "c-0", "c-1", "c-2", "c-3"]
        # and the round behind it stays within one sub-slice too: the one
        # that carried the single may still be on the device
        assert [t.object for t in eng.calls[2]] == ["c-4", "c-5", "c-6", "c-7"]
        for call in eng.calls:
            assert sum(1 for t in call if t.object.startswith("c-")) <= 4
    finally:
        b.stop()


def test_monster_chunk_resolves_across_sub_slices():
    """A chunk wider than a round is answered correctly and in order
    across several dispatch rounds."""
    eng = GateEngine(block_first=False)
    b = CheckBatcher(
        eng, batch_size=3, window_ms=0.5, batch_sub_slice=3,
        interactive_max_tuples=2,
    )
    b.start()
    try:
        chunk = [T(f"m-{i}") for i in range(10)]
        got, token = b.check_batch_with_token(chunk, timeout=30)
        assert got == [i % 2 == 0 for i in range(10)]
        assert token == 7
        assert len(eng.calls) >= 4  # 10 tuples at ≤3 per round
        assert all(len(c) <= 3 for c in eng.calls)
    finally:
        b.stop()


# the take itself, scripted: no collector thread, one ``_take_locked`` a round


def _queue(b, n, lane):
    item = _Item([T(f"q-{i}") for i in range(n)], Future(), None, False, None, lane)
    b._enqueue(item)
    return item


def _take(b):
    """One round off the lanes, as ``[(lane, start, count), ...]``."""
    with b._cond:
        return [(item.lane, start, count) for item, start, count in b._take_locked()]


def _batch_share(segments):
    return sum(count for lane, _, count in segments if lane == BATCH)


def test_quiet_interactive_lane_takes_batch_work_up_to_the_rounds_cap():
    b = CheckBatcher(GateEngine(), batch_size=4096, batch_sub_slice=1024)
    _queue(b, 10000, BATCH)
    _queue(b, 4096, BATCH)
    assert _take(b) == [(BATCH, 0, 4096)]
    assert _take(b) == [(BATCH, 4096, 4096)]
    # a round is filled across calls, the head call first
    assert _take(b) == [(BATCH, 8192, 1808), (BATCH, 0, 2288)]
    assert _take(b) == [(BATCH, 2288, 1808)]
    assert _take(b) == []


def test_with_a_single_in_every_round_batch_work_stays_within_the_sub_slice():
    b = CheckBatcher(GateEngine(), batch_size=4096, batch_sub_slice=1024)
    _queue(b, 20000, BATCH)
    for _ in range(6):
        _queue(b, 1, INTERACTIVE)
        segments = _take(b)
        assert segments[0] == (INTERACTIVE, 0, 1)  # the single rides the next round, at its head
        assert _batch_share(segments) == 1024
    # a single every second round keeps them narrow as well
    for k in range(6):
        if k % 2:
            _queue(b, 1, INTERACTIVE)
        assert _batch_share(_take(b)) == 1024


def test_quiet_to_busy_to_quiet_narrows_at_the_first_single_and_widens_two_rounds_later():
    b = CheckBatcher(GateEngine(), batch_size=4096, batch_sub_slice=1024)
    _queue(b, 30000, BATCH)
    assert _batch_share(_take(b)) == 4096  # quiet
    assert _batch_share(_take(b)) == 4096
    _queue(b, 1, INTERACTIVE)
    segments = _take(b)  # the very next round taken carries it
    assert segments[0] == (INTERACTIVE, 0, 1) and _batch_share(segments) == 1024
    assert _batch_share(_take(b)) == 1024  # the single's round may still be on the device
    assert _batch_share(_take(b)) == 4096  # neither this round nor the one before carried one
    assert _batch_share(_take(b)) == 4096


def test_singles_still_queued_after_a_full_interactive_take_keep_the_round_narrow():
    """More singles than a round holds: what stays queued rides the next
    round, so neither that round nor this one takes more than the reserve or
    the sub-slice of batch work."""
    b = CheckBatcher(GateEngine(), batch_size=16, batch_sub_slice=4, interactive_max_tuples=1)
    _queue(b, 100, BATCH)
    for _ in range(20):
        _queue(b, 1, INTERACTIVE)
    assert _batch_share(_take(b)) == 2  # 14 singles, then the reserve's room
    assert b.lane_depths[INTERACTIVE] == 6
    segments = _take(b)
    assert len(segments) == 7 and _batch_share(segments) == 4
    assert _batch_share(_take(b)) == 4
    assert _batch_share(_take(b)) == 16


@pytest.mark.parametrize("ctrl_cap, quiet, busy", [(2500, 2500, 1024), (600, 600, 600), (1 << 20, 4096, 1024)])
def test_the_slice_controllers_cap_bounds_a_wide_take_too(ctrl_cap, quiet, busy):
    class Ctrl:
        def cap(self):
            return ctrl_cap

    eng = GateEngine()
    eng.stream_ctrl = Ctrl()
    b = CheckBatcher(eng, batch_size=4096, batch_sub_slice=1024)
    _queue(b, 20000, BATCH)
    assert _batch_share(_take(b)) == quiet
    _queue(b, 1, INTERACTIVE)
    assert _batch_share(_take(b)) == busy
    assert _batch_share(_take(b)) == busy
    assert _batch_share(_take(b)) == quiet


def test_lane_classification_by_size_and_hint():
    b = CheckBatcher(GateEngine(block_first=False), interactive_max_tuples=4)
    assert b.classify_lane(1, None) == INTERACTIVE
    assert b.classify_lane(4, None) == INTERACTIVE
    assert b.classify_lane(5, None) == BATCH
    assert b.classify_lane(1, "batch") == BATCH
    assert b.classify_lane(5000, "interactive") == INTERACTIVE


def test_deadline_expiring_while_blocked_on_full_queue_is_504():
    """The double-deadline race: a request that passes the pre-queue
    deadline check but expires while BLOCKED on a full queue must raise
    ErrDeadlineExceeded (504), never a queue-full error."""
    eng = GateEngine()  # first round blocks; queue backs up behind it
    b = CheckBatcher(eng, batch_size=1, window_ms=0.0, max_pending=1)
    b.start()
    try:
        threading.Thread(
            target=lambda: b.check(T("c-0"), timeout=30), daemon=True
        ).start()
        wait_for(lambda: len(eng.calls) == 1, msg="collector blocked in engine")
        threading.Thread(
            target=lambda: b.check(T("c-2"), timeout=30), daemon=True
        ).start()
        wait_for(lambda: b.lane_depths[INTERACTIVE] >= 1, msg="lane full")
        t0 = time.monotonic()
        with pytest.raises(ErrDeadlineExceeded):
            b.check(T("c-4"), timeout=0.3)
        assert 0.2 <= time.monotonic() - t0 < 5
        assert b.shed_count == 0, "the race must not be misreported as a shed"
    finally:
        eng.release.set()
        b.stop()


# -- adaptive admission control ----------------------------------------------


class FakeStats:
    def __init__(self):
        self._vals = []

    def feed(self, *ms):
        self._vals.extend(ms)

    def tail(self, n):
        if n <= 0:
            return [], len(self._vals)
        return self._vals[-n:], len(self._vals)


def test_admission_aimd_shrinks_and_recovers():
    stats = FakeStats()
    ctrl = AdmissionController(
        stats=stats, target_ms=10.0, min_window=16, max_window=1024,
        interval_s=0.0,  # every tick evaluates (tests drive the clock)
    )
    assert ctrl.window == 1024
    assert ctrl.retry_after_s() == 1.0
    # p99 over budget (4x10=40ms): multiplicative decrease, growing advice
    stats.feed(100.0, 120.0, 90.0)
    ctrl.tick()
    assert ctrl.window == 512
    stats.feed(200.0)
    ctrl.tick()
    stats.feed(200.0)
    ctrl.tick()
    assert ctrl.window == 128
    assert ctrl.retry_after_s() == 8.0
    assert ctrl.overloaded
    # healthy slices: additive recovery, advice resets
    for _ in range(8):
        stats.feed(2.0)
        ctrl.tick()
    assert 128 < ctrl.window <= 1024
    assert ctrl.retry_after_s() == 1.0
    assert not ctrl.overloaded
    # floor holds in deep overload
    for _ in range(20):
        stats.feed(500.0)
        ctrl.tick()
    assert ctrl.window == 16


def test_admission_judges_queue_delay_without_slow_slices():
    """A fast device behind 3x offered load never shows slow slices —
    the queue-delay estimate (backlog / observed dispatch rate) must
    trip the limiter on its own."""
    stats = FakeStats()
    ctrl = AdmissionController(
        stats=stats, target_ms=10.0, min_window=16, max_window=1024, interval_s=0.0
    )
    ctrl.observe_round(1000, 0.01)  # 100k tuples/s: fast device
    stats.feed(5.0)  # slices comfortably under budget
    ctrl.tick(backlog=8000)  # 80ms of queue > 40ms budget
    assert ctrl.window == 512
    snap = ctrl.snapshot()
    assert snap["last_queue_delay_ms"] == pytest.approx(80.0)
    assert snap["overloaded"]


def test_admission_sheds_batch_lane_only():
    ctrl = AdmissionController(min_window=8, max_window=8)  # pinned window
    eng = GateEngine(block_first=False)
    b = CheckBatcher(eng, batch_size=8, window_ms=0.5, admission=ctrl)
    b.start()
    try:
        with pytest.raises(ErrTooManyRequests) as exc:
            b.check_batch([T(f"c-{i}") for i in range(9)], timeout=5, lane=BATCH)
        assert exc.value.retry_after_s >= 1.0
        assert b.admission_shed_count == 1
        assert b.shed_by_lane[BATCH] == 1
        # interactive is never admission-limited
        assert b.check(T("i-0"), timeout=5) is True
        # a batch within the window still flows
        assert b.check_batch([T(f"c-{i}") for i in range(8)], timeout=5, lane=BATCH)
    finally:
        b.stop()


def test_admission_precheck_refuses_before_parse():
    ctrl = AdmissionController(min_window=4, max_window=4)
    eng = GateEngine()
    # the collector is intentionally NOT started: precheck judges the
    # QUEUED backlog at the door, and a running collector racing tuples
    # out of the lane into a dispatch round made this assertion flaky —
    # the door decision must not depend on collector timing
    b = CheckBatcher(eng, batch_size=2, window_ms=0.0, admission=ctrl)
    try:
        b.admission_precheck()  # empty lane: admits

        def _bg_batch():
            try:
                b.check_batch([T(f"c-{i}") for i in range(4)], timeout=30, lane=BATCH)
            except RuntimeError:
                pass  # batcher stopped at teardown while we were queued

        threading.Thread(target=_bg_batch, daemon=True).start()
        wait_for(lambda: b.lane_depths[BATCH] >= 4, msg="batch backlog")
        with pytest.raises(ErrTooManyRequests):
            b.admission_precheck()
        assert b.admission_shed_count == 1
    finally:
        eng.release.set()
        b.stop()


# -- REST/gRPC surface: lanes, Retry-After ------------------------------------


@pytest.fixture(scope="module")
def daemon():
    cfg = Config(
        overrides={
            "namespaces": [{"id": 0, "name": "acl"}],
            "dsn": "memory",
            "serve.read.port": 0,
            "serve.write.port": 0,
        }
    )
    d = Daemon(Registry(cfg))
    d.serve_all(block=False)
    store = d.registry.relation_tuple_manager()
    store.write_relation_tuples(*[T(f"obj-{i}", f"user-{i}") for i in range(8)])
    yield d
    d.shutdown()


def _get(port, path, headers=None):
    req = urllib.request.Request(f"http://127.0.0.1:{port}{path}")
    for k, v in (headers or {}).items():
        req.add_header(k, v)
    try:
        with urllib.request.urlopen(req, timeout=10) as r:
            return r.status, json.loads(r.read() or b"null"), dict(r.headers)
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read() or b"null"), dict(e.headers)


def _post(port, path, body, headers=None):
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}{path}", data=json.dumps(body).encode(),
        method="POST", headers={"Content-Type": "application/json", **(headers or {})},
    )
    try:
        with urllib.request.urlopen(req, timeout=10) as r:
            return r.status, json.loads(r.read() or b"null"), dict(r.headers)
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read() or b"null"), dict(e.headers)


def test_rest_batch_check_endpoint(daemon):
    tuples = [
        {"namespace": "acl", "object": f"obj-{i}", "relation": "access",
         "subject_id": f"user-{j}"}
        for i, j in [(0, 0), (1, 2), (3, 3)]
    ]
    status, payload, headers = _post(daemon.read_port, "/check/batch", {"tuples": tuples})
    assert status == 200
    assert payload["results"] == [True, False, True]
    assert "X-Keto-Snaptoken" in headers
    # empty and malformed payloads are 400s
    assert _post(daemon.read_port, "/check/batch", {"tuples": []})[0] == 400
    assert _post(daemon.read_port, "/check/batch", {"nope": 1})[0] == 400


def test_rest_priority_header(daemon):
    path = "/check?namespace=acl&object=obj-1&relation=access&subject_id=user-1"
    status, payload, _ = _get(daemon.read_port, path, {"X-Keto-Priority": "batch"})
    assert (status, payload["allowed"]) == (200, True)
    status, payload, _ = _get(
        daemon.read_port, path, {"X-Keto-Priority": "interactive"}
    )
    assert (status, payload["allowed"]) == (200, True)
    status, payload, _ = _get(daemon.read_port, path, {"X-Keto-Priority": "urgent"})
    assert status == 400
    assert "X-Keto-Priority" in payload["error"]["message"]


def test_rest_429_carries_retry_after(daemon):
    batcher = daemon.registry.check_batcher()
    orig = batcher.check_with_token

    def raiser(*a, **k):
        raise ErrTooManyRequests(retry_after_s=7)

    batcher.check_with_token = raiser
    try:
        status, payload, headers = _get(
            daemon.read_port,
            "/check?namespace=acl&object=obj-1&relation=access&subject_id=user-1",
        )
        assert status == 429
        assert headers["Retry-After"] == "7"
        assert payload["error"]["code"] == 429
    finally:
        batcher.check_with_token = orig


def test_rest_not_serving_503_carries_retry_after(daemon):
    from keto_tpu.driver.health import HealthState

    monitor = daemon.registry.health_monitor()
    monitor.set_override(HealthState.NOT_SERVING, "test drain")
    try:
        status, payload, headers = _get(daemon.read_port, "/health/ready")
        assert status == 503
        assert headers["Retry-After"] == "1"
    finally:
        monitor.set_override(None)


def test_grpc_resource_exhausted_carries_retry_after_metadata(daemon):
    import grpc
    from ory.keto.acl.v1alpha1 import acl_pb2, check_service_pb2

    batcher = daemon.registry.check_batcher()
    orig = batcher.check_with_token

    def raiser(*a, **k):
        raise ErrTooManyRequests(retry_after_s=3)

    batcher.check_with_token = raiser
    try:
        channel = grpc.insecure_channel(f"127.0.0.1:{daemon.read_port}")
        stub = channel.unary_unary(
            "/ory.keto.acl.v1alpha1.CheckService/Check",
            request_serializer=lambda m: m.SerializeToString(),
            response_deserializer=check_service_pb2.CheckResponse.FromString,
        )
        req = check_service_pb2.CheckRequest(
            namespace="acl", object="obj-1", relation="access",
            subject=acl_pb2.Subject(id="user-1"),
        )
        with pytest.raises(grpc.RpcError) as e:
            stub(req, timeout=10)
        assert e.value.code() == grpc.StatusCode.RESOURCE_EXHAUSTED
        trailing = {k: v for k, v in (e.value.trailing_metadata() or ())}
        assert trailing.get("retry-after") == "3"
        channel.close()
    finally:
        batcher.check_with_token = orig


def test_grpc_priority_metadata_accepted(daemon):
    import grpc
    from ory.keto.acl.v1alpha1 import acl_pb2, check_service_pb2

    channel = grpc.insecure_channel(f"127.0.0.1:{daemon.read_port}")
    stub = channel.unary_unary(
        "/ory.keto.acl.v1alpha1.CheckService/Check",
        request_serializer=lambda m: m.SerializeToString(),
        response_deserializer=check_service_pb2.CheckResponse.FromString,
    )
    req = check_service_pb2.CheckRequest(
        namespace="acl", object="obj-2", relation="access",
        subject=acl_pb2.Subject(id="user-2"),
    )
    resp = stub(req, metadata=(("x-keto-priority", "batch"),), timeout=10)
    assert resp.allowed is True
    with pytest.raises(grpc.RpcError) as e:
        stub(req, metadata=(("x-keto-priority", "urgent"),), timeout=10)
    assert e.value.code() == grpc.StatusCode.INVALID_ARGUMENT
    channel.close()


# -- SDK: retry budget + hedging ----------------------------------------------


class _CountingHandler(BaseHTTPRequestHandler):
    protocol_version = "HTTP/1.1"

    def do_GET(self):  # noqa: N802
        srv = self.server
        with srv.lock:
            srv.hits += 1
            n = srv.hits
        mode = srv.mode
        if mode == "brownout":
            body = json.dumps(
                {"error": {"code": 429, "status": "Too Many Requests",
                           "message": "shed"}}
            ).encode()
            self.send_response(429)
            self.send_header("Retry-After", "0")
        elif mode == "slow-first" and n == 1:
            time.sleep(1.5)
            body = json.dumps({"allowed": True}).encode()
            self.send_response(200)
        else:
            body = json.dumps({"allowed": True}).encode()
            self.send_response(200)
        self.send_header("Content-Type", "application/json")
        self.send_header("Content-Length", str(len(body)))
        self.end_headers()
        self.wfile.write(body)

    def log_message(self, *a):
        pass


@pytest.fixture
def counting_server():
    httpd = ThreadingHTTPServer(("127.0.0.1", 0), _CountingHandler)
    httpd.daemon_threads = True
    httpd.hits = 0
    httpd.lock = threading.Lock()
    httpd.mode = "brownout"
    t = threading.Thread(target=httpd.serve_forever, daemon=True)
    t.start()
    yield httpd
    httpd.shutdown()
    httpd.server_close()


def test_retry_budget_caps_brownout_amplification(counting_server):
    """30 reads against a server answering nothing but 429: the token
    bucket (ratio 0.1, initial 1) allows at most ~initial + 0.1×30
    retries on top of the 30 primaries — a brownout is never amplified
    into a retry storm."""
    url = f"http://127.0.0.1:{counting_server.server_address[1]}"
    client = KetoClient(url, url, retry_max_wait_s=5.0, retry_budget_ratio=0.1)
    n = 30
    for _ in range(n):
        with pytest.raises(ErrTooManyRequests):
            client.check(T("obj-1"))
    assert counting_server.hits <= n + 6, (
        f"retry storm: {counting_server.hits} requests for {n} primaries"
    )
    assert counting_server.hits > n  # some retries did happen (within budget)
    assert client.retry_budget.denied > 0  # and the budget said no to the rest


def test_retry_budget_accounting():
    budget = RetryBudget(ratio=0.5, cap=2.0, initial=1.0)
    assert budget.try_spend() is True
    assert budget.try_spend() is False  # empty
    budget.deposit()  # +0.5
    budget.deposit()  # +0.5 → 1.0
    assert budget.try_spend() is True
    assert budget.denied == 1 and budget.spent == 2


def test_hedged_read_amputates_slow_primary(counting_server):
    counting_server.mode = "slow-first"
    url = f"http://127.0.0.1:{counting_server.server_address[1]}"
    client = KetoClient(url, url, hedge_delay_s=0.05)
    t0 = time.monotonic()
    assert client.check(T("obj-1")) is True
    assert time.monotonic() - t0 < 1.2, "hedge did not amputate the slow primary"
    assert client.hedges_launched == 1
    assert client.hedges_won == 1


def test_hedging_is_budget_gated(counting_server):
    counting_server.mode = "slow-first"
    url = f"http://127.0.0.1:{counting_server.server_address[1]}"
    client = KetoClient(url, url, hedge_delay_s=0.05)
    client.retry_budget._tokens = 0.0  # empty bucket: no hedge allowed
    t0 = time.monotonic()
    assert client.check(T("obj-1")) is True
    assert time.monotonic() - t0 >= 1.0, "hedged despite an empty budget"
    assert client.hedges_launched == 0
    assert client.retry_budget.denied >= 1


# -- open-loop harness primitives ---------------------------------------------


def test_arrival_offsets_shapes():
    import random

    from bench import arrival_offsets

    rng = random.Random(11)
    for shape in ("steady", "burst", "diurnal"):
        offs = arrival_offsets(rng, rate=500.0, duration_s=4.0, shape=shape)
        assert all(0 <= t < 4.0 for t in offs)
        assert offs == sorted(offs)
        # mean rate within 20% of requested for every shape
        assert 0.8 * 2000 <= len(offs) <= 1.2 * 2000, (shape, len(offs))
    with pytest.raises(ValueError):
        arrival_offsets(rng, 10, 1.0, "square")


def test_open_loop_charges_lateness_to_latency():
    """Coordinated omission, closed: a stalled 'server' (slow fire fn)
    with one worker cannot slow the schedule — later requests are
    charged their queueing delay from the SCHEDULED arrival."""
    from bench import run_open_loop

    def slow_fire():
        time.sleep(0.05)
        return 200, False

    sched = [(0.0, "interactive", slow_fire), (0.01, "interactive", slow_fire),
             (0.02, "interactive", slow_fire)]
    recs, joined = run_open_loop(sched, n_workers=1)
    assert joined
    lats = sorted(r[1] for r in recs)
    # the third request waited behind two 50ms calls: ≥ ~80ms from its
    # scheduled arrival even though its own service took 50ms
    assert lats[-1] >= 0.08
