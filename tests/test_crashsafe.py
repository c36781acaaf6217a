"""Crash-safe write path: durable-cache hardening, client retries, drain.

The in-process half of the crash-safety story (the real-death half is
tests/test_chaos.py):

- the snapshot cache detects torn writes (size/crc32 per segment, torn
  meta.json) at load, QUARANTINES the corrupt directory (counted as
  ``cache_quarantined``) and rebuilds — never wrong decisions, never a
  crash;
- the REST SDK retries transient connection failures with jittered
  backoff: reads always, writes only when idempotency-keyed;
- idempotency keys GC past their TTL (a resend after the TTL applies as
  a fresh write);
- SIGTERM drain: in-flight checks accepted before shutdown complete
  normally — a rolling restart drops zero requests.
"""

import json
import socket
import threading
import time
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

import pytest

from keto_tpu import namespace as namespace_pkg
from keto_tpu.httpclient import KetoClient
from keto_tpu.relationtuple.model import RelationTuple, SubjectID, SubjectSet
from keto_tpu.x.errors import KetoError


def T(ns, obj, rel, sub):
    return RelationTuple(namespace=ns, object=obj, relation=rel, subject=sub)


NSS = [namespace_pkg.Namespace(id=0, name="d"), namespace_pkg.Namespace(id=1, name="g")]


def make_store():
    from keto_tpu.persistence.memory import MemoryPersister

    return MemoryPersister(namespace_pkg.MemoryManager(NSS))


# -- durable snapshot cache: torn writes detected, quarantined ----------------


def _saved_cache(tmp_path):
    from keto_tpu.graph import snapcache
    from keto_tpu.graph.snapshot import build_snapshot

    p = make_store()
    p.write_relation_tuples(
        T("d", "doc", "view", SubjectSet("g", "team", "member")),
        T("g", "team", "member", SubjectID("alice")),
    )
    rows, wm = p.snapshot_rows()
    cache = tmp_path / "snapcache"
    path = snapcache.save_snapshot(build_snapshot(rows, wm), str(cache))
    assert path is not None
    return cache, path, p


def test_cache_save_records_segment_manifest(tmp_path):
    cache, path, _ = _saved_cache(tmp_path)
    from pathlib import Path

    meta = json.loads((Path(path) / "meta.json").read_text())
    segments = meta["segments"]
    files = {f.name for f in Path(path).iterdir()} - {"meta.json"}
    assert set(segments) == files, "every data file must be checksummed"
    for entry in segments.values():
        assert set(entry) == {"size", "crc32"}


def test_cache_round_trip_verifies_clean(tmp_path):
    from keto_tpu.graph import snapcache

    _, path, p = _saved_cache(tmp_path)
    snap = snapcache.load_snapshot(path)  # verify=True is the default
    assert snap.snapshot_id == p.watermark()


class _Stats:
    def __init__(self):
        self.counts = {}

    def incr(self, key, by=1):
        self.counts[key] = self.counts.get(key, 0) + by


@pytest.mark.parametrize("victim", ["flip", "truncate", "torn-meta"])
def test_torn_cache_is_quarantined_not_served(tmp_path, victim):
    from pathlib import Path

    from keto_tpu.graph import snapcache

    cache, path, _ = _saved_cache(tmp_path)
    target = Path(path)
    if victim == "torn-meta":
        meta = (target / "meta.json").read_bytes()
        (target / "meta.json").write_bytes(meta[: len(meta) // 2])  # torn write
    else:
        seg = target / "fwd_indices.npy"
        data = bytearray(seg.read_bytes())
        if victim == "flip":
            data[len(data) // 2] ^= 0xFF  # bit rot / partial overwrite
        else:
            data = data[:-3]  # torn tail
        seg.write_bytes(bytes(data))

    stats = _Stats()
    assert snapcache.load_latest(str(cache), stats=stats) is None
    assert stats.counts.get("cache_quarantined") == 1
    assert not target.exists(), "corrupt cache left in the serving set"
    quarantined = [d for d in cache.iterdir() if d.name.startswith(".quarantine-")]
    assert len(quarantined) == 1, "corrupt cache not kept for forensics"
    # a second scan must not crash, re-quarantine, or resurrect it
    assert snapcache.load_latest(str(cache), stats=stats) is None
    assert stats.counts.get("cache_quarantined") == 1


def test_torn_cache_falls_back_to_older_good_cache(tmp_path):
    from pathlib import Path

    from keto_tpu.graph import snapcache
    from keto_tpu.graph.snapshot import build_snapshot

    cache, _, p = _saved_cache(tmp_path)
    p.write_relation_tuples(T("g", "team", "member", SubjectID("bob")))
    rows, wm = p.snapshot_rows()
    newest = snapcache.save_snapshot(build_snapshot(rows, wm), str(cache))
    seg = Path(newest) / "fwd_indices.npy"
    data = bytearray(seg.read_bytes())
    data[0] ^= 0xFF
    seg.write_bytes(bytes(data))

    stats = _Stats()
    snap = snapcache.load_latest(str(cache), stats=stats)
    assert snap is not None and snap.snapshot_id == 1, (
        "older intact cache should serve when the newest is corrupt"
    )
    assert stats.counts.get("cache_quarantined") == 1


def test_engine_rebuilds_identically_after_cache_corruption(tmp_path):
    """Engine-level recovery contract: a corrupt cache is rejected, the
    engine rebuilds from the store, decisions match a never-cached
    engine bit for bit, and the quarantine is counted."""
    from pathlib import Path

    from keto_tpu.check.tpu_engine import TpuCheckEngine

    cache = tmp_path / "snapcache"
    p = make_store()
    p.write_relation_tuples(
        T("d", "doc", "view", SubjectSet("g", "team", "member")),
        T("g", "team", "member", SubjectID("alice")),
    )
    a = TpuCheckEngine(p, p.namespaces, snapshot_cache_dir=str(cache))
    try:
        a.snapshot()
        assert a.save_snapshot_cache() is not None
    finally:
        a.close()
    # corrupt every cached dir so the cold engine must rebuild
    for d in list(cache.iterdir()):
        if d.is_dir() and not d.name.startswith("."):
            seg = Path(d) / "raw2dev.npy"
            data = bytearray(seg.read_bytes())
            data[-1] ^= 0x55
            seg.write_bytes(bytes(data))

    b = TpuCheckEngine(p, p.namespaces, snapshot_cache_dir=str(cache))
    oracle = TpuCheckEngine(p, p.namespaces)
    try:
        qs = [
            T("d", "doc", "view", SubjectID("alice")),
            T("d", "doc", "view", SubjectID("ghost")),
            T("g", "team", "member", SubjectID("alice")),
        ]
        assert b.batch_check(qs) == oracle.batch_check(qs)
        stats = b.maintenance.snapshot()
        assert stats.get("cache_quarantined", 0) >= 1
        assert stats.get("cache_loads", 0) == 0
        assert stats.get("full_rebuilds", 0) >= 1
    finally:
        b.close()
        oracle.close()


# -- idempotency key GC -------------------------------------------------------


def _gc_scenario(p):
    t1 = T("d", "doc", "view", SubjectID("alice"))
    t2 = T("d", "doc2", "view", SubjectID("bob"))
    first = p.transact_relation_tuples([t1], (), idempotency_key="gc-key")
    assert first.replayed is False
    # within the TTL the key replays…
    assert p.transact_relation_tuples([t1], (), idempotency_key="gc-key").replayed
    # …but with TTL 0 every later keyed write GCs it
    p.idempotency_ttl_s = 0.0
    time.sleep(1.1)  # sqlite created_at has second granularity
    p.transact_relation_tuples([t2], (), idempotency_key="other")
    res = p.transact_relation_tuples([t1], (), idempotency_key="gc-key")
    assert res.replayed is False, "expired key must not replay"
    assert res.snaptoken > first.snaptoken
    rows, _ = p.snapshot_rows()
    assert len(rows) == 3  # t1 applied twice (pre- and post-GC) + t2


def test_idempotency_gc_memory():
    _gc_scenario(make_store())


def test_idempotency_gc_sqlite(tmp_path):
    from keto_tpu.persistence.sqlite import SQLitePersister

    p = SQLitePersister(
        f"sqlite://{tmp_path/'gc.db'}", namespace_pkg.MemoryManager(NSS)
    )
    try:
        _gc_scenario(p)
    finally:
        p.close()


# -- httpclient: automatic retries against a flaky server ---------------------


class _FlakyHandler(BaseHTTPRequestHandler):
    """Drops the FIRST connection for every (method, path) — the request
    reaches the server and the connection dies before any response, the
    exact shape of a server crashing mid-request — then answers canned
    responses."""

    protocol_version = "HTTP/1.1"
    seen: set = set()
    lock = threading.Lock()

    def _maybe_drop(self) -> bool:
        key = (self.command, self.path.split("?")[0])
        with self.lock:
            if key not in self.seen:
                self.seen.add(key)
                # RST instead of FIN so the client can't mistake it for a
                # clean empty response
                self.connection.setsockopt(
                    socket.SOL_SOCKET, socket.SO_LINGER,
                    b"\x01\x00\x00\x00\x00\x00\x00\x00",
                )
                self.connection.close()
                return True
        return False

    def _reply(self, status, payload=None, headers=()):
        body = b"" if payload is None else json.dumps(payload).encode()
        self.send_response(status)
        self.send_header("Content-Type", "application/json")
        self.send_header("Content-Length", str(len(body)))
        for k, v in headers:
            self.send_header(k, v)
        self.end_headers()
        if body:
            self.wfile.write(body)

    def do_GET(self):
        if self._maybe_drop():
            return
        if self.path.startswith("/check"):
            self._reply(200, {"allowed": True})
        else:
            self._reply(200, {"status": "ok"})

    def do_PUT(self):
        if self._maybe_drop():
            return
        length = int(self.headers.get("Content-Length") or 0)
        body = json.loads(self.rfile.read(length) or b"{}")
        replay = ("X-Keto-Idempotent-Replay", "true") if (
            self.headers.get("X-Idempotency-Key")
        ) else None
        self._reply(201, body, [("X-Keto-Snaptoken", "7")] + ([replay] if replay else []))

    def do_PATCH(self):
        if self._maybe_drop():
            return
        length = int(self.headers.get("Content-Length") or 0)
        self.rfile.read(length)
        headers = [("X-Keto-Snaptoken", "9")]
        if self.headers.get("X-Idempotency-Key"):
            headers.append(("X-Keto-Idempotent-Replay", "true"))
        self._reply(204, None, headers)

    def log_message(self, fmt, *args):
        pass


@pytest.fixture
def flaky_server():
    _FlakyHandler.seen = set()
    httpd = ThreadingHTTPServer(("127.0.0.1", 0), _FlakyHandler)
    thread = threading.Thread(target=httpd.serve_forever, daemon=True)
    thread.start()
    url = f"http://127.0.0.1:{httpd.server_address[1]}"
    yield url
    httpd.shutdown()
    httpd.server_close()


def test_read_retries_through_flaky_connection(flaky_server):
    client = KetoClient(flaky_server, flaky_server, retry_max_wait_s=5.0)
    # first connection is dropped mid-request; the retry answers
    assert client.check(T("d", "doc", "view", SubjectID("alice"))) is True


def test_unkeyed_write_does_not_retry(flaky_server):
    client = KetoClient(flaky_server, flaky_server, retry_max_wait_s=5.0)
    with pytest.raises(Exception) as e:
        client.create_relation_tuple(T("d", "doc", "view", SubjectID("alice")))
    assert not isinstance(e.value, KetoError), (
        "the ambiguous connection failure must surface raw, not be retried"
    )
    # the server is healthy for the NEXT (explicit) attempt
    got = client.create_relation_tuple(T("d", "doc", "view", SubjectID("alice")))
    assert got.object == "doc"


def test_keyed_write_retries_and_reports_replay(flaky_server):
    client = KetoClient(flaky_server, flaky_server, retry_max_wait_s=5.0)
    resp = client.patch_relation_tuples(
        [T("d", "doc", "view", SubjectID("alice"))], idempotency_key="k1"
    )
    assert resp.snaptoken == 9
    assert resp.replayed is True  # the canned server marks keyed retries


def test_retry_budget_zero_disables_retries(flaky_server):
    client = KetoClient(flaky_server, flaky_server, retry_max_wait_s=0.0)
    with pytest.raises(Exception):
        client.check(T("d", "doc", "view", SubjectID("alice")))


# -- SIGTERM drain: zero dropped in-flight requests ---------------------------


def test_rolling_restart_drains_in_flight_checks():
    import urllib.request

    from keto_tpu.config.provider import Config
    from keto_tpu.driver.daemon import Daemon
    from keto_tpu.driver.registry import Registry

    cfg = Config(
        overrides={
            "namespaces": [{"id": 0, "name": "files"}],
            "dsn": "memory",
            "serve.read.port": 0,
            "serve.write.port": 0,
            "serve.drain_timeout_s": 10.0,
            # a wide coalescing window keeps requests IN FLIGHT (queued
            # in the batcher) when the drain starts
            "engine.batch_window_ms": 150.0,
        }
    )
    d = Daemon(Registry(cfg))
    d.serve_all(block=False)
    try:
        # seed + warm the engine so in-flight checks are pure queue time
        body = json.dumps(
            {"namespace": "files", "object": "f", "relation": "view",
             "subject_id": "alice"}
        ).encode()
        req = urllib.request.Request(
            f"http://127.0.0.1:{d.write_port}/relation-tuples",
            data=body, method="PUT",
        )
        urllib.request.urlopen(req, timeout=10).read()
        url = f"http://127.0.0.1:{d.read_port}/check?namespace=files&object=f&relation=view&subject_id=alice"
        with urllib.request.urlopen(url, timeout=10) as r:
            assert r.status == 200

        results: list = []
        lock = threading.Lock()

        def one_check(i):
            try:
                with urllib.request.urlopen(url, timeout=15) as r:
                    status = r.status
            except Exception as e:
                status = e
            with lock:
                results.append(status)

        n = 32
        threads = [
            threading.Thread(target=one_check, args=(i,)) for i in range(n)
        ]
        for t in threads:
            t.start()
        # the contract is about requests ACCEPTED before the signal: wait
        # until the batcher holds (or has answered) every one of them, not
        # for a span of wall clock a loaded host may spend starting threads
        batcher = d.registry.peek("check_batcher")
        give_up = time.monotonic() + 10.0
        while time.monotonic() < give_up:
            with lock:
                answered = len(results)
            if answered + batcher.inflight >= n:
                break
            time.sleep(0.002)
        d.drain_and_shutdown()
        for t in threads:
            t.join(timeout=20)
        assert len(results) == n
        dropped = [r for r in results if r != 200]
        assert not dropped, f"rolling restart dropped in-flight requests: {dropped!r}"
    finally:
        d.shutdown()  # idempotent


def test_drain_resolves_both_priority_lanes():
    """SIGTERM drain while BOTH batcher lanes are non-empty: every
    accepted request — the monster batch-lane chunk mid-sub-slicing AND
    the interactive checks queued around it — resolves definitively
    (served, or shed with a real status), and nothing hangs."""
    import urllib.error
    import urllib.request

    from keto_tpu.config.provider import Config
    from keto_tpu.driver.daemon import Daemon
    from keto_tpu.driver.registry import Registry

    cfg = Config(
        overrides={
            "namespaces": [{"id": 0, "name": "files"}],
            "dsn": "memory",
            "serve.read.port": 0,
            "serve.write.port": 0,
            "serve.drain_timeout_s": 10.0,
            # a wide coalescing window + small sub-slices keep the batch
            # chunk spanning several dispatch rounds when the drain hits:
            # eight with the interactive checks about (64 a round), two
            # should a round find their lane quiet (256 a round)
            "engine.batch_window_ms": 100.0,
            "engine.batch_size": 256,
            "serve.batch_sub_slice": 64,
        }
    )
    d = Daemon(Registry(cfg))
    d.serve_all(block=False)
    try:
        body = json.dumps(
            {"namespace": "files", "object": "f", "relation": "view",
             "subject_id": "alice"}
        ).encode()
        urllib.request.urlopen(
            urllib.request.Request(
                f"http://127.0.0.1:{d.write_port}/relation-tuples",
                data=body, method="PUT",
            ),
            timeout=10,
        ).read()
        url = (
            f"http://127.0.0.1:{d.read_port}/check?namespace=files&object=f"
            f"&relation=view&subject_id=alice"
        )
        with urllib.request.urlopen(url, timeout=10) as r:  # warm engine
            assert r.status == 200

        results: list = []
        lock = threading.Lock()

        def record(kind, outcome):
            with lock:
                results.append((kind, outcome))

        def one_interactive(_):
            try:
                with urllib.request.urlopen(url, timeout=20) as r:
                    record("interactive", r.status)
            except urllib.error.HTTPError as e:
                record("interactive", e.code)
            except Exception as e:
                record("interactive", e)

        def one_batch():
            payload = json.dumps(
                {"tuples": [
                    {"namespace": "files", "object": "f", "relation": "view",
                     "subject_id": "alice"}
                ] * 512}
            ).encode()
            req = urllib.request.Request(
                f"http://127.0.0.1:{d.read_port}/check/batch", data=payload,
                method="POST", headers={"Content-Type": "application/json"},
            )
            try:
                with urllib.request.urlopen(req, timeout=20) as r:
                    n = len(json.loads(r.read())["results"])
                    record("batch", r.status if n == 512 else f"short: {n}")
            except urllib.error.HTTPError as e:
                record("batch", e.code)
            except Exception as e:
                record("batch", e)

        threads = [threading.Thread(target=one_batch, daemon=True)]
        threads += [
            threading.Thread(target=one_interactive, args=(i,), daemon=True)
            for i in range(8)
        ]
        for t in threads:
            t.start()
        # drain only once both lanes actually hold queued work
        batcher = d.registry.check_batcher()
        deadline = time.monotonic() + 5
        while time.monotonic() < deadline:
            depths = batcher.lane_depths
            if depths["interactive"] > 0 and depths["batch"] > 0:
                break
            time.sleep(0.005)
        d.drain_and_shutdown()
        for t in threads:
            t.join(timeout=30)
        assert not any(t.is_alive() for t in threads), (
            f"drain left lane callers hanging: {results!r}"
        )
        assert len(results) == 9
        # every outcome is a definitive HTTP status — served (200) or
        # shed with an explicit overload/unavailable answer — never an
        # exception, a short batch, or a hang
        bad = [r for r in results if r[1] not in (200, 403, 429, 503, 504)]
        assert not bad, f"non-definitive outcomes across drain: {bad!r}"
    finally:
        d.shutdown()  # idempotent


def test_shutdown_signal_event_unblocks_serve_all():
    from keto_tpu.config.provider import Config
    from keto_tpu.driver.daemon import Daemon
    from keto_tpu.driver.registry import Registry

    cfg = Config(
        overrides={
            "namespaces": [{"id": 0, "name": "files"}],
            "dsn": "memory",
            "serve.read.port": 0,
            "serve.write.port": 0,
            "serve.drain_timeout_s": 1.0,
        }
    )
    d = Daemon(Registry(cfg))
    d._on_signal(15, None)  # what the SIGTERM handler does
    # the blocking loop observes the pre-set event, drains, and returns
    t0 = time.monotonic()
    d.serve_all(block=True)
    assert time.monotonic() - t0 < 30
    assert not d._roles, "serve_all(block=True) returned without shutdown"
