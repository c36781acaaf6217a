"""Replica tier (keto_tpu/replica/): bootstrap, feed, gate, cache, chaos.

Covers the full failure matrix the replication design document promises:

- **store** — commit groups land at their primary snaptokens with
  exactly-once application (watermark-guarded), bootstrap raises every
  horizon, the public write path is closed;
- **check cache** — snaptoken-window semantics, global Watch
  invalidation, the insert-after-invalidation race, LRU bounds, and a
  fuzz proof that the cache NEVER serves a hit an applied delta
  invalidated;
- **controller** — bootstrap protocol against a stubbed primary, the
  durable applied-watermark, 412 gate semantics, and the 410→automatic
  re-bootstrap contract (never a crash loop);
- **horizon hygiene** — time-based change-log GC on the memory and
  sqlite stores expires old watch resumes;
- **e2e** — a real primary + replica daemon pair: parity of
  check/expand/list at matching snaptokens, 412 + Retry-After +
  X-Keto-Watermark above the watermark, 403 writes, the replica
  /health/ready body, /snapshot/export surfaces, SDK bounded-staleness
  routing with primary fallback;
- **chaos** — SIGKILL a replica mid-stream and the primary mid-commit
  over one sqlite file; the replica resumes from its durable watermark
  with exactly-once application and bit-parity vs the primary AND the
  CPU oracle.
"""

import json
import os
import threading
import time
import urllib.error
import urllib.request
from pathlib import Path

import pytest

from keto_tpu import namespace as namespace_pkg
from keto_tpu.replica.checkcache import CheckCache
from keto_tpu.replica.controller import DurableWatermark, ReplicaController
from keto_tpu.replica.store import ReplicaStore
from keto_tpu.relationtuple.model import (
    RelationQuery,
    RelationTuple,
    SubjectID,
    SubjectSet,
)
from keto_tpu.x.errors import (
    ErrPreconditionFailed,
    ErrReplicaReadOnly,
    ErrServiceUnavailable,
    ErrWatchExpired,
)

NAMESPACES = [
    namespace_pkg.Namespace(id=0, name="docs"),
    namespace_pkg.Namespace(id=1, name="groups"),
]


def nm():
    return namespace_pkg.MemoryManager(NAMESPACES)


def T(obj, sub, ns="docs", rel="view"):
    subject = sub if not isinstance(sub, str) else SubjectID(sub)
    return RelationTuple(namespace=ns, object=obj, relation=rel, subject=subject)


# -- ReplicaStore -------------------------------------------------------------


def test_apply_commit_lands_at_primary_tokens_exactly_once():
    s = ReplicaStore(nm())
    assert s.apply_commit(5, [T("a", "u1")], [])
    assert s.watermark() == 5
    # re-delivery (watch reconnect replay) is skipped, not re-applied
    assert not s.apply_commit(5, [T("a", "u1")], [])
    assert not s.apply_commit(3, [T("b", "u2")], [])
    assert s.skipped_commits == 2
    # gaps in the token sequence are fine — the commit lands at its token
    assert s.apply_commit(9, [T("b", "u2")], [T("a", "u1")])
    assert s.watermark() == 9
    assert s.applied_commits == 2
    rels, _ = s.get_relation_tuples(RelationQuery())
    assert sorted(map(str, rels)) == ["docs:b#view@u2"]


def test_replica_store_watch_carries_primary_tokens():
    s = ReplicaStore(nm())
    s.apply_commit(7, [T("a", "u1")], [])
    s.apply_commit(12, [T("b", "u2")], [])
    groups, wm = s.watch_changes_since(0)
    assert wm == 12
    assert [g[0] for g in groups] == [7, 12]


def test_bootstrap_replaces_and_raises_horizons():
    s = ReplicaStore(nm())
    s.apply_commit(3, [T("old", "u0")], [])
    s.bootstrap([T("a", "u1"), T("b", "u2")], 40)
    assert s.watermark() == 40
    assert s.bootstraps == 1
    # deltas and watch resumes from before the bootstrap cannot be served
    assert s.rows_since(3) is None
    assert s.changes_since(3) is None
    with pytest.raises(ErrWatchExpired):
        s.watch_changes_since(3)
    # ...but from the bootstrap watermark itself, they can
    groups, wm = s.watch_changes_since(40)
    assert groups == [] and wm == 40
    rows, _ = s.rows_since(40)
    assert rows == []
    # state is the bootstrap set, not a merge with the old state
    rels, _ = s.get_relation_tuples(RelationQuery())
    assert sorted(map(str, rels)) == ["docs:a#view@u1", "docs:b#view@u2"]


def test_public_write_path_is_closed():
    s = ReplicaStore(nm())
    with pytest.raises(ErrReplicaReadOnly):
        s.transact_relation_tuples([T("a", "u1")], ())
    with pytest.raises(ErrReplicaReadOnly):
        s.write_relation_tuples(T("a", "u1"))
    with pytest.raises(ErrReplicaReadOnly):
        s.delete_relation_tuples(T("a", "u1"))


# -- CheckCache ---------------------------------------------------------------


def test_checkcache_open_and_closed_windows():
    c = CheckCache(entries=16)
    assert c.get("k", None) is None  # miss
    assert c.put("k", True, 10)
    # open entry: serves tokenless and any admitted pin
    assert c.get("k", None) == (True, 10)
    assert c.get("k", 4) == (True, 10)
    # an applied delta closes the window at 15
    assert c.note_commit(15) == 1
    # tokenless means "current": a closed window never serves it
    assert c.get("k", None) is None
    # pinned below the close still hits (states 10..14 are identical)
    assert c.get("k", 12) == (True, 12)
    assert c.get("k", 10) == (True, 10)
    # pinned at/above the close is bypassed
    assert c.get("k", 15) is None
    assert c.get("k", 99) is None
    snap = c.snapshot()
    assert snap["hits"] == 4 and snap["invalidations"] == 1


def test_checkcache_put_after_invalidation_is_dropped():
    c = CheckCache(entries=16)
    c.note_commit(20)
    # a decision computed at a pre-invalidation state must not enter open
    assert not c.put("k", True, 19)
    assert c.get("k", None) is None
    # computed at the invalidation point or later is fine
    assert c.put("k", False, 20)
    assert c.get("k", None) == (False, 20)


def test_checkcache_lru_bound():
    c = CheckCache(entries=4)
    for i in range(8):
        c.put(f"k{i}", True, 1)
    assert len(c) == 4
    assert c.get("k0", None) is None
    assert c.get("k7", None) == (True, 1)


def test_checkcache_fuzz_never_serves_invalidated():
    """The acceptance bar: across random writes/invalidations and reads
    (tokenless and pinned), a cache hit must always equal a true decision
    at SOME state satisfying the request's freshness — never a decision
    an applied delta invalidated."""
    import random

    rng = random.Random(7)
    c = CheckCache(entries=64)
    keys = [f"t{i}" for i in range(12)]
    token = 100
    world: set = set()
    history = [(token, frozenset(world))]  # (token, state) per commit

    def decision_at(t, key):
        state = history[0][1]
        for tok, st in history:
            if tok <= t:
                state = st
            else:
                break
        return key in state

    for _ in range(3000):
        op = rng.random()
        if op < 0.25:
            # a commit applies: mutate the world, close every open window
            token += rng.randint(1, 3)
            k = rng.choice(keys)
            world.symmetric_difference_update({k})
            history.append((token, frozenset(world)))
            c.note_commit(token)
        elif op < 0.65:
            # tokenless read: a hit must equal the CURRENT decision
            k = rng.choice(keys)
            got = c.get(k, None)
            if got is not None:
                assert got[0] == decision_at(token, k), (k, token)
            else:
                c.put(k, decision_at(token, k), token)
        else:
            # pinned read at_least=S (gate-admitted: S <= watermark): a
            # hit must equal the decision at some state in [S, token]
            k = rng.choice(keys)
            S = rng.randint(100, token)
            got = c.get(k, S)
            if got is not None:
                candidates = {
                    decision_at(t, k)
                    for t, _ in history
                    if S <= t <= token
                }
                candidates.add(decision_at(S, k))
                assert got[0] in candidates, (k, S, token)
    assert c.snapshot()["hits"] > 100  # the fuzz exercised real hits


# -- DurableWatermark ---------------------------------------------------------


def test_durable_watermark_roundtrip(tmp_path):
    d = DurableWatermark(tmp_path / "wm.json")
    assert d.load() is None
    d.store(41)
    d.store(42)
    # a fresh reader (the restarted process) sees the last stored token
    d2 = DurableWatermark(tmp_path / "wm.json")
    assert d2.load() == 42
    # corrupt file reads as absent, never a crash
    (tmp_path / "wm.json").write_text("{torn")
    assert d2.load() is None


# -- ReplicaController against a stubbed primary ------------------------------


class StubPrimary:
    """An in-memory primary: export + watch over a scripted commit log."""

    def __init__(self):
        self.state: dict = {}  # str -> RelationTuple
        self.watermark = 0
        self.pending: list = []  # (token, [(action, rt)]) retained log
        self.expire_next_watch = False
        self.lock = threading.Lock()
        self.closed = threading.Event()
        # set → live watch generators end (a primary drain / lost
        # connection as the feed experiences it)
        self.end_streams = threading.Event()

    def commit(self, token, changes):
        with self.lock:
            self.watermark = token
            for action, rt in changes:
                if action == "insert":
                    self.state[str(rt)] = rt
                else:
                    self.state.pop(str(rt), None)
            self.pending.append((token, list(changes)))

    # -- the KetoClient surface the controller uses --

    def snapshot_export_manifest(self):
        return {"watermark": str(self.watermark), "format": 1, "cache": None}

    def fetch_snapshot_export(self):
        with self.lock:
            return self.watermark, list(self.state.values())

    def fetch_snapshot_segment(self, tag, name):  # pragma: no cover
        raise AssertionError("no cache advertised")

    def watch(self, snaptoken=0):
        if self.expire_next_watch:
            self.expire_next_watch = False
            raise ErrWatchExpired()
        while not self.closed.is_set() and not self.end_streams.is_set():
            with self.lock:
                ready = [g for g in self.pending if g[0] > snaptoken]
            for token, changes in ready:
                yield token, changes
                snaptoken = token
            time.sleep(0.01)


def make_controller(tmp_path, stub, store=None, **kw):
    store = store or ReplicaStore(nm())
    ctl = ReplicaController(
        store,
        lambda: _NullEngine(),
        "http://primary.test",
        replica_dir=str(tmp_path / "replica"),
        staleness_wait_ms=kw.pop("staleness_wait_ms", 300.0),
        staleness_budget_s=kw.pop("staleness_budget_s", 30.0),
        probe_s=0.05,
        client_factory=lambda: stub,
        **kw,
    )
    return ctl, store


class _NullEngine:
    def snapshot_serving(self):
        return None

    def snapshot(self):
        return None


def wait_until(cond, timeout=10.0, what="condition"):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if cond():
            return
        time.sleep(0.01)
    raise AssertionError(f"timed out waiting for {what}")


def test_controller_bootstrap_feed_and_durable_watermark(tmp_path):
    stub = StubPrimary()
    stub.commit(5, [("insert", T("a", "u1"))])
    ctl, store = make_controller(tmp_path, stub)
    try:
        ctl.start()
        wait_until(lambda: ctl.bootstrapped, what="bootstrap")
        assert ctl.watermark == 5
        assert ctl.durable.load() == 5
        # live commits apply at their tokens and persist the watermark
        stub.commit(9, [("insert", T("b", "u2"))])
        stub.commit(11, [("delete", T("a", "u1"))])
        wait_until(lambda: ctl.watermark == 11, what="feed catch-up")
        # the store's watermark moves in apply_commit, the durable file an
        # fsync later on the feed thread: wait for it, do not race it
        wait_until(lambda: ctl.durable.load() == 11, what="durable watermark")
        assert store.applied_commits == 2
        from keto_tpu.relationtuple.model import RelationQuery

        rels, _ = store.get_relation_tuples(RelationQuery())
        assert sorted(map(str, rels)) == ["docs:b#view@u2"]
        # gate: at/below the watermark passes; above it waits then 412s
        ctl.gate_read(11)
        with pytest.raises(ErrPreconditionFailed) as ei:
            ctl.gate_read(99)
        assert ei.value.details["watermark"] == "11"
        assert ei.value.retry_after_s
        with pytest.raises(ErrPreconditionFailed):
            ctl.gate_read(None, latest=True)
        # a waiter blocked on a pin is released by the apply, not the
        # timeout
        t0 = time.monotonic()
        results = []

        def waiter():
            ctl2_wait_start = time.monotonic()
            ctl.gate_read(14)
            results.append(time.monotonic() - ctl2_wait_start)

        ctl_thread = threading.Thread(target=waiter)
        ctl_thread.start()
        time.sleep(0.03)
        stub.commit(14, [("insert", T("c", "u3"))])
        ctl_thread.join(timeout=5)
        assert results and results[0] < 2.0
        assert time.monotonic() - t0 < 5.0
    finally:
        stub.closed.set()
        ctl.stop()


def test_controller_horizon_loss_triggers_rebootstrap(tmp_path):
    """ErrWatchExpired from the feed is an automatic full re-bootstrap —
    the satellite contract: never a crash loop, never silent divergence."""
    stub = StubPrimary()
    stub.commit(3, [("insert", T("a", "u1"))])
    ctl, store = make_controller(tmp_path, stub)
    try:
        ctl.start()
        wait_until(lambda: ctl.bootstrapped, what="first bootstrap")
        # the primary GC'd its log: history the replica never saw changed
        # the state, the live stream drops, and the re-subscribe answers
        # 410 — recovery MUST be a full re-bootstrap
        stub.commit(8, [("insert", T("b", "u2"))])
        stub.pending.clear()  # that group is gone from the log forever
        stub.expire_next_watch = True
        stub.end_streams.set()  # the live generator ends at its next poll
        wait_until(
            lambda: ctl.bootstraps >= 2 and ctl.watermark == 8,
            what="re-bootstrap",
        )
        rels, _ = store.get_relation_tuples(RelationQuery())
        assert sorted(map(str, rels)) == [
            "docs:a#view@u1", "docs:b#view@u2",
        ]
    finally:
        stub.closed.set()
        ctl.stop()


def test_controller_skips_redelivered_groups(tmp_path):
    """A watch replay below the watermark (a reconnect re-serving
    already-applied groups) is skipped by the store guard — exactly-once
    — never re-applied."""
    stub = StubPrimary()
    stub.commit(4, [("insert", T("a", "u1"))])
    real_watch = stub.watch
    # a faulty feed that ignores the resume cursor and replays from 0
    stub.watch = lambda snaptoken=0: real_watch(snaptoken=0)
    ctl, store = make_controller(tmp_path, stub)
    try:
        ctl.start()
        wait_until(lambda: ctl.bootstrapped, what="bootstrap")
        wait_until(
            lambda: store.skipped_commits >= 1, what="replayed group skipped"
        )
        assert store.applied_commits == 0  # nothing double-applied
        assert ctl.watermark == 4
        rels, _ = store.get_relation_tuples(RelationQuery())
        assert sorted(map(str, rels)) == ["docs:a#view@u1"]
    finally:
        stub.closed.set()
        ctl.stop()


# -- watch-log horizon hygiene (memory + sql_base) ----------------------------


def test_memory_watch_log_time_gc():
    from keto_tpu.persistence.memory import MemoryPersister

    p = MemoryPersister(nm())
    p.watch_log_retention_s = 3600.0
    p.write_relation_tuples(T("a", "u1"))
    p.write_relation_tuples(T("b", "u2"))
    p.delete_relation_tuples(T("a", "u1"))
    wm = p.watermark()
    # within the window: everything replays
    groups, _ = p.watch_changes_since(0)
    assert len(groups) == 3
    # beyond the window: entries prune, floors rise, old resumes expire
    pruned = p.gc_watch_logs(now=time.time() + 3601.0)
    assert pruned > 0
    with pytest.raises(ErrWatchExpired):
        p.watch_changes_since(0)
    assert p.rows_since(0) is None
    # resuming from the current watermark still works
    groups, got_wm = p.watch_changes_since(wm)
    assert groups == [] and got_wm == wm
    # new commits replay from the new horizon
    p.write_relation_tuples(T("c", "u3"))
    groups, _ = p.watch_changes_since(wm)
    assert len(groups) == 1


def test_sqlite_watch_log_time_gc(tmp_path):
    from keto_tpu.persistence.sqlite import SQLitePersister

    p = SQLitePersister(f"sqlite://{tmp_path/'gc.db'}", nm())
    p.write_relation_tuples(T("a", "u1"))
    p.write_relation_tuples(T("b", "u2"))
    p.delete_relation_tuples(T("a", "u1"))
    groups, wm = p.watch_changes_since(0)
    # the deleted tuple's insert elides (documented replay elision);
    # the surviving insert and the delete replay
    assert len(groups) == 2
    # sub-second retention truncates to 0 in SQL epoch terms: every
    # existing delete-log entry is already "older than the window"
    p.watch_log_retention_s = 0.5
    pruned = p.gc_watch_logs()
    assert pruned == 1  # the one delete-log row
    with pytest.raises(ErrWatchExpired):
        p.watch_changes_since(0)
    groups, got_wm = p.watch_changes_since(wm)
    assert groups == [] and got_wm == wm


# -- e2e: a real primary + replica daemon pair --------------------------------


@pytest.fixture
def replica_pair(tmp_path):
    from keto_tpu.config.provider import Config
    from keto_tpu.driver.daemon import Daemon
    from keto_tpu.driver.registry import Registry
    from keto_tpu.httpclient import KetoClient

    ns_json = [{"id": 0, "name": "docs"}, {"id": 1, "name": "groups"}]
    primary_cfg = Config(
        overrides={
            "namespaces": ns_json,
            "dsn": "memory",
            "serve.read.port": 0,
            "serve.write.port": 0,
            "serve.watch_poll_ms": 20,
            "serve.snapshot_cache_dir": str(tmp_path / "primary-cache"),
        }
    )
    primary = Daemon(Registry(primary_cfg))
    primary.serve_all(block=False)
    replica_cfg = Config(
        overrides={
            "namespaces": ns_json,
            "dsn": "memory",  # ignored by design: replicas hold no store
            "serve.read.port": 0,
            "serve.write.port": 0,
            "serve.role": "replica",
            "serve.primary_url": f"http://127.0.0.1:{primary.read_port}",
            "serve.replica_dir": str(tmp_path / "replica"),
            "serve.snapshot_cache_dir": str(tmp_path / "replica-cache"),
            "serve.watch_poll_ms": 20,
            "serve.staleness_wait_ms": 1500.0,
        }
    )
    replica = Daemon(Registry(replica_cfg))
    replica.serve_all(block=False)
    pc = KetoClient(
        f"http://127.0.0.1:{primary.read_port}",
        f"http://127.0.0.1:{primary.write_port}",
    )
    rc = KetoClient(
        f"http://127.0.0.1:{replica.read_port}",
        f"http://127.0.0.1:{replica.write_port}",
    )
    yield primary, replica, pc, rc
    replica.shutdown()
    primary.shutdown()


def ready_body(port):
    with urllib.request.urlopen(
        f"http://127.0.0.1:{port}/health/ready", timeout=5
    ) as resp:
        return json.loads(resp.read())


def wait_replica_ready(replica, timeout=30.0):
    def ok():
        try:
            body = ready_body(replica.read_port)
        except Exception:
            return False
        return body.get("role") == "replica" and body.get("status") == "ok"

    wait_until(ok, timeout=timeout, what="replica SERVING")


def test_replica_e2e_contract(replica_pair):
    primary, replica, pc, rc = replica_pair
    wait_replica_ready(replica)

    # -- writes land on the primary; replica serves them at the pin
    pc.create_relation_tuple(T("m1", "ann", ns="groups", rel="member"))
    res = pc.patch_relation_tuples(
        insert=[
            T("readme", SubjectSet("groups", "m1", "member")),
            T("readme", "bob"),
        ]
    )
    token = res.snaptoken
    assert token is not None
    # pinned read on the replica: blocks until applied, then parity
    assert rc.check(T("readme", "ann"), snaptoken=token)
    assert rc.check(T("readme", "bob"), snaptoken=token)
    assert not rc.check(T("readme", "eve"), snaptoken=token)

    # -- /health/ready carries the replication picture
    body = ready_body(replica.read_port)
    assert body["role"] == "replica"
    assert int(body["watermark"]) >= token
    assert isinstance(body["lag_s"], (int, float))
    assert body["primary_connected"] is True

    # -- expand + list parity at the same pin
    assert str(pc.expand("docs", "readme", "view", 4)) == str(
        rc.expand("docs", "readme", "view", 4)
    )
    assert list(
        rc.list_subjects("docs", "readme", "view", snaptoken=token)
    ) == list(pc.list_subjects("docs", "readme", "view", snaptoken=token))
    assert list(
        rc.list_objects("docs", "view", SubjectID("ann"), snaptoken=token)
    ) == ["readme"]

    # -- a pin far above the watermark answers 412 + advice + watermark
    with pytest.raises(urllib.error.HTTPError) as ei:
        urllib.request.urlopen(
            f"http://127.0.0.1:{replica.read_port}/check?namespace=docs"
            f"&object=readme&relation=view&subject_id=ann"
            f"&snaptoken={token + 1000}&timeout_ms=30000",
            timeout=10,
        )
    assert ei.value.code == 412
    assert ei.value.headers.get("Retry-After")
    assert int(ei.value.headers["X-Keto-Watermark"]) >= token
    err = json.loads(ei.value.read())
    assert err["error"]["details"]["watermark"]

    # -- latest=true is a primary-only promise
    with pytest.raises(urllib.error.HTTPError) as ei:
        urllib.request.urlopen(
            f"http://127.0.0.1:{replica.read_port}/relation-tuples/list-subjects"
            "?namespace=docs&object=readme&relation=view&latest=true",
            timeout=10,
        )
    assert ei.value.code == 412

    # -- writes to the replica are refused with 403 on every verb
    with pytest.raises(ErrReplicaReadOnly):
        rc.create_relation_tuple(T("x", "u"))
    with pytest.raises(ErrReplicaReadOnly):
        rc.patch_relation_tuples(insert=[T("x", "u")])
    with pytest.raises(ErrReplicaReadOnly):
        rc.delete_relation_tuple(T("readme", "bob"))

    # -- check cache: second identical read hits; an applied delta
    # invalidates (zero stale hits after invalidation)
    q = (
        f"http://127.0.0.1:{replica.read_port}/check?namespace=docs"
        "&object=readme&relation=view&subject_id=bob"
    )
    urllib.request.urlopen(q, timeout=10).read()
    with urllib.request.urlopen(q, timeout=10) as resp:
        assert resp.headers.get("X-Keto-Checkcache") == "hit"
    pc.delete_relation_tuple(T("readme", "bob"))
    wm_after = int(
        pc.snapshot_export_manifest()["watermark"]
    )
    # once the replica applied the delete, the tokenless read must NOT
    # serve the invalidated cached allow
    def replica_caught_up():
        return int(ready_body(replica.read_port)["watermark"]) >= wm_after

    wait_until(replica_caught_up, what="replica applies the delete")
    assert not rc.check(T("readme", "bob"))

    # -- /snapshot/export surfaces on the primary
    manifest = pc.snapshot_export_manifest()
    assert int(manifest["watermark"]) >= wm_after
    wm, tuples = pc.fetch_snapshot_export()
    assert wm >= wm_after
    assert "docs:readme#view@bob" not in {str(t) for t in tuples}
    assert "groups:m1#member@ann" in {str(t) for t in tuples}
    # malformed segment requests are 400, unknown segments 404
    for q, want in (
        ("?cache=v6-w1", 400),
        ("?segment=x.npy", 400),
        ("?cache=..%2Fescape&segment=meta.json", 400),
        ("?stream=bogus", 400),
        ("?cache=v6-w999999&segment=meta.json", 404),
    ):
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(
                f"http://127.0.0.1:{primary.read_port}/snapshot/export{q}",
                timeout=10,
            )
        assert ei.value.code == want, q

    # -- SDK bounded-staleness routing: reads ride the replica, fall
    # back to the primary on connection failure / 412
    from keto_tpu.httpclient import KetoClient

    routed = KetoClient(
        f"http://127.0.0.1:{primary.read_port}",
        f"http://127.0.0.1:{primary.write_port}",
        replica_read_urls=[f"http://127.0.0.1:{replica.read_port}"],
    )
    assert routed.check(T("readme", "ann"))
    assert routed.replica_fallbacks == 0
    dead = KetoClient(
        f"http://127.0.0.1:{primary.read_port}",
        f"http://127.0.0.1:{primary.write_port}",
        replica_read_urls=["http://127.0.0.1:1"],  # nothing listens
    )
    assert dead.check(T("readme", "ann"))
    assert dead.replica_fallbacks == 1
    # latest reads pin the primary (and succeed there)
    assert list(
        routed.list_subjects("docs", "readme", "view", latest=True)
    ) == list(pc.list_subjects("docs", "readme", "view"))


def test_replica_e2e_grpc_paths(replica_pair):
    """gRPC on the replica: Check serves (and caches), writes refuse
    with PERMISSION_DENIED, pins above the watermark FAILED_PRECONDITION."""
    grpc = pytest.importorskip("grpc")
    from ory.keto.acl.v1alpha1 import acl_pb2, check_service_pb2

    primary, replica, pc, rc = replica_pair
    wait_replica_ready(replica)
    res = pc.patch_relation_tuples(insert=[T("doc1", "zoe")])
    token = res.snaptoken

    chan = grpc.insecure_channel(f"127.0.0.1:{replica.read_port}")
    check = chan.unary_unary(
        "/ory.keto.acl.v1alpha1.CheckService/Check",
        request_serializer=check_service_pb2.CheckRequest.SerializeToString,
        response_deserializer=check_service_pb2.CheckResponse.FromString,
    )
    req = check_service_pb2.CheckRequest(
        namespace="docs", object="doc1", relation="view",
        subject=acl_pb2.Subject(id="zoe"), snaptoken=str(token),
    )
    assert check(req, timeout=10).allowed
    # far-future pin → FAILED_PRECONDITION
    req_future = check_service_pb2.CheckRequest(
        namespace="docs", object="doc1", relation="view",
        subject=acl_pb2.Subject(id="zoe"), snaptoken=str(token + 10_000),
    )
    with pytest.raises(grpc.RpcError) as ei:
        check(req_future, timeout=10)
    assert ei.value.code() == grpc.StatusCode.FAILED_PRECONDITION
    # writes refuse
    from ory.keto.acl.v1alpha1 import write_service_pb2

    wchan = grpc.insecure_channel(f"127.0.0.1:{replica.write_port}")
    transact = wchan.unary_unary(
        "/ory.keto.acl.v1alpha1.WriteService/TransactRelationTuples",
        request_serializer=(
            write_service_pb2.TransactRelationTuplesRequest.SerializeToString
        ),
        response_deserializer=(
            write_service_pb2.TransactRelationTuplesResponse.FromString
        ),
    )
    delta = write_service_pb2.RelationTupleDelta(
        action=write_service_pb2.RelationTupleDelta.INSERT,
        relation_tuple=acl_pb2.RelationTuple(
            namespace="docs", object="x", relation="view",
            subject=acl_pb2.Subject(id="u"),
        ),
    )
    with pytest.raises(grpc.RpcError) as ei:
        transact(
            write_service_pb2.TransactRelationTuplesRequest(
                relation_tuple_deltas=[delta]
            ),
            timeout=10,
        )
    assert ei.value.code() == grpc.StatusCode.PERMISSION_DENIED
    chan.close()
    wchan.close()


# -- chaos: SIGKILL the replica mid-stream and the primary mid-commit ---------


def test_replica_chaos_sigkill_resume_and_primary_failover(tmp_path):
    """The acceptance chaos scenario over one sqlite file:

    1. a replica SIGKILL'd mid-stream restarts, resumes from its durable
       applied-watermark with exactly-once application, and reaches
       bit-parity with the primary AND the CPU oracle at matching
       snaptokens;
    2. the primary killed mid-commit restarts, and the replica's
       budget-gated reconnect catches up across the failover."""
    from tests.test_chaos import NAMESPACES as CH_NS  # noqa: F401
    from tests.test_chaos import DaemonProc, _local_oracles, read_watermark

    dbfile = tmp_path / "primary.db"
    pcache = tmp_path / "primary-cache"
    rdir = tmp_path / "replica-durable"
    rcache = tmp_path / "replica-cache"
    for d in (pcache, rdir, rcache):
        d.mkdir()

    # the primary serves on PINNED ports so a restarted primary comes
    # back at the address the replica was configured with (the failover
    # story needs the replica's budget-gated reconnect to find it)
    import socket

    def free_port():
        s = socket.socket()
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
        s.close()
        return port

    p_read, p_write = free_port(), free_port()
    primary_args = ("--read-port", str(p_read), "--write-port", str(p_write))

    def primary_proc(faults=""):
        return DaemonProc(
            dbfile, pcache, tmp_path, faults=faults, extra_args=primary_args
        )

    primary = primary_proc()
    procs = [primary]
    assert primary.wait_ports() and primary.wait_alive()
    pclient = primary.client(retry_max_wait_s=4.0)

    def replica_proc():
        proc = DaemonProc(
            dbfile,  # dsn is ignored on replicas; reuse the arg slot
            rcache,
            tmp_path,
            extra_args=(
                "--role", "replica",
                "--primary-url", f"http://127.0.0.1:{p_read}",
                "--replica-dir", str(rdir),
                "--staleness-wait-ms", "3000",
            ),
        )
        procs.append(proc)
        return proc

    def rcheck_url(port, obj, sub, token=None):
        q = (
            f"http://127.0.0.1:{port}/check?namespace=docs&object={obj}"
            f"&relation=view&subject_id={sub}"
        )
        if token is not None:
            q += f"&snaptoken={token}"
        return q

    def http_check(url, timeout=15):
        try:
            with urllib.request.urlopen(url, timeout=timeout) as resp:
                return json.loads(resp.read())["allowed"]
        except urllib.error.HTTPError as e:
            if e.code == 403:
                return False
            raise

    try:
        # seed state + a group edge so decisions are transitive
        pclient.patch_relation_tuples(
            insert=[T("g0", "ann", ns="groups", rel="member")]
        )
        seed = [T(f"o{i}", SubjectSet("groups", "g0", "member")) for i in range(8)]
        seed += [T(f"o{i}", f"u{i}") for i in range(8)]
        res = pclient.patch_relation_tuples(insert=seed)

        replica = replica_proc()
        assert replica.wait_ports() and replica.wait_alive()

        def replica_wm():
            try:
                body = ready_body(replica.ports["read"])
            except Exception:
                return -1
            return int(body.get("watermark", -1)) if body.get(
                "role"
            ) == "replica" else -1

        wait_until(
            lambda: replica_wm() >= res.snaptoken, timeout=60,
            what="replica initial catch-up",
        )

        # background writer keeps the feed busy while we SIGKILL
        stop_writes = threading.Event()
        tokens: list = []

        def writer():
            i = 0
            while not stop_writes.is_set() and i < 400:
                try:
                    r = pclient.patch_relation_tuples(
                        insert=[T(f"w{i}", f"wu{i}")],
                        idempotency_key=f"chaos-{i}",
                    )
                    tokens.append(r.snaptoken)
                except Exception:
                    pass
                i += 1
                time.sleep(0.01)

        wt = threading.Thread(target=writer, daemon=True)
        wt.start()
        time.sleep(0.5)  # mid-stream
        replica.kill()  # SIGKILL, no drain, no flush
        durable = json.loads((rdir / "applied-watermark.json").read_text())
        killed_at = int(durable["watermark"])
        time.sleep(0.3)
        stop_writes.set()
        wt.join(timeout=10)
        assert tokens, "writer made no progress"
        final_token = max(tokens)

        # restart: resumes from the durable watermark, applies the gap
        # exactly once, reaches the primary's watermark
        replica = replica_proc()
        assert replica.wait_ports() and replica.wait_alive()
        wait_until(
            lambda: replica_wm() >= final_token, timeout=60,
            what="replica resume catch-up",
        )
        assert replica_wm() >= killed_at  # never behind its own durable state

        # bit-parity at matching snaptokens: replica == primary == oracle
        store, check_oracle, _ = _local_oracles(dbfile)
        probe = (
            [(f"o{i}", "ann") for i in range(8)]
            + [(f"o{i}", f"u{i}") for i in range(4)]
            + [("w0", "wu0"), ("w1", "wu9"), ("nope", "ann")]
        )
        for obj, sub in probe:
            t = T(obj, sub)
            want = check_oracle.subject_is_allowed(t)
            got_replica = http_check(
                rcheck_url(replica.ports["read"], obj, sub, final_token)
            )
            got_primary = pclient.check(t, snaptoken=final_token)
            assert got_replica == want == got_primary, (obj, sub)
        # expand + list parity too
        rrc = replica.client()
        assert str(
            rrc.expand("docs", "o0", "view", 4)
        ) == str(pclient.expand("docs", "o0", "view", 4))
        assert list(
            rrc.list_subjects("docs", "o0", "view", snaptoken=final_token)
        ) == list(pclient.list_subjects("docs", "o0", "view", snaptoken=final_token))
        store.close()

        # -- primary failover: kill the primary MID-COMMIT, restart it at
        # the same address, the replica reconnects and catches up
        primary_wm_before = read_watermark(dbfile)
        primary.terminate_gracefully()
        killer = primary_proc(faults="transact-commit:kill:3")
        procs.append(killer)
        assert killer.wait_ports() and killer.wait_alive()
        kclient = killer.client()
        # the replica keeps serving at its watermark throughout the kill
        assert http_check(rcheck_url(replica.ports["read"], "o0", "ann"))
        for i in range(10):
            try:
                kclient.patch_relation_tuples(
                    insert=[T(f"f{i}", f"fu{i}")], idempotency_key=f"fail-{i}"
                )
            except Exception:
                break  # the armed kill fired mid-commit
        assert killer.wait_death() != 0  # died by the armed kill, not drain
        assert read_watermark(dbfile) >= primary_wm_before
        # replica still answers while the primary is DOWN
        assert http_check(rcheck_url(replica.ports["read"], "o0", "ann"))
        # revive the primary at the same address: the replica's
        # budget-gated reconnect finds it and catches up on NEW writes
        revived = primary_proc()
        procs.append(revived)
        assert revived.wait_ports() and revived.wait_alive()
        rev_client = revived.client(retry_max_wait_s=4.0)
        res2 = rev_client.patch_relation_tuples(
            insert=[T("post-failover", "pf-user")],
            idempotency_key="post-failover",
        )
        wait_until(
            lambda: replica_wm() >= res2.snaptoken, timeout=60,
            what="replica catch-up across primary failover",
        )
        assert http_check(
            rcheck_url(
                replica.ports["read"], "post-failover", "pf-user",
                res2.snaptoken,
            )
        )
        revived.terminate_gracefully()
        assert replica.terminate_gracefully() == 0
    finally:
        for p in procs:
            try:
                p.kill()
            except Exception:
                pass
