"""Replica controller: bootstrap, the Watch feed, and the read gate.

One object owns a replica's replication lifecycle:

- **Bootstrap** — fetch the primary's ``/snapshot/export``: the manifest
  (primary watermark + its snapshot-cache segment listing), then the
  streamed full tuple state at a consistent watermark, installed into
  the ``ReplicaStore`` at exactly that token. When the primary's cache
  watermark matches the export watermark, the cache segments are also
  fetched into the local snapshot-cache directory so the engine's cold
  start mmap-reloads instead of rebuilding (the quiet-primary fast
  path); otherwise the engine device-builds from the exported rows.
- **Feed** — a supervised worker subscribes to ``/watch`` through the
  SDK's retry-budget-gated reconnect and applies each commit group at
  its primary snaptoken through ``ReplicaStore.apply_commit`` (the
  engine then catches up through its existing delta-overlay/compaction
  path). Every applied token is persisted to the durable
  applied-watermark file BEFORE the next group is read, so a SIGKILL'd
  replica resumes from its last applied snaptoken and the store's
  watermark guard makes re-delivery exactly-once. ``ErrWatchExpired``
  (the primary GC'd the change log past the replica's cursor) triggers
  an automatic full re-bootstrap — never a crash loop, never silent
  divergence — and clears the check cache.
- **Probe** — a second supervised worker polls the primary's export
  manifest for its watermark: replication lag is "seconds since this
  replica last confirmed it was caught up", which keeps growing when the
  primary is unreachable (primary kill → DEGRADED(replication_lag) once
  past ``serve.replica_staleness_budget_s``).
- **Gate** — serving-path admission for pinned reads: ``at_least`` at or
  below the applied watermark passes; above it blocks up to
  ``serve.staleness_wait_ms`` on the apply condition variable, then
  raises 412 + Retry-After carrying the current watermark. ``latest``
  reads are refused with 412 outright — a replica cannot promise
  read-your-writes against the primary.
"""

from __future__ import annotations

import json
import logging
import os
import re
import tempfile
import threading
import time
from collections import deque
from pathlib import Path
from typing import Callable, Optional

from keto_tpu.replica.checkcache import CheckCache
from keto_tpu.replica.store import ReplicaStore
from keto_tpu.x.errors import (
    ErrPreconditionFailed,
    ErrServiceUnavailable,
    ErrWatchExpired,
)
from keto_tpu.x.supervise import SupervisedTask
from keto_tpu.x.tracing import NOOP as NOOP_TRACER
from keto_tpu.x.tracing import parse_traceparent

_log = logging.getLogger("keto_tpu.replica")

_CACHE_TAG_RE = re.compile(r"^v\d+-w\d+$")
_SEGMENT_NAME_RE = re.compile(r"^[A-Za-z0-9._-]+$")

#: durable applied-watermark file name under serve.replica_dir
WATERMARK_FILE = "applied-watermark.json"


class DurableWatermark:
    """The replica's applied snaptoken, surviving SIGKILL.

    One tiny JSON file written atomically (tmp + fsync + rename): after
    a kill the replica resumes from the last token whose application was
    recorded — re-reading a group at or below it is skipped by the
    store's watermark guard, so recovery is exactly-once. ``path=None``
    (no serve.replica_dir) keeps the watermark in memory only."""

    def __init__(self, path: Optional[Path]):
        self._path = path
        self._value: Optional[int] = None
        if path is not None:
            path.parent.mkdir(parents=True, exist_ok=True)

    def load(self) -> Optional[int]:
        if self._path is None or not self._path.exists():
            return self._value
        try:
            return int(json.loads(self._path.read_text())["watermark"])
        except Exception:
            _log.warning(
                "unreadable durable watermark %s; treating as absent",
                self._path, exc_info=True,
            )
            return None

    def store(self, token: int) -> None:
        self._value = int(token)
        if self._path is None:
            return
        payload = json.dumps({"watermark": int(token), "updated_at": time.time()})
        fd, tmp = tempfile.mkstemp(
            dir=str(self._path.parent), prefix=".wm-", suffix=".tmp"
        )
        try:
            with os.fdopen(fd, "w") as f:
                f.write(payload)
                f.flush()
                os.fsync(f.fileno())
            os.replace(tmp, self._path)
        except BaseException:
            try:
                os.unlink(tmp)
            except OSError:
                pass
            raise


class ReplicaController:
    def __init__(
        self,
        store: ReplicaStore,
        engine_source: Callable[[], object],
        primary_url: str,
        *,
        replica_dir: str = "",
        snapshot_cache_dir: str = "",
        staleness_wait_ms: float = 200.0,
        staleness_budget_s: float = 30.0,
        probe_s: float = 1.0,
        checkcache_entries: int = 65536,
        client_factory: Optional[Callable[[], object]] = None,
        stats=None,
        tracer=None,
        apply_delay_histogram=None,
    ):
        if not primary_url:
            raise ValueError("serve.role=replica requires serve.primary_url")
        self._store = store
        self._engine_source = engine_source
        self.primary_url = primary_url.rstrip("/")
        self._cache_dir = snapshot_cache_dir
        self.staleness_wait_s = max(0.0, float(staleness_wait_ms)) / 1e3
        self.staleness_budget_s = float(staleness_budget_s)
        self._probe_s = max(0.05, float(probe_s))
        self._client_factory = client_factory or self._default_client
        self._stats = stats
        # serve.checkcache_entries=0 disables the cache outright
        self.checkcache: Optional[CheckCache] = (
            CheckCache(entries=checkcache_entries)
            if int(checkcache_entries) > 0
            else None
        )
        self.durable = DurableWatermark(
            Path(replica_dir) / WATERMARK_FILE if replica_dir else None
        )
        self._lock = threading.Lock()  # guards: _primary_wm, _caught_up_at, _last_contact
        self._applied = threading.Condition()  # notified per applied commit
        self._stop = threading.Event()
        self._bootstrapped = threading.Event()
        self._primary_wm = 0
        self._caught_up_at: Optional[float] = None
        self._last_contact: Optional[float] = None
        #: feed-apply failures on groups that had to be skipped (namespace
        #: config drift between primary and replica — a deployment bug)
        self.apply_failures = 0
        #: primary watermark regressions observed across re-bootstraps
        self.watermark_regressions = 0
        # REPLICATION-AWARE TRACING: each applied commit group's apply
        # runs under a span joined to the WRITER's traceparent (carried
        # on the watch message), so one trace spans primary transact →
        # watch emit → replica apply → 412-gate visibility; the
        # commit→apply delay feeds keto_replication_apply_delay_seconds
        # with the writer's trace id as the exemplar.
        self._tracer = tracer or NOOP_TRACER
        self._delay_hist = apply_delay_histogram
        #: per-commit replication timelines, newest last — the replica
        #: half of GET /debug/requests (clock-skew caveat: committed_at/
        #: emitted_at are the PRIMARY's wall clock)
        self._replication_log: deque[dict] = deque(maxlen=256)
        self._feed = SupervisedTask("replica-feed", self._feed_pass, stats=stats)
        self._probe = SupervisedTask("replica-probe", self._probe_pass, stats=stats)

    def _default_client(self):
        from keto_tpu.httpclient import KetoClient

        # a short transport timeout bounds how long stop() waits for the
        # feed's blocking readline; idle-stream timeouts reconnect free
        return KetoClient(self.primary_url, self.primary_url, timeout=5.0)

    # -- lifecycle -------------------------------------------------------------

    def start(self) -> None:
        self._feed.kick()
        self._probe.kick()

    def stop(self, timeout: float = 2.0) -> None:
        self._stop.set()
        with self._applied:
            self._applied.notify_all()
        self._feed.stop(timeout=timeout)
        self._probe.stop(timeout=timeout)

    # -- read-side surface -----------------------------------------------------

    @property
    def bootstrapped(self) -> bool:
        return self._bootstrapped.is_set()

    @property
    def watermark(self) -> int:
        return self._store.watermark()

    @property
    def applied_commits(self) -> int:
        return self._store.applied_commits

    @property
    def bootstraps(self) -> int:
        return self._store.bootstraps

    @property
    def primary_connected(self) -> bool:
        with self._lock:
            last = self._last_contact
        return last is not None and (time.monotonic() - last) < 3 * self._probe_s + 2.0

    def lag_s(self) -> float:
        """Seconds since this replica last CONFIRMED being caught up with
        the primary (applied watermark >= the primary's, observed over a
        live connection). Grows while the feed lags — and while the
        primary is unreachable, which is indistinguishable from lagging
        and handled the same way (DEGRADED past the budget)."""
        if not self.bootstrapped:
            return 0.0  # STARTING covers the pre-bootstrap phase
        with self._lock:
            caught = self._caught_up_at
        if caught is None:
            return 0.0
        return max(0.0, time.monotonic() - caught)

    def gate_read(self, at_least: Optional[int], latest: bool = False) -> None:
        """Serving-path admission (check/expand/list/relation-tuples on a
        replica). Raises 503 before the first bootstrap (an empty replica
        must never answer "deny" for everything), 412 for ``latest``
        reads and for pins the feed did not reach within
        ``serve.staleness_wait_ms``."""
        if not self.bootstrapped:
            raise ErrServiceUnavailable(
                "replica has not completed its first bootstrap from the "
                "primary; retry shortly or read from the primary",
                retry_after_s=1.0,
            )
        if latest:
            raise ErrPreconditionFailed(
                "latest=true requires the primary: a replica serves bounded "
                "staleness (any snaptoken <= its applied watermark), not "
                "read-your-writes",
                details={"watermark": str(self.watermark)},
                retry_after_s=1.0,
            )
        if at_least is None:
            return
        at_least = int(at_least)
        if at_least <= self.watermark:
            return
        deadline = time.monotonic() + self.staleness_wait_s
        with self._applied:
            while at_least > self.watermark:
                remaining = deadline - time.monotonic()
                if remaining <= 0 or self._stop.is_set():
                    break
                self._applied.wait(timeout=remaining)
        wm = self.watermark
        if at_least <= wm:
            return
        raise ErrPreconditionFailed(
            f"requested snaptoken {at_least} is ahead of this replica's "
            f"applied watermark {wm}; retry, or read from the primary",
            details={"watermark": str(wm)},
            retry_after_s=1.0,
        )

    def snapshot(self) -> dict:
        """Operator/metrics view."""
        return {
            "role": "replica",
            "primary_url": self.primary_url,
            "bootstrapped": self.bootstrapped,
            "watermark": self.watermark,
            "primary_watermark": self._primary_wm,
            "lag_s": self.lag_s(),
            "primary_connected": self.primary_connected,
            "applied_commits": self.applied_commits,
            "skipped_commits": self._store.skipped_commits,
            "bootstraps": self.bootstraps,
            "apply_failures": self.apply_failures,
            "checkcache": (
                self.checkcache.snapshot() if self.checkcache is not None else {}
            ),
        }

    # -- replication internals -------------------------------------------------

    def _incr(self, event: str) -> None:
        if self._stats is not None:
            self._stats.incr(event)

    def _note_contact(self, primary_wm: Optional[int] = None) -> None:
        now = time.monotonic()
        with self._lock:
            self._last_contact = now
            if primary_wm is not None:
                self._primary_wm = max(self._primary_wm, int(primary_wm))
            if self._store.watermark() >= self._primary_wm:
                self._caught_up_at = now

    def _probe_pass(self) -> None:
        """One probe-loop lifetime: poll the primary's export manifest
        for its watermark until stop. Failures raise into the supervised
        backoff (the feed keeps running independently)."""
        client = self._client_factory()
        while not self._stop.is_set():
            manifest = client.snapshot_export_manifest()
            self._note_contact(int(manifest.get("watermark", 0)))
            if self._stop.wait(timeout=self._probe_s):
                return

    def _feed_pass(self) -> None:
        """One feed-loop lifetime: bootstrap if needed, then tail the
        changefeed, applying commit groups exactly-once. A clean watch
        end (SDK retry budget spent, primary drain) loops into a fresh
        budget-gated subscribe; exceptions raise into the supervised
        jittered-backoff retry."""
        client = self._client_factory()
        reconnect_wait = 0.2
        while not self._stop.is_set():
            if not self.bootstrapped:
                self._bootstrap()
            try:
                for token, changes in client.watch(snaptoken=self.watermark):
                    reconnect_wait = 0.2
                    self._apply_group(
                        int(token), changes,
                        meta=getattr(client, "last_commit_meta", None),
                    )
                    if self._stop.is_set():
                        return
            except ErrWatchExpired:
                # the primary GC'd its change log past our cursor: the
                # ONLY correct recovery is a full re-bootstrap — resuming
                # anywhere else silently diverges, crashing loops forever
                _log.warning(
                    "watch horizon lost at watermark %d; re-bootstrapping "
                    "from the primary", self.watermark,
                )
                self._incr("replica_horizon_losses")
                self._bootstrapped.clear()
                continue
            if self._stop.is_set():
                return
            # watch generator ended without error (SDK retry budget
            # drained or the primary closed the stream): pause — growing
            # while the primary stays silent, so a dead primary is not
            # stormed past what the budget already allowed — then
            # resubscribe from the durable cursor
            if self._stop.wait(timeout=reconnect_wait):
                return
            reconnect_wait = min(2.0, reconnect_wait * 2)

    def _apply_group(self, token: int, changes, meta: Optional[dict] = None) -> None:
        insert = [rt for action, rt in changes if action == "insert"]
        delete = [rt for action, rt in changes if action != "insert"]
        meta = meta or {}
        remote = parse_traceparent(str(meta.get("traceparent", "") or ""))
        t_recv = time.time()
        # the apply span joins the WRITER's trace (carried on the watch
        # message) and closes only after the watermark is raised and the
        # 412 gate notified — its end IS the visibility point
        with self._tracer.span(
            "replica.apply", remote_parent=remote, snaptoken=token,
            changes=len(changes),
        ) as span:
            try:
                applied = self._store.apply_commit(token, insert, delete)
            except Exception:
                # namespace-config drift between primary and replica is the
                # only way a replayed commit can fail to apply; skipping the
                # group (loudly) keeps the feed alive — retrying it forever
                # would freeze the watermark and take the whole replica down
                self.apply_failures += 1
                self._incr("replica_apply_failures")
                _log.error(
                    "failed to apply watch commit group at snaptoken %d; "
                    "skipping it (namespace config drift?)", token, exc_info=True,
                )
                return
            if span is not None:
                span.tags["applied"] = applied
            if applied:
                # first, before the durable write: the store's watermark is
                # already raised, and until the open windows are closed a
                # tokenless read admitted at it could be served a decision
                # this delta invalidated (a file write, on a loaded host,
                # is long enough for a reader to come by)
                if self.checkcache is not None:
                    self.checkcache.note_commit(token)
                self.durable.store(token)
                with self._applied:
                    self._applied.notify_all()
                # ride the engine's existing delta-overlay/compaction path
                # eagerly so pinned reads above the old snapshot land fast
                try:
                    self._engine().snapshot_serving()
                except Exception:
                    _log.debug("post-apply engine refresh failed", exc_info=True)
        if applied:
            self._note_replication(token, len(changes), meta, remote, t_recv)
        self._note_contact(token)

    def _note_replication(
        self, token: int, n_changes: int, meta: dict, remote, t_recv: float
    ) -> None:
        """Record one commit's replication timeline and feed the
        commit→visible delay histogram (trace-id exemplar = the writer's
        trace). ``committed_at``/``emitted_at`` come from the primary's
        clock — delays are cross-clock and clamped at zero."""
        now = time.time()
        committed = meta.get("committed_at")
        delay = None
        if committed is not None:
            try:
                delay = max(0.0, now - float(committed))
            except (TypeError, ValueError):
                delay = None
        entry = {
            "snaptoken": token,
            "changes": n_changes,
            "trace_id": remote[0] if remote else "",
            "committed_at": committed,
            "emitted_at": meta.get("emitted_at"),
            "received_at": round(t_recv, 6),
            "visible_at": round(now, 6),
            "commit_to_visible_s": round(delay, 6) if delay is not None else None,
        }
        self._replication_log.append(entry)
        if self._delay_hist is not None and delay is not None:
            self._delay_hist.observe(
                (), delay, trace_id=remote[0] if remote else ""
            )

    def replication_timelines(self) -> list[dict]:
        """Per-commit replication timelines, newest first (the replica
        section of GET /debug/requests)."""
        return list(reversed(self._replication_log))

    def _engine(self):
        return self._engine_source()

    def _bootstrap(self) -> None:
        """Full-state install from the primary (cold start and every
        horizon-loss recovery)."""
        client = self._client_factory()
        manifest = client.snapshot_export_manifest()
        self._note_contact(int(manifest.get("watermark", 0)))
        watermark, tuples = client.fetch_snapshot_export()
        prior = self.durable.load()
        if prior is not None and watermark < prior:
            # the primary answered with LESS history than we already
            # durably applied (restored from backup?) — re-bootstrapping
            # forward from what it has is the only consistent option,
            # but it must never pass silently
            self.watermark_regressions += 1
            self._incr("replica_watermark_regressions")
            _log.error(
                "primary export watermark %d is behind this replica's "
                "durable applied watermark %d; re-bootstrapping onto the "
                "primary's (shorter) history", watermark, prior,
            )
        cache = manifest.get("cache")
        if cache and self._cache_dir and int(cache.get("watermark", -1)) == watermark:
            try:
                self._fetch_cache_segments(client, cache)
            except Exception:
                # strictly a fast-path: the engine builds from rows
                _log.warning(
                    "snapshot-cache segment fetch failed; cold start will "
                    "device-build from the exported rows", exc_info=True,
                )
        self._store.bootstrap(tuples, watermark)
        self.durable.store(watermark)
        if self.checkcache is not None:
            self.checkcache.clear(watermark)
        self._bootstrapped.set()
        self._incr("replica_bootstraps")
        self._note_contact(watermark)
        with self._applied:
            self._applied.notify_all()
        _log.info(
            "replica bootstrapped: %d tuples at snaptoken %d (bootstrap #%d)",
            len(tuples), watermark, self.bootstraps,
        )
        # build/reload the device snapshot off the serving path NOW so
        # the first read doesn't pay it; the segment fast path above
        # makes this an mmap reload when the watermarks lined up
        try:
            self._engine().snapshot()
        except Exception:
            _log.warning(
                "post-bootstrap snapshot build failed; first read will "
                "build inline", exc_info=True,
            )

    def _fetch_cache_segments(self, client, cache: dict) -> None:
        """Mirror the primary's newest snapshot-cache directory into the
        local cache dir (atomic: temp dir + rename) so the engine's
        ordinary cold-start reload finds it. Tag/segment names are
        validated against the manifest grammar — the server enforces the
        same on its side."""
        tag = str(cache.get("tag", ""))
        if not _CACHE_TAG_RE.match(tag):
            raise ValueError(f"malformed cache tag {tag!r}")
        base = Path(self._cache_dir)
        if (base / tag).exists():
            return  # already mirrored (a prior bootstrap or shared volume)
        base.mkdir(parents=True, exist_ok=True)
        tmp = Path(
            tempfile.mkdtemp(dir=str(base), prefix=f".fetch-{tag}-")
        )
        try:
            for seg in cache.get("segments", ()):
                name = str(seg["name"])
                if not _SEGMENT_NAME_RE.match(name):
                    raise ValueError(f"malformed segment name {name!r}")
                data = client.fetch_snapshot_segment(tag, name)
                (tmp / name).write_bytes(data)
            os.replace(tmp, base / tag)
        except BaseException:
            import shutil

            shutil.rmtree(tmp, ignore_errors=True)
            raise
        _log.info(
            "mirrored primary snapshot cache %s (%d segments)",
            tag, len(cache.get("segments", ())),
        )


__all__ = ["ReplicaController", "DurableWatermark", "WATERMARK_FILE"]
