"""tail-smoke: the CI gate on the slice tail.

Boots a real daemon over a pre-populated sqlite store and drives a
MIXED-DEPTH check workload — direct grants next to chains of depth 2–8
and wildcard patterns, the route mix (label | hybrid | bfs | host) whose
slow members used to blow the stream's p99 — then asserts the slice-tail
machinery end to end:

1. the per-slice service-time p99/p50 ratio stays at or below the
   configured bound (``serve.stream_tail_ratio``, also the bench
   acceptance gate) — or the p99 itself is under the slice target
   (a sub-target tail is not a tail problem, which is exactly the
   controller's own engagement rule);
2. ZERO oracle mismatches: every REST decision is compared client-side
   against the CPU reference engine, and the shadow-parity auditor
   (sample rate 1.0) re-verifies served decisions with zero mismatches;
3. native pack == numpy pack BYTE parity on the serving snapshot
   (every packed kernel array and host-decided grant), and the native
   path actually ran (keto_native_pack_chunks_total, ``native`` or ``fused``),
   as did ``resolve``'s one native pass (keto_check_resolve_chunks_total);
4. the staging ledger reconciles: the governor's ``staging`` tag equals
   the engine pool's own accounting, with zero outstanding leases after
   the workload drains;
5. under KETO_TPU_SANITIZE=1, zero lock-order inversions and zero
   deadlock-watchdog trips.

Exit 0 when all hold; 1 with the violations listed.
"""

from __future__ import annotations

import json
import os
import random
import sys
import tempfile
import time
import urllib.request
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

N_USERS = int(os.environ.get("TAIL_SMOKE_USERS", "120"))
N_DOCS = int(os.environ.get("TAIL_SMOKE_DOCS", "80"))
MAX_DEPTH = int(os.environ.get("TAIL_SMOKE_DEPTH", "8"))
N_ROUNDS = int(os.environ.get("TAIL_SMOKE_ROUNDS", "6"))
BATCH = int(os.environ.get("TAIL_SMOKE_BATCH", "512"))
TAIL_RATIO = float(os.environ.get("TAIL_SMOKE_RATIO", "5.0"))
TARGET_MS = float(os.environ.get("TAIL_SMOKE_TARGET_MS", "40.0"))


def build_store(dbfile: str) -> list:
    from keto_tpu import namespace as namespace_pkg
    from keto_tpu.persistence.sqlite import SQLitePersister
    from keto_tpu.relationtuple.model import RelationTuple, SubjectID, SubjectSet

    rng = random.Random(71)
    nm = namespace_pkg.MemoryManager(
        [namespace_pkg.Namespace(id=0, name="docs"),
         namespace_pkg.Namespace(id=1, name="groups")]
    )
    store = SQLitePersister(f"sqlite://{dbfile}", lambda: nm)
    rows = []
    n_groups = 24
    for g in range(n_groups):
        for _ in range(5):
            rows.append(RelationTuple(
                namespace="groups", object=f"g{g}", relation="member",
                subject=SubjectID(f"u{rng.randrange(N_USERS)}")))
    for d in range(N_DOCS):
        rows.append(RelationTuple(
            namespace="docs", object=f"doc{d}", relation="view",
            subject=SubjectSet("groups", f"g{rng.randrange(n_groups)}", "member")))
    # chains of increasing depth: deep BFS/hybrid slices ride next to
    # the one-hop label hits above
    for k in range(2, MAX_DEPTH + 1):
        for i in range(k):
            rows.append(RelationTuple(
                namespace="groups", object=f"c{k}-{i}", relation="member",
                subject=SubjectSet("groups", f"c{k}-{i+1}", "member")))
        rows.append(RelationTuple(
            namespace="groups", object=f"c{k}-{k}", relation="member",
            subject=SubjectID(f"deep{k}")))
        rows.append(RelationTuple(
            namespace="docs", object=f"chain{k}", relation="view",
            subject=SubjectSet("groups", f"c{k}-0", "member")))
    store.write_relation_tuples(*rows)
    store.close()
    return rows


def workload(rng) -> list[dict]:
    out = []
    for _ in range(BATCH):
        r = rng.random()
        if r < 0.7:
            out.append({"namespace": "docs", "object": f"doc{rng.randrange(N_DOCS)}",
                        "relation": "view",
                        "subject_id": f"u{rng.randrange(N_USERS)}"})
        else:
            k = rng.randrange(2, MAX_DEPTH + 1)
            who = f"deep{k}" if rng.random() < 0.5 else f"u{rng.randrange(N_USERS)}"
            out.append({"namespace": "docs", "object": f"chain{k}",
                        "relation": "view", "subject_id": who})
    return out


def main() -> int:
    from bench import log
    from keto_tpu.check import native_pack
    from keto_tpu.check.engine import CheckEngine
    from keto_tpu.check.pack import pack_chunk
    from keto_tpu.config.provider import Config
    from keto_tpu.driver.daemon import Daemon
    from keto_tpu.driver.registry import Registry
    from keto_tpu.relationtuple.model import RelationTuple, SubjectID
    from keto_tpu.x.metrics import parse_exposition

    problems: list[str] = []
    tmp = tempfile.mkdtemp(prefix="keto-tail-smoke-")
    dbfile = str(Path(tmp) / "store.sqlite")
    build_store(dbfile)

    cfg = Config(overrides={
        "namespaces": [{"id": 0, "name": "docs"}, {"id": 1, "name": "groups"}],
        "dsn": f"sqlite://{dbfile}",
        "serve.read.port": 0,
        "serve.write.port": 0,
        "serve.stream_slice_target_ms": TARGET_MS,
        "serve.stream_tail_ratio": TAIL_RATIO,
        "serve.audit_sample_rate": 1.0,
        # a tiny landmark cap leaves most label pairs uncertified, so the
        # workload actually exercises the hybrid/BFS routes next to label
        # hits — the mix whose slow members the tail gate is about
        "serve.labels_landmarks": 4,
    })
    registry = Registry(cfg)
    daemon = Daemon(registry)
    daemon.serve_all(block=False)
    rng = random.Random(1234)
    try:
        base = f"http://127.0.0.1:{daemon.read_port}"
        with urllib.request.urlopen(f"{base}/health/ready", timeout=30) as resp:
            if resp.status != 200:
                problems.append(f"/health/ready answered {resp.status}")

        oracle = CheckEngine(registry.relation_tuple_manager())
        engine = registry.permission_engine()

        wrong = 0
        checked = 0
        for _ in range(N_ROUNDS):
            tuples = workload(rng)
            body = json.dumps({"tuples": tuples}).encode()
            req = urllib.request.Request(
                f"{base}/check/batch", data=body,
                headers={"Content-Type": "application/json"},
            )
            with urllib.request.urlopen(req, timeout=60) as r:
                results = json.loads(r.read())["results"]
            for t, got in zip(tuples, results):
                want = oracle.subject_is_allowed(RelationTuple(
                    namespace=t["namespace"], object=t["object"],
                    relation=t["relation"], subject=SubjectID(t["subject_id"])))
                checked += 1
                if bool(got) != want:
                    wrong += 1
        log(f"[tail-smoke] {checked} mixed-depth checks, {wrong} wrong")
        if wrong:
            problems.append(f"{wrong}/{checked} decisions diverged from the oracle")

        # slice tail: the engine's own service-time stats (the numbers
        # the controller steers and /metrics exposes)
        svc = engine.stream_slice_stats.snapshot()
        ratio = (svc["p99_ms"] / svc["p50_ms"]) if svc["p50_ms"] else 0.0
        ctrl = engine.dispatch.stream_ctrl.snapshot()
        log(
            f"[tail-smoke] slices={svc['count']} p50={svc['p50_ms']:.2f}ms "
            f"p99={svc['p99_ms']:.2f}ms ratio={ratio:.2f} "
            f"(bound {TAIL_RATIO}, target {TARGET_MS}ms, "
            f"guard={ctrl['tail_guard']}, routes={sorted(ctrl['routes'])})"
        )
        if svc["count"] < 4:
            problems.append(f"only {svc['count']} slices landed — workload too small")
        if ratio > TAIL_RATIO and svc["p99_ms"] > TARGET_MS:
            problems.append(
                f"slice tail blown: p99/p50 = {ratio:.1f} > {TAIL_RATIO} "
                f"with p99 {svc['p99_ms']:.1f}ms over the {TARGET_MS}ms target"
            )

        # native pack ran, and == numpy byte parity on the live snapshot
        if not native_pack.available():
            problems.append("native pack library not available in the smoke")
        else:
            if native_pack.COUNTERS["native"] + native_pack.COUNTERS["fused"] == 0:
                problems.append("native pack path never ran")
            if not engine.maintenance.snapshot().get("resolve_chunks_native", 0):
                problems.append("native resolve pass never ran")
            snap = engine.snapshot()
            qs = [RelationTuple(namespace=t["namespace"], object=t["object"],
                                relation=t["relation"],
                                subject=SubjectID(t["subject_id"]))
                  for t in workload(rng)]
            sd, tg, multi = engine.dispatch._resolve_bulk(snap, qs)
            pn, hn = pack_chunk(snap, sd, tg, multi, 0, len(qs), native=True)
            pp, hp = pack_chunk(snap, sd, tg, multi, 0, len(qs), native=False)
            if (hn != hp).any() or (pn is None) != (pp is None):
                problems.append("native/numpy pack host answers diverge")
            elif pn is not None:
                for k, (a, b) in enumerate(zip(pn, pp)):
                    if a.dtype != b.dtype or a.shape != b.shape or (a != b).any():
                        problems.append(f"native/numpy pack array {k} not byte-identical")
                        break

        # staging ledger reconciles with the pool, zero leases leaked
        st = engine.dispatch.staging_snapshot()
        led = engine.hbm.ledger().get("staging", 0)
        if st["leased"] != 0:
            problems.append(f"{st['leased']} staging leases outlived their slices")
        if led != st["bytes"]:
            problems.append(
                f"staging ledger tag {led} != pool accounting {st['bytes']}"
            )

        # shadow auditor: give it a beat, then demand zero mismatches
        deadline = time.monotonic() + 15
        while time.monotonic() < deadline and engine.health()["audit_checks"] == 0:
            time.sleep(0.1)
        h = engine.health()
        log(f"[tail-smoke] auditor: {h['audit_checks']} checks, "
            f"{h['audit_mismatches']} mismatches")
        if h["audit_mismatches"]:
            problems.append(f"shadow auditor found {h['audit_mismatches']} mismatches")

        # scrape: the tail/route/pack families render and agree
        with urllib.request.urlopen(f"{base}/metrics", timeout=30) as resp:
            families = parse_exposition(resp.read().decode())
        for fam in ("keto_stream_tail_ratio", "keto_stream_route_slices_total",
                    "keto_native_pack_chunks_total", "keto_check_resolve_chunks_total"):
            if fam not in families:
                problems.append(f"{fam} missing from the scrape")

        from keto_tpu.x import lockwatch

        if lockwatch.installed():
            problems.extend(lockwatch.violations())
            rep = lockwatch.report()
            log(f"[tail-smoke] lockwatch: {rep['acquires']} acquires, "
                f"{len(rep['inversions'])} inversions, "
                f"{len(rep['watchdog_trips'])} watchdog trips")
    finally:
        daemon.shutdown()

    if problems:
        print("tail-smoke FAILED:", file=sys.stderr)
        for p in problems:
            print(f"  - {p}", file=sys.stderr)
        return 1
    print("tail-smoke OK: mixed-depth stream held the slice-tail bound, zero "
          "oracle mismatches, native pack byte-identical to numpy, staging "
          "ledger reconciled, sanitizer clean")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
