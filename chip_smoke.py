#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the served Check path starts and
answers correctly on the chip.

Drives the system the way a user does, once, at BASELINE config 3's full
size (1M-tuple RBAC graph, 3-level group nesting — bench.build_workload):

1. asks a short-lived child which platform JAX finds (a TPU is required
   unless ``--platform cpu`` asks for the rehearsal), then builds the three
   native libraries with ``make native`` and requires all of them to load;
2. generates the graph from ``--seed`` — plus the shapes the kernels
   special-case: wildcard subjects, membership cycles, empty-relation
   subject sets — and writes it into a SQLite store;
3. starts ONE child, ``python -m keto_tpu.cmd serve``, with
   ``engine.backend: tpu``. The child is the only process that touches the
   chip while it lives: this parent never imports jax (asserted at exit);
4. waits for ``/health/ready`` = ok, the boot label build and the kernel
   width-ladder warm-up, then drives the daemon from outside through the
   SDK and a gRPC stub: >= 20,000 batched checks in calls of 4,096, ~200
   single REST checks, ~200 gRPC checks, a handful of expand /
   list-objects / list-subjects, then a write of a group->group edge and
   checks pinned to the snaptoken it returned;
5. compares every decision with the repo's own references over the same
   SQLite file — the analytic expectation of the generator for every
   batched check, the CPU oracle ``CheckEngine`` for a >= 2,000 sample,
   all singles and all post-write checks; the Manager-backed expand and
   list engines for the trees and listings. One mismatch fails the run;
6. scrapes ``/metrics`` and ``/debug/requests`` and fails unless the device
   the DAEMON reports is the platform asked for, the label and BFS kernels
   both served slices, the native pack path ran, the device build paths
   ran, and no fallback / error / degraded counter moved;
7. SIGTERMs the child and requires exit status 0.

Every line says which platform the run is for. Times are smoke timings of
single phases, printed to show where a run spends its wall clock — they are
not benchmark numbers. When every phase passed, the last two lines of stdout
are a ``summary:`` line (one JSON object: tuples, checks, routes, cache
entries, smoke timings, ending in ``"claim": null``) and then the result,
exactly ``{"ok": true, "device": {"platform": ..., "kind": ..., "count":
...}}`` with the device as the daemon reported it. Any failure — no
accelerator, a directory that holds this file and nothing else of the repo,
a failed phase — exits non-zero without either.

    python chip_smoke.py                                # needs a TPU
    python chip_smoke.py --chips 4                      # sharded, 4 devices
    python chip_smoke.py --platform cpu --tuples 20000  # CPU rehearsal
"""

from __future__ import annotations

import argparse
import contextlib
import json
import os
import random
import re
import shutil
import signal
import socket
import subprocess
import sys
import tempfile
import time
import urllib.error
import urllib.request
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT))

BATCH = 4096
N_BATCHED = 20_000
N_SINGLE = 200
N_GRPC = 200
N_ORACLE_SAMPLE = 2_000
N_SPECIAL = 64  # per special shape
N_JOINS = 300  # one-tuple writes ahead of the fold
FOLD_LIMIT_MS = 5000.0  # a fold that compiles takes 13-19 s on the chip (PR 46)


def _from_owner(rel: str) -> dict:
    return {"tuple_to_userset": {"tupleset": "owner", "computed_userset": rel}}


#: ``orgs`` and ``repos`` carry userset rewrites with the two gates of
#: OpenFGA's modelling guide (docs/concepts/userset-rewrites.md): a blocklist
#: (``can_read = reader but not blocked from owner``) and a two-condition
#: permission (``can_delete = admin and member from owner``)
NAMESPACES = [
    {"id": 0, "name": "docs"}, {"id": 1, "name": "groups"},
    {"id": 2, "name": "orgs"},
    {"id": 3, "name": "repos", "config": {"relations": {
        "reader": {"union": [{"this": {}}, _from_owner("member")]},
        "can_read": {"exclusion": {"base": {"computed_userset": "reader"},
                                   "subtract": _from_owner("blocked")}},
        "can_delete": {"intersection": [{"computed_userset": "admin"}, _from_owner("member")]},
    }}},
]
#: the gated case: rows, and ``(object, relation, user) -> allowed``
GATE_ROWS = (
    ("orgs", "acme", "member", "gate-member"), ("orgs", "acme", "member", "gate-blocked"),
    ("orgs", "acme", "blocked", "gate-blocked"),
    ("repos", "site", "admin", "gate-member"), ("repos", "site", "admin", "gate-outsider"),
)
GATE_CHECKS = (
    ("site", "reader", "gate-blocked", True),  # a member, so a reader ...
    ("site", "can_read", "gate-blocked", False),  # ... and blocked: the grant does not count
    ("site", "can_read", "gate-member", True),
    ("site", "can_delete", "gate-member", True),  # an admin and a member
    ("site", "can_delete", "gate-outsider", False),  # an admin, no member
    ("site", "can_delete", "gate-blocked", False),
    ("none", "can_read", "gate-member", False),  # no row mentions the repo
)

#: maintenance events that must not have moved / must have moved
#: (keto_maintenance_events_total{event=...})
MUST_BE_ZERO = (
    "fallback_checks", "warm_failures", "label_device_build_errors",
    "device_build_errors", "device_errors", "refresh_failures",
)
MUST_BE_POSITIVE = ("label_device_builds", "device_build_dispatches")


class SmokeFailure(Exception):
    """A phase failed; the message says which and why."""


class Smoke:
    def __init__(self, args):
        self.args = args
        self.platform = args.platform
        self.tag = f"[chip_smoke platform={self.platform}]"
        self.child = None
        self.child_log = None
        self.workdir = Path(tempfile.mkdtemp(prefix="keto-chip-smoke-"))
        self.out = Path(args.out).resolve()
        self.out.mkdir(parents=True, exist_ok=True)
        self.timings: dict[str, float] = {}
        self.mismatches = 0

    # -- plumbing ------------------------------------------------------------

    def say(self, msg: str) -> None:
        print(f"{self.tag} {msg}", flush=True)

    @contextlib.contextmanager
    def phase(self, name: str):
        t0 = time.monotonic()
        self.say(f"phase {name}: start")
        state = "FAILED"
        try:
            yield
            state = "done"
        finally:
            dt = time.monotonic() - t0
            self.timings[name] = round(dt, 2)
            self.say(f"phase {name}: {state} (smoke timing {dt:.1f}s)")

    def child_env(self) -> dict:
        env = dict(os.environ)
        if self.platform == "cpu":
            env["JAX_PLATFORMS"] = "cpu"
            if self.args.chips > 1:
                env["XLA_FLAGS"] = (
                    env.get("XLA_FLAGS", "")
                    + f" --xla_force_host_platform_device_count={self.args.chips}"
                ).strip()
        return env

    def log_tail(self, n: int = 60) -> str:
        try:
            lines = (self.out / "daemon.log").read_text(errors="replace").splitlines()
        except OSError:
            return "(no daemon log)"
        return "\n".join(lines[-n:])

    def http(self, method: str, url: str, body=None, timeout: float = 120.0):
        data = json.dumps(body).encode() if body is not None else None
        req = urllib.request.Request(url, data=data, method=method)
        if data is not None:
            req.add_header("Content-Type", "application/json")
        try:
            with urllib.request.urlopen(req, timeout=timeout) as resp:
                return resp.status, resp.read(), dict(resp.headers)
        except urllib.error.HTTPError as e:
            return e.code, e.read(), dict(e.headers)

    def scrape(self) -> dict:
        from keto_tpu.x.metrics import parse_exposition

        status, raw, _ = self.http("GET", f"{self.read_url}/metrics")
        if status != 200:
            raise SmokeFailure(f"/metrics answered {status}")
        return parse_exposition(raw.decode())

    @staticmethod
    def metric(families: dict, family: str, **labels) -> float:
        """Sum of the samples of ``family`` matching ``labels`` (0 when the
        family or the series is absent — a counter that never moved)."""
        total = 0.0
        for _, sample_labels, value in families.get(family, {}).get("samples", ()):
            if all(sample_labels.get(k) == v for k, v in labels.items()):
                total += value
        return total

    def event(self, families: dict, name: str) -> float:
        return self.metric(families, "keto_maintenance_events_total", event=name)

    # -- phases --------------------------------------------------------------

    def probe_device(self) -> None:
        """Which platform does JAX find? Asked of a child that exits before
        the daemon starts, so the chip is never shared; the device that
        goes into the result is the one the daemon itself reports."""
        code = (
            "import json, jax; d = jax.devices(); "
            "print(json.dumps({'platform': d[0].platform, "
            "'kind': d[0].device_kind, 'count': len(d)}))"
        )
        proc = subprocess.run(
            [sys.executable, "-c", code], env=self.child_env(),
            capture_output=True, text=True, timeout=300,
        )
        if proc.returncode != 0:
            raise SmokeFailure(
                "JAX could not initialize a backend:\n" + proc.stderr[-2000:]
            )
        found = json.loads(proc.stdout.strip().splitlines()[-1])
        self.say(
            f"JAX finds platform={found['platform']} kind={found['kind']!r} "
            f"count={found['count']}"
        )
        if found["platform"] != self.platform:
            raise SmokeFailure(
                f"this run needs platform {self.platform!r} but JAX found "
                f"{found['platform']!r} ({found['count']} x {found['kind']}); "
                f"the CPU rehearsal is an explicit request: "
                f"--platform cpu --tuples 20000"
            )
        if found["count"] < self.args.chips:
            raise SmokeFailure(
                f"--chips {self.args.chips} but JAX found {found['count']} devices"
            )

    def build_native(self) -> None:
        proc = subprocess.run(
            ["make", "native"], cwd=ROOT, capture_output=True, text=True,
            timeout=600,
        )
        if proc.returncode != 0:
            raise SmokeFailure(f"make native failed:\n{proc.stdout}{proc.stderr}")
        from keto_tpu.check import native_pack
        from keto_tpu.graph import native as native_ingest
        from keto_tpu.servers import native_mux

        missing = [
            name
            for name, lib in (
                ("libketoingest.so", native_ingest.load_library()),
                ("libketopack.so", native_pack.load_library()),
                ("libketomux.so", native_mux.load_library()),
            )
            if lib is None
        ]
        if missing:
            raise SmokeFailure(f"native libraries built but not loadable: {missing}")
        self.say("native libraries built and loaded: ingest, pack, mux")

    def generate(self) -> None:
        """BASELINE config 3 from the seed, plus the special shapes."""
        import bench
        from keto_tpu.relationtuple.model import SubjectID, SubjectSet

        rng = random.Random(self.args.seed)
        n_special = 6 * N_SPECIAL  # tuples the three shapes add below
        (tuples, doc_grant, membership, user_reaches, member_of, n_users, T) = (
            bench.build_workload(rng, max(1000, self.args.tuples - n_special))
        )
        self.T = T
        n_rbac_tuples = len(tuples)
        # queries over the special shapes; the CPU oracle decides them
        special_q = []
        any_relation = set()  # leaf groups under an empty-relation subject set
        for i in range(N_SPECIAL):
            # wildcard subject
            tuples.append(T("docs", f"pub-{i}", "view", SubjectID("*")))
            special_q.append(T("docs", f"pub-{i}", "view", SubjectID("*")))
            special_q.append(
                T("docs", f"pub-{i}", "view", SubjectID(f"user-{rng.randrange(n_users)}"))
            )
            # membership cycle a <-> b, one user inside, one doc outside
            a, b = f"cyc-{i}-a", f"cyc-{i}-b"
            tuples.append(T("groups", a, "member", SubjectSet("groups", b, "member")))
            tuples.append(T("groups", b, "member", SubjectSet("groups", a, "member")))
            tuples.append(T("groups", b, "member", SubjectID(f"cyc-user-{i}")))
            tuples.append(T("docs", f"cyc-doc-{i}", "view", SubjectSet("groups", a, "member")))
            special_q.append(T("docs", f"cyc-doc-{i}", "view", SubjectID(f"cyc-user-{i}")))
            special_q.append(
                T("docs", f"cyc-doc-{i}", "view", SubjectID(f"user-{rng.randrange(n_users)}"))
            )
            # empty-relation subject set over an existing leaf group
            u = rng.randrange(n_users)
            leaves = sorted(membership.get(u, ())) or [0]
            any_relation.add(leaves[0])
            tuples.append(
                T("docs", f"anyrel-{i}", "view", SubjectSet("groups", f"leaf-{leaves[0]}", ""))
            )
            special_q.append(T("docs", f"anyrel-{i}", "view", SubjectID(f"user-{u}")))
            special_q.append(
                T("docs", f"anyrel-{i}", "view", SubjectID(f"user-{rng.randrange(n_users)}"))
            )
        special_q.append(T("nope", "x", "y", SubjectID("z")))  # unknown namespace
        # the gated case rides the first call too, and is held to its
        # hand-written answers besides the oracle's (``drive``)
        tuples.append(T("repos", "site", "owner", SubjectSet("orgs", "acme", "...")))
        tuples += [T(ns, obj, rel, SubjectID(u)) for ns, obj, rel, u in GATE_ROWS]
        self.gate_q = [T("repos", obj, rel, SubjectID(u)) for obj, rel, u, _ in GATE_CHECKS]
        special_q += self.gate_q
        self.tuples = tuples
        self.special_q = special_q
        n_rbac = N_BATCHED + BATCH + N_SINGLE + N_GRPC
        pairs = list(
            bench.iter_queries(rng, n_rbac, doc_grant, n_users, user_reaches, member_of, T)
        )
        self.rbac_q = [q for q, _ in pairs]
        self.rbac_expected = [e for _, e in pairs]
        self.doc_grant = doc_grant
        self.user_reaches = user_reaches
        # leaf groups that hold a member: one more member is an overlay edge,
        # where a group's first member would change its node's class. Not
        # the ones an empty-relation subject set names: an edge out of a
        # wildcard node cannot be folded, only rebuilt around
        self.joinable = sorted(
            {g for groups in membership.values() for g in groups} - any_relation)

        # the write: cycle group 0-a gains cycle group 1-b as a member. An
        # edge between two interior rows the layout can never peel (cycle
        # members stay active bitmap rows), so it lands in the delta
        # overlay's ELL stage and dirties the labels — an edge into a
        # passive or peeled row would force a full relayout instead, and
        # which RBAC groups are passive is the peel heuristic's business
        self.write_case = ("cyc-0-a", "cyc-1-b", "cyc-doc-0", "cyc-user-1")
        self.say(
            f"generated {len(tuples)} tuples (seed {self.args.seed}): "
            f"{n_rbac_tuples} RBAC (BASELINE config 3) + {n_special} in "
            f"{N_SPECIAL} wildcard subjects, {N_SPECIAL} membership cycles and "
            f"{N_SPECIAL} empty-relation subject sets; "
            f"{len(self.rbac_q)} RBAC + {len(special_q)} special-shape queries"
        )

    def open_store(self):
        from keto_tpu import namespace as namespace_pkg
        from keto_tpu.persistence.sqlite import SQLitePersister

        nm = namespace_pkg.MemoryManager(
            [namespace_pkg.namespace_from_json(n) for n in NAMESPACES]
        )
        return SQLitePersister(f"sqlite://{self.workdir / 'store.sqlite'}", lambda: nm)

    def load_store(self) -> None:
        store = self.open_store()
        try:
            chunk = 250_000
            for i in range(0, len(self.tuples), chunk):
                store.write_relation_tuples(*self.tuples[i : i + chunk])
        finally:
            store.close()
        size = (self.workdir / "store.sqlite").stat().st_size
        self.say(f"wrote {len(self.tuples)} tuples to SQLite ({size / 1e6:.0f} MB)")
        self.n_tuples = len(self.tuples)
        del self.tuples

    def start_daemon(self) -> None:
        from keto_tpu.driver import compile_cache

        cache_dir, from_env = compile_cache.resolve("")
        if os.environ.get(compile_cache.ENV_VAR):
            source = f"${compile_cache.ENV_VAR}"
        else:
            source = "fixed in-checkout path, passed as serve.compile_cache_dir"
        self.cache_dir = Path(cache_dir)
        self.cache_before = self.cache_entries()
        self.say(
            f"compile cache: {cache_dir} ({source}); "
            f"{self.cache_before} entries before"
        )
        ports = []
        for _ in range(2):
            with socket.socket() as s:
                s.bind(("127.0.0.1", 0))
                ports.append(s.getsockname()[1])
        self.read_url = f"http://127.0.0.1:{ports[0]}"
        self.write_url = f"http://127.0.0.1:{ports[1]}"
        self.grpc_target = f"127.0.0.1:{ports[0]}"
        serve = {
            "read": {"host": "127.0.0.1", "port": ports[0]},
            "write": {"host": "127.0.0.1", "port": ports[1]},
            # BASELINE config 3 is shallow: its interior ELL holds ~8 slots
            # per 1,000 tuples, under the default 65,536-slot gate of the
            # device label build at 1M tuples. The smoke exists to run that
            # build on the chip, so it forces it: 0 takes both halves of the
            # gate away, the slots and (since PR 38) the host builder going
            # first, which indexes this shallow graph in well under a second
            # and would leave the device build unrun. (By the slots alone
            # ~80k at --tuples 10000000 would pass the default — an
            # estimate: the one 10M run had the gate at 1.)
            "labels_device_min_edges": 0,
            # the daemon's own shadow auditor re-verifies a sample of the
            # served decisions against the CPU oracle
            "audit_sample_rate": 0.02,
        }
        if not from_env:
            # an explicit request, like the variable: the daemon warms the
            # kernel-width ladder at boot, before the smoke sends traffic
            serve["compile_cache_dir"] = cache_dir
        if self.args.chips > 1:
            serve["mesh_graph"] = self.args.chips
        cfg = {
            "namespaces": NAMESPACES,
            "dsn": f"sqlite://{self.workdir / 'store.sqlite'}",
            "serve": serve,
            "engine": {"backend": "tpu" if self.platform == "tpu" else "auto"},
            "log": {"level": "info"},
        }
        cfg_path = self.workdir / "keto.json"  # JSON is YAML
        cfg_path.write_text(json.dumps(cfg, indent=1))
        self.child_log = open(self.out / "daemon.log", "wb")
        self.child = subprocess.Popen(
            [sys.executable, "-m", "keto_tpu.cmd", "serve", "-c", str(cfg_path)],
            cwd=ROOT, env=self.child_env(), stdout=self.child_log,
            stderr=subprocess.STDOUT, start_new_session=True,
        )
        self.say(
            f"started daemon pid {self.child.pid}: engine.backend="
            f"{cfg['engine']['backend']}, read {self.read_url}, "
            f"mesh_graph={self.args.chips}"
        )

    def cache_entries(self) -> int:
        try:
            return sum(1 for p in self.cache_dir.iterdir() if p.is_file())
        except OSError:
            return 0

    def wait_ready(self) -> None:
        """/health/ready = ok, then the boot label build, then the ladder
        warm-up — the daemon logs the last one, the metrics show the rest."""
        deadline = time.monotonic() + self.args.boot_timeout
        log = self.out / "daemon.log"

        def still_booting(what: str) -> None:
            if self.child.poll() is not None:
                raise SmokeFailure(
                    f"daemon exited with status {self.child.returncode} "
                    f"waiting for {what}:\n{self.log_tail()}"
                )
            if time.monotonic() > deadline:
                raise SmokeFailure(
                    f"{what}: not after {self.args.boot_timeout}s:\n{self.log_tail()}"
                )
            time.sleep(1.0)

        last = ""
        while True:
            try:
                status, raw, _ = self.http("GET", f"{self.read_url}/health/ready", timeout=5)
                body = json.loads(raw)
            except (OSError, ValueError):
                still_booting("/health/ready")
                continue
            now = f"{status} {json.dumps(body, sort_keys=True)[:160]}"
            if now != last:
                self.say(f"/health/ready: {now}")
                last = now
            if status == 200 and body.get("status") == "ok":
                break
            still_booting("/health/ready = ok")
        while True:
            fam = self.scrape()
            if self.event(fam, "label_device_build_errors") or self.event(fam, "warm_failures"):
                raise SmokeFailure(f"boot build failed:\n{self.log_tail()}")
            if self.event(fam, "label_device_builds") >= 1:
                break
            still_booting("the device label build")
        self.say(
            f"boot label build done on device: coverage "
            f"{self.metric(fam, 'keto_label_coverage_ratio'):.3f}"
        )
        while "width-ladder warmup" not in log.read_text(errors="replace"):
            if self.event(self.scrape(), "warm_failures"):
                raise SmokeFailure(f"warm-up failed:\n{self.log_tail()}")
            still_booting("the ladder warm-up")
        for line in self.log_tail(400).splitlines():
            if any(k in line for k in ("check engine devices", "HBM governor",
                                        "width-ladder warmup",
                                        "persistent compilation cache")):
                self.say(f"daemon: {line.strip()[-200:]}")

    def compare(self, what: str, queries, got, want) -> None:
        bad = [(q, g, w) for q, g, w in zip(queries, got, want) if bool(g) != bool(w)]
        self.mismatches += len(bad)
        self.say(f"{what}: {len(queries)} decisions, {len(bad)} mismatches")
        for q, g, w in bad[:5]:
            self.say(f"  MISMATCH {q}: served {g}, reference {w}")

    def drive(self) -> None:
        from keto_tpu.check.engine import CheckEngine
        from keto_tpu.cmd import client as grpc_client
        from keto_tpu.expand.engine import ExpandEngine
        from keto_tpu.httpclient import KetoClient
        from keto_tpu.list.engine import ListEngine
        from keto_tpu.relationtuple.model import SubjectID, SubjectSet
        from keto_tpu.relationtuple.proto_codec import subject_to_proto
        from ory.keto.acl.v1alpha1 import check_service_pb2

        rng = random.Random(self.args.seed + 1)
        client = KetoClient(self.read_url, self.write_url, timeout=300.0)
        store = self.open_store()
        oracle = CheckEngine(store)
        counts = {}
        try:
            # batched checks: POST /check/batch in calls of 4,096
            with self.phase("batched checks"):
                served_q, served, expected = [], [], []
                pos = 0
                first = True
                while len(served) < N_BATCHED:
                    qs = self.rbac_q[pos : pos + BATCH]
                    ex = self.rbac_expected[pos : pos + BATCH]
                    pos += BATCH
                    if first:  # the special shapes ride the first call
                        qs = qs[: BATCH - len(self.special_q)] + self.special_q
                        ex = ex[: BATCH - len(self.special_q)] + [None] * len(self.special_q)
                        first = False
                    served += client.batch_check(qs)
                    served_q += qs
                    expected += ex
                counts["batched"] = len(served)
                analytic = [i for i, e in enumerate(expected) if e is not None]
                self.compare(
                    "batched vs the generator's analytic expectation",
                    [served_q[i] for i in analytic], [served[i] for i in analytic],
                    [expected[i] for i in analytic],
                )
                special = [i for i, e in enumerate(expected) if e is None]
                sample = special + rng.sample(analytic, N_ORACLE_SAMPLE)
                self.compare(
                    "batched sample vs CheckEngine over the same SQLite file",
                    [served_q[i] for i in sample], [served[i] for i in sample],
                    [oracle.subject_is_allowed(served_q[i]) for i in sample],
                )
                counts["oracle_sample"] = len(sample)
                by_query = dict(zip(served_q, served))
                self.compare(
                    "gated checks (a blocklist, a two-condition permission) vs their "
                    "hand-written answers",
                    self.gate_q, [by_query[q] for q in self.gate_q],
                    [want for *_, want in GATE_CHECKS],
                )
            singles = self.rbac_q[pos : pos + N_SINGLE]
            pos += N_SINGLE
            with self.phase("single REST checks"):
                got = [client.check(q) for q in singles]
                self.compare("GET /check vs CheckEngine", singles, got,
                             [oracle.subject_is_allowed(q) for q in singles])
                counts["single_rest"] = len(got)
            grpc_q = self.rbac_q[pos : pos + N_GRPC]
            with self.phase("gRPC checks"):
                got = []
                with grpc_client.conn(self.grpc_target) as ch:
                    for q in grpc_q:
                        resp = grpc_client.unary(
                            ch, "/ory.keto.acl.v1alpha1.CheckService/Check",
                            check_service_pb2.CheckRequest(
                                namespace=q.namespace, object=q.object,
                                relation=q.relation,
                                subject=subject_to_proto(q.subject),
                            ),
                            check_service_pb2.CheckResponse,
                        )
                        got.append(resp.allowed)
                self.compare("gRPC CheckService/Check vs CheckEngine", grpc_q, got,
                             [oracle.subject_is_allowed(q) for q in grpc_q])
                counts["single_grpc"] = len(got)

            with self.phase("expand and reverse queries"):
                def canon(tree):
                    if tree is None:
                        return None
                    j = tree.to_json()

                    def walk(n):
                        kids = sorted((walk(c) for c in n.get("children", ())),
                                      key=lambda c: json.dumps(c, sort_keys=True))
                        return {**{k: v for k, v in n.items() if k != "children"},
                                "children": kids}

                    return walk(j)

                expander = ExpandEngine(store)
                lister = ListEngine(store)
                docs = sorted(self.doc_grant)
                bad = 0
                for d in rng.sample(docs, 4):
                    got = client.expand("docs", f"doc-{d}", "view", max_depth=4)
                    want = expander.build_tree(SubjectSet("docs", f"doc-{d}", "view"), 4)
                    bad += canon(got) != canon(want)
                for d in rng.sample(docs, 4):
                    got = list(client.list_subjects("docs", f"doc-{d}", "view"))
                    bad += got != lister.list_subjects("docs", f"doc-{d}", "view")
                # list-objects is, by definition, every object Check
                # allows: the generator's expectation over all its docs
                # plus the oracle over the special-shape docs (the
                # Manager-backed lister pays ~8 SQL reads per object found
                # — minutes per listing at 1M tuples)
                special_docs = sorted({q.object for q in self.special_q
                                       if q.namespace == "docs"})
                for q in rng.sample(singles, 4):
                    got = list(client.list_objects("docs", "view", q.subject))
                    u = int(q.subject.id.removeprefix("user-"))
                    want = {f"doc-{d}" for d, (kind, g) in self.doc_grant.items()
                            if self.user_reaches(u, kind, g)}
                    want |= {o for o in special_docs if oracle.subject_is_allowed(
                        self.T("docs", o, "view", q.subject))}
                    bad += got != sorted(want)
                self.mismatches += bad
                counts["expand_list"] = 12
                self.say(f"4 expand + 4 list-subjects vs the Manager-backed "
                         f"engines, 4 list-objects vs Check-by-definition: "
                         f"{bad} mismatches")

            # the write: PUT a group -> group edge, then read it back
            # pinned to the snaptoken the write returned
            with self.phase("write and pinned reads"):
                src, dst, doc, user = self.write_case
                flipped = self.T("docs", doc, "view", SubjectID(user))
                if client.check(flipped):
                    raise SmokeFailure(f"{flipped} allowed before the write")
                edge = self.T("groups", src, "member",
                              SubjectSet("groups", dst, "member"))
                status, _, headers = self.http(
                    "PUT", f"{self.write_url}/relation-tuples", edge.to_json())
                token = headers.get("X-Keto-Snaptoken")
                if status != 201 or not token:
                    raise SmokeFailure(f"PUT answered {status}, snaptoken {token!r}")
                token = int(token)
                self.say(f"PUT {edge} -> 201, snaptoken {token}")
                post = [flipped] + rng.sample(self.rbac_q[:N_BATCHED], 255)
                got = client.batch_check(post, snaptoken=token)
                got += [client.check(q, snaptoken=token) for q in post[:32]]
                post += post[:32]
                want = [oracle.subject_is_allowed(q) for q in post]
                if not want[0]:
                    raise SmokeFailure("the oracle does not see the written edge")
                self.compare("post-write checks pinned to the snaptoken vs "
                             "CheckEngine", post, got, want)
                counts["post_write"] = len(post)
                self.overlay_after_write = self.metric(self.scrape(), "keto_overlay_edges")

            # a store that is written to: N_JOINS one-tuple writes, then reads
            # beside the fold that compact_after_s brings (5 s after the
            # overlay was born); a fold sorts through programs the boot build
            # compiled, or on the host, and compiles nothing
            with self.phase("writes, a fold and reads beside it"):
                before = self.scrape()
                joins = [
                    self.T("groups", f"leaf-{self.joinable[i % len(self.joinable)]}",
                           "member", SubjectID(f"smoke-joiner-{i}"))
                    for i in range(N_JOINS)
                ]
                for t in joins:
                    status, _, _ = self.http(
                        "PUT", f"{self.write_url}/relation-tuples", t.to_json())
                    if status != 201:
                        raise SmokeFailure(f"PUT {t} answered {status}")
                asked, got = [], []
                deadline = time.monotonic() + 90
                while True:
                    # unpinned singles, each sent after its write's acknowledgement
                    qs = rng.sample(joins, 24) + rng.sample(self.rbac_q[:N_BATCHED], 8)
                    asked += qs
                    got += [client.check(q) for q in qs]
                    fam = self.scrape()
                    folds = (self.metric(fam, "keto_fold_runs_total")
                             - self.metric(before, "keto_fold_runs_total"))
                    if folds >= 1 and self.metric(fam, "keto_overlay_edges") == 0:
                        break
                    if time.monotonic() > deadline:
                        raise SmokeFailure("no fold 90 s after the writes")
                qs = joins + rng.sample(self.rbac_q[:N_BATCHED], 212)
                asked += qs
                got += client.batch_check(qs)  # the folded snapshot
                want = [oracle.subject_is_allowed(q) for q in asked]
                if not all(want[:24]):
                    raise SmokeFailure("the oracle does not see the joins")
                self.compare("reads beside and after the fold vs CheckEngine", asked, got, want)
                counts["beside_fold"] = len(asked)
                sorts = {
                    f"{labels['backend']}/{labels['why']}": int(value)
                    for _, labels, value in fam.get("keto_build_sort_total", {}).get("samples", ())
                }
                waits = {
                    labels["site"]: round(value, 4)
                    for _, labels, value in
                    fam.get("keto_engine_lock_wait_seconds_total", {}).get("samples", ())
                }
                stale = self.metric(fam, "keto_snapshot_stale_serves_total")
                fold_ms = [float(ms) for ms in re.findall(
                    r"overlay compacted in ([0-9.]+) ms", (self.out / "daemon.log").read_text())]
                self.say(f"{N_JOINS} writes, {int(folds)} fold(s) of {fold_ms} ms; sort batches "
                         f"since boot by backend/why {json.dumps(sorts)}; lock waits by site "
                         f"{json.dumps(waits)} s; stale serves {int(stale)}")
                if stale:
                    raise SmokeFailure(f"{int(stale)} rounds were served a stale snapshot")
                if sorts.get("host/error"):
                    raise SmokeFailure(f"{sorts['host/error']} sort batches failed on the device")
                if not fold_ms or max(fold_ms) > FOLD_LIMIT_MS:
                    raise SmokeFailure(f"a fold took over {FOLD_LIMIT_MS} ms: {fold_ms}")
        finally:
            store.close()
        self.counts = counts

    def check_metrics(self) -> None:
        from keto_tpu.driver.hbm import FALLBACK_BUDGET_BYTES
        from keto_tpu.graph.device_build import DEFAULT_MIN_EDGES

        # the shadow auditor works off the serving path: give it a moment
        deadline = time.monotonic() + 60
        while True:
            fam = self.scrape()
            if self.event(fam, "audit_checks") > 0 or time.monotonic() > deadline:
                break
            time.sleep(0.5)
        problems = []
        dev = [
            (labels, value)
            for _, labels, value in fam.get("keto_device_info", {}).get("samples", ())
        ]
        if len(dev) != 1:
            raise SmokeFailure(f"keto_device_info: expected one series, got {dev}")
        labels, count = dev[0]
        self.device = {
            "platform": labels["platform"], "kind": labels["device_kind"],
            "count": int(count),
        }
        self.say(f"daemon reports device {json.dumps(self.device)}")
        if self.device["platform"] != self.platform:
            problems.append(f"daemon serves from {self.device['platform']!r}")
        if self.device["count"] < self.args.chips:
            problems.append(f"daemon sees {self.device['count']} devices")

        routes = {
            r: int(self.metric(fam, "keto_stream_route_slices_total", route=r))
            for r in ("label", "hybrid", "bfs", "host", "cpu")
        }
        self.routes = routes
        self.say(f"route slices: {json.dumps(routes)}")
        if routes["label"] <= 0:
            problems.append("no slice was answered by the label kernel")
        if routes["bfs"] + routes["hybrid"] <= 0:
            problems.append("no slice was answered by the BFS kernel")
        if routes["cpu"]:
            problems.append(f"{routes['cpu']} slices answered by the CPU fallback")
        gated = {
            served: int(self.metric(fam, "keto_check_gate_checks_total", served=served))
            for served in ("device", "oracle")
        }
        self.say(f"gated checks by who served them: {json.dumps(gated)}")
        if gated["device"] < len(GATE_CHECKS) - 1:  # ``reader`` reaches no gate
            problems.append("the gated checks were not expanded on the device")
        if gated["oracle"]:
            problems.append(f"{gated['oracle']} gated checks went to the CPU oracle")
        native = int(self.metric(fam, "keto_native_pack_chunks_total", path="native"))
        self.say(f"native pack chunks: {native}")
        if native <= 0:
            problems.append("the native pack path never ran")
        resolved = {
            path: int(self.metric(fam, "keto_check_resolve_chunks_total", path=path))
            for path in ("native", "numpy")
        }
        self.say(f"resolved chunks by path: {json.dumps(resolved)}")
        if resolved["native"] <= 0:
            problems.append("the native resolve pass never ran")
        events = {e: int(self.event(fam, e)) for e in MUST_BE_ZERO + MUST_BE_POSITIVE}
        events["audit_checks"] = int(self.event(fam, "audit_checks"))
        events["label_invalidations"] = int(self.event(fam, "label_invalidations"))
        self.say(f"maintenance events: {json.dumps(events)}")
        problems += [f"{e} = {events[e]}" for e in MUST_BE_ZERO if events[e]]
        must_move = list(MUST_BE_POSITIVE)
        if self.n_tuples < 2 * DEFAULT_MIN_EDGES:
            # the build's sorts only go to the device past DEFAULT_MIN_EDGES
            # keys; a rehearsal-sized graph stays under it by design
            must_move.remove("device_build_dispatches")
            self.say(f"device_build_dispatches not required under "
                     f"{2 * DEFAULT_MIN_EDGES} tuples")
        problems += [f"{e} = 0" for e in must_move if not events[e]]
        if not events["audit_checks"]:
            problems.append("the shadow auditor verified nothing")
        if not events["label_invalidations"]:
            problems.append("the interior-edge write did not dirty the labels")
        gauges = {
            "audit_mismatches": self.metric(fam, "keto_audit_mismatches_total"),
            "degraded": self.metric(fam, "keto_health_state", state="degraded"),
            "overlay_edges": self.metric(fam, "keto_overlay_edges"),
            "list_device": self.metric(fam, "keto_list_requests_total", path="device"),
            "list_device_errors": self.metric(fam, "keto_list_device_errors_total"),
            "oom_events": self.metric(fam, "keto_oom_events_total"),
        }
        self.say(f"gauges: {json.dumps(gauges)}")
        for name in ("audit_mismatches", "degraded", "list_device_errors", "oom_events"):
            if gauges[name]:
                problems.append(f"{name} = {gauges[name]}")
        if self.overlay_after_write <= 0:  # read then: a fold has emptied it since
            problems.append("the write did not land in the delta overlay")
        if gauges["list_device"] <= 0:
            problems.append("no reverse query ran on the device")
        budget = int(self.metric(fam, "keto_hbm_budget_bytes"))
        resident = int(self.metric(fam, "keto_hbm_resident_bytes"))
        self.say(f"HBM governor: budget {budget} bytes, resident {resident} bytes")
        if self.platform == "tpu" and budget == FALLBACK_BUDGET_BYTES:
            problems.append(
                f"HBM budget is the {FALLBACK_BUDGET_BYTES}-byte fallback, "
                f"not derived from the device's bytes_limit"
            )
        if self.args.chips > 1:
            rounds = int(self.event(fam, "shard_halo_rounds"))
            shards = {
                labels.get("shard"): int(value)
                for _, labels, value in fam.get(
                    "keto_shard_hbm_resident_bytes", {}).get("samples", ())
            }
            self.say(f"sharded: halo rounds {rounds}, per-shard resident {shards}")
            if rounds <= 0:
                problems.append("the sharded kernel never halo-exchanged")
            if len(shards) != self.args.chips or not all(shards.values()):
                problems.append(f"not every shard holds state: {shards}")

        status, raw, _ = self.http("GET", f"{self.read_url}/debug/requests?n=200")
        body = json.loads(raw) if status == 200 else {}
        seen = {
            stage.get("attrs", {}).get("route")
            for tl in body.get("recent", ())
            for stage in tl.get("stages", ())
            if stage["stage"] == "device"
        } - {None}
        self.say(f"/debug/requests: {body.get('finished')} timelines finished, "
                 f"device-stage routes in the recent ring: {sorted(seen)}")
        if not seen & {"label", "hybrid", "bfs"}:
            problems.append("/debug/requests shows no device stage")
        if problems:
            raise SmokeFailure("the daemon's own counters disagree:\n  - "
                               + "\n  - ".join(problems))

    def stop_daemon(self) -> None:
        self.child.send_signal(signal.SIGTERM)
        try:
            rc = self.child.wait(timeout=120)
        except subprocess.TimeoutExpired:
            raise SmokeFailure("daemon ignored SIGTERM for 120s") from None
        if rc != 0:
            raise SmokeFailure(f"daemon exited with status {rc} on SIGTERM:\n"
                               f"{self.log_tail()}")
        self.cache_after = self.cache_entries()
        self.say(f"daemon exited 0 on SIGTERM; compile cache {self.cache_dir}: "
                 f"{self.cache_before} entries before, {self.cache_after} after")

    def cleanup(self) -> None:
        if self.child is not None and self.child.poll() is None:
            try:
                os.killpg(self.child.pid, signal.SIGKILL)
            except OSError:
                pass
            self.child.wait(timeout=30)
        if self.child_log is not None:
            self.child_log.close()
        shutil.rmtree(self.workdir, ignore_errors=True)

    def run(self) -> dict:
        steps = (
            ("probe device", self.probe_device),
            ("make native", self.build_native),
            ("generate", self.generate),
            ("load store", self.load_store),
            ("start daemon", self.start_daemon),
            ("boot (snapshot, label build, warm-up)", self.wait_ready),
            ("drive", self.drive),
            ("metrics", self.check_metrics),
            ("shutdown", self.stop_daemon),
        )
        for name, fn in steps:
            with self.phase(name):
                fn()
        if self.mismatches:
            raise SmokeFailure(f"{self.mismatches} decisions differ from the reference")
        return {
            "tuples": self.n_tuples,
            "checks": self.counts,
            "mismatches": 0,
            "routes": self.routes,
            "compile_cache": {
                "dir": str(self.cache_dir),
                "entries_before": self.cache_before,
                "entries_after": self.cache_after,
            },
            "smoke_timings_s": self.timings,
            "claim": None,
        }


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--platform", choices=("tpu", "cpu"), default="tpu",
                    help="tpu (default) fails without a TPU; cpu is the rehearsal")
    ap.add_argument("--tuples", type=int, default=1_000_000)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=21)
    ap.add_argument("--out", default=str(ROOT / "chiprun_out" / "chip_smoke"),
                    help="where the daemon log is kept")
    ap.add_argument("--boot-timeout", type=float, default=900.0)
    args = ap.parse_args()

    if not (ROOT / "keto_tpu" / "cmd").is_dir() or not (ROOT / "Makefile").is_file():
        # the script alone proves nothing: it drives the program beside it
        print(f"[chip_smoke platform={args.platform}] FAILED: {ROOT} holds "
              f"chip_smoke.py but not the keto_tpu package and its Makefile",
              file=sys.stderr, flush=True)
        return 1
    smoke = Smoke(args)
    # a kill at a time limit still stops the daemon and removes the store
    signal.signal(signal.SIGTERM, lambda *_: sys.exit(143))
    try:
        result = smoke.run()
    except SmokeFailure as e:
        print(f"{smoke.tag} FAILED: {e}", file=sys.stderr, flush=True)
        return 1
    finally:
        smoke.cleanup()
    if "jax" in sys.modules:
        # the parent's half of one-process-per-chip
        print(f"{smoke.tag} FAILED: the parent imported jax", file=sys.stderr)
        return 1
    smoke.say(f"summary: {json.dumps(result)}")
    # the result line: these keys and no others, the device as the daemon
    # named it on /metrics
    print(json.dumps({"ok": True, "device": smoke.device}), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
