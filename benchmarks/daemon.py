"""The daemon fixture: native libraries, the SQLite store, ONE child
``keto-tpu serve`` (through daemon_entry.py) with ``engine.backend: tpu`` and
otherwise the configuration's defaults, readiness, ``/metrics`` scrapes and
the drain. The pattern of chip_smoke.py, without its probe child: the device
is the one the daemon itself reports. This process never imports jax.
"""

from __future__ import annotations

import ctypes
import json
import os
import random
import re
import shutil
import signal
import socket
import sqlite3
import subprocess
import sys
import tempfile
import time
import urllib.error
import urllib.request
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
HERE = Path(__file__).resolve().parent
NATIVE_LIBS = ("libketoingest.so", "libketopack.so", "libketomux.so")
CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
BOOT_TIMEOUT_S = 1100.0  # a first run compiles; the contract gives it 1200 s

#: keto_maintenance_events_total{event} that must not move in a run
MUST_BE_ZERO = ("fallback_checks", "device_errors", "warm_failures", "refresh_failures")
DEVICE_ROUTES = ("label", "hybrid", "bfs")

_SAMPLE = re.compile(r"^([A-Za-z_:][A-Za-z0-9_:]*)(?:\{(.*)\})?\s+(\S+)")
_LABEL = re.compile(r'([A-Za-z_][A-Za-z0-9_]*)="((?:[^"\\]|\\.)*)"')


class BenchFailure(Exception):
    """The run cannot give a result; the message says why."""


class Metrics:
    """One scrape of ``/metrics`` (Prometheus text exposition)."""

    def __init__(self, text: str):
        self.samples: dict[str, list[tuple[dict, float]]] = {}
        for line in text.splitlines():
            if not line or line[0] == "#":
                continue
            m = _SAMPLE.match(line)
            if not m:
                continue
            name, labels, value = m.groups()
            try:
                v = float(value)
            except ValueError:
                continue
            self.samples.setdefault(name, []).append((dict(_LABEL.findall(labels or "")), v))

    def get(self, name: str, **labels) -> float:
        """Sum of the samples of ``name`` matching ``labels``; 0 when absent
        (a counter that never moved)."""
        return sum(
            v for sample_labels, v in self.samples.get(name, ())
            if all(sample_labels.get(k) == want for k, want in labels.items())
        )

    def series(self, name: str):
        return list(self.samples.get(name, ()))

    def event(self, name: str) -> float:
        return self.get("keto_maintenance_events_total", event=name)


def ensure_native(say) -> None:
    """``make native`` only when a library is missing; all three must load."""
    missing = [n for n in NATIVE_LIBS if not (ROOT / "native" / n).is_file()]
    if missing:
        proc = subprocess.run(["make", "native"], cwd=ROOT, capture_output=True,
                              text=True, timeout=600)
        if proc.returncode != 0:
            raise BenchFailure(f"make native failed:\n{proc.stdout}{proc.stderr}")
        say(f"built native libraries (missing: {missing})")
    for n in NATIVE_LIBS:
        try:
            ctypes.CDLL(str(ROOT / "native" / n))
        except OSError as e:
            raise BenchFailure(f"native library {n} does not load: {e}") from None


def compile_cache_dir() -> tuple[str, bool]:
    """``(directory, from the environment)``: where the machine says, else a
    fixed path inside the checkout (the path is part of the cache's key)."""
    env = os.environ.get(CACHE_ENV, "")
    return (env, True) if env else (str(ROOT / ".jax_cache"), False)


def cache_entries(directory: str) -> int:
    try:
        return sum(1 for p in Path(directory).iterdir() if p.is_file())
    except OSError:
        return 0


def free_ports(n: int) -> list[int]:
    """Ports below the kernel's ephemeral range (32768 up): a port the kernel
    hands out itself can come back as the local end of one of the run's own
    client connections, or sit in TIME_WAIT from the run before, by the time
    the daemon binds it."""
    rng, ports = random.SystemRandom(), []
    for _ in range(200):
        port = rng.randrange(20000, 30000)
        if port in ports:
            continue
        with socket.socket() as s:
            try:
                s.bind(("127.0.0.1", port))
            except OSError:
                continue
        ports.append(port)
        if len(ports) == n:
            return ports
    raise BenchFailure("no free port between 20000 and 30000")


def load_store(path: Path, rows, namespaces: list[dict]) -> None:
    """The generated rows into a SQLite store the daemon reads through its
    normal persister. The program's persister creates the schema (its
    migrations); the rows go in as one transaction at commit_time 1, the way
    the persister's own first write would leave them."""
    from keto_tpu import namespace as namespace_pkg
    from keto_tpu.persistence.sqlite import SQLitePersister

    nm = namespace_pkg.MemoryManager(
        [namespace_pkg.Namespace(id=n["id"], name=n["name"]) for n in namespaces]
    )
    store = SQLitePersister(f"sqlite://{path}", lambda: nm)
    nid = store.network_id
    store.close()
    ns_id = {n["name"]: n["id"] for n in namespaces}
    db = sqlite3.connect(str(path), isolation_level=None)
    try:
        db.execute("PRAGMA synchronous=OFF")
        db.execute("PRAGMA journal_mode=MEMORY")
        db.execute("PRAGMA cache_size=-2000000")  # 2 GB: the four indexes stay in memory
        db.execute("BEGIN")
        db.execute("INSERT INTO keto_watermarks (nid, watermark) VALUES (?, 1) "
                   "ON CONFLICT(nid) DO UPDATE SET watermark = 1", (nid,))
        db.executemany(
            "INSERT INTO keto_relation_tuples (shard_id, nid, namespace_id, object, "
            "relation, subject_id, subject_set_namespace_id, subject_set_object, "
            "subject_set_relation, commit_time) VALUES (?, ?, ?, ?, ?, ?, ?, ?, ?, 1)",
            (
                (f"b-{i}", nid, ns_id[ns], obj, rel, sid,
                 None if sns is None else ns_id[sns], sobj, srel)
                for i, (ns, obj, rel, sid, sns, sobj, srel) in enumerate(rows)
            ),
        )
        db.execute("COMMIT")
    finally:
        db.close()


class Daemon:
    def __init__(self, config: dict, platform: str, entry: Path | None = None):
        self.config, self.platform = config, platform
        self.entry = entry or HERE / "daemon_entry.py"
        self.workdir = Path(tempfile.mkdtemp(prefix="keto-bench-"))
        self.log_path = self.workdir / "daemon.log"
        self.store_path = self.workdir / "store.sqlite"
        self.trace_dir = self.workdir / "trace"
        self.device_file = self.workdir / "device.json"
        self.child = None
        self._log = None
        self.cache_dir, self._cache_from_env = compile_cache_dir()

    # -- life ------------------------------------------------------------------

    def start(self) -> None:
        ports = free_ports(2)
        self.read_port, self.write_port = ports
        serve = dict(self.config.get("serve", {}))
        serve["read"] = {"host": "127.0.0.1", "port": ports[0]}
        serve["write"] = {"host": "127.0.0.1", "port": ports[1]}
        if not self._cache_from_env:
            # an explicit request, like the variable: the daemon then warms
            # its kernel-width ladder at boot
            serve["compile_cache_dir"] = self.cache_dir
        cfg = {
            "namespaces": self.config["namespaces"],
            "dsn": f"sqlite://{self.store_path}",
            "serve": serve,
            "engine": {"backend": "tpu" if self.platform == "tpu" else "auto"},
            "log": {"level": "info"},
        }
        cfg_path = self.workdir / "keto.json"  # JSON is YAML
        cfg_path.write_text(json.dumps(cfg, indent=1))
        env = dict(os.environ)
        env["KETO_BENCH_CONTROL_DIR"] = str(self.workdir)
        if self.platform == "cpu":
            env["JAX_PLATFORMS"] = "cpu"
        self._log = open(self.log_path, "wb")
        self.child = subprocess.Popen(
            [sys.executable, str(self.entry), "serve", "-c", str(cfg_path)],
            cwd=ROOT, env=env, stdout=self._log, stderr=subprocess.STDOUT,
            start_new_session=True,
        )

    def log_tail(self, n: int = 60) -> str:
        try:
            return "\n".join(self.log_path.read_text(errors="replace").splitlines()[-n:])
        except OSError:
            return "(no daemon log)"

    def http(self, method: str, port: int, path: str, timeout: float = 30.0):
        req = urllib.request.Request(f"http://127.0.0.1:{port}{path}", method=method)
        try:
            with urllib.request.urlopen(req, timeout=timeout) as resp:
                return resp.status, resp.read()
        except urllib.error.HTTPError as e:
            return e.code, e.read()

    def scrape(self) -> Metrics:
        status, raw = self.http("GET", self.read_port, "/metrics")
        if status != 200:
            raise BenchFailure(f"/metrics answered {status}")
        return Metrics(raw.decode())

    def wait_ready(self, timeout: float = BOOT_TIMEOUT_S) -> dict:
        """``/health/ready`` = ok, then the boot label build and the kernel
        ladder warm-up (the daemon logs the latter; it joins the former).
        Returns the host-clock stamps of both."""
        deadline = time.monotonic() + timeout
        stamps = {}

        def still_booting(what: str) -> None:
            if self.child.poll() is not None:
                raise BenchFailure(f"daemon exited with status {self.child.returncode} "
                                   f"waiting for {what}:\n{self.log_tail()}")
            if time.monotonic() > deadline:
                raise BenchFailure(f"{what}: not after {timeout}s:\n{self.log_tail()}")
            time.sleep(0.25)

        while True:
            try:
                status, raw = self.http("GET", self.read_port, "/health/ready", timeout=5)
                if status == 200 and json.loads(raw).get("status") == "ok":
                    break
            except (OSError, ValueError):
                pass
            still_booting("/health/ready = ok")
        stamps["ready"] = time.monotonic()
        while "width-ladder warmup" not in (log := self.log_path.read_text(errors="replace")):
            if "boot snapshot warm failed" in log:
                raise BenchFailure(f"warm-up failed:\n{self.log_tail()}")
            still_booting("the ladder warm-up")
        stamps["warmed"] = time.monotonic()
        return stamps

    def signal_and_wait(self, sig: int, marker: Path, timeout: float = 120.0) -> None:
        """Signal daemon_entry.py's helper and wait for the file it writes."""
        marker.unlink(missing_ok=True)
        self.child.send_signal(sig)
        deadline = time.monotonic() + timeout
        while not marker.exists():
            if self.child.poll() is not None or time.monotonic() > deadline:
                raise BenchFailure(f"daemon did not write {marker.name}:\n{self.log_tail()}")
            time.sleep(0.02)

    def start_trace(self) -> None:
        self.signal_and_wait(signal.SIGUSR1, self.workdir / "trace_started")

    def stop_trace_and_report_device(self) -> dict:
        """Stops a running trace and has the daemon write what JAX says of
        its devices: platform, kind, count, peak bytes on the fullest chip."""
        self.signal_and_wait(signal.SIGUSR2, self.device_file)
        return json.loads(self.device_file.read_text())

    def stop(self) -> None:
        self.child.send_signal(signal.SIGTERM)
        try:
            rc = self.child.wait(timeout=120)
        except subprocess.TimeoutExpired:
            raise BenchFailure("daemon ignored SIGTERM for 120s") from None
        if rc != 0:
            raise BenchFailure(f"daemon exited with status {rc} on SIGTERM:\n{self.log_tail()}")

    def cleanup(self, keep_log_in: Path | None = None) -> None:
        if self.child is not None and self.child.poll() is None:
            try:
                os.killpg(self.child.pid, signal.SIGKILL)
            except OSError:
                pass
            self.child.wait(timeout=30)
        if self._log is not None:
            self._log.close()
        if keep_log_in is not None and self.log_path.exists():
            keep_log_in.mkdir(parents=True, exist_ok=True)
            shutil.copy(self.log_path, keep_log_in / "daemon.log")
            if (self.workdir / "trace.json").exists():
                shutil.copy(self.workdir / "trace.json", keep_log_in / "trace.json")
                for pb in self.trace_dir.glob("plugins/profile/*/*.xplane.pb"):
                    shutil.copy(pb, keep_log_in / "trace.xplane.pb")
        shutil.rmtree(self.workdir, ignore_errors=True)
