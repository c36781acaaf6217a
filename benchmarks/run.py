#!/usr/bin/env python3
"""The benchmark's one command.

    python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One run of one cell of ``BENCHMARK.json``: generate the configuration's graph
from the seed, load it into SQLite, boot ONE ``keto-tpu serve`` child on the
chip with the daemon's defaults, warm it with the cell's own request shape,
drive the cell's traffic over REST for ``--seconds``, compare what the window
served with the generator's analytic expectation and the plain reference, and
print one JSON line. ``--trace 0`` reports the end-to-end metrics; ``--trace
1`` runs the same traffic with a few seconds of it under the profiler and
reports the per-layer metrics, the device's busy time and a breakdown.

Cells, configurations, traffic mixes, drivers, per-layer readers and plain
references are found by name: ``configs/<config>.json`` (which names
``generators/<name>.py`` and may name ``references/<name>.py``, the judge of
its own semantics; without the key it is ``reference.py``, Check as Keto v0.7
defines it), ``traffic/<mix>.json`` (which names ``drivers/<name>.py``) and
``layers/<metric>.py``. Adding one adds files and a manifest entry.

A run that did not serve from the device prints no result. ``--platform cpu
--tuples 20000`` is the rehearsal and says ``cpu`` in every line.
"""

from __future__ import annotations

import time

_T_START = time.monotonic()

import argparse  # noqa: E402
import bisect  # noqa: E402
import contextlib  # noqa: E402
import importlib.util  # noqa: E402
import itertools  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import random  # noqa: E402
import signal  # noqa: E402
import subprocess  # noqa: E402
import sys  # noqa: E402
import threading  # noqa: E402
from pathlib import Path  # noqa: E402
from types import SimpleNamespace  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT))

from benchmarks import manifest as manifest_mod  # noqa: E402
from benchmarks import traffic  # noqa: E402
from benchmarks.daemon import (  # noqa: E402
    DEVICE_ROUTES, MUST_BE_ZERO, BenchFailure, Daemon, cache_entries, ensure_native, load_store,
)
from benchmarks.layer_util import delta  # noqa: E402
from benchmarks.reference import Reference  # noqa: E402
from benchmarks.rooflines.peaks import peaks_for  # noqa: E402

REFERENCE_SAMPLE = 2048
TRACE_SECONDS = 3.0


def load_module(kind: str, name: str):
    """``benchmarks/<kind>/<name>.py`` as a module, found by name."""
    path = HERE / kind / f"{name}.py"
    if not path.is_file():
        raise BenchFailure(f"no {kind[:-1]} {name!r}: {path} is missing")
    spec = importlib.util.spec_from_file_location(f"benchmarks.{kind}.{name}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def load_config(entry: dict) -> dict:
    with open(ROOT / entry["file"]) as f:
        return json.load(f)


def load_reference(config: dict) -> SimpleNamespace:
    """The configuration's plain reference, found by name: ``file`` says
    where it lives and ``build(rows)`` makes it. A configuration that names
    none is judged by ``reference.py``; a named one is
    ``references/<name>.py``, whose ``Reference(rows, config)`` reads its
    schema from the configuration's file."""
    name = config.get("reference")
    if name is None:
        return SimpleNamespace(file="benchmarks/reference.py", build=Reference)
    module = load_module("references", name)
    cls = getattr(module, "Reference", None)
    if not callable(getattr(cls, "allowed", None)):
        raise BenchFailure(f"reference {name!r}: {module.__file__} has no class Reference with "
                           f"allowed(ns, obj, rel, user, max_depth=None)")
    return SimpleNamespace(file=f"benchmarks/references/{name}.py",
                           build=lambda rows: cls(rows, config))


def guard(run, platform: str, chips: int) -> list[str]:
    """Why this run is not a measurement of the device path, if it is not."""
    problems = []
    dev = run.device
    if dev["platform"] != platform:
        problems.append(f"the daemon serves from platform {dev['platform']!r}, not {platform!r}")
    if dev["count"] < chips:
        problems.append(f"the daemon sees {dev['count']} devices, the cell asks for {chips}")
    info = run.after.series("keto_device_info")
    if len(info) != 1 or info[0][0].get("platform") != dev["platform"]:
        problems.append(f"keto_device_info disagrees with jax: {info}")
    moved = sum(delta(run, "keto_stream_route_slices_total", route=r) for r in DEVICE_ROUTES)
    if moved <= 0:
        problems.append("no slice was answered by a device kernel inside the window")
    if run.after.get("keto_stream_route_slices_total", route="cpu"):
        problems.append("slices were answered by the CPU fallback")
    problems += [f"{e} = {int(run.after.event(e))}" for e in MUST_BE_ZERO if run.after.event(e)]
    if run.after.get("keto_health_state", state="degraded"):
        problems.append("the daemon is degraded")
    return problems


def check_sample(reference, answered, seed: int, n: int, max_depth=None):
    """A seeded sample of what the window served against the plain reference:
    ``(compared, mismatches)``. ``answered`` is ``[(queries, results)]``."""
    starts = list(itertools.accumulate((len(q) for q, _ in answered), initial=0))
    total = starts[-1]
    picks = range(total) if total <= n else random.Random(seed + 2).sample(range(total), n)
    bad = 0
    for pick in picks:
        block = bisect.bisect_right(starts, pick) - 1
        queries, results = answered[block]
        q, got = queries[pick - starts[block]], bool(results[pick - starts[block]])
        bad += reference.allowed(*q, max_depth=max_depth) != got
    return len(picks), bad


class Cell:
    """Everything one cell names, found by name."""

    def __init__(self, workload: str, tuples: int = 0):
        self.manifest = manifest_mod.load()
        self.workload, self.config_entry = manifest_mod.cell(self.manifest, workload)
        self.name = self.workload["name"]
        self.config = load_config(self.config_entry)
        self.mix = traffic.load_mix(self.workload["traffic"])
        self.generator = load_module("generators", self.config["generator"])
        self.driver = load_module("drivers", self.mix["driver"])
        self.reference = load_reference(self.config)
        reported = lambda m: manifest_mod.reported_by(m, self.name, self.manifest)
        self.e2e = [m for m in self.manifest["end_to_end"] if reported(m)]
        self.layers = [m for m in self.manifest["per_layer"] if reported(m)]
        self.readers = {m["name"]: load_module("layers", m["name"]) for m in self.layers}
        self.n_tuples = tuples or int(self.config["tuples"])
        self.chips = int(self.workload["chips"])

    def inputs(self, seed: int, seconds: float) -> SimpleNamespace:
        """The graph from the seed, and what the driver needs to build and
        send the cell's requests. A configuration that names a ``work_seed``
        draws its graph and its requests from that one whatever the run's
        seed is, so that every seed does the same work: the run's seed then
        puts the requests in another order and draws the reference's sample."""
        work_seed = int(self.config.get("work_seed", seed))
        graph = self.generator.build(random.Random(work_seed), self.n_tuples)
        return SimpleNamespace(
            mix=self.mix, seed=seed, work_seed=work_seed, graph=graph,
            generator=self.generator, seconds=seconds,
            config_name=self.config_entry["name"], read_port=None, write_port=None,
        )


@contextlib.contextmanager
def served(cell: Cell, ctx, args, stamps: dict, entry: Path | None = None):
    """The store loaded, ONE daemon booted, ready, warmed by its own ladder and
    by one untimed pass of the cell's request shape. Yields ``(daemon, driver
    state)``; stops and removes everything on the way out."""
    daemon = Daemon(cell.config, args.platform, entry=entry)
    try:
        load_store(daemon.store_path, ctx.graph.rows, cell.config["namespaces"])
        stamps["loaded"] = time.monotonic()
        daemon.start()
        # the cell's requests are built while the daemon boots
        state = cell.driver.prepare(ctx)
        stamps.update(daemon.wait_ready())
        ctx.read_port, ctx.write_port = daemon.read_port, daemon.write_port
        cell.driver.warm(ctx, state)
        stamps["warm"] = time.monotonic()
        yield daemon, state
    finally:
        daemon.cleanup(keep_log_in=Path(args.keep_log) if args.keep_log else None)


def execute(args, say, entry: Path | None = None) -> dict:
    """One run; returns the result line's object. ``entry`` replaces the
    child's entry script (the tests break the timed path with it)."""
    cell = Cell(args.workload, args.tuples)
    say(f"cell {cell.name}: configuration {cell.config_entry['name']} at {cell.n_tuples} "
        f"tuples, traffic {cell.workload['traffic']} ({cell.mix['driver']}), {cell.chips} "
        f"chip(s), seed {args.seed}, {args.seconds}s, trace {args.trace}")
    ensure_native(say)
    stamps = {"start": _T_START}
    ctx = cell.inputs(args.seed, args.seconds)
    stamps["generated"] = time.monotonic()
    with served(cell, ctx, args, stamps, entry) as (daemon, state):
        setup_s = stamps["warm"] - _T_START
        say("set-up " + ", ".join(
            f"{b} {stamps[b] - stamps[a]:.1f}s" for a, b in
            zip(("start", "generated", "loaded", "ready", "warmed"),
                ("generated", "loaded", "ready", "warmed", "warm"))) + f"; setup_s {setup_s:.2f}")

        run = SimpleNamespace(stamps=stamps, trace=None)
        run.cache_before = cache_entries(daemon.cache_dir)
        run.before = daemon.scrape()
        tracer = None
        if args.trace:
            # a few seconds of the steady window under the profiler
            def trace_window():
                time.sleep(min(2.0, args.seconds / 4))
                daemon.start_trace()
                time.sleep(min(TRACE_SECONDS, args.seconds / 2))
                run.device = daemon.stop_trace_and_report_device()

            tracer = threading.Thread(target=trace_window, daemon=True)
            tracer.start()
        run.result = cell.driver.run(ctx, state, args.seconds)
        if tracer is not None:
            tracer.join(timeout=300)
            if tracer.is_alive() or not hasattr(run, "device"):
                raise BenchFailure(f"the traced window did not close:\n{daemon.log_tail()}")
        run.device = daemon.stop_trace_and_report_device()
        run.after = daemon.scrape()
        run.cache_after = cache_entries(daemon.cache_dir)
        if args.platform == "tpu":
            peaks_for(run.device["kind"])  # an unknown device kind is an error
        problems = guard(run, args.platform, cell.chips)
        if args.trace:
            run.trace = reduce_trace(daemon.trace_dir, daemon.workdir / "trace.json")
            if run.trace["busy_s"] <= 0:
                problems.append("the traced window shows no operation on the device")
        daemon.stop()
        if problems:
            raise BenchFailure("not a measurement of the device path:\n  - "
                               + "\n  - ".join(problems))
    return result_line(cell, ctx, run, args, setup_s, say)


def result_line(cell: Cell, ctx, run, args, setup_s: float, say) -> dict:
    """Correctness, once the window has closed and the daemon has gone: every
    answer against the generator's analytic expectation (the drivers count
    ``wrong``) and a seeded sample against the configuration's plain
    reference. Then the line."""
    result = run.result
    reference = cell.reference.build(ctx.graph.rows)
    compared, ref_bad = check_sample(reference, result["answered"], args.seed, REFERENCE_SAMPLE)
    delivered = result["attempted"] - result["failed"]
    say(f"correct: answers differing from the analytic expectation {result['wrong']} of "
        f"{delivered} (limit 0); sample differing from the plain reference "
        f"({cell.reference.file}) {ref_bad} of {compared} (limit 0); failed or shed "
        f"{result['failed']} of {result['attempted']}")
    correct = result["wrong"] == 0 and ref_bad == 0 and delivered > 0 and compared > 0

    line = {"correct": correct, "attempted": result["attempted"], "failed": result["failed"]}
    device = {k: run.device[k] for k in ("platform", "kind", "count", "memory_peak_bytes")}
    if args.trace:
        values = {name: reader.read(run) for name, reader in cell.readers.items()}
        line["metrics"] = {
            m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
            for m in cell.layers if values[m["name"]] is not None
        }
        device["busy_s"] = run.trace["busy_s"]
        device["window_s"] = run.trace["window_s"]
        line["breakdown"] = {"device_ops": run.trace["device_ops"],
                             "idle_gaps": run.trace["idle_gaps"]}
    else:
        values = dict(result["metrics"], setup_s=setup_s)
        missing = [m["name"] for m in cell.e2e if m["name"] not in values]
        if missing:
            raise BenchFailure(f"driver {cell.mix['driver']} does not report {missing}")
        line["metrics"] = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                           for m in cell.e2e}
    line["device"] = device

    extras = {k: result[k] for k in ("window_s", "calls", "late_p95_ms", "statuses", "reconnects",
                                     "client_ms_median", "server_ms_median") if k in result}
    routes = {r: int(delta(run, "keto_stream_route_slices_total", route=r))
              for r in ("label", "hybrid", "bfs", "host", "cpu")}
    shed = run.after.get("keto_admission_shed_total") + run.after.get("keto_lane_shed_total")
    say(f"daemon: route slices in the window {json.dumps(routes)}; label coverage "
        f"{run.after.get('keto_label_coverage_ratio'):.3f}; admission window "
        f"{int(run.after.get('keto_admission_window'))} tuples, {int(shed)} shed since boot; "
        f"label builds on the device {int(run.after.event('label_device_builds'))}")
    say(f"window: {json.dumps(extras)}; compile cache {run.cache_before} -> {run.cache_after} "
        f"entries inside the window")
    # each number compared, beside its limit: last in the line, and main()
    # repeats them as the last lines of standard error
    line["compared"] = {"differ_from_analytic": {"value": result["wrong"], "limit": 0},
                        "differ_from_reference": {"value": ref_bad, "limit": 0}}
    return line


def reduce_trace(trace_dir: Path, out: Path) -> dict:
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, str(HERE / "trace_reduce.py"), str(trace_dir), str(out)],
                          env=env, capture_output=True, text=True, timeout=600)
    if proc.returncode != 0:
        raise BenchFailure(f"trace_reduce failed:\n{proc.stderr[-3000:]}")
    return json.loads(out.read_text())


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--platform", choices=("tpu", "cpu"), default="tpu",
                    help="tpu (default) gives no result without a TPU; cpu is the rehearsal")
    ap.add_argument("--tuples", type=int, default=0,
                    help="rehearsal only: a smaller graph than the configuration's")
    ap.add_argument("--keep-log", default="", help="directory to keep the daemon's log in")
    args = ap.parse_args(argv)
    tag = f"[bench platform={args.platform}]"

    def say(msg: str) -> None:
        print(f"{tag} {msg}", flush=True)

    if not (ROOT / "keto_tpu" / "cmd").is_dir() or not (ROOT / "Makefile").is_file():
        print(f"{tag} FAILED: {ROOT} holds the benchmark but not the keto_tpu package "
              f"and its Makefile", file=sys.stderr, flush=True)
        return 1
    if args.tuples and args.platform != "cpu":
        print(f"{tag} FAILED: --tuples is for the --platform cpu rehearsal", file=sys.stderr)
        return 1
    # a kill at a time limit still stops the daemon and removes the store
    signal.signal(signal.SIGTERM, lambda *_: sys.exit(143))
    try:
        line = execute(args, say)
    except (BenchFailure, manifest_mod.ManifestError) as e:
        print(f"{tag} FAILED: {e}", file=sys.stderr, flush=True)
        return 1
    if "jax" in sys.modules:
        print(f"{tag} FAILED: the parent imported jax", file=sys.stderr)
        return 1
    for name, c in line["compared"].items():
        print(f"{tag} compared {name} = {c['value']} (limit {c['limit']})", file=sys.stderr,
              flush=True)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
