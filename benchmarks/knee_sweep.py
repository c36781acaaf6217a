#!/usr/bin/env python3
"""Find, once, the highest rate of single checks a configuration sustains:
one daemon's life, steps of ``--step-seconds`` at each of ``--rates``. The
knee is the highest step whose p95 is under ``--limit-ms`` with nothing shed
or failed and a generator lateness p95 under 5 ms. The cell's rate, 0.8 x the
knee, is then written by hand into ``traffic/interactive.json``; PERF.md
records every step. Not part of a benchmark run.

    python3 benchmarks/knee_sweep.py --workload rbac-groups.interactive --seed 9
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from benchmarks.daemon import BenchFailure, ensure_native  # noqa: E402
from benchmarks.run import Cell, guard, served  # noqa: E402
from types import SimpleNamespace  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--rates", type=float, nargs="+",
                    default=[50, 100, 200, 400, 800, 1600])
    ap.add_argument("--step-seconds", type=float, default=10.0)
    ap.add_argument("--limit-ms", type=float, default=50.0)
    ap.add_argument("--platform", choices=("tpu", "cpu"), default="tpu")
    ap.add_argument("--tuples", type=int, default=0)
    ap.add_argument("--keep-log", default="")
    args = ap.parse_args()
    tag = f"[knee_sweep platform={args.platform}]"
    say = lambda msg: print(f"{tag} {msg}", flush=True)

    cell = Cell(args.workload, args.tuples)
    driver = cell.driver
    ensure_native(say)
    ctx = cell.inputs(args.seed, args.step_seconds)
    ctx.mix = dict(ctx.mix, rate=args.rates[0])
    knee, steps = None, []
    try:
        with served(cell, ctx, args, {}) as (daemon, _):
            first = daemon.scrape()
            for k, rate in enumerate(args.rates):
                ctx.seed = args.seed + 1000 * k
                state = driver.prepare(ctx, rate=rate, seconds=args.step_seconds)
                before = daemon.scrape()
                out = driver.run(ctx, state, args.step_seconds)
                after = daemon.scrape()
                shed = sum(after.get(n) - before.get(n)
                           for n in ("keto_lane_shed_total", "keto_admission_shed_total"))
                step = {
                    "rate": rate, "requests": out["attempted"],
                    "p50_ms": out["metrics"]["check_p50_ms"],
                    "p95_ms": out["metrics"]["check_p95_ms"],
                    "failed": out["failed"], "wrong": out["wrong"], "shed": shed,
                    "late_p95_ms": out["late_p95_ms"], "statuses": out["statuses"],
                }
                step["meets"] = bool(step["p95_ms"] < args.limit_ms and not shed
                                     and not out["failed"] and not out["wrong"]
                                     and step["late_p95_ms"] < 5.0)
                steps.append(step)
                say(json.dumps(step))
                if step["meets"]:
                    knee = rate
                time.sleep(1.0)
            device = daemon.stop_trace_and_report_device()
            problems = guard(SimpleNamespace(device=device, before=first, after=daemon.scrape()),
                             args.platform, cell.chips)
            daemon.stop()
    except BenchFailure as e:
        print(f"{tag} FAILED: {e}", file=sys.stderr, flush=True)
        return 1
    if problems:
        print(f"{tag} FAILED: {problems}", file=sys.stderr, flush=True)
        return 1
    print(json.dumps({"workload": cell.name, "platform": device["platform"],
                      "limit_ms": args.limit_ms, "knee_req_per_s": knee,
                      "cell_rate": None if knee is None else 0.8 * knee, "steps": steps}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
