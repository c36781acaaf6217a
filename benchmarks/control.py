#!/usr/bin/env python3
"""The control of ``correct``: the configuration's own plain reference cut
short of its deepest grant chain, put in the program's place; it has to come
out as NOT correct.

The configurations state that every answer equals their reference's (the one
the configuration's file names, ``reference.py`` where it names none: the
same one a run is judged by). The control answers with that reference's
search cut one step short of the configuration's deepest grant chain
(``control_max_depth`` in the configuration's file): the step a later PR
would be tempted by, fewer hops per slice. It needs no daemon:
for each seed it builds the cell's graph and requests at the cell's own size,
answers them as the control, and runs them through the comparison a run makes
(every answer against the analytic expectation, a seeded sample against the
full reference). Both counts have the limit 0, and both must be above it.

    python3 benchmarks/control.py --workload rbac-groups.bulk --seeds 11 12 13
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from benchmarks.run import REFERENCE_SAMPLE, Cell, check_sample  # noqa: E402

ANSWERED = 20_000  # answers the control gives per seed


def control_run(cell: Cell, seed: int, seconds: float, n: int = ANSWERED) -> dict:
    ctx = cell.inputs(seed, seconds)
    queries, expected = requests_of(cell, ctx)
    queries, expected = queries[:n], expected[:n]
    reference = cell.reference.build(ctx.graph.rows)
    depth = int(cell.config["control_max_depth"])
    served = [reference.allowed(*q, max_depth=depth) for q in queries]
    sound = [reference.allowed(*q) for q in queries]
    return {
        "seed": seed, "answers": len(queries), "reference": cell.reference.file,
        "control_max_depth": depth,
        "control_vs_analytic": sum(1 for g, w in zip(served, expected) if g != w),
        "control_vs_reference_sample": check_sample(
            reference, [(queries, served)], seed, REFERENCE_SAMPLE)[1],
        "reference_vs_analytic": sum(1 for g, w in zip(sound, expected) if g != w),
    }


def requests_of(cell: Cell, ctx):
    """The cell's queries and their analytic expectation, as its driver builds
    them at set-up."""
    state = cell.driver.prepare(ctx)
    if "pool" in state:
        return ([q for _, qs, _ in state["pool"] for q in qs],
                [e for _, _, es in state["pool"] for e in es])
    return state["queries"], state["expected"]


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--tuples", type=int, default=0)
    args = ap.parse_args()
    cell = Cell(args.workload, args.tuples)
    ok = True
    for seed in args.seeds:
        out = control_run(cell, seed, args.seconds)
        failed = out["control_vs_analytic"] > 0 and out["control_vs_reference_sample"] > 0
        sound = out["reference_vs_analytic"] == 0
        ok = ok and failed and sound
        print(json.dumps({"workload": cell.name, **out,
                          "control_comes_out_not_correct": failed,
                          "reference_agrees_with_generator": sound}), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    raise SystemExit(main())
