"""CPU rehearsal of every cell at 20k tuples: counts and parity only. Nothing
here is a device number; every line of a rehearsal says ``cpu``."""

import json
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from benchmarks import manifest as manifest_mod  # noqa: E402

MANIFEST = manifest_mod.load()
CELLS = [w["name"] for w in MANIFEST["workloads"]]


def rehearse(cell: str, trace: int, seed: int):
    proc = subprocess.run(
        [sys.executable, str(ROOT / "benchmarks" / "run.py"), "--workload", cell,
         "--seed", str(seed), "--seconds", "3", "--trace", str(trace),
         "--platform", "cpu", "--tuples", "20000"],
        cwd=ROOT, capture_output=True, text=True, timeout=600,
    )
    assert proc.returncode == 0, proc.stdout[-2000:] + proc.stderr[-2000:]
    lines = proc.stdout.strip().splitlines()
    assert all("platform=cpu" in ln for ln in lines[:-1])
    return json.loads(lines[-1])


@pytest.mark.parametrize("cell", CELLS)
def test_cell_reports_its_end_to_end_metrics(cell):
    line = rehearse(cell, trace=0, seed=3_000_000_017)  # more than 31 bits
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 0
    assert line["device"]["platform"] == "cpu"
    want = {m["name"] for m in MANIFEST["end_to_end"]
            if manifest_mod.reported_by(m, cell, MANIFEST)}
    assert set(line["metrics"]) == want
    assert all(v["value"] > 0 for v in line["metrics"].values())


@pytest.mark.parametrize("cell", CELLS)
def test_cell_reports_its_per_layer_metrics(cell):
    line = rehearse(cell, trace=1, seed=41)
    assert line["correct"] is True
    allowed = {m["name"] for m in MANIFEST["per_layer"]
               if manifest_mod.reported_by(m, cell, MANIFEST)}
    assert line["metrics"] and set(line["metrics"]) <= allowed
    device = line["device"]
    assert device["busy_s"] > 0 and device["window_s"] > device["busy_s"]
    assert len(line["breakdown"]["device_ops"]) <= 10
    assert len(line["breakdown"]["idle_gaps"]) <= 10


def test_no_result_beside_nothing_of_the_program(tmp_path):
    """In a directory that holds only BENCHMARK.json and the files under
    ``paths`` the command exits non-zero and prints no result."""
    import shutil

    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "benchmarks", tmp_path / "benchmarks",
                    ignore=shutil.ignore_patterns("__pycache__"))
    proc = subprocess.run(
        [sys.executable, "benchmarks/run.py", "--workload", CELLS[0], "--seed", "1",
         "--seconds", "1", "--trace", "0"], cwd=tmp_path, capture_output=True, text=True,
        timeout=120)
    assert proc.returncode != 0 and proc.stdout.strip() == ""
