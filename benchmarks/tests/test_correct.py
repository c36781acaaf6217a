"""What decides ``correct`` has been shown to fail: the control (the reference
with the configuration's guarantee broken) comes out not correct on every
seed, and a whole run over a daemon whose timed path alters answers where they
are produced prints ``correct: false``."""

import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from benchmarks import control, run  # noqa: E402
from benchmarks import manifest as manifest_mod  # noqa: E402

CELLS = [w["name"] for w in manifest_mod.load()["workloads"]]


@pytest.mark.parametrize("cell", CELLS)
def test_control_comes_out_not_correct(cell):
    for seed in (5, 6, 2_400_000_011):
        out = control.control_run(run.Cell(cell, tuples=40_000), seed, seconds=3.0, n=6_000)
        assert out["reference_vs_analytic"] == 0, out
        assert out["control_vs_analytic"] > 0, out
        assert out["control_vs_reference_sample"] > 0, out


@pytest.mark.parametrize("cell", CELLS)
def test_altered_answers_come_out_not_correct(cell):
    """The rest of a run, past the look for a chip (``--platform cpu``), on a
    daemon that inverts every 997th decision."""
    args = SimpleNamespace(workload=cell, seed=77, seconds=3.0, trace=0, platform="cpu",
                           tuples=20_000, keep_log="")
    line = run.execute(args, lambda msg: None, entry=Path(__file__).with_name("broken_entry.py"))
    assert line["correct"] is False
    assert line["device"]["platform"] == "cpu"


def test_sound_run_is_correct():
    args = SimpleNamespace(workload=CELLS[0], seed=78, seconds=2.0, trace=0, platform="cpu",
                           tuples=20_000, keep_log="")
    assert run.execute(args, lambda msg: None)["correct"] is True
