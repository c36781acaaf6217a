"""``round_tuples_mean.bulk`` (``benchmarks/layers/round_tuples_mean.bulk.py``)
over two canned ``/metrics`` scrapes: the tuples the window's dispatch rounds
took off the lanes over those rounds, both ``overlapped`` values together. A
program that does not count a round's tuples (every one before PR 31, the
recorded scrapes beside this file among them) reads None."""

import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

ROOT = Path(__file__).resolve().parents[2]
HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT))

from benchmarks import manifest as manifest_mod  # noqa: E402
from benchmarks.daemon import Metrics  # noqa: E402
from benchmarks.run import load_module  # noqa: E402

READ = load_module("layers", "round_tuples_mean.bulk").read

ROUNDS_BEFORE = """\
keto_dispatch_rounds_total{overlapped="true"} 100
keto_dispatch_rounds_total{overlapped="false"} 40
"""
ROUNDS_AFTER = """\
keto_dispatch_rounds_total{overlapped="true"} 1090
keto_dispatch_rounds_total{overlapped="false"} 50
"""
TUPLES_BEFORE = "keto_dispatch_round_tuples_total 143360\n"
TUPLES_AFTER = "keto_dispatch_round_tuples_total 4143360\n"


def run_of(before: str, after: str):
    return SimpleNamespace(before=Metrics(before), after=Metrics(after), trace=None, result={})


def test_tuples_over_rounds_of_the_window_with_both_overlap_values_summed():
    # 4,000,000 tuples in 990 + 10 rounds; what was counted before the window is not
    got = READ(run_of(ROUNDS_BEFORE + TUPLES_BEFORE, ROUNDS_AFTER + TUPLES_AFTER))
    assert got == pytest.approx(4000.0)


@pytest.mark.parametrize("program", ["rounds without their tuples", "recorded, PR 25", "neither family"])
def test_none_from_a_program_that_does_not_count_a_rounds_tuples(program):
    before, after = {
        "rounds without their tuples": (ROUNDS_BEFORE, ROUNDS_AFTER),
        "recorded, PR 25": tuple(
            (HERE / f"recorded_scrape_{name}.txt").read_text() for name in ("before", "after")
        ),
        "neither family": ("", ""),
    }[program]
    assert "keto_dispatch_round_tuples_total" not in after
    assert READ(run_of(before, after)) is None


def test_a_window_without_rounds_has_nothing_to_read():
    scrape = ROUNDS_BEFORE + TUPLES_BEFORE
    assert READ(run_of(scrape, scrape)) is None


def test_the_manifest_declares_it_for_the_three_bulk_cells():
    (entry,) = [m for m in manifest_mod.load()["per_layer"] if m["name"] == "round_tuples_mean.bulk"]
    cells = entry.pop("workloads")  # a later bulk cell may be appended
    assert cells[:3] == ["rbac-groups.bulk", "github-org.bulk", "drive-docs.bulk"]
    assert entry == {
        "name": "round_tuples_mean.bulk", "unit": "tuples", "better": "higher",
        "source": "program_counter", "layer": "admission + lanes + batcher",
        "moves": "checks_per_s",
    }
