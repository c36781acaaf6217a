"""``overlap_share.bulk`` (``benchmarks/layers/overlap_share.bulk.py``) over two
canned ``/metrics`` scrapes: the share of the window's dispatch rounds that were
launched while another was still on the device. A program whose round counter
carries no ``overlapped`` label (the recorded scrapes of PR 25 beside this file
are such a program's), or no such family at all, reads None."""

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

READ = load_module("layers", "overlap_share.bulk").read

BEFORE = """\
# HELP keto_dispatch_rounds_total Dispatch rounds the batcher's collector has taken off the lanes
# TYPE keto_dispatch_rounds_total counter
keto_dispatch_rounds_total{overlapped="true"} 100
keto_dispatch_rounds_total{overlapped="false"} 40
"""
AFTER = """\
# HELP keto_dispatch_rounds_total Dispatch rounds the batcher's collector has taken off the lanes
# TYPE keto_dispatch_rounds_total counter
keto_dispatch_rounds_total{overlapped="true"} 2050
keto_dispatch_rounds_total{overlapped="false"} 90
"""


def run_of(before: str, after: str):
    return SimpleNamespace(before=Metrics(before), after=Metrics(after), trace=None, result={})


def recorded(name: str) -> str:
    return (HERE / f"recorded_scrape_{name}.txt").read_text()


def test_share_of_the_windows_rounds_that_were_overlapped():
    # 1,950 of the window's 2,000 rounds; what was counted before it is not
    assert READ(run_of(BEFORE, AFTER)) == pytest.approx(97.5)


def test_a_window_with_no_overlapped_round_reads_zero_not_none():
    after = AFTER.replace('"true"} 2050', '"true"} 100')
    assert READ(run_of(BEFORE, after)) == 0.0


def test_a_window_without_rounds_has_nothing_to_read():
    assert READ(run_of(BEFORE, BEFORE)) is None


@pytest.mark.parametrize("program", ["unlabelled counter", "no such family"])
def test_none_from_a_program_that_does_not_say(program):
    before, after = recorded("before"), recorded("after")
    assert "keto_dispatch_rounds_total 192" in before  # a counter, and it moved
    if program == "no such family":
        before, after = (
            "\n".join(ln for ln in text.splitlines() if "keto_dispatch_rounds" not in ln)
            for text in (before, after)
        )
    assert READ(run_of(before, after)) is None


def test_the_manifest_declares_it_for_the_three_bulk_cells():
    (entry,) = [m for m in manifest_mod.load()["per_layer"] if m["name"] == "overlap_share.bulk"]
    cells = entry.pop("workloads")  # a later bulk cell may be appended
    assert cells[:3] == ["rbac-groups.bulk", "github-org.bulk", "drive-docs.bulk"]
    assert entry == {
        "name": "overlap_share.bulk", "unit": "%", "better": "higher",
        "source": "program_counter", "layer": "admission + lanes + batcher",
        "moves": "checks_per_s",
    }
