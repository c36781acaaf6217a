"""``door_resolve_share.bulk`` (``benchmarks/layers/door_resolve_share.bulk.py``)
over two canned ``/metrics`` scrapes: the queries whose raw node ids came from
the thread that framed their body, of all that the window's rounds resolved. A
program that does not count where (every one before PR 35, the recorded scrapes
beside this file among them) reads None."""

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

READ = load_module("layers", "door_resolve_share.bulk").read

BEFORE = """\
keto_check_resolve_tuples_total{where="door"} 8192
keto_check_resolve_tuples_total{where="thread"} 4100
"""
AFTER = """\
keto_check_resolve_tuples_total{where="door"} 3008192
keto_check_resolve_tuples_total{where="thread"} 1004100
"""


def run_of(before: str, after: str):
    return SimpleNamespace(before=Metrics(before), after=Metrics(after), trace=None, result={})


def test_door_over_door_and_thread_of_the_window():
    # 3,000,000 at the door and 1,000,000 on the thread; the warm pass is not counted
    assert READ(run_of(BEFORE, AFTER)) == pytest.approx(75.0)


def test_every_frame_resolved_at_the_door_reads_100():
    after = AFTER.replace("1004100", "4100")
    assert READ(run_of(BEFORE, after)) == pytest.approx(100.0)


@pytest.mark.parametrize("program", ["recorded, PR 25", "no such family"])
def test_none_from_a_program_that_does_not_count_where(program):
    before, after = {
        "recorded, PR 25": tuple(
            (HERE / f"recorded_scrape_{name}.txt").read_text() for name in ("before", "after")
        ),
        "no such family": ("", ""),
    }[program]
    assert "keto_check_resolve_tuples_total" not in after
    assert READ(run_of(before, after)) is None


def test_a_window_without_rounds_has_nothing_to_read():
    assert READ(run_of(BEFORE, BEFORE)) is None


def test_the_manifest_declares_it_for_the_four_bulk_cells():
    (entry,) = [m for m in manifest_mod.load()["per_layer"] if m["name"] == "door_resolve_share.bulk"]
    cells = entry.pop("workloads")  # a later bulk cell may be appended
    assert cells[:4] == ["rbac-groups.bulk", "github-org.bulk", "drive-docs.bulk", "nested-groups.bulk"]
    assert entry == {
        "name": "door_resolve_share.bulk", "unit": "%", "better": "higher",
        "source": "program_counter", "layer": "resolve + pack", "moves": "checks_per_s",
    }
