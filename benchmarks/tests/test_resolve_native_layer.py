"""``resolve_native_share.bulk`` (``benchmarks/layers/resolve_native_share.bulk.py``)
over two canned ``/metrics`` scrapes: the chunks the dispatch thread resolved
in its one native pass, of all the chunks it resolved inside the window. A
program without the family (every one before PR 44, the recorded scrapes
beside this file among them) reads None and does not raise."""

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

READ = load_module("layers", "resolve_native_share.bulk").read

BEFORE = """\
keto_check_resolve_chunks_total{path="native"} 48
keto_check_resolve_chunks_total{path="numpy"} 2
keto_check_resolve_declines_total{reason="no_library"} 0
keto_check_resolve_declines_total{reason="special"} 2
"""
AFTER = """\
keto_check_resolve_chunks_total{path="native"} 1548
keto_check_resolve_chunks_total{path="numpy"} 502
keto_check_resolve_declines_total{reason="no_library"} 0
keto_check_resolve_declines_total{reason="special"} 502
"""
PARENT = """\
keto_native_pack_chunks_total{path="fused"} %d
keto_check_pack_declines_total{reason="multi"} 0
"""


def run_of(before: str, after: str):
    return SimpleNamespace(before=Metrics(before), after=Metrics(after), trace=None, result={})


def test_native_over_both_paths_of_the_window():
    # 1,500 native, 500 numpy; the warm pass is not counted
    assert READ(run_of(BEFORE, AFTER)) == pytest.approx(75.0)


def test_every_chunk_through_the_pass_reads_100():
    after = AFTER.replace("502", "2")
    assert READ(run_of(BEFORE, after)) == pytest.approx(100.0)


def test_a_stale_library_reads_0_not_none():
    # the program has the family, and every chunk of the window resolved with numpy
    after = BEFORE.replace('numpy"} 2', 'numpy"} 902')
    assert READ(run_of(BEFORE, after)) == 0.0


@pytest.mark.parametrize("program", ["the parent", "recorded, PR 25", "no such family"])
def test_none_from_a_program_without_the_family(program):
    before, after = {
        "the parent": (PARENT % 50, PARENT % 1550),
        "recorded, PR 25": tuple(
            (HERE / f"recorded_scrape_{name}.txt").read_text() for name in ("before", "after")
        ),
        "no such family": ("", ""),
    }[program]
    assert "keto_check_resolve_chunks_total" not in after
    assert READ(run_of(before, after)) is None


def test_a_window_without_chunks_has_nothing_to_read():
    assert READ(run_of(BEFORE, BEFORE)) is None


def test_the_manifest_declares_it_for_the_six_bulk_cells():
    (entry,) = [m for m in manifest_mod.load()["per_layer"] if m["name"] == "resolve_native_share.bulk"]
    cells = entry.pop("workloads")  # a later bulk cell may be appended
    assert cells[:6] == ["rbac-groups.bulk", "github-org.bulk", "drive-docs.bulk",
                         "nested-groups.bulk", "github-rewrites.bulk", "github-blocklist.bulk"]
    assert entry == {
        "name": "resolve_native_share.bulk", "unit": "%", "better": "higher",
        "source": "program_counter", "layer": "resolve + pack", "moves": "checks_per_s",
    }
