"""``pack_fused_share.bulk`` (``benchmarks/layers/pack_fused_share.bulk.py``)
over two canned ``/metrics`` scrapes: the chunks the label route's one native
pass packed, of all the chunks packed inside the window. A program without the
path (every one before PR 39, the recorded scrapes beside this file among
them) reads None."""

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

READ = load_module("layers", "pack_fused_share.bulk").read

BEFORE = """\
keto_native_pack_chunks_total{path="fused"} 48
keto_native_pack_chunks_total{path="native"} 2
keto_native_pack_chunks_total{path="numpy"} 0
"""
AFTER = """\
keto_native_pack_chunks_total{path="fused"} 1548
keto_native_pack_chunks_total{path="native"} 402
keto_native_pack_chunks_total{path="numpy"} 100
"""
PARENT = """\
keto_native_pack_chunks_total{path="native"} %d
keto_native_pack_chunks_total{path="numpy"} 0
"""


def run_of(before: str, after: str):
    return SimpleNamespace(before=Metrics(before), after=Metrics(after), trace=None, result={})


def test_fused_over_every_path_of_the_window():
    # 1,500 fused, 400 native, 100 numpy; the warm pass is not counted
    assert READ(run_of(BEFORE, AFTER)) == pytest.approx(75.0)


def test_every_chunk_through_the_pass_reads_100():
    after = AFTER.replace("402", "2").replace("100\n", "0\n")
    assert READ(run_of(BEFORE, after)) == pytest.approx(100.0)


def test_a_stale_library_reads_0_not_none():
    # the program has the path, and every chunk of the window packed with numpy
    after = BEFORE.replace('numpy"} 0', 'numpy"} 900')
    assert READ(run_of(BEFORE, after)) == 0.0


@pytest.mark.parametrize("program", ["the parent", "recorded, PR 25", "no such family"])
def test_none_from_a_program_without_the_path(program):
    before, after = {
        "the parent": (PARENT % 50, PARENT % 1550),
        "recorded, PR 25": tuple(
            (HERE / f"recorded_scrape_{name}.txt").read_text() for name in ("before", "after")
        ),
        "no such family": ("", ""),
    }[program]
    assert 'path="fused"' not in after
    assert READ(run_of(before, after)) is None


def test_a_window_without_chunks_has_nothing_to_read():
    assert READ(run_of(BEFORE, BEFORE)) is None


def test_the_manifest_declares_it_for_the_five_bulk_cells():
    (entry,) = [m for m in manifest_mod.load()["per_layer"] if m["name"] == "pack_fused_share.bulk"]
    cells = entry.pop("workloads")  # a later bulk cell may be appended
    assert cells[:5] == ["rbac-groups.bulk", "github-org.bulk", "drive-docs.bulk",
                         "nested-groups.bulk", "github-rewrites.bulk"]
    assert entry == {
        "name": "pack_fused_share.bulk", "unit": "%", "better": "higher",
        "source": "program_counter", "layer": "resolve + pack", "moves": "checks_per_s",
    }
