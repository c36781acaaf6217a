"""The five readers of PR 41 (``benchmarks/layers/{narrow_round_share,
cut_chunk_share,ctrl_narrow_events,long_stay_ms}.bulk.py`` and
``long_stay_ms.interactive.py``, over ``benchmarks/layer_ctrl.py``) on recorded
``/metrics`` scrapes: ``recorded_ctrl_scrape_{before,after}.txt`` are the
window's two scrapes of a CPU rehearsal of ``drive-docs.bulk`` whose takes the
slice controller narrowed (PR 41's tree, 20,000 tuples, the ``keto_dispatch_*``
and ``keto_stream_*`` families), ``recorded_ctrl_scrape_parent_*`` the same run
on PR 41's parent, which has none of the counters: every reader reads None
there, as on the scrapes recorded in PR 25."""

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

BULK = ["rbac-groups.bulk", "github-org.bulk", "drive-docs.bulk", "nested-groups.bulk",
        "github-rewrites.bulk"]
#: reader -> (what the recorded window reads, unit, layer, moves, cells)
READERS = {
    # 62 of the window's 338 rounds had the controller's cap for their room
    "narrow_round_share.bulk": (100.0 * 62 / 338, "%", "admission + lanes + batcher",
                                "checks_per_s", BULK),
    # 2 of its 338 chunks were cut by the entry budget alone (64 by the geometry)
    "cut_chunk_share.bulk": (100.0 * 2 / 338, "%",
                             "staging + transfers + kernel as the host sees it",
                             "checks_per_s", BULK),
    "ctrl_narrow_events.bulk": (4.0, "count", "staging + transfers + kernel as the host sees it",
                                "checks_per_s", BULK),
    # device_wait 270.2 + launch 117.8 + pack 246.3 + resolve 45.8 ms
    "long_stay_ms.bulk": (680.0719, "ms", "admission + lanes + batcher", "checks_per_s", BULK),
    "long_stay_ms.interactive": (680.0719, "ms", "admission + lanes + batcher", "check_p95_ms",
                                 ["rbac-groups.interactive"]),
}


def recorded(name: str) -> str:
    return (HERE / f"recorded_{name}.txt").read_text()


def run_of(before: str, after: str):
    return SimpleNamespace(before=Metrics(before), after=Metrics(after), trace=None, result={})


def read(name: str, before: str, after: str):
    return load_module("layers", name).read(run_of(before, after))


@pytest.mark.parametrize("name", sorted(READERS))
def test_the_recorded_window_of_a_narrowed_run(name):
    got = read(name, recorded("ctrl_scrape_before"), recorded("ctrl_scrape_after"))
    assert got == pytest.approx(READERS[name][0], rel=1e-6)


@pytest.mark.parametrize("name", sorted(READERS))
@pytest.mark.parametrize("program", ["the parent", "recorded, PR 25", "no scrape"])
def test_none_from_a_program_without_the_family(name, program):
    before, after = {
        "the parent": (recorded("ctrl_scrape_parent_before"), recorded("ctrl_scrape_parent_after")),
        "recorded, PR 25": (recorded("scrape_before"), recorded("scrape_after")),
        "no scrape": ("", ""),
    }[program]
    assert "keto_dispatch_round_cap_total" not in after
    assert read(name, before, after) is None


@pytest.mark.parametrize("name", sorted(READERS))
def test_a_steady_window_reads_0_and_a_window_without_rounds_nothing(name):
    """From boot every family has its whole label set at 0: a window in which
    nothing narrowed reads 0, not None. The two shares have nothing to divide
    by in a window without a round."""
    before = recorded("ctrl_scrape_before")
    steady = before.replace(
        'keto_dispatch_round_cap_total{by="batch_size"} 47',
        'keto_dispatch_round_cap_total{by="batch_size"} 947',
    ).replace(
        'keto_stream_chunks_total{cut="none"} 46', 'keto_stream_chunks_total{cut="none"} 946'
    )
    assert steady != before
    assert read(name, before, steady) == 0.0
    still = read(name, before, before)
    assert still is None if "share" in name else still == 0.0


def test_a_long_stay_in_wait_work_is_the_callers_not_the_threads():
    before = recorded("ctrl_scrape_before")
    line = 'keto_dispatch_long_stay_seconds_sum{state="wait_work"} 0'
    assert line in before
    after = before.replace(line, 'keto_dispatch_long_stay_seconds_sum{state="wait_work"} 2.5')
    assert read("long_stay_ms.bulk", before, after) == 0.0
    line = 'keto_dispatch_long_stay_seconds_sum{state="fill"} 0'
    after = before.replace(line, 'keto_dispatch_long_stay_seconds_sum{state="fill"} 2.5')
    assert read("long_stay_ms.interactive", before, after) == pytest.approx(2500.0)


def test_narrow_events_sum_over_the_routes():
    before = recorded("ctrl_scrape_before")
    after = before.replace(
        'keto_stream_ctrl_events_total{event="narrow",route="hybrid"} 0',
        'keto_stream_ctrl_events_total{event="narrow",route="hybrid"} 2',
    ).replace(
        'keto_stream_ctrl_events_total{event="widen",route="bfs"}',
        'keto_stream_ctrl_events_total{event="widen",route="bfs"} 99 #',
    )
    assert read("ctrl_narrow_events.bulk", before, after) == 2.0


@pytest.mark.parametrize("name", sorted(READERS))
def test_the_manifest_declares_it_last_with_its_cells(name):
    _, unit, layer, moves, cells = READERS[name]
    per_layer = manifest_mod.load()["per_layer"]
    (entry,) = [m for m in per_layer if m["name"] == name]
    assert entry.pop("workloads")[: len(cells)] == cells  # a later cell may be appended
    assert entry == {"name": name, "unit": unit, "better": "lower", "source": "program_counter",
                     "layer": layer, "moves": moves}
    # appended, in the issue's order, behind everything the benchmark had
    assert [m["name"] for m in per_layer].index(name) >= 42
