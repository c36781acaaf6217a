"""The four readers ``nested-groups.bulk`` brought (``hybrid_route_share.bulk``,
``target_rows_mean.bulk``, ``label_build_s``, ``check_step_roofline.bulk`` under
``benchmarks/layers/``) over canned ``/metrics`` scrapes, and over the recorded scrapes of PR 25 beside this
file, which hold none of their families: a program without a counter reads
None, never 0."""

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

NEW = {
    "hybrid_route_share.bulk": ("%", "higher", "program_counter", "kernels", "checks_per_s"),
    "target_rows_mean.bulk": ("rows", "lower", "program_counter", "resolve + pack", "checks_per_s"),
    "label_build_s": ("s", "lower", "program_span", "snapshot build", "setup_s"),
    "check_step_roofline.bulk": ("%", "higher", "device_trace", "kernels", "checks_per_s"),
}
READ = {name: load_module("layers", name).read for name in NEW}

BEFORE = """\
keto_stream_route_slices_total{route="label"} 10
keto_stream_route_slices_total{route="hybrid"} 500
keto_stream_route_slices_total{route="bfs"} 6
keto_stream_route_slices_total{route="host"} 3
keto_stream_route_slices_total{route="cpu"} 0
keto_check_pack_rows_total{side="seed"} 2000000
keto_check_pack_rows_total{side="target"} 900000
keto_check_packed_total 196608
keto_check_pull_words_total 40000
keto_snapshot_ell_slots{kind="edge"} 232000
keto_snapshot_ell_slots{kind="pad"} 89206
keto_build_phase_duration_seconds_sum{phase="scan"} 4.5
keto_build_phase_duration_seconds_count{phase="scan"} 1
keto_build_phase_duration_seconds_sum{phase="labels"} 41.25
keto_build_phase_duration_seconds_count{phase="labels"} 1
"""
AFTER = """\
keto_stream_route_slices_total{route="label"} 20
keto_stream_route_slices_total{route="hybrid"} 9460
keto_stream_route_slices_total{route="bfs"} 36
keto_stream_route_slices_total{route="host"} 403
keto_stream_route_slices_total{route="cpu"} 0
keto_check_pack_rows_total{side="seed"} 42000000
keto_check_pack_rows_total{side="target"} 20900000
keto_check_packed_total 4196608
keto_check_pull_words_total 511552
keto_snapshot_ell_slots{kind="edge"} 232000
keto_snapshot_ell_slots{kind="pad"} 89206
keto_build_phase_duration_seconds_sum{phase="scan"} 4.5
keto_build_phase_duration_seconds_count{phase="scan"} 1
keto_build_phase_duration_seconds_sum{phase="labels"} 41.25
keto_build_phase_duration_seconds_count{phase="labels"} 1
"""


# the traced window of `nested-groups.bulk` as a v5e recorded it (my chip run, PR 34,
# call 4, seed 340401): seconds on the device by XLA module, and the window's length
TRACE = {"window_s": 3.002915208,
         "modules": {"jit_check_step": 2.8129, "jit_label_step": 0.1828}}
TPU = {"platform": "tpu", "kind": "TPU v5 lite"}


def run_of(before: str, after: str, trace=None, device=TPU):
    return SimpleNamespace(before=Metrics(before), after=Metrics(after), trace=trace,
                           result={"window_s": 10.172157495}, device=device)


def recorded():
    return run_of(*((HERE / f"recorded_scrape_{name}.txt").read_text()
                    for name in ("before", "after")))


def without(text: str, family: str) -> str:
    return "\n".join(ln for ln in text.splitlines() if family not in ln) + "\n"


def test_hybrid_slices_over_the_slices_a_device_route_landed_in_the_window():
    # 8,960 hybrid of 8,960 + 10 label + 30 bfs; host slices are no device route's
    assert READ["hybrid_route_share.bulk"](run_of(BEFORE, AFTER)) == pytest.approx(100 * 8960 / 9000)


def test_target_rows_over_the_checks_packed_in_the_window():
    assert READ["target_rows_mean.bulk"](run_of(BEFORE, AFTER)) == pytest.approx(20_000_000 / 4_000_000)


def test_label_build_is_the_labels_phase_at_ready_and_not_the_whole_build():
    assert READ["label_build_s"](run_of(BEFORE, AFTER)) == 41.25


def test_the_pulls_share_of_the_memory_bandwidth_stays_under_the_roofline():
    """471,552 pull words (614 takes of ~12 pulls at 64 words) over 321,206
    slots and 4 bytes, in the 10.17 s x 2.8129 / 3.0029 that ``check_step`` was
    on the device, against 819 GB/s."""
    share = READ["check_step_roofline.bulk"](run_of(BEFORE, AFTER, TRACE))
    assert share == pytest.approx(
        100 * 471552 * 321206 * 4 / (10.172157495 * 2.8129 / 3.002915208 * 819e9))
    assert 5 < share < 100


@pytest.mark.parametrize("trace, device", [
    (None, TPU),  # no traced window
    (TRACE, {"platform": "cpu", "kind": "cpu"}),  # a rehearsal: no peak to hold it against
    ({"window_s": 3.0, "modules": {"jit_label_step": 0.2}}, TPU),  # check_step never ran
])
def test_the_roofline_share_has_nothing_to_read(trace, device):
    assert READ["check_step_roofline.bulk"](run_of(BEFORE, AFTER, trace, device)) is None


@pytest.mark.parametrize("metric", sorted(NEW))
def test_none_on_the_recorded_scrapes_of_a_program_without_the_family(metric):
    text = (HERE / "recorded_scrape_after.txt").read_text()
    for family in ("keto_stream_route_slices_total", "keto_check_pack", "keto_build_phase_duration",
                   "keto_check_pull_words_total", "keto_snapshot_ell_slots"):
        assert family not in text
    run = recorded()
    run.trace = TRACE
    assert READ[metric](run) is None


@pytest.mark.parametrize("metric, family", [
    ("hybrid_route_share.bulk", "keto_stream_route_slices_total"),
    ("target_rows_mean.bulk", "keto_check_packed_total"),
    ("target_rows_mean.bulk", "keto_check_pack"),
    ("label_build_s", 'phase="labels"'),
    ("check_step_roofline.bulk", "keto_check_pull_words_total"),
    ("check_step_roofline.bulk", "keto_snapshot_ell_slots"),
])
def test_none_where_the_program_lacks_the_family(metric, family):
    assert READ[metric](run_of(without(BEFORE, family), without(AFTER, family), TRACE)) is None


@pytest.mark.parametrize(
    "metric", ["hybrid_route_share.bulk", "target_rows_mean.bulk", "check_step_roofline.bulk"])
def test_a_window_in_which_nothing_moved_has_nothing_to_read(metric):
    assert READ[metric](run_of(BEFORE, BEFORE, TRACE)) is None


def test_a_window_without_a_hybrid_slice_reads_zero_not_none():
    after = AFTER.replace('route="hybrid"} 9460', 'route="hybrid"} 500')
    assert READ["hybrid_route_share.bulk"](run_of(BEFORE, after)) == 0.0


@pytest.mark.parametrize("metric", sorted(NEW))
def test_the_manifest_declares_it_for_the_new_cell(metric):
    (entry,) = [m for m in manifest_mod.load()["per_layer"] if m["name"] == metric]
    cells = entry.pop("workloads")  # a later cell may be appended
    assert cells[0] == "nested-groups.bulk"
    unit, better, source, layer, moves = NEW[metric]
    assert entry == {"name": metric, "unit": unit, "better": better, "source": source,
                     "layer": layer, "moves": moves}
