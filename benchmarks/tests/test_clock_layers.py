"""The readers of the program's own clocks (``benchmarks/layer_clock.py``)
over two recorded ``/metrics`` scrapes of a CPU rehearsal of
``rbac-groups.bulk`` (20k tuples, 3 s: counts and parity, not device
numbers), and over the same scrapes with the new families taken out, as an
older commit would answer them. Then the rehearsal itself: every cell's
``--trace 1`` line carries its new metrics and names its idle gaps by the
dispatch thread's states."""

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
from benchmarks.tests.test_rehearsal import rehearse  # noqa: E402

MANIFEST = manifest_mod.load()

#: what each reader gives on the recorded window, reckoned by hand from the
#: two files (sums and counts subtracted, 303,104 correct checks delivered)
RECORDED = {
    "pool_wait_ms.bulk": 138.55639035133774,
    "pool_wait_ms.interactive": 138.55639035133774,
    "decode_ms.bulk": 27.682512729742285,
    "encode_write_ms.bulk": 22.316474364875166,
    "dispatch_thread_ms_per_kcheck.bulk": 10.587362126533305,
    "device_wait_share.bulk": 1.8056287122825851,
    "admission_decreases.bulk": 0.0,
    "admission_delay_share.bulk": 32.61605558082589,
    "window_compile_s.bulk": 0.0,
    "window_compile_s.interactive": 0.0,
    "boot_compile_s": 2.4056990146636963,
}
#: families and label values a commit before PR 25 does not scrape
NEW = ("keto_dispatch_", "keto_compile", "keto_admission_decreases_total",
       "keto_admission_queue_delay_seconds", 'stage="pool_wait"', 'stage="decode"',
       'stage="encode_write"')


def recorded_run(without_new: bool = False):
    def scrape(name):
        text = (HERE / f"recorded_scrape_{name}.txt").read_text()
        if without_new:
            text = "\n".join(ln for ln in text.splitlines() if not any(n in ln for n in NEW))
        return Metrics(text)

    return SimpleNamespace(
        before=scrape("before"), after=scrape("after"), trace=None,
        result={"attempted": 303104, "failed": 0, "wrong": 0},
    )


def test_every_metric_this_file_knows_is_in_the_manifest_with_a_reader():
    declared = {m["name"] for m in MANIFEST["per_layer"]}
    assert set(RECORDED) <= declared
    for name in RECORDED:
        assert callable(load_module("layers", name).read)


@pytest.mark.parametrize("metric", sorted(RECORDED))
def test_reader_over_the_recorded_scrapes(metric):
    assert load_module("layers", metric).read(recorded_run()) == pytest.approx(RECORDED[metric])


@pytest.mark.parametrize("metric", sorted(RECORDED))
def test_reader_returns_none_where_the_family_is_absent(metric):
    assert load_module("layers", metric).read(recorded_run(without_new=True)) is None


def test_a_moving_decrease_counter_is_read_over_all_signals():
    run = recorded_run()
    run.after.samples["keto_admission_decreases_total"] = [
        ({"signal": "queue_delay"}, 2.0), ({"signal": "slice_p99"}, 1.0), ({"signal": "stall"}, 0.0),
    ]
    assert load_module("layers", "admission_decreases.bulk").read(run) == 3.0


@pytest.mark.parametrize("cell", [w["name"] for w in MANIFEST["workloads"]])
def test_traced_rehearsal_reports_the_new_metrics_and_names_gaps_by_dispatch_state(cell):
    line = rehearse(cell, trace=1, seed=2_500_000_025)
    want = {m["name"] for m in MANIFEST["per_layer"]
            if m["name"] in RECORDED and manifest_mod.reported_by(m, cell, MANIFEST)}
    assert want and want <= set(line["metrics"])
    assert all(line["metrics"][m]["value"] is not None for m in want)
    gaps = line["breakdown"]["idle_gaps"]
    assert gaps[0][0].startswith("keto.dispatch."), gaps
    by_state = sum(s for name, s in gaps if name.startswith("keto.dispatch."))
    assert by_state > 0.5 * sum(s for _, s in gaps)
