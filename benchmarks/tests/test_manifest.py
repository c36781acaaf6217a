"""The manifest's validation: each fault is an error before anything starts."""

import copy
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from benchmarks import manifest as manifest_mod  # noqa: E402
from benchmarks.rooflines.peaks import peaks_for  # noqa: E402
from benchmarks import traffic  # noqa: E402

GOOD = manifest_mod.load()


def broken(edit):
    m = copy.deepcopy(GOOD)
    edit(m)
    return m


@pytest.mark.parametrize("edit", [
    lambda m: m["workloads"][0].update(name="rbac groups/bulk"),
    lambda m: m["end_to_end"][0].update(name="checks per s"),
    lambda m: m["end_to_end"][0].update(unit="checks-per-second-x"),
    lambda m: m["per_layer"][0].update(moves="check_p50_ms", workloads=["rbac-groups.bulk"]),
    lambda m: m["per_layer"][0].update(moves="no_such_metric"),
    lambda m: m["per_layer"][0].update(workloads=["rbac-groups.interactive"]),
    lambda m: m["end_to_end"].pop(),
    lambda m: m["workloads"][0].update(config="nope"),
    lambda m: m["workloads"][0].update(chips=2),
    lambda m: m["configs"][0].update(file="keto_tpu/x.json"),
], ids=["name-space-slash", "metric-name", "unit-17", "moves-not-reported-by-cell",
        "moves-unknown", "layer-cell-lacks-target", "no-setup_s", "unknown-config",
        "chips-2", "file-outside-paths"])
def test_fault_is_an_error(edit):
    with pytest.raises(manifest_mod.ManifestError):
        manifest_mod.validate(broken(edit))


def test_unknown_device_kind_is_an_error():
    assert peaks_for("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        peaks_for("TPU v9 imaginary")


def test_every_seed_offers_the_same_work():
    a = traffic.arrival_offsets(1, 400, 10)
    b = traffic.arrival_offsets(2_500_000_000, 400, 10)
    assert len(a) == len(b) == 4000 and a != b
    gaps = lambda xs: sorted(round(y - x, 6) for x, y in zip([0.0] + xs, xs))
    assert gaps(a) == pytest.approx(gaps(b), abs=2e-6)
    assert a == traffic.arrival_offsets(1, 400, 10)
    objs = traffic.skewed_objects(7, 1000, 5000, {"kind": "zipf", "theta": 0.99})
    assert objs == traffic.skewed_objects(7, 1000, 5000, {"kind": "zipf", "theta": 0.99})
    assert max(objs) < 1000 and len(set(objs)) > 100
    top = max(set(objs), key=objs.count)
    assert objs.count(top) > 5000 / 20  # rank 1 of Zipf(0.99) over 1000 draws ~13%
