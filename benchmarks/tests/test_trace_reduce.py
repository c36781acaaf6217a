"""trace_reduce against a small recorded trace: ``recorded_tpu_trace.json``
holds the planes, lines and events of 40 ms cut from a ``--trace 1`` run of
``rbac-groups.bulk`` on one TPU v5e (PR 23), as ``trace_reduce.load`` returns
them; ``recorded_cpu_trace.xplane.pb`` is a whole, tiny trace of the CPU
backend, read through ``jax.profiler.ProfileData`` as a run reads its own."""

import json
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from benchmarks import trace_reduce  # noqa: E402

HERE = Path(__file__).resolve().parent


def test_union_merges_overlaps():
    assert trace_reduce.union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3), (5, 8)]


def test_reduction_of_hand_made_planes():
    planes = {
        "/device:TPU:0": {
            "XLA Modules": [("jit_label_step(1)", 100, 400), ("jit_check_step(2)", 600, 700)],
            "XLA Ops": [("%fusion.1 = u32[8] fusion(...), calls=%fused.1", 100, 300),
                        ("%copy.2 = s32[8] copy(...)", 250, 400), ("%fusion.1 = u32[8] fusion(...), calls=%fused.1", 600, 700)],
        },
        "/host:CPU": {"python3": [("np.asarray(jax.Array)", 0, 90), ("pack", 420, 590)]},
    }
    out = trace_reduce.reduce_planes(planes)
    assert out["window_s"] == pytest.approx(700e-9)
    assert out["busy_s"] == pytest.approx(400e-9)  # [100,400] and [600,700]
    assert out["modules"] == {"jit_label_step(1)": pytest.approx(300e-9),
                              "jit_check_step(2)": pytest.approx(100e-9)}
    assert out["device_ops"][0] == ["fusion.1 (fused.1)", pytest.approx(300e-9)]
    gaps = dict(out["idle_gaps"])
    assert gaps["pack [python3]"] == pytest.approx(200e-9)
    assert gaps["np.asarray(jax.Array) [python3]"] == pytest.approx(100e-9)


def test_recorded_tpu_trace():
    planes = json.loads((HERE / "recorded_tpu_trace.json").read_text())
    out = trace_reduce.reduce_planes(planes)
    want = json.loads((HERE / "recorded_tpu_trace.expected.json").read_text())
    assert out["device_planes"] == ["/device:TPU:0"]
    assert out["busy_s"] == pytest.approx(want["busy_s"])
    assert out["window_s"] == pytest.approx(want["window_s"])
    assert 0 < out["busy_s"] < out["window_s"]
    assert any("label_step" in m or "check_step" in m for m in out["modules"])
    # the ops of a module run inside it: module time covers op time
    assert sum(out["modules"].values()) >= out["busy_s"] * 0.99
    assert [n for n, _ in out["device_ops"]] == [n for n, _ in want["device_ops"]]
    assert len(out["idle_gaps"]) <= 10 and out["idle_gaps"][0][1] > 0


def test_recorded_cpu_trace_file():
    out = trace_reduce.reduce_planes(trace_reduce.load(HERE / "recorded_cpu_trace.xplane.pb"))
    assert out["busy_s"] > 0 and out["window_s"] > out["busy_s"]
    assert out["device_ops"]
