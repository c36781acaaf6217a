"""chip_smoke.py on the CPU, and the three strictness rules it rests on:
``engine.backend: tpu`` means a TPU, the compile cache is placed by one
resolver that never overrides the environment, and a TPU without memory
stats gets no invented HBM budget."""

import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

from keto_tpu.config.provider import Config
from keto_tpu.driver import compile_cache
from keto_tpu.driver.registry import Registry

ROOT = Path(__file__).resolve().parents[1]
NAMESPACES = [{"id": 0, "name": "docs"}]


def _smoke(*argv, tmp_path):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    # one virtual device, not the conftest's eight: the rehearsal is the
    # single-device path
    env.pop("XLA_FLAGS", None)
    return subprocess.run(
        [sys.executable, str(ROOT / "chip_smoke.py"), "--out", str(tmp_path), *argv],
        env=env, capture_output=True, text=True, timeout=600,
    )


def test_cpu_rehearsal_passes(tmp_path):
    proc = _smoke("--platform", "cpu", "--tuples", "20000", tmp_path=tmp_path)
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    lines = proc.stdout.strip().splitlines()
    # the last line is the result, these keys and no others
    result = json.loads(lines[-1])
    assert result == {
        "ok": True, "device": {"platform": "cpu", "kind": "cpu", "count": 1},
    }
    assert list(result) == ["ok", "device"]
    assert list(result["device"]) == ["platform", "kind", "count"]
    # the line before it is the summary of what ran
    tag, _, summary = lines[-2].partition(" summary: ")
    assert tag == "[chip_smoke platform=cpu]"
    result = json.loads(summary)
    assert result["mismatches"] == 0
    assert result["checks"]["batched"] >= 20_000
    assert result["routes"]["label"] > 0 and result["routes"]["bfs"] > 0
    assert list(result)[-1] == "claim" and result["claim"] is None
    # an explicit rehearsal says so in every line it prints
    assert all("cpu" in line for line in lines)


def test_without_a_tpu_it_fails_and_names_the_platform(tmp_path):
    proc = _smoke(tmp_path=tmp_path)
    assert proc.returncode != 0
    assert "'cpu'" in proc.stderr
    # no result: neither the summary nor the JSON line
    assert not any(line.startswith("{") or " summary: " in line
                   for line in proc.stdout.splitlines())


def test_alone_it_fails_and_prints_no_result(tmp_path):
    """In a directory that holds chip_smoke.py and nothing else of the repo."""
    alone = tmp_path / "alone"
    alone.mkdir()
    shutil.copy(ROOT / "chip_smoke.py", alone / "chip_smoke.py")
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    for argv in ([], ["--platform", "cpu", "--tuples", "20000"]):
        proc = subprocess.run(
            [sys.executable, "chip_smoke.py", *argv], cwd=alone, env=env,
            capture_output=True, text=True, timeout=120,
        )
        assert proc.returncode != 0
        assert proc.stdout.strip() == ""
        assert "not the keto_tpu package" in proc.stderr
    assert sorted(p.name for p in alone.iterdir()) == ["chip_smoke.py"]


def test_backend_tpu_refuses_the_cpu_backend():
    cfg = Config(overrides={
        "namespaces": NAMESPACES, "dsn": "memory", "engine.backend": "tpu",
    })
    registry = Registry(cfg)
    try:
        with pytest.raises(RuntimeError, match="found platform 'cpu'"):
            registry.permission_engine()
    finally:
        registry.close()
    # auto keeps taking whatever JAX selected, and names it on /metrics
    auto = Registry(Config(overrides={"namespaces": NAMESPACES, "dsn": "memory"}))
    try:
        assert 'keto_device_info{platform="none"' in auto.metrics().render()
        assert hasattr(auto.permission_engine(), "snapshot")
        assert (
            'keto_device_info{platform="cpu",device_kind="cpu"}'
            in auto.metrics().render()
        )
    finally:
        auto.close()


def test_tpu_without_memory_stats_gets_no_invented_budget(monkeypatch):
    import jax

    from keto_tpu.driver import hbm

    class StatlessTpu:
        platform = "tpu"

        def memory_stats(self):
            return None

    assert hbm.device_budget_bytes() == hbm.FALLBACK_BUDGET_BYTES  # cpu
    monkeypatch.setattr(jax, "local_devices", lambda: [StatlessTpu()])
    with pytest.raises(RuntimeError, match="bytes_limit"):
        hbm.device_budget_bytes()


def test_compile_cache_resolver(monkeypatch, tmp_path):
    monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    # nothing asked: the fixed in-checkout path, flagged as not explicit
    assert compile_cache.resolve("") == (str(ROOT / ".jax_cache"), False)
    assert compile_cache.resolve("/opt/cc") == ("/opt/cc", True)
    # the environment wins over the option, untouched
    monkeypatch.setenv(compile_cache.ENV_VAR, "/env/cc")
    assert compile_cache.resolve("/opt/cc") == ("/env/cc", True)

    import jax

    updates = []
    monkeypatch.setattr(
        jax.config, "update", lambda key, value: updates.append((key, value))
    )
    assert compile_cache.configure("/opt/cc") == "/env/cc"
    assert not any(key == "jax_compilation_cache_dir" for key, _ in updates)

    monkeypatch.delenv(compile_cache.ENV_VAR)
    del updates[:]
    # in-process daemons: no request, no cache (and so no ladder warm-up)
    assert compile_cache.configure("") is None
    assert updates == []
    assert compile_cache.configure("", allow_default=True) == str(ROOT / ".jax_cache")
    assert ("jax_compilation_cache_dir", str(ROOT / ".jax_cache")) in updates
    del updates[:]
    assert compile_cache.configure(str(tmp_path)) == str(tmp_path)
    assert ("jax_compilation_cache_dir", str(tmp_path)) in updates
