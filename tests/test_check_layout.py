"""The check path's layout: which module may know of which, and the
switches that went with the split (ISSUE 30).

``check/tpu_engine.py`` keeps a snapshot on the chip and is the only module
under ``keto_tpu/check/`` that knows of the engine; ``dispatch.py`` answers
a batch from a snapshot it is given, on top of ``pack.py`` (host),
``kernels.py`` (device), ``slice_ctrl.py`` and ``geometry.py``. Each module
is imported alone, in an interpreter of its own, and must not have pulled in
what sits above it.
"""

import os
import pkgutil
import subprocess
import sys

import pytest

import keto_tpu.check
from keto_tpu.check.tpu_engine import TpuCheckEngine
from keto_tpu.config.provider import Config
from keto_tpu.x.errors import ErrBadRequest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CHECK = "keto_tpu.check."
MODULES = sorted(m.name for m in pkgutil.iter_modules(keto_tpu.check.__path__))

#: what a module of keto_tpu/check/ must not import, besides tpu_engine
ABOVE = {
    "kernels": ("dispatch", "pack", "slice_ctrl", "geometry"),
    "pack": ("dispatch", "kernels", "slice_ctrl"),
    "slice_ctrl": ("dispatch", "kernels"),
    "geometry": ("dispatch", "kernels", "pack", "slice_ctrl"),
}


def _imported_with(module: str) -> set:
    out = subprocess.run(
        [sys.executable, "-c",
         f"import sys, {module}; print('\\n'.join(sorted(sys.modules)))"],
        cwd=REPO, env={**os.environ, "JAX_PLATFORMS": "cpu"},
        capture_output=True, text=True, timeout=120,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    return set(out.stdout.split())


def test_every_check_module_is_covered():
    assert {"dispatch", "kernels", "pack", "slice_ctrl", "tpu_engine"} <= set(MODULES)


@pytest.mark.parametrize("name", [m for m in MODULES if m != "tpu_engine"])
def test_check_module_does_not_import_the_engine(name):
    loaded = _imported_with(CHECK + name)
    assert CHECK + "tpu_engine" not in loaded
    for other in ABOVE.get(name, ()):
        assert CHECK + other not in loaded, f"{name} imports {other}"


def test_list_engine_takes_its_kernel_from_kernels():
    loaded = _imported_with("keto_tpu.list.tpu_engine")
    assert CHECK + "kernels" in loaded
    assert CHECK + "tpu_engine" not in loaded and CHECK + "dispatch" not in loaded


@pytest.mark.parametrize("key", ["serve.native_pack_enabled", "serve.staging_enabled"])
def test_config_rejects_the_deleted_options(key):
    with pytest.raises(ErrBadRequest):
        Config(overrides={key: True})


@pytest.mark.parametrize("kwarg", ["native_pack_enabled", "staging_enabled"])
def test_engine_rejects_the_deleted_constructor_flags(kwarg):
    with pytest.raises(TypeError):
        TpuCheckEngine(None, None, **{kwarg: False})


def test_deleted_environment_switches_switch_nothing(monkeypatch):
    """``KETO_TPU_DONATE`` and ``KETO_TPU_NATIVE_PACK`` are read by nobody:
    the kernel variant follows the platform, the pack path the library."""
    from keto_tpu.check import kernels, native_pack

    was = native_pack.available()
    monkeypatch.setenv("KETO_TPU_DONATE", "1")
    monkeypatch.setenv("KETO_TPU_NATIVE_PACK", "0")
    assert kernels._donation_default() is False  # the tests run on the CPU
    monkeypatch.setattr(native_pack, "_lib_checked", False)  # load it anew
    monkeypatch.setattr(native_pack, "_lib", None)
    assert native_pack.available() == was
