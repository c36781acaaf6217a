"""Test bootstrap.

Tests run on a virtual 8-device CPU mesh so multi-chip sharding paths are
exercised without TPU hardware (the analog of the reference's dockertest
database matrix, reference internal/x/dbx/dsn_testutils.go:22-78). The env
must be set before JAX is imported anywhere.
"""

import os

# an ambient persistent compilation cache is an explicit request to warm
# the whole kernel-width ladder at every daemon boot
# (keto_tpu/driver/compile_cache.py); tests that want it set it themselves
os.environ.pop("JAX_COMPILATION_CACHE_DIR", None)

_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (_flags + " --xla_force_host_platform_device_count=8").strip()

# CI hang diagnosis: with KETO_TEST_HANG_DUMP_S set, every thread's stack
# dumps to stderr that many seconds in (repeating), so a wedged supervisor
# or a deadlocked refresh shows up in the job log instead of as a silent
# runner-level timeout kill.
_hang_dump_s = os.environ.get("KETO_TEST_HANG_DUMP_S")
if _hang_dump_s:
    import faulthandler

    faulthandler.dump_traceback_later(float(_hang_dump_s), repeat=True)

import jax

# force CPU even when the ambient environment pins JAX_PLATFORMS to an
# accelerator: tests need the virtual 8-device mesh; the chip is reached
# through chip_smoke.py (and bench.py), never through pytest
jax.config.update("jax_platforms", "cpu")

import pytest

from keto_tpu import namespace as namespace_pkg
from keto_tpu.persistence.memory import MemoryPersister


def pytest_configure(config):
    """A fresh checkout has no native libraries (``*.so`` is git-ignored)
    and ~40 tests skip without them — the count then depends on whether
    something else happened to run ``make native`` in the tree before.
    Build them once, in the controlling process and before any xdist
    worker imports a test file (the skip conditions are evaluated at
    import). ``make`` goes by time stamps, so a tree whose libraries are
    older than their sources (an entry point added since) is rebuilt too,
    and a fresh one costs nothing. Where there is no compiler the tests
    skip as before."""
    if hasattr(config, "workerinput"):
        return
    import subprocess
    from pathlib import Path

    root = Path(__file__).resolve().parents[1]
    try:
        subprocess.run(["make", "native"], cwd=root, capture_output=True, timeout=600)
    except (OSError, subprocess.TimeoutExpired):
        pass


@pytest.fixture
def make_persister():
    """Factory: persister over a fresh store with the given namespaces."""

    def factory(namespaces, network_id="default"):
        nss = [
            namespace_pkg.Namespace(id=n[1], name=n[0]) if isinstance(n, tuple) else n
            for n in namespaces
        ]
        return MemoryPersister(namespace_pkg.MemoryManager(nss), network_id=network_id)

    return factory
