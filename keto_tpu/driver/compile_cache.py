"""Where the persistent XLA compilation cache lives — the one place that
decides.

Compiled kernel geometries are expensive (seconds each on an accelerator)
and keyed, among other things, by the cache directory's own path, so the
directory must be *placeable from outside* and *stable across runs*:

1. ``JAX_COMPILATION_CACHE_DIR`` set → that directory. JAX reads the
   variable itself at import; this module never overrides it.
2. else ``serve.compile_cache_dir`` when the operator gave one;
3. else ``DEFAULT_DIR`` — one fixed, git-ignored path inside the
   checkout, resolved from this package's location (never a temp dir, a
   pid or a timestamp: a directory that moves never hits).

Cases 1 and 2 are an *explicit request*; the boot-time width-ladder
warm-up (``Daemon._warm_snapshot`` → ``engine.warm_compile``) stays keyed
to that. Case 3 applies on the CLI ``serve`` path only
(``Registry.use_default_compile_cache``): daemons that tests construct
in-process get no persistent cache — and no ladder warm-up — merely
because a default path exists.

What compiling costs is counted here too, from the runtime and not from
the directory: ``install_listener`` registers one ``jax.monitoring``
listener whose counts (``COMPILES``) the registry exports as
``keto_compile_seconds_total`` / ``keto_compiles_total`` /
``keto_compile_cache_hits_total``.
"""

from __future__ import annotations

import os
import threading
from pathlib import Path
from typing import Optional

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"

#: the fixed in-checkout default (listed in .gitignore)
DEFAULT_DIR = str(Path(__file__).resolve().parents[2] / ".jax_cache")


#: jax's own event names (jax/_src/dispatch.py, jax/_src/compiler.py). A
#: backend compile event spans ``compile_or_get_cached``: a program loaded
#: from the persistent cache counts as a (short) compile AND as a hit
_BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT = "/jax/compilation_cache/cache_hits"


class CompileCounts:
    """Backend compiles of this process, as ``jax.monitoring`` reports
    them. jax compiles on whichever thread first calls a program (warm-up,
    label build, the dispatch thread), hence the lock; a listener call is
    a comparison and two adds, and fires only when something compiles."""

    def __init__(self):
        self._lock = threading.Lock()  # guards: seconds, compiles, cache_hits
        self.seconds = 0.0
        self.compiles = 0
        self.cache_hits = 0
        self.installed = False

    def _on_duration(self, event: str, duration: float, **_kw) -> None:
        if event == _BACKEND_COMPILE:
            with self._lock:
                self.seconds += duration
                self.compiles += 1

    def _on_event(self, event: str, **_kw) -> None:
        if event == _CACHE_HIT:
            with self._lock:
                self.cache_hits += 1

    def snapshot(self) -> tuple[float, int, int]:
        with self._lock:
            return self.seconds, self.compiles, self.cache_hits


#: jax's listeners are process-wide and cannot be told apart once
#: registered, so the process has one set of counts
COMPILES = CompileCounts()


def install_listener() -> CompileCounts:
    """Register ``COMPILES`` with ``jax.monitoring``; idempotent."""
    if not COMPILES.installed:
        from jax import monitoring

        COMPILES.installed = True
        monitoring.register_event_duration_secs_listener(COMPILES._on_duration)
        monitoring.register_event_listener(COMPILES._on_event)
    return COMPILES


def resolve(option: str = "") -> tuple[str, bool]:
    """``(directory, explicit)`` per the precedence in the module
    docstring. ``option`` is ``serve.compile_cache_dir``."""
    env = os.environ.get(ENV_VAR, "")
    if env:
        return env, True
    if option:
        return option, True
    return DEFAULT_DIR, False


def configure(option: str = "", *, allow_default: bool = False) -> Optional[str]:
    """Point JAX's persistent compilation cache at the resolved directory
    and return it, or None when nothing was requested and the default is
    not allowed. With the environment variable set the directory is left
    exactly as JAX read it — no ``jax.config.update`` for it."""
    directory, explicit = resolve(option)
    if not explicit and not allow_default:
        return None
    import jax

    if not os.environ.get(ENV_VAR):
        jax.config.update("jax_compilation_cache_dir", directory)
    # every kernel geometry is worth keeping: the serving ladder is many
    # sub-second compiles whose sum is the boot cost
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return directory
