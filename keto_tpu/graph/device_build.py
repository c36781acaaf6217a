"""Device-side snapshot construction: stable sorts on the accelerator.

Building a 50M-tuple snapshot is dominated by O(E log E) host sorts: the
device-id renumbering lexsort, the ELL edge grouping, the forward CSR,
the sink reverse CSR, the transposed CSR, and both reverse-query list
layouts — six stable argsorts over edge-scale arrays executed serially
by numpy (keto_tpu/graph/snapshot.py documents each). TrieJax's framing
(PAPERS.md) applies directly: they are relational sort/group-by passes
that map cleanly onto the accelerator.

This module provides the **sorter seam** those builders now go through:

- ``HostSorter`` — ``np.argsort(kind="stable")``, the legacy path and
  the bit-exactness oracle;
- ``DeviceSorter`` — the same stable argsort executed by ``jax.lax.sort``
  (via ``jnp.argsort(stable=True)``), batched so one build round-trips
  the device a handful of times (``argsort_many`` fuses independent
  sorts into one dispatch) instead of once per numpy pass.

**Bit-identity is the contract, not a goal.** Every key array the build
sorts is integral and fits int32 (device ids and edge endpoints are
int32 throughout the layout), and a stable sort over equal integer keys
is unique — so the permutation the device returns is *defined* to equal
the host one, and tests/test_streaming_build.py fuzz-asserts byte
equality of every derived snapshot array. Anything non-sort (searchsorted
offsets, bucket scatters) stays on host over the returned permutations:
those passes are O(E) memcpy-speed and keeping them host-side keeps the
two paths one code path.

**A sort's program depends on a rung, never on a count.** Every key
array is padded to a length from a short ladder (``rung_of``: the powers
of two from ``DEFAULT_MIN_EDGES`` up, as the label build pads its batches)
with a sentinel that sorts last (``int32`` max). The sort is stable and
the padding sits behind the real keys, so the first ``n`` entries of the
padded permutation are the unpadded permutation, bit for bit; a graph
that grows by 57 edges sorts through the program it sorted through before.
A real key equal to the sentinel is refused (``SentinelCollision``) and
the batch sorts on the host.

The engine registers the transient sort footprint with the HBM governor
under the ``build`` tag and falls back to ``HostSorter`` (same answers,
host speed) when the plan does not fit — a cold start must never evict
serving state just to build faster (keto_tpu/driver/hbm.py). **While a
snapshot is serving a sort never compiles**: a batch whose rungs this
process has not compiled sorts on the host (numpy sorts a million keys in
a tenth of a second; the chip's compiler takes 14-20 s over the program)
and the program is compiled on a worker thread that holds no lock of the
engine's, so the next fold of that size finds it (``GovernedSorter``).
"""

from __future__ import annotations

import contextlib
import logging
import queue
import threading
import time
from typing import Callable, Optional, Sequence

import numpy as np

from keto_tpu.x.profiling import SESSION

_log = logging.getLogger("keto_tpu.device_build")

#: below this many keys (the largest array of a batch; the engine compares
#: against max(n_nodes, n_edges)) a sort stays on the host: dispatch and
#: transfer cost more than numpy does. It is also the ladder's first rung.
#: At or above it a sort goes to the device, unless the governor's plan
#: does not fit or, while a snapshot is serving, the batch's rungs are not
#: compiled in this process yet: then the host again.
DEFAULT_MIN_EDGES = 65536

#: the padding key: sorts behind every real key
SENTINEL = int(np.iinfo(np.int32).max)

#: why a batch sorted where it did (``keto_build_sort_total{backend,why}``):
#: the device only ever for ``ok``
SORT_WHYS = ("small", "pressure", "cold", "error")

#: keys are cast and range-checked this many at a time, so that a block is
#: still in cache when its minimum and maximum are taken
_PAD_BLOCK = 1 << 16

_jit_lock = threading.Lock()  # guards: _jit_cache, _compiled
_jit_cache: dict[int, object] = {}
#: rung tuples (one rung an array of the batch, so the arity is the
#: tuple's length) whose program this process has compiled
_compiled: set = set()


class SentinelCollision(OverflowError):
    """A real key equals the padding sentinel (``int32`` max)."""


def rung_of(n: int) -> int:
    """The padded length a key array of ``n`` sorts at: the smallest power
    of two that holds it, ``DEFAULT_MIN_EDGES`` at the least."""
    return max(DEFAULT_MIN_EDGES, 1 << max(0, int(n) - 1).bit_length())


def compiled_rungs() -> frozenset:
    """The rung tuples whose program this process has compiled."""
    with _jit_lock:
        return frozenset(_compiled)


def _is_compiled(rungs: tuple) -> bool:
    return rungs in _compiled  # a set's membership test needs no lock


def _sort_fn(n_arrays: int):
    """A jitted function computing ``n_arrays`` independent stable
    argsorts in one dispatch. One function an arity; XLA compiles one
    program a tuple of array lengths, and those are rungs (``rung_of``),
    never exact counts: one program a rung and arity."""
    fn = _jit_cache.get(n_arrays)
    if fn is None:
        with _jit_lock:
            fn = _jit_cache.get(n_arrays)
            if fn is None:
                import jax
                import jax.numpy as jnp

                def many(*keys):
                    return tuple(jnp.argsort(k, stable=True) for k in keys)

                fn = jax.jit(many)
                _jit_cache[n_arrays] = fn
    return fn


def _note_compiled(rungs: tuple) -> None:
    with _jit_lock:
        _compiled.add(rungs)


def compile_rungs(rungs: tuple) -> None:
    """Compile one rung tuple's program by running it once on padding
    alone; from then on a sort of those rungs compiles nothing."""
    outs = _sort_fn(len(rungs))(*(np.full(r, SENTINEL, np.int32) for r in rungs))
    for o in outs:
        o.block_until_ready()
    _note_compiled(tuple(rungs))


def pad_keys(keys: np.ndarray, rung: int) -> np.ndarray:
    """``keys`` as ``int32[rung]``, the tail filled with ``SENTINEL``. The
    cast, the range check and the look for a key that equals the sentinel
    are one pass over the keys, a block at a time. A key outside int32
    raises ``OverflowError`` instead of corrupting (never observed: node
    counts are bounded far below 2^31 by the int32 CSR layout); a key equal
    to the sentinel raises ``SentinelCollision``."""
    a = np.asarray(keys)
    n = a.size
    out = np.empty(rung, np.int32)
    out[n:] = SENTINEL
    lo, hi = 0, 0
    for s in range(0, n, _PAD_BLOCK):
        block = a[s:s + _PAD_BLOCK]
        lo, hi = min(lo, int(block.min())), max(hi, int(block.max()))
        out[s:s + block.size] = block
    if lo < -SENTINEL - 1 or hi > SENTINEL:
        raise OverflowError("build sort key outside int32 range")
    if hi == SENTINEL:
        raise SentinelCollision("build sort key equals the padding sentinel")
    return out


class HostSorter:
    """The numpy stable-argsort backend (the legacy build path)."""

    backend = "host"

    def argsort(self, keys: np.ndarray) -> np.ndarray:
        return np.argsort(keys, kind="stable").astype(np.int64, copy=False)

    def argsort_many(self, arrays: Sequence[np.ndarray]) -> list:
        return [self.argsort(a) for a in arrays]


class DeviceSorter:
    """Stable argsorts executed on the accelerator.

    Keys are downcast to int32 before upload (jax's default x64-disabled
    mode would silently truncate int64 anyway): every build key — bucket
    keys, device ids, CSR endpoints — fits int32 by construction, and
    sorting the int32 copies yields the identical permutation. Each array
    is padded to its rung (``pad_keys``), so the program that sorts it is
    one of a short ladder's and not its exact length's."""

    backend = "device"

    def argsort(self, keys: np.ndarray) -> np.ndarray:
        return self.argsort_many([keys])[0]

    def argsort_many(self, arrays: Sequence[np.ndarray]) -> list:
        """All permutations in one device dispatch (the "one device
        pass" over the interned edge array: independent sorts fuse)."""
        arrays = [np.asarray(a) for a in arrays]
        rungs = tuple(rung_of(a.size) for a in arrays)
        outs = _sort_fn(len(arrays))(*(pad_keys(a, r) for a, r in zip(arrays, rungs)))
        # the padding sorts last and the sort is stable: the head of each
        # permutation is the unpadded one
        perms = [np.asarray(o)[:a.size].astype(np.int64) for o, a in zip(outs, arrays)]
        _note_compiled(rungs)
        return perms


_HOST = HostSorter()


def host_sorter() -> HostSorter:
    return _HOST


def device_available() -> bool:
    """True when a jax backend exists to sort on. Cheap after first call."""
    try:
        import jax

        return len(jax.local_devices()) > 0
    except Exception:
        return False


def estimate_sort_bytes(n_nodes: int, n_edges: int) -> int:
    """Transient device bytes a full build's sorts peak at: keys + iota +
    sorted outputs for the largest concurrent batch (3 edge-scale sorts),
    plus the node-scale renumbering sort, each at its rung. int32
    everywhere; XLA holds input and output buffers live across the fused
    sort."""
    per_edge_sort = 4 * 4  # key in, iota, sorted key, sorted iota
    return 3 * per_edge_sort * rung_of(n_edges) + per_edge_sort * rung_of(n_nodes)


class GovernedSorter:
    """The engine's build-sort policy: each argsort batch runs on the
    device when (a) a backend exists, (b) the largest array clears
    ``min_size`` (below it dispatch overhead wins), (c) its rungs'
    program is compiled in this process or nothing is serving yet
    (``serving()`` false: the boot build compiles as it goes) and (d) the
    HBM governor's transient plan fits WITHOUT evicting — a build must
    never push serving state off the chip just to finish faster.
    Otherwise it sorts on the host, bit-identically, and says why
    (``build_sort_host_<why>``; ``build_sort_device_ok`` for the device).
    A batch that went to the host because its program was cold has that
    program compiled on a worker thread (``keto-tpu-sort-compile``): a
    fold asks under the engine's lock and must not wait for a compiler.
    The transient footprint is ledgered under the ``build`` tag for the
    duration of the dispatch, and failures of any kind demote to host
    (counted as ``device_build_errors``; answers unchanged)."""

    backend = "governed"

    def __init__(
        self, hbm=None, *, min_size: int = DEFAULT_MIN_EDGES, stats=None,
        serving: Optional[Callable[[], bool]] = None,
    ):
        self._dev = make_device_sorter()
        self._host = host_sorter()
        self._hbm = hbm
        self._min_size = int(min_size)
        self._stats = stats  # MaintenanceStats or None
        self._serving = serving or (lambda: False)
        self._lock = threading.Lock()  # guards: _asked, _inflight, _worker
        self._asked: set = set()  # rung tuples ever handed to the worker
        self._inflight = 0
        self._queue: queue.SimpleQueue = queue.SimpleQueue()
        self._worker: Optional[threading.Thread] = None
        self._closed = False

    def _incr(self, key: str) -> None:
        if self._stats is not None:
            self._stats.incr(key)

    def argsort(self, keys: np.ndarray) -> np.ndarray:
        return self.argsort_many([keys])[0]

    def argsort_many(self, arrays: Sequence[np.ndarray]) -> list:
        arrays = [np.asarray(a) for a in arrays]
        rungs = tuple(rung_of(a.size) for a in arrays)
        t0 = time.perf_counter()
        if not SESSION.open:
            out, backend, why = self._sort(arrays, rungs)
        else:
            with SESSION.annotation("keto.build.sort", rung=max(rungs, default=0),
                                    arity=len(rungs)):
                out, backend, why = self._sort(arrays, rungs)
        if self._stats is not None:
            self._stats.incr(f"build_sort_{backend}_{why}")
            self._stats.observe_ms(f"build_sort_{backend}", (time.perf_counter() - t0) * 1e3)
            self._stats.set_gauge("build_sort_rungs", len(_compiled))
        return out

    def _sort(self, arrays: list, rungs: tuple) -> tuple[list, str, str]:
        """``(permutations, backend, why)``."""
        if self._dev is None or max((a.size for a in arrays), default=0) < self._min_size:
            return self._host.argsort_many(arrays), "host", "small"
        if self._serving() and not _is_compiled(rungs):
            self._compile_later(rungs)
            return self._host.argsort_many(arrays), "host", "cold"
        with self._transient(rungs, "device build transient") as fits:
            if not fits:
                # memory pressure: the build yields, serving state stays
                self._incr("device_build_skipped")
                return self._host.argsort_many(arrays), "host", "pressure"
            try:
                out = self._dev.argsort_many(arrays)
                self._incr("device_build_dispatches")
                return out, "device", "ok"
            except Exception as e:
                # a key equal to the sentinel is the one the padding cannot
                # sort behind, and numpy's by design; anything else is news
                if not isinstance(e, SentinelCollision):
                    _log.warning(
                        "device build sort failed; falling back to host (bit-identical)",
                        exc_info=True,
                    )
                self._incr("device_build_errors")
                return self._host.argsort_many(arrays), "host", "error"

    @contextlib.contextmanager
    def _transient(self, rungs: tuple, what: str):
        """The batch's device footprint (key in, iota, sorted key, sorted
        iota: 16 bytes a padded key) planned with the governor without
        evicting and ledgered under ``build`` for as long as the block
        lasts; yields whether it fits."""
        need, gov = sum(16 * r for r in rungs), self._hbm
        if gov is None:
            yield True
        elif not gov.plan(need, what=what, evict=False):
            yield False
        else:
            gov.register("build", need)
            try:
                yield True
            finally:
                gov.release("build")

    # -- the cold rungs' compiles, off every lock of the engine's -----------

    def _compile_later(self, rungs: tuple) -> None:
        with self._lock:
            if rungs in self._asked or self._closed:
                return
            # asked once: a compile that failed is not tried again a fold later
            self._asked.add(rungs)
            self._inflight += 1
            self._queue.put(rungs)
            if self._worker is None:
                self._worker = threading.Thread(
                    target=self._work, name="keto-tpu-sort-compile", daemon=True
                )
                self._worker.start()

    def _work(self) -> None:
        while True:
            rungs = self._queue.get()
            if rungs is None:
                return
            try:
                if not self._closed:
                    self._compile(rungs)
            except Exception:
                # sorts of these rungs stay on the host while something serves
                _log.warning("background compile of sort rungs %s failed", rungs, exc_info=True)
            with self._lock:
                self._inflight -= 1

    def _compile(self, rungs: tuple) -> None:
        with self._transient(rungs, "device build sort compile") as fits:
            if not fits:
                with self._lock:
                    self._asked.discard(rungs)  # ask again when there is room
                return
            if not SESSION.open:
                compile_rungs(rungs)
            else:
                with SESSION.annotation("keto.geometry.compile", kernel="build_sort",
                                        shape="", sizes="x".join(map(str, rungs))):
                    compile_rungs(rungs)
        if self._stats is not None:
            self._stats.set_gauge("build_sort_rungs", len(_compiled))

    def pending(self) -> int:
        """Background compiles queued or running (tests wait for 0)."""
        with self._lock:
            return self._inflight

    def close(self, timeout: float = 60.0) -> None:
        """No further compiles; waits for the one in flight."""
        with self._lock:
            self._closed = True
            worker = self._worker
        if worker is not None:
            self._queue.put(None)
            worker.join(timeout)


def shard_row_ranges(n_rows: int, n_shards: int) -> list:
    """Contiguous ``[lo, hi)`` row ranges assigning ``n_rows`` rows to
    ``n_shards`` equal slabs of ``ceil(n_rows / n_shards)`` rows each (the
    last may be short). This is THE shard assignment of the sharded
    serving path: keto_tpu/parallel/sharded.py partitions the bitmap /
    bucket / label rows with it at upload time, and the snapshot cache
    (keto_tpu/graph/snapcache.py FORMAT_VERSION 6) stripes its bucket
    segments with the same ranges so a mesh cold-starts by loading each
    shard's stripe in parallel — one function, one assignment, no drift."""
    n_shards = max(1, int(n_shards))
    rps = -(-max(1, int(n_rows)) // n_shards)  # ceil div; ≥ 1
    return [
        (min(s * rps, n_rows), min((s + 1) * rps, n_rows))
        for s in range(n_shards)
    ]


def make_device_sorter() -> Optional[DeviceSorter]:
    """A ``DeviceSorter`` when a backend is present, else None. The
    caller gates on size and on the HBM governor's plan; this only
    answers "is there hardware"."""
    if not device_available():
        return None
    return DeviceSorter()
