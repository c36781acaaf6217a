"""ctypes binding for the native pack walk (native/pack.cpp).

``pack_chunk``'s host walk — frontier expansion of host-propagated
starts through the forward CSR, (query, row) seen/seed dedup, target-hit
grants, and the sink answer gather — runs here as one GIL-released C++
call on the eligible path, so resolve/pack of slice k+2 genuinely
overlaps device execution of k+1 instead of fighting the GIL. The numpy
implementation in keto_tpu/check/pack.py remains the contract
(bit-identical output, fuzz-compared in tests/test_native_pack.py) and
the fallback.

**Eligibility** (``walk_eligible``): the walk reads ONLY the base
forward/sink CSRs, so any overlay state that would change what
``out_neighbors_bulk``/``sink_in_rows_bulk`` return routes the chunk to
numpy: host out-adjacency (``ov_out``), tombstones (``ov_removed``), or
overlay sink in-edges (``ov_sink_in``). Interior overlay-ELL edges are
device-side and do not affect the host walk, so the common
insert-only-delta serving state keeps the native path.

Loading is opportunistic: ``load_library()`` returns None (and callers
fall back to numpy) when the shared object is absent, stale
(``keto_pack_version`` mismatch) or ``KETO_TPU_NATIVE=0`` (every native
off: a box without a compiler). Build with ``make native``.

``COUNTERS`` tracks which path packed each chunk; the registry scrapes
it as ``keto_native_pack_chunks_total{path}``.
"""

from __future__ import annotations

import ctypes
import os
from pathlib import Path
from typing import Optional

import numpy as np

_ABI_VERSION = 2

_lib: Optional[ctypes.CDLL] = None
_lib_checked = False

#: chunks packed per path since process start (scraped as
#: ``keto_native_pack_chunks_total{path}``; "numpy" counts fallbacks for
#: ANY reason — library absent, disabled, or overlay-ineligible)
COUNTERS = {"native": 0, "numpy": 0}

_I64 = ctypes.POINTER(ctypes.c_int64)
_I32 = ctypes.POINTER(ctypes.c_int32)
_U8 = ctypes.POINTER(ctypes.c_uint8)


def _candidate_paths():
    if os.environ.get("KETO_TPU_PACK_LIB"):
        yield Path(os.environ["KETO_TPU_PACK_LIB"])
    root = Path(__file__).resolve().parents[2]
    yield root / "native" / "libketopack.so"


def load_library() -> Optional[ctypes.CDLL]:
    global _lib, _lib_checked
    if _lib_checked:
        return _lib
    _lib_checked = True
    if os.environ.get("KETO_TPU_NATIVE", "1") == "0":
        return None
    for path in _candidate_paths():
        if not path.exists():
            continue
        try:
            lib = ctypes.CDLL(str(path))
        except OSError:
            continue  # corrupt / wrong-arch build → numpy fallback
        c = ctypes.c_int64
        p = ctypes.c_void_p
        try:
            lib.keto_pack_version.restype = c
            if lib.keto_pack_version() != _ABI_VERSION:
                continue  # stale build → numpy fallback
        except AttributeError:
            continue
        lib.keto_pack_walk.restype = p
        lib.keto_pack_walk.argtypes = [
            _I64, _I32, c, c, c, _I64, _I64, c, _I64, c, c,
        ]
        lib.keto_pack_n_seeds.restype = c
        lib.keto_pack_n_seeds.argtypes = [p]
        lib.keto_pack_fetch.argtypes = [p, _I64, _I64, _U8]
        lib.keto_pack_free.argtypes = [p]
        lib.keto_sink_gather.restype = p
        lib.keto_sink_gather.argtypes = [_I64, _I32, _I64, c]
        lib.keto_gather_n.restype = c
        lib.keto_gather_n.argtypes = [p]
        lib.keto_gather_fetch.argtypes = [p, _I32, _I64]
        lib.keto_gather_free.argtypes = [p]
        lib.keto_pairs_member.argtypes = [_I32, _I32, c, _I32, _I32, c, _U8]
        _lib = lib
        return _lib
    return None


def available() -> bool:
    return load_library() is not None


def walk_eligible(snap) -> bool:
    """True when the native walk would read exactly what the numpy walk
    reads: base CSRs present, no host-visible overlay adjacency, no
    tombstones, no overlay sink in-edges."""
    return (
        snap.fwd_indptr is not None
        and snap.fwd_indices is not None
        and not snap.ov_out
        and not snap.ov_sink_in
        and (snap.ov_removed is None or snap.ov_removed.size == 0)
    )


def _ptr(arr: np.ndarray, ctype):
    return arr.ctypes.data_as(ctypes.POINTER(ctype))


def pack_walk(
    snap, rows: np.ndarray, pq: np.ndarray, tgc: np.ndarray
) -> tuple[np.ndarray, np.ndarray, Optional[np.ndarray]]:
    """Run the frontier walk natively. ``rows``/``pq`` are the initial
    host-propagated (row, query) pairs (int64), ``tgc`` the per-query
    target rows (int64, -1 = none). Returns ``(seed_rows, seed_q,
    host_hits)`` — the globally (query, row)-deduplicated device seeds in
    first-occurrence order and the host-decided grants — bit-identical to
    the numpy walk by contract."""
    lib = load_library()
    assert lib is not None, "pack_walk called without the native library"
    indptr = np.ascontiguousarray(snap.fwd_indptr, np.int64)
    indices = np.ascontiguousarray(snap.fwd_indices, np.int32)
    rows = np.ascontiguousarray(rows, np.int64)
    pq = np.ascontiguousarray(pq, np.int64)
    tgc = np.ascontiguousarray(tgc, np.int64)
    nq = tgc.shape[0]
    h = lib.keto_pack_walk(
        _ptr(indptr, ctypes.c_int64),
        _ptr(indices, ctypes.c_int32),
        snap.n_base_nodes,
        snap.num_int,
        snap.sink_base,
        _ptr(rows, ctypes.c_int64),
        _ptr(pq, ctypes.c_int64),
        rows.shape[0],
        _ptr(tgc, ctypes.c_int64),
        nq,
        0,
    )
    try:
        n = lib.keto_pack_n_seeds(h)
        seed_rows = np.empty(n, np.int64)
        seed_q = np.empty(n, np.int64)
        hits = np.zeros(nq, np.uint8)
        lib.keto_pack_fetch(
            h,
            _ptr(seed_rows, ctypes.c_int64),
            _ptr(seed_q, ctypes.c_int64),
            _ptr(hits, ctypes.c_uint8),
        )
    finally:
        lib.keto_pack_free(h)
    return seed_rows, seed_q, (hits.view(bool) if hits.any() else None)


def sink_gather(snap, sinks: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    """Native twin of the overlay-free arm of ``sink_in_rows_bulk``:
    ``(concatenated interior in-neighbor rows, per-target counts)`` for
    sink-class device ids ``sinks``."""
    lib = load_library()
    assert lib is not None, "sink_gather called without the native library"
    indptr = np.ascontiguousarray(snap.sink_indptr, np.int64)
    indices = np.ascontiguousarray(snap.sink_indices, np.int32)
    local = np.ascontiguousarray(np.asarray(sinks, np.int64) - snap.sink_base)
    n = local.shape[0]
    h = lib.keto_sink_gather(
        _ptr(indptr, ctypes.c_int64),
        _ptr(indices, ctypes.c_int32),
        _ptr(local, ctypes.c_int64),
        n,
    )
    try:
        total = lib.keto_gather_n(h)
        rows = np.empty(total, np.int32)
        cnts = np.empty(n, np.int64)
        lib.keto_gather_fetch(
            h, _ptr(rows, ctypes.c_int32), _ptr(cnts, ctypes.c_int64)
        )
    finally:
        lib.keto_gather_free(h)
    return rows, cnts


def pairs_member(
    set_rows: np.ndarray, set_q: np.ndarray, rows: np.ndarray, q: np.ndarray
) -> np.ndarray:
    """``bool[len(rows)]``: is the (row, query) pair ``(rows[i], q[i])``
    among the pairs ``(set_rows[j], set_q[j])``. One hash set off the GIL
    where the library is there, sorted keys otherwise: the same answer."""
    lib = load_library()
    if lib is None:
        key = (q.astype(np.int64) << 32) | rows.astype(np.int64)
        return np.isin(key, (set_q.astype(np.int64) << 32) | set_rows.astype(np.int64))
    set_rows = np.ascontiguousarray(set_rows, np.int32)
    set_q = np.ascontiguousarray(set_q, np.int32)
    rows = np.ascontiguousarray(rows, np.int32)
    q = np.ascontiguousarray(q, np.int32)
    out = np.zeros(rows.shape[0], np.uint8)
    lib.keto_pairs_member(
        _ptr(set_rows, ctypes.c_int32), _ptr(set_q, ctypes.c_int32), set_rows.shape[0],
        _ptr(rows, ctypes.c_int32), _ptr(q, ctypes.c_int32), rows.shape[0],
        _ptr(out, ctypes.c_uint8),
    )
    return out.view(bool)
