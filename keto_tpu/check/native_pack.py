"""ctypes binding for the native pack (native/pack.cpp).

Before a chunk is packed it is resolved, and that state of the dispatch
thread is one GIL-released call too (``resolve_chunk``:
``keto_resolve_chunk``, over a ``ResolveView`` of the snapshot alone): from
the raw node ids the door or the thread's own probes gave, the device rows
``sd`` / ``tg``, the closure byte a query under a rewrite plan, and the
running sums of the per-query entry counts, plain and with the queries
zeroed that no pull can change, which is all a cut of the chunk asks.
``check/dispatch.py`` ``_resolve_chunk`` takes it wherever nothing declines
it (``keto_check_resolve_chunks_total{path}``,
``keto_check_resolve_declines_total{reason}``); ``_resolve_records``,
``_entry_counts`` and the reach mask there are the contract it is fuzzed
against (tests/test_resolve_fused.py) and the fallback.

Three paths pack a chunk of a check slice, counted a chunk in ``COUNTERS``
(``keto_native_pack_chunks_total{path}``):

- ``fused`` — the label route's whole ``pack`` as ONE GIL-released call
  (``pack_labeled``: ``keto_pack_labeled``): query by query the host walk
  from a host-propagated start, the seed rows, a sink target's answer or
  relay rows, the route's decisions (``self_hit``, ``pair_cap``,
  ``uncertifiable``, ``whole_slice``) and the pairs, which a second call
  copies straight into the staging lease ``label_step`` ships; a third
  hands out ``pack_chunk``'s seven arrays for the queries that fell back.
  ``check/dispatch.py`` ``_device_batch_labeled`` takes it wherever nothing
  declines it, and counts a decline once a chunk by reason
  (``keto_check_pack_declines_total{reason}``): ``no_library``, ``mesh``
  (the sharded kernels route entries themselves), ``overlay`` (below),
  ``multi`` (a wildcard or multi-start query in the chunk). A snapshot
  with hub sinks is handled: relay rows are one CSR slice a query.
- ``native`` — ``pack_chunk``'s host walk (frontier expansion through the
  forward CSR, (query, row) seen/seed dedup, target-hit grants) and its
  sink answer gather as two GIL-released calls with numpy around them:
  the BFS route, the sharded riders and every chunk the fused pass
  declines for ``multi`` or ``mesh``.
- ``numpy`` — keto_tpu/check/pack.py alone. It remains the contract
  (``native`` is bit-identical to it, ``fused`` equal set for set: both
  fuzz-compared in tests/test_native_pack.py and tests/test_pack_fused.py)
  and the fallback.

**Eligibility** (``walk_eligible``): the native walks read ONLY the base
forward/sink CSRs, so any overlay state that would change what
``out_neighbors_bulk``/``sink_in_rows_bulk`` return routes the chunk to
numpy: host out-adjacency (``ov_out``), tombstones (``ov_removed``), or
overlay sink in-edges (``ov_sink_in``). Interior overlay-ELL edges are
device-side and do not affect the host walk, so the common
insert-only-delta serving state keeps the native paths.

Loading is opportunistic: ``load_library()`` returns None (and callers
fall back to numpy) when the shared object is absent, stale
(``keto_pack_version`` is not ABI 4) or ``KETO_TPU_NATIVE=0`` (every native
off: a box without a compiler). Build with ``make native``.
"""

from __future__ import annotations

import collections
import ctypes
import os
import weakref
from pathlib import Path
from typing import Optional

import numpy as np

_ABI_VERSION = 4

_lib: Optional[ctypes.CDLL] = None
_lib_checked = False

#: chunks packed per path since process start (scraped as
#: ``keto_native_pack_chunks_total{path}``; "numpy" counts fallbacks for
#: ANY reason — library absent, disabled, or overlay-ineligible)
COUNTERS = {"fused": 0, "native": 0, "numpy": 0}

_I64 = ctypes.POINTER(ctypes.c_int64)
_I32 = ctypes.POINTER(ctypes.c_int32)
_U8 = ctypes.POINTER(ctypes.c_uint8)


class _KetoPackView(ctypes.Structure):
    """``KetoPackView`` of native/pack.cpp, field for field."""

    _fields_ = [
        (name, ctypes.c_void_p)
        for name in (
            "fwd_indptr", "fwd_indices", "sink_indptr", "sink_indices",
            "hub_ptr", "hub_rows", "out_ok", "in_ok", "processed",
        )
    ] + [
        (name, ctypes.c_int64)
        for name in ("n_base", "ni", "sb", "nl", "n_lab", "pair_cap")
    ]


class _KetoResolveView(ctypes.Structure):
    """``KetoResolveView`` of native/pack.cpp, field for field."""

    _fields_ = [
        (name, ctypes.c_void_p)
        for name in ("raw2dev", "fwd_indptr", "sink_indptr", "hub_ptr", "reach", "flags")
    ] + [
        (name, ctypes.c_int64)
        for name in ("n_raw", "n_base", "ni", "sb", "nl", "n_flags", "rewritten")
    ]


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
        lib.keto_pack_labeled.restype = None
        lib.keto_pack_labeled.argtypes = [p, p, p, c, c, c, p, p, p]
        lib.keto_pack_labeled_pairs.restype = c
        lib.keto_pack_labeled_pairs.argtypes = [p, c]
        lib.keto_pack_labeled_riders.restype = None
        lib.keto_pack_labeled_riders.argtypes = [p, p, p, p, p, p, p, c]
        lib.keto_resolve_chunk.restype = None
        lib.keto_resolve_chunk.argtypes = [p, c, p, p, p, c, p, c, p, p, p, p, p, p]
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


#: what ``keto_pack_labeled`` counts over a chunk, in the order of its
#: int64[13] (native/pack.cpp): any seed at all (``pack_chunk``'s packed is
#: not None), the pairs, the queries fallen back, whether the whole slice
#: rides, the fallbacks by reason, the chunk's seed and target-side rows (a
#: relay row counts the rows it holds), the riders' e1, e2 and answer entries
LabeledCounts = collections.namedtuple("LabeledCounts", [
    "packed", "pairs", "fallbacks", "whole",
    "self_hit", "pair_cap", "uncertifiable", "whole_slice",
    "seed_rows", "target_rows", "rider_e1", "rider_e2", "rider_answers",
])


class PackView:
    """One snapshot's arrays as ``keto_pack_labeled`` reads them
    (``KetoPackView``), made once a snapshot and label index and kept by
    the caller: a chunk then passes one pointer. Holds the arrays it points
    into; ``of`` says whether it still describes what a chunk is about to
    be packed against. ``hub``: answer entries may name the snapshot's relay
    rows (``pack.hub_usable``). Only for a ``walk_eligible`` snapshot."""

    __slots__ = ("_snap", "_labels", "_hub", "_arrays", "_struct", "ref")

    def __init__(self, snap, labels, pair_cap: int, hub: bool):
        self._snap = weakref.ref(snap)
        self._labels = labels
        self._hub = hub
        flags = [
            np.ascontiguousarray(a).view(np.uint8)
            for a in (labels.out_ok, labels.in_ok, labels.processed)
        ]
        self._arrays = [
            np.ascontiguousarray(snap.fwd_indptr, np.int64),
            np.ascontiguousarray(snap.fwd_indices, np.int32),
            np.ascontiguousarray(snap.sink_indptr, np.int64),
            np.ascontiguousarray(snap.sink_indices, np.int32),
            np.ascontiguousarray(snap.hub_ptr, np.int64) if hub else None,
            np.ascontiguousarray(snap.hub_rows, np.int64) if hub else None,
            *flags,
        ]
        self._struct = _KetoPackView(
            *(None if a is None else a.ctypes.data for a in self._arrays),
            snap.n_base_nodes, snap.num_int, snap.sink_base, snap.num_live,
            min(f.shape[0] for f in flags), pair_cap,
        )
        self.ref = ctypes.addressof(self._struct)

    def of(self, snap, labels, hub: bool) -> bool:
        return self._snap() is snap and self._labels is labels and self._hub == hub


def pack_labeled(view: PackView, sd: np.ndarray, tg: np.ndarray, i0: int, i1: int,
                 whole_min: int):
    """The fused pass over queries ``[i0, i1)`` of a resolved batch with no
    ``multi`` entry among them. Returns ``(host_ans, fallback, counts)``:
    bool[nq] each, and a ``LabeledCounts``. The pairs and the riders'
    entries wait in the calling thread's native scratch for
    ``labeled_pairs`` / ``labeled_riders``, until its next call here."""
    lib = load_library()
    assert lib is not None, "pack_labeled called without the native library"
    sd = np.ascontiguousarray(sd, np.int64)
    tg = np.ascontiguousarray(tg, np.int64)
    if not 0 <= i0 <= i1 <= min(sd.shape[0], tg.shape[0]):
        raise ValueError(f"chunk [{i0}, {i1}) outside a batch of {sd.shape[0]}")
    nq = i1 - i0
    host_ans = np.empty(nq, np.uint8)
    fallback = np.empty(nq, np.uint8)
    counts = np.empty(len(LabeledCounts._fields), np.int64)
    lib.keto_pack_labeled(
        view.ref, sd.ctypes.data, tg.ctypes.data, i0, i1, whole_min,
        host_ans.ctypes.data, fallback.ctypes.data, counts.ctypes.data,
    )
    COUNTERS["fused"] += 1
    return host_ans.view(bool), fallback.view(bool), LabeledCounts._make(counts.tolist())


def labeled_pairs(out: np.ndarray, P: int) -> None:
    """The pairs of this thread's last ``pack_labeled`` into ``out``
    (contiguous int32[3 * P], ``P`` at least their count) at the three
    offsets ``label_step`` reads, padded (ni, ni, 0)."""
    if out.dtype != np.int32 or out.shape != (3 * P,) or not out.flags.c_contiguous:
        raise ValueError("the pairs need a contiguous int32[3 * P]")
    if load_library().keto_pack_labeled_pairs(out.ctypes.data, P) < 0:
        raise ValueError(f"more pairs than the {P} the buffer was sized for")


def labeled_riders(counts: LabeledCounts, B: int):
    """``pack_chunk``'s seven arrays for the queries this thread's last
    ``pack_labeled`` took off the label route, unpadded (``device_part``
    strips padding first thing): their entries under their positions in
    the chunk, and the chunk's targets at width ``B``."""
    n1, n2, na = counts.rider_e1, counts.rider_e2, counts.rider_answers
    arrays = tuple(
        np.empty(n, np.int32) for n in (n1, n1, n2, n2, na, na, B)
    )
    load_library().keto_pack_labeled_riders(*(a.ctypes.data for a in arrays), B)
    return arrays


class ResolveView:
    """One snapshot's arrays as ``keto_resolve_chunk`` reads them
    (``KetoResolveView``), made once a snapshot and kept by the caller, like
    ``PackView`` but of the snapshot alone (no label index: a BFS-only
    snapshot takes it too). ``hub``: a hub sink's answer counts its relay
    rows (``pack.hub_usable``); ``reach``: ``bool[num_live]``, the targets a
    pull can change, or None where every target counts (``csum_reach`` then
    repeats ``csum``); ``flags``: the rewrite plan's closure byte a device
    row, or None without a plan. ``of`` says whether it still describes the
    snapshot a chunk is about to be resolved against."""

    __slots__ = ("_snap", "_hub", "_reach", "_flags", "_arrays", "_struct", "ref", "has_flags")

    def __init__(self, snap, hub: bool, reach, flags, rewritten: int):
        self._snap = weakref.ref(snap)
        self._hub, self._reach, self._flags = hub, reach, flags
        self.has_flags = flags is not None
        self._arrays = [
            np.ascontiguousarray(snap.raw2dev, np.int64),
            np.ascontiguousarray(snap.fwd_indptr, np.int64),
            np.ascontiguousarray(snap.sink_indptr, np.int64),
            np.ascontiguousarray(snap.hub_ptr, np.int64) if hub else None,
            None if reach is None else np.ascontiguousarray(reach).view(np.uint8),
            None if flags is None else np.ascontiguousarray(flags, np.uint8),
        ]
        self._struct = _KetoResolveView(
            *(None if a is None else a.ctypes.data for a in self._arrays),
            self._arrays[0].shape[0], snap.n_base_nodes, snap.num_int, snap.sink_base,
            snap.num_live, 0 if flags is None else flags.shape[0], rewritten,
        )
        self.ref = ctypes.addressof(self._struct)

    def of(self, snap, hub: bool, reach, flags) -> bool:
        return (
            self._snap() is snap and self._hub == hub
            and self._reach is reach and self._flags is flags
        )


#: what ``keto_resolve_chunk`` hands back: ``sd`` / ``tg`` as
#: ``_resolve_records`` defines them, ``flags`` (uint8[n], None without a
#: plan), ``sums`` (int64[2, n + 1]: the running sums of ``_entry_counts``
#: and of the same with the reach mask applied; None where the caller asked
#: for no count), and its int64[4]: the queries other than the dead that miss
#: a start or a target, the closure bytes with the REWRITTEN bit, the starts
#: past the plan's flags, the inputs out of range
ResolvedChunk = collections.namedtuple("ResolvedChunk", [
    "sd", "tg", "flags", "sums", "misses", "rewritten", "overlay_starts", "bad_inputs",
])


_NO_MARKS = np.zeros(0, np.int64)


def _resolve_call(view: ResolveView, n: int, ids, marks, sd, tg, flags, count: bool = True):
    sums = np.empty((2, n + 1), np.int64) if count else None
    counts = np.empty(4, np.int64)
    load_library().keto_resolve_chunk(
        view.ref, n, *(None if a is None else a.ctypes.data for a in ids),
        marks[0].ctypes.data, marks[0].shape[0], marks[1].ctypes.data, marks[1].shape[0],
        sd.ctypes.data, tg.ctypes.data, None if flags is None else flags.ctypes.data,
        *((sums[0].ctypes.data, sums[1].ctypes.data) if count else (None, None)),
        counts.ctypes.data,
    )
    return sums, counts.tolist()


def resolve_chunk(
    view: ResolveView, start_raw, sub_raw, dead=(), no_target=(), count: bool = True
) -> ResolvedChunk:
    """The ``resolve`` of one chunk in one call: the raw node ids of its
    queries (``start_raw`` / ``sub_raw``, -1 where the tables hold none) and
    the marks the records could not carry (``dead``: both rows forced to -1;
    ``no_target``: the target) as a ``ResolvedChunk``. ``count=False``: no
    running sums (the caller will count other rows: ``entry_sums``)."""
    assert load_library() is not None, "resolve_chunk called without the native library"
    a = np.ascontiguousarray(start_raw, np.int64)
    b = np.ascontiguousarray(sub_raw, np.int64)
    n = a.shape[0]
    if b.shape[0] != n:
        raise ValueError("start_raw and sub_raw differ in length")
    marks = [np.asarray(m, np.int64) for m in (dead, no_target)]
    sd, tg = np.empty(n, np.int64), np.empty(n, np.int64)
    flags = np.empty(n, np.uint8) if view.has_flags else None
    sums, counts = _resolve_call(view, n, (a, b), marks, sd, tg, flags, count)
    return ResolvedChunk(sd, tg, flags, sums, *counts)


def entry_sums(view: ResolveView, sd: np.ndarray, tg: np.ndarray) -> np.ndarray:
    """``int64[2, n + 1]``, the ``sums`` of a ``ResolvedChunk``, of device
    rows that are already resolved: the counting half of the same call, for
    the positions a gate expansion made of a chunk."""
    assert load_library() is not None, "entry_sums called without the native library"
    sd = np.ascontiguousarray(sd, np.int64)
    tg = np.ascontiguousarray(tg, np.int64)
    n = sd.shape[0]
    if tg.shape[0] != n:
        raise ValueError("sd and tg differ in length")
    return _resolve_call(view, n, (None, None), (_NO_MARKS, _NO_MARKS), sd, tg, None)[0]
