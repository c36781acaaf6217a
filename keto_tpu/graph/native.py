"""ctypes binding for the native (C++) tuple→graph interner.

``native/ingest.cpp`` implements the same interning contract as
``keto_tpu.graph.interner.intern_rows`` (same node-id assignment order, same
wildcard-expansion edges, same dedup), parsing a packed byte buffer in one
native pass and keeping the string tables resident so per-query resolution
stays in C++. Build it with ``make native`` (repo root); loading is
opportunistic — ``load_library()`` returns None and callers fall back to the
Python interner when the shared object is absent or
``KETO_TPU_NATIVE=0``.
"""

from __future__ import annotations

import ctypes
import os
from pathlib import Path
from typing import Iterable, Optional

import numpy as np

_FIELD = b"\x1f"
_RECORD = b"\x1e"

_lib: Optional[ctypes.CDLL] = None
_lib_checked = False


def _candidate_paths():
    if os.environ.get("KETO_TPU_NATIVE_LIB"):
        yield Path(os.environ["KETO_TPU_NATIVE_LIB"])
    root = Path(__file__).resolve().parents[2]
    yield root / "native" / "libketoingest.so"


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
            continue  # corrupt / wrong-arch build → Python fallback
        c = ctypes.c_int64
        p = ctypes.c_void_p
        lib.graph_build.restype = p
        lib.graph_build.argtypes = [ctypes.c_char_p, c, ctypes.POINTER(c), c]
        if hasattr(lib, "graph_build_columnar"):
            pc = ctypes.POINTER(c)
            pb = ctypes.c_char_p
            lib.graph_build_columnar.restype = p
            lib.graph_build_columnar.argtypes = (
                [c, pc, ctypes.POINTER(ctypes.c_uint8), pc]
                + [pb, pc, pc] * 5
                + [pc, c]
            )
        if hasattr(lib, "graph_build_ucs4"):
            pc = ctypes.POINTER(c)
            pu = ctypes.POINTER(ctypes.c_uint32)
            lib.graph_build_ucs4.restype = p
            lib.graph_build_ucs4.argtypes = (
                [c, pc, ctypes.POINTER(ctypes.c_uint8), pc]
                + [pu, c] * 5
                + [pc, c]
            )
        if hasattr(lib, "stream_build_new"):
            # streaming (chunk-fed) builder: scan chunks intern on a
            # worker pool concurrently with the caller's next fetch
            lib.stream_build_new.restype = p
            lib.stream_build_new.argtypes = [ctypes.POINTER(c), c, c]
            lib.stream_build_feed.restype = c
            lib.stream_build_feed.argtypes = [p, ctypes.c_char_p, c, c]
            lib.stream_build_finish.restype = p
            lib.stream_build_finish.argtypes = [p]
            lib.stream_build_abort.argtypes = [p]
        lib.graph_free.argtypes = [p]
        for fn in ("graph_num_sets", "graph_num_leaves", "graph_num_edges"):
            getattr(lib, fn).restype = c
            getattr(lib, fn).argtypes = [p]
        for fn in ("graph_num_obj_codes", "graph_num_rel_codes"):
            if hasattr(lib, fn):
                getattr(lib, fn).restype = c
                getattr(lib, fn).argtypes = [p]
        lib.graph_edges.argtypes = [p, ctypes.POINTER(c), ctypes.POINTER(c)]
        lib.graph_release_edges.argtypes = [p]
        lib.graph_keys.argtypes = [
            p, ctypes.POINTER(c), ctypes.POINTER(c), ctypes.POINTER(c),
            ctypes.POINTER(ctypes.c_uint8),
        ]
        lib.graph_resolve_set.restype = c
        lib.graph_resolve_set.argtypes = [p, c, ctypes.c_char_p, c, ctypes.c_char_p, c]
        if hasattr(lib, "graph_resolve_queries"):
            lib.graph_resolve_queries.restype = c
            lib.graph_resolve_queries.argtypes = [
                p, ctypes.c_char_p, c, c, ctypes.POINTER(c), ctypes.POINTER(c),
            ]
        if hasattr(lib, "check_frame_body"):
            # /check/batch query framer (native/ingest.cpp)
            lib.check_frame_table_new.restype = p
            lib.check_frame_table_new.argtypes = [ctypes.c_char_p, c]
            lib.check_frame_table_free.argtypes = [p]
            lib.check_frame_body.restype = c
            lib.check_frame_body.argtypes = [
                p, ctypes.c_char_p, c, c, p, c, ctypes.POINTER(c), c,
                ctypes.POINTER(ctypes.c_uint8), ctypes.POINTER(c),
            ]
        for fn in ("graph_resolve_leaf", "graph_obj_code", "graph_rel_code"):
            getattr(lib, fn).restype = c
            getattr(lib, fn).argtypes = [p, ctypes.c_char_p, c]
        for fn in ("graph_obj_str", "graph_rel_str", "graph_leaf_str"):
            if hasattr(lib, fn):
                getattr(lib, fn).restype = p
                getattr(lib, fn).argtypes = [p, c, ctypes.POINTER(c)]
        _lib = lib
        return _lib
    return None


def encode_row(r) -> bytes:
    """One InternalRow-shaped row in the parser's record format — the single
    Python-side definition of the wire encoding (native/ingest.cpp parses
    it; InternalRow.packed() caches it)."""
    if r.subject_id is not None:
        sub = b"1" + _FIELD + r.subject_id.encode() + _FIELD + _FIELD
    else:
        sub = (
            b"0" + _FIELD + str(r.sset_namespace_id).encode() + _FIELD
            + r.sset_object.encode() + _FIELD + r.sset_relation.encode()
        )
    return (
        str(r.namespace_id).encode() + _FIELD + r.object.encode() + _FIELD
        + r.relation.encode() + _FIELD + sub + _RECORD
    )


def pack_rows(rows) -> bytes:
    """Serialize rows into the parser's buffer format. Rows exposing
    ``packed()`` (keto_tpu.persistence.memory.InternalRow) amortize the
    encoding across snapshot rebuilds."""
    if not isinstance(rows, list):
        rows = list(rows)
    if not rows:
        return b""
    if hasattr(rows[0], "packed"):
        return b"".join(r.packed() for r in rows)
    return b"".join(encode_row(r) for r in rows)


#: check_frame_body's decline codes (native/ingest.cpp ``FrameDecline``),
#: as the ``reason`` label of ``keto_check_frame_declines_total``
FRAME_DECLINES = {-1: "shape", -2: "escape", -3: "encoding", -4: "size", -5: "capacity"}


class FrameTable:
    """A namespace manager's name -> id table in the form the native
    query framer reads (``check_frame_table_new``): built once per
    manager, read-only after, freed with this object."""

    def __init__(self, lib: ctypes.CDLL, handle: int):
        self._lib = lib
        self._handle = handle

    def __del__(self):
        lib = getattr(self, "_lib", None)
        if lib is not None and self._handle:
            lib.check_frame_table_free(self._handle)
            self._handle = None

    @classmethod
    def build(cls, manager) -> Optional["FrameTable"]:
        """None when the framer cannot stand in for the object path under
        this manager: no native library (or one without the entry point,
        or ``KETO_TPU_NATIVE=0``), a namespace named "" (the wildcard
        namespace: the engine's pattern resolver owns it), or an id the
        record format cannot carry."""
        lib = load_library()
        if lib is None or not hasattr(lib, "check_frame_body"):
            return None
        ids: dict[bytes, int] = {}
        for ns in manager.namespaces():
            if ns.name == "" or not 0 <= int(ns.id) < 2**63:
                return None
            name = ns.name.encode("utf-8", "surrogatepass")
            if min(name) >= 0x20:
                # a name with a control byte can only be written with an
                # escape, and a body with an escape is never framed
                ids[name] = int(ns.id)
        buf = b"".join(b"%b\x1f%d\x1e" % kv for kv in ids.items())
        handle = lib.check_frame_table_new(buf, len(buf))
        return cls(lib, handle) if handle else None

    def frame(self, body: bytes, max_tuples: int):
        """Frame one ``POST /check/batch`` body. Returns ``(buf, off,
        flags)`` — the query records ``graph_resolve_queries`` parses,
        ``n + 1`` record offsets and one flag byte a record (0 literal, 1
        special, 2 dead, 3 no-target) — or the reason it declined, a
        string. The GIL is released for the pass over the body."""
        n_body = len(body)
        # a plain element is at least 58 bytes, and a record is shorter
        # than the element it came from; the framer checks both anyway
        cap_n = min(max_tuples, n_body // 50 + 1) + 1
        out = np.empty(n_body + 16, np.uint8)
        off = np.empty(cap_n + 1, np.int64)
        flags = np.empty(cap_n, np.uint8)
        out_len = ctypes.c_int64()
        c = ctypes.c_int64
        n = self._lib.check_frame_body(
            self._handle, body, n_body, max_tuples,
            out.ctypes.data, out.size,
            off.ctypes.data_as(ctypes.POINTER(c)), off.size,
            flags.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
            ctypes.byref(out_len),
        )
        if n <= 0:
            return FRAME_DECLINES.get(int(n), "shape")
        return out[: out_len.value].tobytes(), off[: n + 1], flags[:n]


class NativeInterned:
    """Drop-in for ``InternedGraph``: same arrays and resolution interface,
    backed by the resident C++ intern tables."""

    def __init__(self, lib: ctypes.CDLL, handle: int):
        self._lib = lib
        self._handle = handle
        c = ctypes.c_int64
        self.num_sets = int(lib.graph_num_sets(handle))
        self.num_leaves = int(lib.graph_num_leaves(handle))
        n_edges = int(lib.graph_num_edges(handle))
        self.src = np.empty(n_edges, np.int64)
        self.dst = np.empty(n_edges, np.int64)
        if n_edges:
            lib.graph_edges(
                handle,
                self.src.ctypes.data_as(ctypes.POINTER(c)),
                self.dst.ctypes.data_as(ctypes.POINTER(c)),
            )
        lib.graph_release_edges(handle)  # numpy owns the copies now
        self.key_ns = np.empty(self.num_sets, np.int64)
        self.key_obj = np.empty(self.num_sets, np.int64)
        self.key_rel = np.empty(self.num_sets, np.int64)
        self.key_wild = np.empty(self.num_sets, np.uint8)
        if self.num_sets:
            lib.graph_keys(
                handle,
                self.key_ns.ctypes.data_as(ctypes.POINTER(c)),
                self.key_obj.ctypes.data_as(ctypes.POINTER(c)),
                self.key_rel.ctypes.data_as(ctypes.POINTER(c)),
                self.key_wild.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
            )
        self.key_wild = self.key_wild.astype(bool)

    @property
    def num_nodes(self) -> int:
        return self.num_sets + self.num_leaves

    def num_obj_codes(self) -> Optional[int]:
        """Size of the object-string code table, or None when the loaded
        .so predates the export (compaction then falls back to a full
        rebuild rather than guessing a safe code range)."""
        if not hasattr(self._lib, "graph_num_obj_codes"):
            return None
        return int(self._lib.graph_num_obj_codes(self._handle))

    def num_rel_codes(self) -> Optional[int]:
        if not hasattr(self._lib, "graph_num_rel_codes"):
            return None
        return int(self._lib.graph_num_rel_codes(self._handle))

    def __del__(self):
        lib = getattr(self, "_lib", None)
        if lib is not None and self._handle:
            lib.graph_free(self._handle)
            self._handle = None

    def resolve_set(self, ns_id: int, obj: str, rel: str) -> int:
        o, r = obj.encode(), rel.encode()
        return int(self._lib.graph_resolve_set(self._handle, ns_id, o, len(o), r, len(r)))

    def resolve_queries(self, buf: bytes, n: int):
        """Bulk literal-query resolution: ``buf`` packs ``n`` records in the
        row wire format (kind 1: f0 = subject id; kind 0: subject set).
        Returns ``(start_raw, sub_raw)`` int64 arrays (-1 = not present;
        leaf subjects offset by num_sets), or None when the lib predates
        the entry point or rejects the buffer framing."""
        if not hasattr(self._lib, "graph_resolve_queries"):
            return None
        c = ctypes.c_int64
        start = np.empty(n, np.int64)
        sub = np.empty(n, np.int64)
        rc = self._lib.graph_resolve_queries(
            self._handle, buf, len(buf), n,
            start.ctypes.data_as(ctypes.POINTER(c)),
            sub.ctypes.data_as(ctypes.POINTER(c)),
        )
        if rc != 0:
            return None
        return start, sub

    def resolve_leaf(self, subject_id: str) -> int:
        s = subject_id.encode()
        return int(self._lib.graph_resolve_leaf(self._handle, s, len(s)))

    def obj_code(self, s: str) -> int:
        b = s.encode()
        return int(self._lib.graph_obj_code(self._handle, b, len(b)))

    def rel_code(self, s: str) -> int:
        b = s.encode()
        return int(self._lib.graph_rel_code(self._handle, b, len(b)))

    # -- reverse lookups (expand-tree reconstruction) ------------------------

    def _str_at(self, fn_name: str, idx: int) -> str:
        fn = getattr(self._lib, fn_name, None)
        if fn is None:
            # silently returning None would embed null strings in expand
            # trees; fail loud with the remedy instead
            raise RuntimeError(
                "libketoingest.so predates the expand reverse-lookup "
                "exports — rebuild it with `make native` (or set "
                "KETO_TPU_NATIVE=0 to use the Python interner)"
            )
        n = ctypes.c_int64()
        ptr = fn(self._handle, idx, ctypes.byref(n))
        if not ptr:
            raise IndexError(f"{fn_name}({idx}) out of range")
        return ctypes.string_at(ptr, n.value).decode()

    def set_key_of(self, raw_id: int):
        """``(ns_id, object, relation)`` of set node ``raw_id`` — field
        codes come from the resident key arrays, strings from the C tables."""
        return (
            int(self.key_ns[raw_id]),
            self._str_at("graph_obj_str", int(self.key_obj[raw_id])),
            self._str_at("graph_rel_str", int(self.key_rel[raw_id])),
        )

    def leaf_str(self, idx: int) -> Optional[str]:
        """Subject-id string of leaf ``idx`` (not offset by num_sets)."""
        return self._str_at("graph_leaf_str", idx)


def _string_column(strs: list) -> Optional[tuple[bytes, np.ndarray, np.ndarray]]:
    """(utf-8 blob, byte starts, byte lens) for a string column, built in
    a handful of vectorized passes (the columnar fast path's whole point:
    no per-row Python encode). Joins on NUL — multi-byte UTF-8 never
    contains a 0x00 byte, so separator positions are exactly the zero
    bytes of the encoded blob. None when a string embeds NUL (nothing
    legitimate does; the packed-buffer path handles it by falling back to
    the Python interner)."""
    n = len(strs)
    if n == 0:
        return b"", np.zeros(0, np.int64), np.zeros(0, np.int64)
    joined = "\x00".join(strs)
    if joined.count("\x00") != n - 1:
        return None
    blob = joined.encode()
    seps = np.nonzero(np.frombuffer(blob, np.uint8) == 0)[0]
    starts = np.empty(n, np.int64)
    starts[0] = 0
    starts[1:] = seps + 1
    ends = np.empty(n, np.int64)
    ends[:-1] = seps
    ends[-1] = len(blob)
    return blob, starts, ends - starts


def native_intern_rows_columnar(
    lib, rows: list, wild_ns_ids
) -> Optional[NativeInterned]:
    from operator import attrgetter

    n = len(rows)
    c = ctypes.c_int64
    # C-speed column extraction: one attrgetter map per column (a Python
    # per-row loop over six attributes dominated the handoff at 10M rows)
    ns = np.fromiter(map(attrgetter("namespace_id"), rows), np.int64, n)
    col_sid = list(map(attrgetter("subject_id"), rows))
    kind = np.fromiter((s is not None for s in col_sid), np.uint8, n)
    sns = np.fromiter(
        (v if v is not None else 0 for v in map(attrgetter("sset_namespace_id"), rows)),
        np.int64,
        n,
    )
    cols = []
    for attr, none_ok in (
        ("object", False), ("relation", False), ("subject_id", True),
        ("sset_object", True), ("sset_relation", True),
    ):
        vals = col_sid if attr == "subject_id" else list(map(attrgetter(attr), rows))
        if none_ok:
            # `or ""` maps None→"" and keeps "" as-is — the only falsy str
            vals = [v or "" for v in vals]
        col = _string_column(vals)
        if col is None:
            return None
        cols.append(col)

    def ptr(a):
        return a.ctypes.data_as(ctypes.POINTER(c))

    wild = np.asarray(sorted(wild_ns_ids), np.int64)
    args = [n, ptr(ns), kind.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)), ptr(sns)]
    for blob, starts, lens in cols:
        args += [blob, ptr(starts), ptr(lens)]
    args += [ptr(wild), len(wild)]
    handle = lib.graph_build_columnar(*args)
    if not handle:
        return None
    return NativeInterned(lib, handle)


def _ucs4_ok(arr: np.ndarray) -> bool:
    """True when every cell's NUL padding is trailing-only: an embedded
    NUL code point would truncate in the C++ decoder (NUL is the pad)."""
    if arr.dtype.itemsize == 0 or arr.size == 0:
        return True
    v = arr.view(np.uint32).reshape(arr.shape[0], -1)
    if v.shape[1] <= 1:
        return True
    z = v == 0
    return not bool(np.any(z[:, :-1] & (v[:, 1:] != 0)))


def native_intern_columns(lib, columns: dict, wild_ns_ids) -> Optional[NativeInterned]:
    """Intern from the store's cached sorted column bundle (numpy '<U*'
    string arrays + int/kind arrays) — zero per-row Python work; the C++
    side decodes UCS4 cells straight out of the numpy buffers."""
    if not hasattr(lib, "graph_build_ucs4"):
        return None
    c = ctypes.c_int64
    n = int(columns["ns"].shape[0])
    str_cols = []
    for name in ("obj", "rel", "sid", "sso", "ssr"):
        arr = np.ascontiguousarray(columns[name])
        if arr.dtype.kind != "U" or not _ucs4_ok(arr):
            return None
        str_cols.append(arr)
    ns = np.ascontiguousarray(columns["ns"], np.int64)
    kind = np.ascontiguousarray(columns["kind"], np.uint8)
    sns = np.ascontiguousarray(columns["sns"], np.int64)

    def ptr(a):
        return a.ctypes.data_as(ctypes.POINTER(c))

    wild = np.asarray(sorted(wild_ns_ids), np.int64)
    args = [n, ptr(ns), kind.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)), ptr(sns)]
    for arr in str_cols:
        args += [
            arr.ctypes.data_as(ctypes.POINTER(ctypes.c_uint32)),
            arr.dtype.itemsize // 4,
        ]
    args += [ptr(wild), len(wild)]
    handle = lib.graph_build_ucs4(*args)
    if not handle:
        return None
    return NativeInterned(lib, handle)


class NativeStreamBuilder:
    """Chunk-fed native interner (native/ingest.cpp stream_build_*).

    ``feed(rows)`` packs one scan chunk into the wire format and hands
    it to the C++ worker pool — the call returns as soon as the chunk is
    enqueued (or after blocking briefly on the bounded queue), so the
    caller's next store fetch overlaps interning. ``finish()`` merges
    the per-chunk shards in feed order, which reproduces the one-shot
    build's first-occurrence ids bit-identically
    (tests/test_streaming_build.py asserts equality against both the
    one-shot native path and the Python interner).

    A chunk the packer cannot frame (strings containing the separator
    control bytes — nothing legitimate does) poisons the native stream;
    ``feed`` then returns False and the caller falls back to the Python
    interner over its accumulated rows.
    """

    def __init__(self, lib: ctypes.CDLL, wild_ns_ids):
        self._lib = lib
        wild = np.asarray(sorted(wild_ns_ids), np.int64)
        self._handle = lib.stream_build_new(
            wild.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)), len(wild), 0
        )
        self._dead = self._handle is None or not self._handle

    @classmethod
    def create(cls, wild_ns_ids) -> Optional["NativeStreamBuilder"]:
        lib = load_library()
        if lib is None or not hasattr(lib, "stream_build_new"):
            return None
        sb = cls(lib, wild_ns_ids)
        return None if sb._dead else sb

    def feed(self, rows: list) -> bool:
        """Enqueue one chunk; False when the stream is unusable (framing
        rejection or an earlier malformed chunk)."""
        if self._dead:
            return False
        buf = pack_rows(rows)
        if buf.count(_FIELD) != 6 * len(rows) or buf.count(_RECORD) != len(rows):
            self.abort()
            return False
        rc = self._lib.stream_build_feed(self._handle, buf, len(buf), len(rows))
        if rc != 0:
            self.abort()
            return False
        return True

    def finish(self) -> Optional[NativeInterned]:
        """Join the workers and merge; None when the stream died (the
        caller falls back to the Python interner)."""
        if self._dead:
            return None
        handle = self._lib.stream_build_finish(self._handle)
        self._handle = None
        self._dead = True
        if not handle:
            return None
        return NativeInterned(self._lib, handle)

    def abort(self) -> None:
        if not self._dead:
            self._lib.stream_build_abort(self._handle)
            self._handle = None
            self._dead = True


def native_intern_rows(
    rows: Iterable, wild_ns_ids=frozenset(), columns: Optional[dict] = None
) -> Optional[NativeInterned]:
    """Native counterpart of ``intern_rows``; None when the lib is absent.
    ``columns`` is an optional pre-extracted column bundle (the store's
    bulk-ingest cache) that skips row iteration entirely."""
    lib = load_library()
    if lib is None:
        return None
    if columns is not None:
        got = native_intern_columns(lib, columns, wild_ns_ids)
        if got is not None:
            return got
    if not isinstance(rows, list):
        rows = list(rows)
    if rows and hasattr(lib, "graph_build_columnar") and hasattr(rows[0], "namespace_id"):
        got = native_intern_rows_columnar(lib, rows, wild_ns_ids)
        if got is not None:
            return got
    buf = pack_rows(rows)
    # strings containing the separator control bytes would corrupt the
    # framing — detectable as a field-count mismatch; fall back to Python
    if buf.count(_FIELD) != 6 * len(rows) or buf.count(_RECORD) != len(rows):
        return None
    wild = np.asarray(sorted(wild_ns_ids), np.int64)
    handle = lib.graph_build(
        buf,
        len(buf),
        wild.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
        len(wild),
    )
    if not handle:
        return None  # parser rejected the buffer → Python fallback
    return NativeInterned(lib, handle)
