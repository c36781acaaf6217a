"""Query frames: a ``POST /check/batch`` body as device-ready records.

The device path needs one thing of a check query: its 7-field wire record
(``native/ingest.cpp`` ``graph_resolve_queries``). The REST layer used to
decode a body into dicts, then ``RelationTuple`` objects, and the engine
turned those back into records, all under the GIL and once per tuple. A
``QueryFrame`` is the body framed straight into records by the native
framer (``keto_tpu/graph/native.py`` ``FrameTable.frame``): one buffer,
``n + 1`` record offsets, one flag byte a record. It rides the batcher as
an item's ``tuples`` and the engine resolves ranges of it without looking
at a single tuple.

Objects are built ON DEMAND only, by the same ``json.loads`` +
``RelationTuple.from_json`` the general path runs, over the body the
frame kept: for the paths that need tuples (the CPU oracle, a truncated
slice's exact re-run, an audit sample, the host pattern resolver, ...).
``MATERIALIZED`` counts those by reason — once a call, never per tuple.

A ``QueryBatch`` is an ordered run of queries cut from frames and from
plain lists of ``RelationTuple`` (a dispatch round mixes both): what the
engine's stream takes per slice when a frame is in play.
"""

from __future__ import annotations

import json
import threading
from typing import Optional, Sequence

import numpy as np

from keto_tpu.relationtuple.model import RelationTuple

#: the flag byte of a framed record (native/ingest.cpp check_frame_body)
LITERAL, SPECIAL, DEAD, NO_TARGET = 0, 1, 2, 3

#: times a frame's tuples were turned into objects, by reason (scraped as
#: ``keto_check_frame_materialized_total{why}``)
MATERIALIZED: dict[str, int] = {}


def check_frame_metrics(m):
    """The ``/check/batch`` path counters (idempotent by name; the REST
    apps record into them and the driver registry pre-declares them):
    tuples by the path that carried them in, and bodies the framer
    declined, by reason."""
    return (
        m.counter(
            "keto_check_batch_tuples_total",
            "Tuples of POST /check/batch calls by the form they reached the "
            "batcher in: framed (one buffer of query records written by the "
            "native framer, no object per tuple) or objects (json.loads + "
            "RelationTuple.from_json).",
            ("path",),
        ),
        m.counter(
            "keto_check_frame_declines_total",
            "POST /check/batch bodies the native framer declined and the "
            "general decode served, by reason: shape (not the plain form), "
            "escape (a backslash in a string), encoding, size, capacity, "
            "unavailable (no native framer, or a namespace named \"\").",
            ("reason",),
        ),
    )


_note_lock = threading.Lock()  # guards: MATERIALIZED (one batcher thread a tenant)


def _note(why: str) -> None:
    with _note_lock:
        MATERIALIZED[why] = MATERIALIZED.get(why, 0) + 1


class QueryFrame:
    """One framed request body. ``buf[off[i]:off[i + 1]]`` is query ``i``'s
    record and ``flags[i]`` its flag; ``manager`` is the namespace manager
    whose ids the records carry (the frame is only valid against that very
    object); ``body`` is kept for ``tuples`` / ``pick``.

    ``door`` is None, or ``(interned, start_raw, sub_raw)``: the records'
    raw node ids as ``interned.resolve_queries`` gave them on the thread
    that framed the body (``resolve_at_door``). Raw ids are a function of
    the immutable intern tables alone, so a round whose snapshot has that
    very ``interned`` takes slices of them and resolves nothing; any other
    round resolves the records itself. Holding ``interned`` here is also
    what keeps its native tables alive while a frame points at them."""

    __slots__ = ("buf", "off", "flags", "body", "manager", "n", "door", "_raw", "_tuples")

    def __init__(self, buf: bytes, off: np.ndarray, flags: np.ndarray, body: bytes, manager):
        self.buf = buf
        self.off = off
        self.flags = flags
        self.body = body
        self.manager = manager
        self.n = int(flags.shape[0])
        self.door: Optional[tuple] = None
        self._raw: Optional[list] = None
        self._tuples: Optional[list] = None

    def __len__(self) -> int:
        return self.n

    def resolve_at_door(self, snap) -> None:
        """Resolve the records against ``snap``'s intern tables, on the
        caller's thread (the native call releases the GIL). ``snap`` is
        whatever the engine is serving right now, or None; a snapshot
        whose interner has no bulk entry point, or one that rejects the
        buffer, leaves the frame as it was."""
        interned = getattr(snap, "interned", None)
        resolve = getattr(interned, "resolve_queries", None)
        if resolve is None:
            return
        got = resolve(self.buf, self.n)
        if got is not None:
            self.door = (interned, *got)

    def _decoded(self) -> list:
        if self._raw is None:
            self._raw = json.loads(self.body)["tuples"]
        return self._raw

    def tuples(self, why: str) -> list[RelationTuple]:
        """Every query as a ``RelationTuple``, decoded once."""
        _note(why)
        if self._tuples is None:
            self._tuples = [RelationTuple.from_json(t) for t in self._decoded()]
        return self._tuples

    def pick(self, indices: Sequence[int], why: str) -> list[RelationTuple]:
        """The queries at ``indices`` as objects; only those are built."""
        _note(why)
        if self._tuples is not None:
            return [self._tuples[i] for i in indices]
        raw = self._decoded()
        return [RelationTuple.from_json(raw[i]) for i in indices]


def as_tuples(queries, why: str) -> Sequence[RelationTuple]:
    """``queries`` as ``RelationTuple``s: itself unless it is framed."""
    if isinstance(queries, (QueryFrame, QueryBatch)):
        return queries.tuples(why)
    return queries


def pick_tuples(queries, indices: Sequence[int], why: str) -> list[RelationTuple]:
    """The queries at ``indices`` (ascending) as ``RelationTuple``s."""
    if isinstance(queries, (QueryFrame, QueryBatch)):
        return queries.pick(indices, why)
    return [queries[i] for i in indices]


class QueryBatch:
    """``parts = [(source, a, b), ...]``: queries ``source[a:b]`` of each
    part in order, a source being a ``QueryFrame`` or a list of
    ``RelationTuple``. Sliceable by range; never indexed per query."""

    __slots__ = ("parts", "n")

    def __init__(self, parts: list):
        self.parts = parts
        self.n = sum(b - a for _, a, b in parts)

    def __len__(self) -> int:
        return self.n

    def __getitem__(self, s: slice) -> "QueryBatch":
        lo, hi, step = s.indices(self.n)
        if step != 1:
            raise ValueError("a QueryBatch is cut by contiguous ranges")
        parts = []
        base = 0
        for src, a, b in self.parts:
            n = b - a
            p_lo, p_hi = max(lo - base, 0), min(hi - base, n)
            if p_lo < p_hi:
                parts.append((src, a + p_lo, a + p_hi))
            base += n
            if base >= hi:
                break
        return QueryBatch(parts)

    def tuples(self, why: str) -> list[RelationTuple]:
        out: list = []
        for src, a, b in self.parts:
            out.extend(as_tuples(src, why)[a:b])
        return out

    def pick(self, indices: Sequence[int], why: str) -> list[RelationTuple]:
        """Objects for ``indices`` (ascending positions in this batch)."""
        out: list = []
        k = 0
        base = 0
        n_idx = len(indices)
        for src, a, b in self.parts:
            end = base + (b - a)
            local = []
            while k < n_idx and indices[k] < end:
                local.append(indices[k] - base + a)
                k += 1
            if local:
                out.extend(pick_tuples(src, local, why))
            base = end
        return out
