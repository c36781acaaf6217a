"""Streaming, overlapped snapshot construction: scan → intern → layout.

The legacy cold start was strictly serial: a blocking full-table read
(``snapshot_rows``) materialized every row, THEN interning ran, THEN the
host lay out the device arrays — at 50M tuples, minutes in which the
store connection, the CPU interner, and the accelerator each sat idle
two-thirds of the time. This module runs the stages as a pipeline:

1. **Streaming scan** — the persister's chunked-cursor seam
   (``snapshot_scan`` on keto_tpu/persistence/sql_base.py and
   memory.py) hands over row chunks as they arrive, in the store's
   ORDER BY order;
2. **Overlapped intern** — each chunk feeds the native streaming
   builder (native/ingest.cpp ``stream_build_*``): a worker pool
   interns chunk *k* while the scan fetches chunk *k+1*, and the
   deterministic chunk-order merge reproduces the serial
   first-occurrence ids bit-identically. Without the native library the
   chunks intern through ``IncrementalInterner`` — same ids, no
   thread-level overlap;
3. **Device-side layout** — ``layout_snapshot`` with a
   ``DeviceSorter`` (keto_tpu/graph/device_build.py) runs the edge-scale
   stable sorts on the accelerator.

``BuildProgress`` is the observability spine of the pipeline: the
engine exposes it through ``health()`` (a STARTING boot reports
``{phase, pct}`` instead of a silent wait — keto_tpu/driver/health.py)
and the ``keto_build_*`` metric families bridge it into /metrics
(keto_tpu/driver/registry.py).

A transient store failure mid-scan aborts the in-flight builder and the
caller's retry policy (the engine's ``_read_store`` → x/retry seam)
re-runs the whole attempt with a fresh builder — chunks are never
replayed into a half-fed interner.
"""

from __future__ import annotations

import contextlib
import threading
import time
from typing import Callable, Optional

from keto_tpu.graph.gate_tables import tables_of
from keto_tpu.graph.interner import IncrementalInterner
from keto_tpu.graph.snapshot import GraphSnapshot, build_snapshot, layout_snapshot

#: default rows per scan chunk: large enough that per-chunk overheads
#: (pack, enqueue, shard tables) amortize, small enough that the intern
#: pool stays busy while the cursor fetches the next chunk
DEFAULT_CHUNK_ROWS = 262144

#: build phases in pipeline order; "idle" means no build in flight.
#: "rewrites" (the expansion of userset rewrites into edges, inside the
#: scan's chunk loop and just after the intern) is observed only by a build
#: under a schema that has rewrites
PHASES = ("scan", "intern", "rewrites", "device_build", "labels", "cache_save")

#: per-phase weight of the pct estimate (scan/intern dominate at scale;
#: labels/cache_save land after the snapshot already serves)
_PCT_WEIGHTS = {
    "scan": 0.35, "intern": 0.25, "device_build": 0.30,
    "labels": 0.10, "cache_save": 0.0,
}


class BuildProgress:
    """Thread-safe phase/progress tracker for snapshot builds.

    Counters (rows/edges ingested) are cumulative across builds — they
    bridge to monotone ``keto_build_*_total`` families — while phase and
    per-phase durations describe the in-flight (or most recent) build.
    ``attach_histogram`` mirrors phase durations into a labeled
    /metrics histogram the same way DurationStats mirrors slice times.
    """

    def __init__(self, stats=None):
        self._lock = threading.Lock()
        self._phase = "idle"
        self._rows = 0
        self._edges = 0
        self._durations: dict[str, float] = {}
        self._done: set[str] = set()
        self._hist = None
        self._stats = stats  # MaintenanceStats or None

    def attach_histogram(self, histogram) -> None:
        """Mirror phase durations into ``histogram`` (anything with
        ``observe((phase,), seconds)``)."""
        self._hist = histogram

    # -- build lifecycle -----------------------------------------------------

    def start(self) -> None:
        """A new full build begins: reset the per-build view (cumulative
        counters keep counting)."""
        with self._lock:
            self._durations = {}
            self._done = set()
            self._phase = "scan"

    def finish(self) -> None:
        with self._lock:
            self._phase = "idle"

    @contextlib.contextmanager
    def phase(self, name: str):
        """Run one pipeline phase: sets the live phase gauge, records
        the duration on exit (into the build view, the maintenance
        stats, and the attached histogram)."""
        with self._lock:
            self._phase = name
        t0 = time.monotonic()
        try:
            yield self
        finally:
            self.observe(name, time.monotonic() - t0)
            with self._lock:
                self._phase = "idle"

    def set_phase(self, name: str) -> None:
        with self._lock:
            self._phase = name

    def observe(self, name: str, seconds: float) -> None:
        """Record ``seconds`` spent in phase ``name`` (additive — the
        streaming scan attributes fetch time and intern time separately
        out of one interleaved loop)."""
        s = max(0.0, float(seconds))
        with self._lock:
            self._durations[name] = self._durations.get(name, 0.0) + s
            self._done.add(name)
        hist = self._hist
        if hist is not None:
            hist.observe((name,), s)
        if self._stats is not None:
            self._stats.observe_ms(f"build_{name}", s * 1e3)

    def add_rows(self, n: int) -> None:
        with self._lock:
            self._rows += int(n)

    def add_edges(self, n: int) -> None:
        with self._lock:
            self._edges += int(n)

    # -- read side -----------------------------------------------------------

    @property
    def rows_ingested(self) -> int:
        with self._lock:
            return self._rows

    @property
    def edges_ingested(self) -> int:
        with self._lock:
            return self._edges

    @property
    def current_phase(self) -> str:
        with self._lock:
            return self._phase

    def pct(self) -> float:
        """Coarse completion estimate of the in-flight build: completed
        phases count their full weight, the live phase half of its —
        honest about being an estimate (the scan does not know the table
        size), monotone enough for a progress probe."""
        with self._lock:
            phase = self._phase
            done = set(self._done)
        if phase == "idle":
            return 1.0 if done else 0.0
        got = sum(_PCT_WEIGHTS.get(p, 0.0) for p in done if p != phase)
        got += 0.5 * _PCT_WEIGHTS.get(phase, 0.0)
        return round(min(0.99, got), 3)

    def durations(self) -> dict:
        """Per-phase seconds of the current/most recent build."""
        with self._lock:
            return dict(self._durations)

    def snapshot(self) -> dict:
        pct = self.pct()
        with self._lock:
            return {
                "phase": self._phase,
                "pct": pct,
                "rows_ingested": self._rows,
                "edges_ingested": self._edges,
                "durations_s": {k: round(v, 3) for k, v in self._durations.items()},
            }


def _scan_and_intern(store, wild_ns_ids, progress, chunk_rows, rewrites=None):
    """One streaming scan+intern attempt: returns ``(interned, wm,
    expander)``. Raises on store failure with the in-flight native builder
    aborted — the caller's retry policy re-runs with fresh state. Under a
    rewrite schema every chunk goes through a ``RowExpander`` first: both
    interners see the derived edges as rows and deduplicate them as they
    deduplicate any other."""
    from keto_tpu.graph.native import NativeStreamBuilder

    expander = None
    if rewrites:
        from keto_tpu.namespace.rewrites import RowExpander

        expander = RowExpander(rewrites)
    state = {
        "native": NativeStreamBuilder.create(wild_ns_ids),
        "py": None,
        "rows": [],  # chunk refs: fallback insurance while native feeds
        "intern_s": 0.0,
    }
    if state["native"] is None:
        state["py"] = IncrementalInterner(wild_ns_ids)

    def on_chunk(chunk):
        n_stored = len(chunk)
        if expander is not None:
            chunk = expander.expand(chunk)
        t0 = time.monotonic()
        nb = state["native"]
        if nb is not None:
            state["rows"].append(chunk)
            if not nb.feed(chunk):
                # native stream died (framing rejection): replay the
                # accumulated chunks through the Python interner —
                # identical ids, the stream just loses its overlap
                state["native"] = None
                it = IncrementalInterner(wild_ns_ids)
                for c in state["rows"]:
                    it.add_rows(c)
                state["rows"] = []
                state["py"] = it
        else:
            state["py"].add_rows(chunk)
        state["intern_s"] += time.monotonic() - t0
        progress.add_rows(n_stored)

    progress.set_phase("scan")
    t_scan = time.monotonic()
    try:
        wm = store.snapshot_scan(on_chunk, chunk_rows=chunk_rows)
    except BaseException:
        if state["native"] is not None:
            state["native"].abort()
        raise
    scan_wall = time.monotonic() - t_scan

    progress.set_phase("intern")
    t0 = time.monotonic()
    if state["native"] is not None:
        g = state["native"].finish()
        if g is None:
            it = IncrementalInterner(wild_ns_ids)
            for c in state["rows"]:
                it.add_rows(c)
            g = it.finish()
    else:
        g = state["py"].finish()
    state["intern_s"] += time.monotonic() - t0

    # attribute the interleaved loop honestly: fetch time is the scan
    # wall minus the time on_chunk spent packing/feeding; the intern
    # phase is that packing/feeding plus the merge tail. With the native
    # pool the worker time overlaps the fetches entirely — which is the
    # point — so scan_s + intern_s may exceed the pipeline wall.
    in_chunks = state["intern_s"] + (expander.seconds if expander is not None else 0.0)
    progress.observe("scan", scan_wall - min(in_chunks, scan_wall))
    progress.observe("intern", state["intern_s"])
    return g, wm, expander


def _planned(snap: GraphSnapshot, rewrites, g, expander, prog) -> GraphSnapshot:
    """``snap`` with the plan of the schema it was built under
    (``GraphSnapshot.rewrites``), and the ``rewrites`` phase observed: the
    chunk loop's expansion, the closure over the relation graph and, where
    the schema has gates, the gates each row reaches
    (keto_tpu/graph/gate_tables.py)."""
    if rewrites:
        from keto_tpu.namespace.rewrites import plan_for

        t0 = time.monotonic()
        snap.rewrites = plan_for(rewrites, g, expander)
        tables_of(snap)
        spent = expander.seconds if expander is not None else 0.0
        prog.observe("rewrites", spent + time.monotonic() - t0)
    return snap


def full_build(
    store,
    wild_ns_ids=frozenset(),
    *,
    peel_seed_cap: float = 4.0,
    sorter=None,
    progress: Optional[BuildProgress] = None,
    read_retry: Optional[Callable] = None,
    chunk_rows: int = DEFAULT_CHUNK_ROWS,
    rewrites=None,
) -> GraphSnapshot:
    """Build a full snapshot from ``store`` at its current watermark via
    the fastest available path, in preference order:

    1. the store's sorted **column bundle** (``snapshot_columns`` right
       after a bulk load) through the native zero-copy interner — no row
       objects at all;
    2. the **streaming scan+intern pipeline** (``snapshot_scan``) when
       the store prefers it (SQL persisters: I/O overlaps interning);
    3. the legacy ``snapshot_rows`` one-shot.

    All three produce bit-identical snapshots; ``read_retry`` (the
    engine's ``_read_store`` — x/retry with backoff) wraps each store
    read so a transient failure mid-scan retries with fresh state.

    ``rewrites`` (a truthy ``RewriteSchema``, keto_tpu/namespace/rewrites.py)
    compiles the union-class rewrites into edges on the way into the
    interner; the column bundle holds stored rows only and is passed over.
    Without one nothing here differs from a build before rewrites existed.
    """
    prog = progress if progress is not None else BuildProgress()
    read_retry = read_retry or (lambda fn, *a: fn(*a))
    prog.start()
    try:
        # -- 1) column-bundle fast path (native interner required) -----------
        cols_fn = getattr(store, "snapshot_columns", None)
        if cols_fn is not None and not rewrites:
            wm = store.watermark()
            columns = cols_fn(wm)
            if columns is not None:
                from keto_tpu.graph import native as native_mod

                lib = native_mod.load_library()
                if lib is not None:
                    with prog.phase("intern"):
                        g = native_mod.native_intern_columns(
                            lib, columns, wild_ns_ids
                        )
                    if g is not None:
                        prog.add_rows(int(columns["ns"].shape[0]))
                        return layout_snapshot(
                            g, wm, wild_ns_ids, peel_seed_cap=peel_seed_cap,
                            sorter=sorter, progress=prog,
                        )

        # -- 2) streaming scan+intern ----------------------------------------
        scan_fn = getattr(store, "snapshot_scan", None)
        if scan_fn is not None and getattr(store, "scan_chunks_preferred", True):
            g, wm, expander = read_retry(
                lambda: _scan_and_intern(store, wild_ns_ids, prog, chunk_rows, rewrites)
            )
            snap = layout_snapshot(
                g, wm, wild_ns_ids, peel_seed_cap=peel_seed_cap,
                sorter=sorter, progress=prog,
            )
            return _planned(snap, rewrites, g, expander, prog)

        # -- 3) legacy one-shot ----------------------------------------------
        with prog.phase("scan"):
            rows, wm = read_retry(store.snapshot_rows)
        if rewrites:
            from keto_tpu.namespace.rewrites import RowExpander

            expander = RowExpander(rewrites)
            snap = build_snapshot(
                expander.expand(rows), wm, wild_ns_ids, peel_seed_cap=peel_seed_cap,
                sorter=sorter, progress=prog,
            )
            return _planned(snap, rewrites, snap.interned, expander, prog)
        cols = cols_fn(wm) if cols_fn is not None else None
        return build_snapshot(
            rows, wm, wild_ns_ids, peel_seed_cap=peel_seed_cap,
            columns=cols, sorter=sorter, progress=prog,
        )
    finally:
        prog.finish()
