"""Gates on the device: what the check path needs of a schema's
intersections and exclusions (Zanzibar, USENIX ATC '19, section 2.3).

``keto_tpu/namespace/rewrites.py`` cuts a gated relation into union-only
*operands* (hidden relations whose edges the ingest seam derives like any
union-class rewrite's) and a postfix program over them (``GateDef``); the
relation's own node, the *gate*, has no out-edges. So an ordinary device
check ``(t, u)`` answers the part of ``t``'s closure that crosses no gate,
and what is left is, for every gate ``g`` that ``t`` reaches, ``g``'s
program over the answers of ``(operand, u)``: ordinary device checks again.

- ``GateTables`` (``keto_tpu/graph/gate_tables.py``, a snapshot artefact):
  by base device row, which gates its closure reaches in the cut graph, each
  gate's operand rows, and whether the device can serve the row at all.
- ``split``: a resolved batch's checks by who answers them. The oracle's
  are answered here, each counted by reason; the rest of the gated ones are
  expanded (``expand``) into positions of the same chunk: the check's own
  row where it reaches anything but gates, and every operand of every gate,
  recursively where an operand reaches a further gate.
- ``GateSlice.combine``: when a slice lands, one pass over its decoded
  answer bits by program shape gives each check its answer.

``W``, ``B``, the entry counts and the slice controller work on positions;
what counts checks goes on counting checks (docs/concepts/observability.md).
"""

from __future__ import annotations

import time

import numpy as np

from keto_tpu.check.frame import pick_tuples
from keto_tpu.graph.gate_tables import (
    CYCLE, DEVICE, FANOUT, GATE_ROW, SELF, GateTables, _segments, tables_of,
)
from keto_tpu.graph.snapshot import _csr_gather_counts
from keto_tpu.namespace.rewrites import AND, GATED, HIDDEN, OR, REWRITTEN
from keto_tpu.x.timeline import RESOLVE, dispatch_clock

#: reasons a gated check is handed to the oracle (``keto_check_rewrite_oracle_total``)
ORACLE_REASONS = (
    "gated_closure", "gated_pattern", "gated_unresolved", "fanout", "cycle", "overlay",
)
SERVED = ("device", "oracle")
POSITION_KINDS = ("self", "operand")
PHASES = ("expand", "combine")
NODE_KINDS = ("gate", "operand")


class GateSlice:
    """What one slice's landing needs to turn its positions' answers into
    its checks': by check its own position (-1: it has none), and the gate
    occurrences, each with its program, its first operand position, and
    where its answer goes (a check, or the operand position it hangs off)."""

    __slots__ = ("n_checks", "n_pos", "n_gated", "self_pos", "occ_check", "occ_def",
                 "occ_pos0", "occ_parent", "occ_depth", "defs")

    def combine(self, bits: np.ndarray) -> np.ndarray:
        """``bool[n_checks]`` from the positions' ``bool[n_pos]``."""
        val = np.array(bits[: self.n_pos], bool)
        out = np.zeros(self.n_checks, bool)
        own = self.self_pos >= 0
        out[own] = val[self.self_pos[own]]
        if not self.occ_def.size:
            return out
        for depth in range(int(self.occ_depth.max()), -1, -1):
            at_depth = self.occ_depth == depth
            for d in np.unique(self.occ_def[at_depth]).tolist():
                idx = np.flatnonzero(at_depth & (self.occ_def == d))
                k, program = self.defs[d]
                cols = val[self.occ_pos0[idx][:, None] + np.arange(k)]
                stack = []
                for step in program:
                    if step >= 0:
                        stack.append(cols[:, step])
                        continue
                    b, a = stack.pop(), stack.pop()
                    stack.append(a & b if step == AND else a | b if step == OR else a & ~b)
                granted = idx[stack.pop()]
                parent = self.occ_parent[granted]
                top = parent < 0
                out[self.occ_check[granted[top]]] = True
                val[parent[~top]] = True
        return out


class Expansion:
    """One resolved chunk's positions, ordered by check: ``sd`` / ``tg`` as
    ``_resolve_bulk`` gives them, one entry a position; ``pos_ptr[i]`` is
    check ``i``'s first position."""

    __slots__ = ("sd", "tg", "pos_ptr", "self_pos", "gated", "occ_ptr", "occ_check",
                 "occ_def", "occ_pos0", "occ_parent", "occ_depth", "defs")

    def cut(self, c0: int, c1: int) -> GateSlice:
        """The checks ``[c0, c1)`` as a slice of their own."""
        p0, o0, o1 = int(self.pos_ptr[c0]), int(self.occ_ptr[c0]), int(self.occ_ptr[c1])
        s = GateSlice()
        s.n_checks = c1 - c0
        s.n_pos = int(self.pos_ptr[c1]) - p0
        s.n_gated = int(np.count_nonzero(self.gated[c0:c1]))
        own = self.self_pos[c0:c1]
        s.self_pos = np.where(own >= 0, own - p0, -1)
        s.occ_check = self.occ_check[o0:o1] - c0
        s.occ_def = self.occ_def[o0:o1]
        s.occ_pos0 = self.occ_pos0[o0:o1] - p0
        parent = self.occ_parent[o0:o1]
        s.occ_parent = np.where(parent >= 0, parent - p0, -1)
        s.occ_depth = self.occ_depth[o0:o1]
        s.defs = self.defs
        return s

    def check_at(self, pos: int, after: int) -> int:
        """The start of the check that holds position ``pos``, as a check
        index: rounded down, but past ``after``."""
        c = int(np.searchsorted(self.pos_ptr, pos, side="right")) - 1
        return max(c, after + 1)


def expand(tables: GateTables, sd: np.ndarray, tg: np.ndarray, gi: np.ndarray,
           keep_self: np.ndarray) -> Expansion:
    """The chunk ``(sd, tg)`` with its checks ``gi`` (rows the tables serve)
    expanded: ``keep_self[j]`` says whether ``gi[j]`` is asked of its own row
    too. Every other check stays the one position it is."""
    n = sd.shape[0]
    row_ptr, row_gates = tables.row_ptr, tables.row_gates
    gate_def, gate_ops, def_k = tables.gate_def, tables.gate_ops, tables.def_k
    own = np.ones(n, bool)
    own[gi[~keep_self]] = False
    selfs = np.flatnonzero(own)
    p_check, p_row = [selfs], [sd[selfs]]
    n_pos = selfs.shape[0]
    rows = sd[gi]
    cnt = row_ptr[rows + 1] - row_ptr[rows]
    o_gate, _ = _csr_gather_counts(row_ptr, row_gates, rows, cnt)
    o_check = np.repeat(gi, cnt)
    o_parent = np.full(o_gate.shape[0], -1, np.int64)
    occ_check, occ_def, occ_pos0, occ_parent, occ_depth = [], [], [], [], []
    depth = 0
    while o_gate.size:
        d = gate_def[o_gate]
        k = def_k[d]
        first = np.cumsum(k) - k
        total = int(k.sum())
        of_occ = np.repeat(np.arange(o_gate.shape[0]), k)
        rows = gate_ops[o_gate[of_occ], np.arange(total) - np.repeat(first, k)].astype(np.int64)
        checks = o_check[of_occ]
        occ_check.append(o_check)
        occ_def.append(d)
        occ_pos0.append(first + n_pos)
        occ_parent.append(o_parent)
        occ_depth.append(np.full(o_gate.shape[0], depth, np.int8))
        p_check.append(checks)
        p_row.append(rows)
        at = np.maximum(rows, 0)
        cnt = np.where(rows >= 0, row_ptr[at + 1] - row_ptr[at], 0)
        o_gate, _ = _csr_gather_counts(row_ptr, row_gates, at, cnt)
        o_check = np.repeat(checks, cnt)
        o_parent = np.repeat(np.arange(n_pos, n_pos + total, dtype=np.int64), cnt)
        n_pos += total
        depth += 1
    p_check = np.concatenate(p_check)
    order = np.argsort(p_check, kind="stable")  # a check's own position first
    new_of = np.empty(n_pos, np.int64)
    new_of[order] = np.arange(n_pos)
    e = Expansion()
    e.sd = np.ascontiguousarray(np.concatenate(p_row)[order], np.int64)
    e.tg = np.ascontiguousarray(tg[p_check[order]], np.int64)
    e.tg[e.sd == -1] = -1  # an operand with no node; a pattern (-2) keeps its target
    e.pos_ptr = np.concatenate(
        [np.zeros(1, np.int64), np.cumsum(np.bincount(p_check, minlength=n))]
    )
    e.self_pos = np.where(own, e.pos_ptr[:-1], -1)
    e.gated = np.zeros(n, bool)
    e.gated[gi] = True
    chk = np.concatenate(occ_check) if occ_check else np.zeros(0, np.int64)
    by_check = np.argsort(chk, kind="stable")
    e.occ_check = chk[by_check]
    e.occ_ptr = _segments(e.occ_check, n)
    e.occ_def = np.concatenate(occ_def)[by_check] if occ_def else np.zeros(0, np.int32)
    e.occ_pos0 = new_of[np.concatenate(occ_pos0)[by_check]] if occ_pos0 else chk
    parent = np.concatenate(occ_parent)[by_check] if occ_parent else chk
    e.occ_parent = np.where(parent >= 0, new_of[np.maximum(parent, 0)], -1)
    e.occ_depth = np.concatenate(occ_depth)[by_check] if occ_depth else np.zeros(0, np.int8)
    e.defs = tables.defs
    return e


def _overlay_objects(snap) -> set:
    """``(namespace id, object)`` of every object#relation node the overlay
    brought, worked out once a snapshot."""
    got = snap._pattern_cache.get("_gate_ov_objects")
    if got is None:
        got = {(k[0], k[1]) for k in (snap.ov_set_ids or ())}
        snap._pattern_cache["_gate_ov_objects"] = got
    return got


def split(dispatch, snap, queries, sd, tg, multi, flags, cap_q: int):
    """The gated part of ``CheckDispatch._rewrite_split``, for a plan that
    has gates: ``flags`` are the closure bits of the batch's positions.
    Returns ``(oracle answers as bool[n] or None, Expansion or None)``;
    where an ``Expansion`` comes back the caller goes on with its ``sd`` /
    ``tg``, and re-keys ``multi`` and the oracle's answers by
    ``self_pos``."""
    plan = snap.rewrites
    incr = dispatch.maintenance.incr
    clk = dispatch_clock()
    n = sd.shape[0]
    nb = int(snap.n_base_nodes)
    tables = None if plan.gates_stale else tables_of(snap)
    # a name of ours: no query may ask it (a pattern that matches one
    # matches its gate too, and is the oracle's)
    hidden = np.flatnonzero((flags & HIDDEN > 0) & (sd >= 0))
    if hidden.size:
        sd[hidden] = tg[hidden] = -1
        flags[hidden] = 0
    in_base = (sd >= 0) & (sd < nb)
    gated = flags & GATED > 0
    mode = np.zeros(n, np.uint8)
    stale = np.zeros(n, bool)
    if tables is not None:
        mode[in_base] = tables.row_mode[sd[in_base]]
        # a check that would not fit a slice of this snapshot's is the oracle's
        big = in_base & (mode & DEVICE > 0)
        big[big] = tables.row_npos[sd[big]] > cap_q
        mode[big] = FANOUT
    else:
        stale = in_base & gated  # the tables do not hold: the oracle's
    reasons = {
        "gated_pattern": np.flatnonzero((sd == -2) & gated),
        "overlay": np.flatnonzero(((sd >= nb) & gated) | stale),
        "fanout": np.flatnonzero(mode & FANOUT > 0),
        "cycle": np.flatnonzero(mode & CYCLE > 0),
    }
    # a gated relation's node is in the base rows for every object the store
    # mentions: a check that found no start is denied, unless the overlay
    # brought the object
    missing = np.flatnonzero(sd == -1)
    missing = missing[~np.isin(missing, hidden)]
    denied, brought = 0, []
    if missing.size:
        ns_of = dispatch._ns_resolver()
        at = missing.tolist()
        for i, rt in zip(at, pick_tuples(queries, at, "rewrite")):
            ns_id = ns_of(rt.namespace)
            if isinstance(ns_id, int) and plan.relation_flags(ns_id, rt.relation) & GATED:
                if (ns_id, rt.object) in _overlay_objects(snap):
                    brought.append(i)
                else:
                    denied += 1
    if brought:
        reasons["overlay"] = np.union1d(reasons["overlay"], brought)
    gi = np.flatnonzero(mode & DEVICE > 0)
    taken = np.concatenate(list(reasons.values()))
    incr("gate_checks_device", by=int(gi.size) + denied)
    incr("gate_checks_oracle", by=int(taken.size))
    incr("rewrite_route_oracle", by=int(taken.size))
    rewritten = (flags & REWRITTEN > 0)
    incr("rewrite_route_device", by=int(rewritten.sum()) - int(rewritten[taken].sum()))
    if gi.size or taken.size:
        # the rest of this resolve says what the chunk holds
        positions = n - int(gi.size) + int(tables.row_npos[sd[gi]].sum()) if gi.size else n
        clk.gates(int(gi.size) + int(taken.size) + denied, positions)
        clk.enter(RESOLVE)
    oracle_ans = None
    if taken.size:
        t0 = time.perf_counter()
        for reason, at in reasons.items():
            if at.size:
                incr(f"rewrite_oracle_{reason}", by=int(at.size))
                clk.mark("keto.gate.oracle", reason=reason, n=int(at.size))
        taken = np.sort(taken)
        oracle = dispatch._oracle()
        oracle_ans = np.zeros(n, bool)
        at = taken.tolist()
        for i, rt in zip(at, pick_tuples(queries, at, "rewrite")):
            oracle_ans[i] = oracle.subject_is_allowed(rt)
            multi.pop(i, None)
        sd[taken] = tg[taken] = -1
        incr("rewrite_oracle_seconds", by=time.perf_counter() - t0)
    if not gi.size:
        return oracle_ans, None
    t0 = time.perf_counter()
    keep_self = tables.row_mode[sd[gi]] & SELF > 0
    # a check that asks about a gate's own userset is granted by an edge to it
    to = tg[gi]
    to_base = (to >= 0) & (to < nb)
    keep_self[to_base] |= tables.row_mode[to[to_base]] & GATE_ROW > 0
    if snap.ov_fwd:
        # a row that gained an out-edge since the tables were made
        keep_self |= np.isin(sd[gi], np.fromiter(snap.ov_fwd, np.int64, len(snap.ov_fwd)))
    exp = expand(tables, sd, tg, gi, keep_self)
    n_self = int(np.count_nonzero(keep_self))
    incr("gate_positions_self", by=n_self)
    incr("gate_positions_operand", by=int(exp.sd.shape[0]) - (n - int(gi.size)) - n_self)
    incr("gate_seconds_expand", by=time.perf_counter() - t0)
    return oracle_ans, exp


def gate_metrics(m, stats_of) -> None:
    """Declare this module's families on ``m`` (driver/registry.py calls
    this once); ``stats_of()`` is the serving engine's maintenance counters
    and gauges (``MaintenanceStats.raw()``), empty while there is no engine."""

    def counts(prefix: str, labels: tuple):
        def read():
            counters = stats_of()[0]
            return [((lab,), float(counters.get(prefix + lab, 0))) for lab in labels]
        return read

    m.register_callback(
        "keto_check_gate_checks_total", "counter",
        "Checks whose closure reaches a gate (an intersection or an exclusion "
        "of the rewrite schema), by who served them: device (expanded into "
        "one position an operand and combined when the slice landed) or "
        "oracle (keto_check_rewrite_oracle_total says why). Counted in "
        "checks, once a resolved batch; a gated check is a rewritten one too.",
        counts("gate_checks_", SERVED), ("served",),
    )
    m.register_callback(
        "keto_check_gate_positions_total", "counter",
        "Device positions the expansion made for gated checks: self (the "
        "check's own row, where it reaches anything besides gates) and "
        "operand (one an operand of every gate reached, nested gates "
        "included). Over keto_check_gate_checks_total{served=\"device\"}: "
        "what a gate costs pack and the kernels.",
        counts("gate_positions_", POSITION_KINDS), ("kind",),
    )
    m.register_callback(
        "keto_check_gate_seconds_total", "counter",
        "The dispatch thread's seconds in the gates' own work: expand (from "
        "a resolved batch to its positions, inside the resolve state) and "
        "combine (from a landed slice's position bits to its checks' "
        "answers, inside the fill state).",
        counts("gate_seconds_", PHASES), ("phase",),
    )
    m.register_callback(
        "keto_check_rewrite_oracle_seconds_total", "counter",
        "The dispatch thread's seconds inside the CPU oracle for the checks "
        "keto_check_rewrite_oracle_total counts (part of the resolve state).",
        lambda: [((), float(stats_of()[0].get("rewrite_oracle_seconds", 0)))],
    )

    def nodes():
        gauges = stats_of()[1]
        return [((kind,), float(gauges.get(f"gate_nodes_{kind}", 0))) for kind in NODE_KINDS]

    m.register_callback(
        "keto_snapshot_gate_nodes", "gauge",
        "Nodes of the serving snapshot that stand for a gate: gate (the "
        "gated relation's own node, one an object the store mentions) and "
        "operand (its hidden union-only operands that have edges), at its upload.",
        nodes, ("kind",),
    )
    m.register_callback(
        "keto_snapshot_set_nodes", "gauge",
        "Object#relation nodes of the serving snapshot at its upload, hidden "
        "ones included: what keto_snapshot_gate_nodes is a share of.",
        lambda: [((), float(stats_of()[1].get("snapshot_set_nodes", 0)))],
    )
    m.register_callback(
        "keto_snapshot_gate_reach_rows", "gauge",
        "Base rows of the serving snapshot whose closure reaches a gate: a "
        "check that starts on one is a gated check.",
        lambda: [((), float(stats_of()[1].get("gate_reach_rows", 0)))],
    )
