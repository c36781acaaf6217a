"""The gates of a snapshot's rows: which intersections and exclusions of the
rewrite schema (Zanzibar, USENIX ATC '19, section 2.3) each base row's
closure reaches, as the check path's expansion needs them
(``keto_tpu/check/gates.py``).

``keto_tpu/namespace/rewrites.py`` cuts a gated relation into union-only
*operands* and a postfix program over them; the relation's own node, the
*gate*, has no out-edges. ``GateTables`` holds, by base device row, the gates
its closure reaches in that cut graph (a CSR), each gate's operand rows, and
whether the expansion can serve the row at all (``row_mode``). A snapshot
artefact like the reverse CSR: worked out in the build's ``rewrites`` phase
(``stream_build._planned``), kept by the snapshot cache behind the plan's
fingerprint (``snapcache``), carried over overlay deltas (``overlay``) and
made anew by the compaction thread before a folded snapshot is published
(``compaction``), since a fold renumbers the rows.
"""

from __future__ import annotations

from typing import Optional

import numpy as np

from keto_tpu.graph.snapshot import _csr_gather_counts, _csr_gather_host, build_rev_csr

#: gates a row's closure may reach and still be expanded. Every gate costs
#: its operands as positions of the slice, for every check on the row: past
#: a handful the check is cheaper as one descent of the oracle than as a
#: page of device positions (8 leaves room for a row under a few gated
#: parents; on OpenFGA's ``github`` store with a blocklist it is 1)
GATE_FANOUT_CAP = 8
#: positions one check may become, nested gates expanded: bounds what an
#: adversarial nesting costs a slice, and keeps a check inside any slice width
GATE_POSITION_CAP = 64

#: bits of ``GateTables.row_mode``; 0: the row reaches no gate
DEVICE = 1  # the expansion serves a check on this row
SELF = 2  # ... and the row reaches something besides gates: it is asked itself too
FANOUT = 4  # past a cap: the oracle answers
CYCLE = 8  # an operand reaches its own gate: the oracle answers
GATE_ROW = 16  # the row is a gate's own node

_TABLE_ARRAYS = (
    "row_ptr", "row_gates", "row_mode", "row_npos", "gate_row", "gate_def", "gate_ops",
)


class GateTables:
    """The gates of one snapshot's base rows (device ids below
    ``n_base_nodes``); see the module's docstring."""

    __slots__ = _TABLE_ARRAYS + ("defs", "def_k", "n_operand_nodes")

    def __init__(self, defs, **arrays):
        #: ``[(operands, program)]`` by ``gate_def``
        self.defs = [(int(k), tuple(int(x) for x in program)) for k, program in defs]
        self.def_k = np.asarray([k for k, _ in self.defs], np.int64)
        for name in _TABLE_ARRAYS:
            setattr(self, name, arrays[name])
        self.n_operand_nodes = int(np.count_nonzero(np.asarray(self.gate_ops) >= 0))

    @property
    def n_gate_nodes(self) -> int:
        return int(self.gate_row.shape[0])

    @property
    def n_reach_rows(self) -> int:
        return int(np.count_nonzero(self.row_mode))

    def to_meta(self) -> dict:
        return {"defs": [[k, list(program)] for k, program in self.defs]}

    def save(self, sv) -> None:
        for name in _TABLE_ARRAYS:
            sv(f"gate_{name}", getattr(self, name))

    @classmethod
    def load(cls, mm, meta: dict) -> "GateTables":
        return cls(meta["defs"], **{name: mm(f"gate_{name}.npy") for name in _TABLE_ARRAYS})


def tables_of(snap, build: bool = True) -> Optional[GateTables]:
    """The gate tables of ``snap``'s base rows: what the build or the cache
    left on it, else worked out now (a compaction fold renumbers the rows)
    and kept. None for a plan without gates."""
    plan = snap.rewrites
    if plan is None or not plan.has_gated:
        return None
    tables = snap.__dict__.get("_gate_tables")
    if tables is None and build:
        tables = snap.__dict__["_gate_tables"] = build_tables(snap, plan.schema)
    return tables


def _segments(index: np.ndarray, n: int) -> np.ndarray:
    """``int64[n+1]``: where each of ``n`` keys starts in the sorted ``index``."""
    return np.searchsorted(index, np.arange(n + 1)).astype(np.int64)


def build_tables(snap, schema) -> GateTables:
    n = int(snap.n_base_nodes)
    n_sets = int(snap.num_sets)
    interned = snap.interned
    defs = list(schema.gates.items())
    k_max = max((len(g.operands) for _, g in defs), default=1)
    gate_row = [np.zeros(0, np.int64)]
    gate_def = [np.zeros(0, np.int32)]
    gate_ops = [np.zeros((0, k_max), np.int32)]
    if n_sets and defs:
        key_ns = np.asarray(interned.key_ns, np.int64)[:n_sets]
        key_obj = np.asarray(interned.key_obj, np.int64)[:n_sets]
        key_rel = np.asarray(interned.key_rel, np.int64)[:n_sets]
        raw2dev = np.asarray(snap.raw2dev)
        n_rel = int(key_rel.max()) + 1
        pair, first, inv = np.unique(
            key_ns * n_rel + key_rel, return_index=True, return_inverse=True
        )
        by_pair = np.argsort(inv, kind="stable")
        starts = _segments(inv[by_pair], pair.shape[0])
        pair_of = {}
        for i, raw in enumerate(first.tolist()):
            ns_id, _obj, rel = interned.set_key_of(int(raw))
            pair_of[(ns_id, rel)] = i

        def raws_of(ns_id, rel):
            i = pair_of.get((ns_id, rel))
            return by_pair[:0] if i is None else by_pair[starts[i]: starts[i + 1]]

        for d, ((ns_id, rel), gate) in enumerate(defs):
            g_raw = raws_of(ns_id, rel)
            if not g_raw.size:
                continue
            g_obj = key_obj[g_raw]
            ops = np.full((g_raw.shape[0], k_max), -1, np.int32)
            for k, (name, _expr) in enumerate(gate.operands):
                o_raw = raws_of(ns_id, name)
                if not o_raw.size:
                    continue
                o_raw = o_raw[np.argsort(key_obj[o_raw], kind="stable")]
                o_obj = key_obj[o_raw]
                at = np.minimum(np.searchsorted(o_obj, g_obj), o_obj.shape[0] - 1)
                hit = o_obj[at] == g_obj
                ops[hit, k] = raw2dev[o_raw[at[hit]]]
            gate_row.append(raw2dev[g_raw].astype(np.int64))
            gate_def.append(np.full(g_raw.shape[0], d, np.int32))
            gate_ops.append(ops)
    gate_row = np.concatenate(gate_row)
    gate_def = np.concatenate(gate_def)
    gate_ops = np.concatenate(gate_ops)
    n_gates = gate_row.shape[0]
    def_k = np.asarray([len(g.operands) for _, g in defs] or [0], np.int64)

    # which gates a row reaches: their rows, pulled backwards along the
    # in-edges (a gate has no out-edges, so no path crosses one)
    rev_ptr, rev_idx = snap.rev_indptr, snap.rev_indices
    if rev_ptr is None:
        rev_ptr, rev_idx = build_rev_csr(
            np.asarray(snap.fwd_indptr), np.asarray(snap.fwd_indices), n
        )
    rev_ptr, rev_idx = np.asarray(rev_ptr), np.asarray(rev_idx)
    known = (gate_row << 32) | np.arange(n_gates, dtype=np.int64)
    known.sort()
    over = np.zeros(n, bool)  # reaches more gates than the cap
    f_rows, f_gates = gate_row, np.arange(n_gates, dtype=np.int64)
    while f_rows.size:
        preds, cnts = _csr_gather_host(rev_ptr, rev_idx, f_rows)
        preds = preds.astype(np.int64)
        key = np.unique((preds << 32) | np.repeat(f_gates, cnts))
        key = key[~over[key >> 32]]
        at = np.minimum(np.searchsorted(known, key), known.shape[0] - 1)
        key = key[known[at] != key]
        if not key.size:
            break
        known = np.union1d(known, key)
        per_row = np.bincount(known >> 32, minlength=n)
        over |= per_row > GATE_FANOUT_CAP
        key = key[~over[key >> 32]]
        f_rows, f_gates = key >> 32, key & 0xFFFFFFFF
    front = np.flatnonzero(over)
    while front.size:  # whatever reaches such a row reaches as many
        preds, _ = _csr_gather_host(rev_ptr, rev_idx, front)
        preds = np.unique(preds)
        front = preds[~over[preds]]
        over[front] = True
    known = known[~over[known >> 32]]
    k_rows = known >> 32
    row_gates = (known & 0xFFFFFFFF).astype(np.int32)
    row_ptr = _segments(k_rows, n)

    # a gate's nesting: the gates its operands reach
    slot_gate, slot_k = np.nonzero(gate_ops >= 0)
    slot_row = gate_ops[slot_gate, slot_k].astype(np.int64)
    gate_over = np.zeros(n_gates, bool)
    gate_over[slot_gate[over[slot_row]]] = True
    c_cnt = row_ptr[slot_row + 1] - row_ptr[slot_row]
    child, _ = _csr_gather_counts(row_ptr, row_gates, slot_row, c_cnt)
    parent = np.repeat(slot_gate, c_cnt)
    by_child = np.argsort(child, kind="stable")
    parent_by_child = parent[by_child].astype(np.int64)
    child_ptr = _segments(child[by_child], n_gates)
    front = np.flatnonzero(gate_over)
    while front.size:  # a gate above one past the cap is past it too
        above, _ = _csr_gather_host(child_ptr, parent_by_child, front)
        above = np.unique(above)
        front = above[~gate_over[above]]
        gate_over[front] = True
    waiting = np.bincount(parent, minlength=n_gates)
    gate_level = np.full(n_gates, -1, np.int64)
    gate_size = def_k[gate_def].astype(np.int64) if n_gates else np.zeros(0, np.int64)
    wave = np.flatnonzero((waiting == 0) & ~gate_over)
    gate_level[wave] = 0
    while wave.size:
        parents, cnts = _csr_gather_host(child_ptr, parent_by_child, wave)
        kids = np.repeat(wave, cnts)
        np.add.at(gate_size, parents, gate_size[kids])
        np.minimum(gate_size, GATE_POSITION_CAP + 1, out=gate_size)
        np.maximum.at(gate_level, parents, gate_level[kids] + 1)
        np.subtract.at(waiting, parents, 1)
        wave = np.unique(parents)
        wave = wave[(waiting[wave] == 0) & ~gate_over[wave]]
    gate_level[waiting > 0] = -1  # never settled: on a cycle, or above one
    gate_cyclic = (gate_level < 0) & ~gate_over

    # by row: served or not, and as how many positions
    row_mode = np.zeros(n, np.uint8)
    row_npos = np.ones(n, np.int32)
    row_mode[over] = FANOUT
    has = np.flatnonzero(np.diff(row_ptr))
    if has.size:
        seg = row_ptr[has]
        size = np.add.reduceat(gate_size[row_gates], seg)
        cyclic = np.add.reduceat(gate_cyclic[row_gates].astype(np.int64), seg) > 0
        past = np.add.reduceat(gate_over[row_gates].astype(np.int64), seg) > 0
        # a row that reaches anything but gates is asked itself too
        fwd_ptr, fwd_idx = np.asarray(snap.fwd_indptr), np.asarray(snap.fwd_indices)
        is_gate = np.zeros(n + 1, bool)
        is_gate[gate_row] = True
        nbrs, deg = _csr_gather_host(fwd_ptr, fwd_idx, has)
        to_gates = np.bincount(
            np.repeat(np.arange(has.shape[0]), deg), weights=is_gate[nbrs],
            minlength=has.shape[0],
        )
        keep_self = deg > to_gates
        npos = size + keep_self
        mode = np.where(keep_self, DEVICE | SELF, DEVICE).astype(np.uint8)
        mode[past | (npos > GATE_POSITION_CAP)] = FANOUT
        mode[cyclic] = CYCLE
        row_mode[has] = mode
        row_npos[has] = np.minimum(npos, GATE_POSITION_CAP + 1)
    row_mode[gate_row] |= GATE_ROW
    return GateTables(
        [(len(g.operands), g.program) for _, g in defs],
        row_ptr=row_ptr, row_gates=row_gates, row_mode=row_mode, row_npos=row_npos,
        gate_row=gate_row, gate_def=gate_def, gate_ops=gate_ops,
    )
