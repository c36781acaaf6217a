"""Userset rewrites (Zanzibar, USENIX ATC '19, section 2.3): the schema a
namespace carries under ``config.relations``, parsed and validated when the
namespaces load, and what the graph build needs of it.

The schema format, its semantics and its departures from the paper are
stated once, in the docstring of ``benchmarks/references/rewrites.py`` (the
plain reference a benchmark configuration names as its judge);
``docs/concepts/userset-rewrites.md`` repeats them for the operator. In one
line: ``{"<relation>": <expr>}`` with ``<expr>`` one of ``this``,
``computed_userset``, ``tuple_to_userset{tupleset, computed_userset}``,
``union``, ``intersection`` and ``exclusion{base, subtract}``; a relation
without an entry is ``this``, Keto v0.7's whole rule.

Two classes of relation follow from an expression:

- **union class**: ``this``, ``computed_userset``, ``tuple_to_userset`` and
  ``union`` only. Such a rewrite is a set of edges, each local to one stored
  row, and ``RowExpander`` / ``expand_delta`` compile it into the graph at
  the ingest seam: everything downstream of the interner sees a union-only
  graph and stays as it is.
- **gated class**: the expression holds an ``intersection`` or an
  ``exclusion``. The node of such a relation (a *gate*) has no out-edges:
  the expression is cut into its maximal union-only subtrees, each an
  *operand*, a hidden relation on the same object (``operand_name``) that
  compiles into edges as any union-class rewrite does, and a postfix boolean
  program over the operands (``GateDef``). A check whose closure reaches a
  gate is asked of the device once an operand and combined on the host
  (``keto_tpu/check/gates.py``); what that does not cover goes to the CPU
  oracle (``keto_tpu/check/engine.py``), counted by reason.
"""

from __future__ import annotations

import hashlib
import json
from typing import Iterable, Optional

import numpy as np

THIS = {"this": {}}

#: bits of ``RewritePlan.flags_of``
REWRITTEN = 1  # the closure of the node's (namespace, relation) holds a rewrite
GATED = 2  # ... holds an intersection or an exclusion: is a gate, or reaches one through edges
IS_GATE = 4  # the relation's own expression holds one: its node is a gate
HIDDEN = 8  # an operand or the anchor: a name of ours, which no query may ask

#: first character of the relation names the gate plan makes up (ASCII group
#: separator). In a namespace that has a gate such names are reserved: a
#: stored row that carries one is no edge (``RowExpander.reserved``), a query
#: that names one is denied
HIDDEN_MARK = "\x1d"
#: the relation of the node that keeps an object's gate nodes in the graph
#: whether or not a row names them: ``o#ANCHOR -> o#<gate>`` for every gate
#: of the namespace, stated with the object's ``computed_userset`` edges.
#: Nothing points at an anchor, so no check walks through one
ANCHOR = HIDDEN_MARK + "gates"

#: a gate's postfix program: operand indexes (>= 0) and these
AND, OR, AND_NOT = -1, -2, -3
#: part of a gated schema's fingerprint: a snapshot cache built under another
#: way of cutting gates is not loaded
GATE_PLAN_VERSION = 1


def operand_name(relation: str, k: int) -> str:
    """The hidden relation of operand ``k`` of the gate ``relation``."""
    return f"{HIDDEN_MARK}{relation}{HIDDEN_MARK}{k}"


def _union_only(expr) -> bool:
    return not any(next(iter(e)) in ("intersection", "exclusion") for e in _walk(expr))


class GateDef:
    """A gated relation cut at its operators: ``operands[k]`` is ``(hidden
    relation, union-only expression)``, ``program`` the postfix program over
    operand indexes that gives the relation's answer from theirs."""

    __slots__ = ("relation", "operands", "program")

    def __init__(self, relation: str, expr: dict):
        self.relation = relation
        self.operands: list[tuple[str, dict]] = []
        program: list[int] = []

        def operand(e):
            program.append(len(self.operands))
            self.operands.append((operand_name(relation, len(self.operands)), e))

        def cut(e):
            if _union_only(e):
                return operand(e)
            (op, arg), = e.items()
            if op == "exclusion":
                cut(arg["base"])
                cut(arg["subtract"])
                program.append(AND_NOT)
                return
            if op == "union":
                # the union-only children are one operand between them
                plain = [c for c in arg if _union_only(c)]
                parts = [c for c in arg if not _union_only(c)]
                if plain:
                    operand({"union": plain} if len(plain) > 1 else plain[0])
                for c in parts:
                    cut(c)
                program.extend([OR] * (bool(plain) + len(parts) - 1))
                return
            for c in arg:  # intersection
                cut(c)
            program.extend([AND] * (len(arg) - 1))

        cut(expr)
        self.program = tuple(program)


class SchemaError(ValueError):
    """A namespace's ``config.relations`` is malformed or has no agreed
    meaning. The message names the namespace and the relation."""


def _validated(expr, where: str):
    if not isinstance(expr, dict) or len(expr) != 1:
        raise SchemaError(
            f"{where}: an expression is an object with exactly one key, not {expr!r}"
        )
    (op, arg), = expr.items()
    ok = False
    if op == "this":
        ok = arg == {}
    elif op == "computed_userset":
        ok = isinstance(arg, str)
    elif op == "tuple_to_userset":
        ok = (
            isinstance(arg, dict)
            and set(arg) == {"tupleset", "computed_userset"}
            and all(isinstance(v, str) for v in arg.values())
        )
    elif op in ("union", "intersection"):
        ok = isinstance(arg, list) and bool(arg)
        if ok:
            for child in arg:
                _validated(child, where)
    elif op == "exclusion":
        ok = isinstance(arg, dict) and set(arg) == {"base", "subtract"}
        if ok:
            _validated(arg["base"], where)
            _validated(arg["subtract"], where)
    if not ok:
        raise SchemaError(f"{where}: not an expression of the rewrite language: {expr!r}")
    return expr


def _walk(expr):
    """Every sub-expression of ``expr``, itself included."""
    yield expr
    (op, arg), = expr.items()
    if op in ("union", "intersection"):
        for child in arg:
            yield from _walk(child)
    elif op == "exclusion":
        yield from _walk(arg["base"])
        yield from _walk(arg["subtract"])


class _NsPlan:
    """What one namespace's rewrites make of a stored row on it."""

    __slots__ = ("computed", "ttu", "no_this", "plain_tuplesets", "derivers",
                 "this_to", "gates")

    def __init__(self):
        #: ``[(r, r')]``: an edge ``o#r -> o#r'`` on every object of the namespace
        self.computed: list[tuple[str, str]] = []
        #: tupleset relation -> ``[(r, r')]``: a row ``o#t@ns2:o2#_`` is an
        #: edge ``o#r -> ns2:o2#r'``
        self.ttu: dict[str, list[tuple[str, str]]] = {}
        #: relations whose expression has no ``this``: a stored row is no edge
        self.no_this: set[str] = set()
        #: tupleset relations whose own expression is plain ``this``: their
        #: rows are their out-edges and nothing else is (``expand_delta``)
        self.plain_tuplesets: set[str] = set()
        #: ``(r, r') -> [tupleset | None]``: who derives an edge ``o#r ->
        #: _#r'`` (None: a ``computed_userset``, on the same object only). A
        #: stored row of that shape may coincide with a derived edge
        self.derivers: dict[tuple[str, str], list] = {}
        #: gated relation -> the operands whose expression has a ``this``: a
        #: stored row on the relation is an edge of each of them, never of
        #: the gate's own node
        self.this_to: dict[str, list[str]] = {}
        #: the namespace's gated relations: ``o#ANCHOR -> o#<gate>`` on every
        #: object (``RowExpander._first_mention``)
        self.gates: list[str] = []


class RewriteSchema:
    """The rewrites of every configured namespace, validated. Falsy when no
    namespace has one: callers then take the path they took before rewrites
    existed."""

    def __init__(self, namespaces: Iterable = ()):
        #: ``(namespace id, relation) -> expression``; plain ``this`` entries left out
        self.exprs: dict[tuple[int, str], dict] = {}
        self.ns_names: dict[int, str] = {}
        doc = {}
        for n in namespaces:
            self.ns_names[n.id] = n.name
            relations = (n.config or {}).get("relations")
            if relations is None:
                continue
            if not isinstance(relations, dict):
                raise SchemaError(
                    f"namespace {n.name!r}: config.relations is an object of "
                    f"relation -> expression, not {relations!r}"
                )
            kept = {}
            for rel, expr in relations.items():
                _validated(expr, f"{n.name}#{rel}")
                if expr != THIS:
                    self.exprs[(n.id, rel)] = kept[rel] = expr
            if kept:
                doc[str(n.id)] = kept
        #: ``(namespace id, gated relation) -> GateDef``, in a fixed order
        self.gates: dict[tuple[int, str], GateDef] = {
            key: GateDef(key[1], self.exprs[key])
            for key in sorted(self.exprs) if not _union_only(self.exprs[key])
        }
        if self.gates:
            # how a gate is cut is part of what a snapshot was built under
            doc["gates"] = GATE_PLAN_VERSION
        #: stable over key order and over entries that say ``this``; "" without rewrites
        self.fingerprint = (
            hashlib.sha256(json.dumps(doc, sort_keys=True).encode()).hexdigest()[:32]
            if doc else ""
        )
        #: the hidden relations: every gate's operands, and the anchor of a
        #: namespace that has a gate
        self.hidden: set[tuple[int, str]] = set()
        self.plans: dict[int, _NsPlan] = {}
        union_class = [(key, expr) for key, expr in self.exprs.items() if key not in self.gates]
        for (ns_id, rel), gate in self.gates.items():
            plan = self.plans.setdefault(ns_id, _NsPlan())
            plan.no_this.add(rel)  # the gate's own node has no out-edges
            plan.gates.append(rel)
            self.hidden.add((ns_id, ANCHOR))
            for name, expr in gate.operands:
                self.hidden.add((ns_id, name))
                union_class.append(((ns_id, name), expr))
                if any("this" in e for e in _walk(expr)):
                    plan.this_to.setdefault(rel, []).append(name)
        for (ns_id, rel), expr in union_class:
            plan = self.plans.setdefault(ns_id, _NsPlan())
            leaves = [next(iter(e.items())) for e in _walk(expr)]
            if not any(op == "this" for op, _ in leaves):
                plan.no_this.add(rel)
            for op, arg in leaves:
                if op == "computed_userset":
                    plan.computed.append((rel, arg))
                    plan.derivers.setdefault((rel, arg), []).append(None)
                elif op == "tuple_to_userset":
                    pair = (rel, arg["computed_userset"])
                    plan.ttu.setdefault(arg["tupleset"], []).append(pair)
                    plan.derivers.setdefault(pair, []).append(arg["tupleset"])
        for ns_id, plan in self.plans.items():
            plan.plain_tuplesets = {t for t in plan.ttu if (ns_id, t) not in self.exprs}
        # what the schema alone decides; the rows' part is checked at build
        self.refuse_cycles_through_subtract({})

    def __bool__(self) -> bool:
        return bool(self.exprs)

    def expr(self, ns_id: int, rel: str) -> dict:
        return self.exprs.get((ns_id, rel), THIS)

    def union_leaves(self, ns_id, rel: str) -> Optional[list]:
        """The ``(operator, argument)`` leaves of a union-class rewrite
        (``this``, ``computed_userset``, ``tuple_to_userset``), unions
        flattened; None for a relation that holds an intersection or an
        exclusion. What walks the schema edge by edge (List, witnesses)
        reads a relation through this."""
        if (ns_id, rel) in self.gates:
            return None
        return [
            next(iter(e.items())) for e in _walk(self.exprs.get((ns_id, rel), THIS))
            if "union" not in e
        ]

    def name(self, relation: tuple[int, str]) -> str:
        return f"{self.ns_names.get(relation[0], relation[0])}#{relation[1]}"

    # -- the relation graph --------------------------------------------------

    def edges(self, relation: tuple[int, str], targets: dict) -> list:
        """``[(relation reached, through a subtract)]`` from ``relation``:
        by its rewrite, and by the subject sets its rows hold (``targets``:
        ``(ns, rel) -> {(ns2, rel2)}``)."""
        ns_id, _ = relation
        out = []
        todo = [(self.exprs.get(relation, THIS), False)]
        while todo:
            expr, negated = todo.pop()
            (op, arg), = expr.items()
            if op == "this":
                out.extend((t, negated) for t in targets.get(relation, ()))
            elif op == "computed_userset":
                out.append(((ns_id, arg), negated))
            elif op == "tuple_to_userset":
                reached = {t[0] for t in targets.get((ns_id, arg["tupleset"]), ())}
                out.extend(((ns2, arg["computed_userset"]), negated) for ns2 in reached)
            elif op == "exclusion":
                todo.append((arg["base"], negated))
                todo.append((arg["subtract"], True))
            else:
                todo.extend((child, negated) for child in arg)
        return out

    def refuse_cycles_through_subtract(self, targets: dict) -> None:
        """Raise ``SchemaError`` if a relation reaches itself through the
        subtract side of an exclusion: the one schema the reference refuses."""
        memo: dict = {}

        def edges_of(relation):
            got = memo.get(relation)
            if got is None:
                got = memo[relation] = self.edges(relation, targets)
            return got

        for start in self.gates:
            for first, negated in edges_of(start):
                if not negated:
                    continue
                came_from, frontier = {first: None}, [first]
                while frontier and start not in came_from:
                    nxt = []
                    for relation in frontier:
                        for reached, _ in edges_of(relation):
                            if reached not in came_from:
                                came_from[reached] = relation
                                nxt.append(reached)
                    frontier = nxt
                if start in came_from:
                    back, at = [], start
                    while at is not None:
                        back.append(at)
                        at = came_from[at]
                    raise SchemaError(
                        f"relation {self.name(start)} reaches itself through the "
                        f"subtract side of its exclusion, which has no agreed meaning: "
                        + " -> ".join(self.name(r) for r in [start] + back[::-1])
                    )

    def closure_flags(self, targets: dict) -> dict[tuple[int, str], int]:
        """``relation -> REWRITTEN | GATED | IS_GATE | HIDDEN`` bits for
        every relation that has a rewrite, holds rows, or is reached from
        one that does."""
        nodes = set(self.exprs) | set(targets) | self.hidden
        fwd: dict = {}
        todo = list(nodes)
        while todo:
            relation = todo.pop()
            reached = fwd[relation] = [r for r, _ in self.edges(relation, targets)]
            for r in reached:
                if r not in nodes:
                    nodes.add(r)
                    todo.append(r)
        rev: dict = {}
        for relation, reached in fwd.items():
            for r in reached:
                rev.setdefault(r, []).append(relation)
        flags = dict.fromkeys(nodes, 0)
        for bit, seeds in ((REWRITTEN, list(self.exprs)), (GATED, list(self.gates))):
            stack = seeds
            for s in stack:
                flags[s] |= bit
            while stack:
                for r in rev.get(stack.pop(), ()):
                    if not flags[r] & bit:
                        flags[r] |= bit
                        stack.append(r)
        for relation in self.gates:
            flags[relation] |= IS_GATE
        for relation in self.hidden:
            flags[relation] |= HIDDEN
        return flags


EMPTY = RewriteSchema()


def schema_of(manager) -> RewriteSchema:
    """A namespace manager's rewrite schema. ``MemoryManager`` parses it
    once when it is built (a manager is immutable: a reload makes a new
    one); any other manager is parsed on first use and the schema kept on
    it where it lets us."""
    got = getattr(manager, "rewrites", None)
    if got is None:
        got = RewriteSchema(manager.namespaces())
        try:
            manager.rewrites = got
        except AttributeError:
            pass
    return got


def schema_for(namespaces, store=None):
    """``(namespace manager, its rewrite schema)`` for an engine built over
    ``store``: ``namespaces`` is a manager, or a zero-arg callable returning
    the current one (hot-reload safe); without it the store's own
    (``store.namespaces``) is asked. ``(None, EMPTY)`` where nobody can say:
    the engine then runs without rewrites."""
    src = namespaces if namespaces is not None else getattr(store, "namespaces", None)
    if src is None:
        return None, EMPTY
    nm = src() if callable(src) else src
    return nm, schema_of(nm)


def _virtual_row(ns_id, obj, rel, sns, sobj, srel, subject_id=None):
    from keto_tpu.persistence.memory import InternalRow

    return InternalRow(ns_id, obj, rel, subject_id, sns, sobj, srel, 0)


def _moved_row(r, rel: str):
    """The stored row ``r`` as a row on ``rel`` of the same object (a gate's
    ``this`` rows are edges of its operands)."""
    return _virtual_row(r.namespace_id, r.object, rel, r.sset_namespace_id,
                        r.sset_object, r.sset_relation, r.subject_id)


class RowExpander:
    """The bulk half of the ingest seam: stored rows in, the rows the
    interner should see out. A stored row is passed on where its relation's
    expression has a ``this``; the first row on an object brings that
    object's ``computed_userset`` edges; a tupleset row brings its
    ``tuple_to_userset`` edges. Derived edges are stated once (the interner
    would deduplicate them anyway; stating them once is what lets them be
    counted). A stored row on a gated relation is an edge of the operands
    whose expression has a ``this``; the first row on an object also brings
    the anchor's edges to the object's gate nodes, so that every object the
    store mentions has them."""

    def __init__(self, schema: RewriteSchema):
        self.schema = schema
        self._plans = schema.plans
        self._seen_objects: set = set()
        self._seen_tupleset_rows: set = set()
        #: ``(ns, rel, ns2, rel2)`` of subject-set rows that are no edge
        self.dropped: set = set()
        #: key7 of stored rows that are edges of a shape the schema derives
        #: too (``RewritePlan.stored_too``)
        self.stored_too: set = set()
        self.n_computed = 0
        self.n_ttu = 0
        #: stored rows under a reserved name (``HIDDEN_MARK``), passed over
        self.reserved = 0
        self.seconds = 0.0

    def expand(self, rows) -> list:
        import time

        t0 = time.monotonic()
        plans = self._plans
        seen_ts = self._seen_tupleset_rows
        out = []
        keep = out.append
        for r in rows:
            ns_id = r.namespace_id
            plan = plans.get(ns_id)
            if r.subject_id is None:
                # an object named only as a subject is mentioned too
                named = plans.get(r.sset_namespace_id)
                if named is not None and (named.computed or named.gates):
                    self._first_mention(named, r.sset_namespace_id, r.sset_object, keep)
            if plan is None:
                keep(r)
                continue
            obj, rel = r.object, r.relation
            if plan.gates and rel.startswith(HIDDEN_MARK):
                self.reserved += 1
                continue
            if rel not in plan.no_this:
                keep(r)
                if r.subject_id is None and (rel, r.sset_relation) in plan.derivers:
                    self.stored_too.add(r.key7())
            else:
                for name in plan.this_to.get(rel, ()):
                    keep(_moved_row(r, name))
                if r.subject_id is None:
                    # no edge of the relation's own node: the relation graph
                    # still reads it off the row
                    self.dropped.add((ns_id, rel, r.sset_namespace_id, r.sset_relation))
            if plan.computed or plan.gates:
                self._first_mention(plan, ns_id, obj, keep)
            through = plan.ttu.get(rel)
            if through and r.subject_id is None:
                key = (ns_id, obj, rel, r.sset_namespace_id, r.sset_object)
                if key not in seen_ts:
                    seen_ts.add(key)
                    for a, b in through:
                        keep(_virtual_row(ns_id, obj, a, r.sset_namespace_id, r.sset_object, b))
                    self.n_ttu += len(through)
        self.seconds += time.monotonic() - t0
        return out

    def _first_mention(self, plan: _NsPlan, ns_id: int, obj: str, keep) -> None:
        """The ``computed_userset`` edges of ``ns_id:obj`` and its anchor's,
        the first time the store mentions that object."""
        if (ns_id, obj) in self._seen_objects:
            return
        self._seen_objects.add((ns_id, obj))
        for a, b in plan.computed:
            keep(_virtual_row(ns_id, obj, a, ns_id, obj, b))
        for gate in plan.gates:
            keep(_virtual_row(ns_id, obj, ANCHOR, ns_id, obj, gate))
        self.n_computed += len(plan.computed)


def expand_delta(plan: "RewritePlan", base, ops: list) -> Optional[tuple]:
    """The overlay half of the seam: an ordered op list of stored rows
    (``("ins", row) | ("del", key7)``) as ``(the op list apply_delta should
    see, the plan after it)``, derived edges added and retired with the rows
    that state them. The graph holds an edge once, whoever states it: a
    stored row on a relation with ``this``, a ``computed_userset`` (which
    stays: it leads nowhere on an object without rows), or a
    ``tuple_to_userset`` by way of any tupleset row to the same object
    (rows that differ in the subject's relation alone state the same edge).
    An edge is retired only when the last of them goes: the stored rows
    that coincide with a derivation are kept by key in
    ``RewritePlan.stored_too``, the tupleset rows are read off the
    snapshot. None where that cannot be told from the snapshot (a tupleset
    relation with a rewrite of its own): the caller rebuilds."""
    plans = plan.schema.plans
    net: dict = {}
    for kind, payload in ops:
        key = payload if kind == "del" else payload.key7()
        net[key] = (kind, payload)
    inserted_ts = {
        k[:3] + k[4:6] for k, (kind, _) in net.items()
        if kind == "ins" and k[3] is None
    }
    deleted = {k for k, (kind, _) in net.items() if kind == "del"}
    moved = set()
    for key, (kind, _) in net.items():
        p = plans.get(key[0]) if key[3] is None else None
        if (p is not None and (key[2], key[6]) in p.derivers and key[2] not in p.no_this
                and (kind == "ins") != (key in plan.stored_too)):
            moved.add(key)
    if moved:
        plan = plan.with_stored_too(plan.stored_too ^ moved)
    stored_too = plan.stored_too

    def derived(ns_id, obj, a, sns, sobj, b) -> Optional[bool]:
        """Does the schema derive ``ns_id:obj#a -> sns:sobj#b`` once this
        delta is in; None where the snapshot cannot say."""
        p, unknown = plans[ns_id], False
        for t in p.derivers.get((a, b), ()):
            if t is None:
                if (sns, sobj) == (ns_id, obj):
                    return True
            elif (ns_id, obj, t, sns, sobj) in inserted_ts:
                return True
            elif t not in p.plain_tuplesets:
                unknown = True
            elif _tupleset_row_stays(base, deleted, ns_id, obj, t, sns, sobj):
                return True
        return None if unknown else False

    out: list = []
    for key, (kind, payload) in net.items():
        ns_id, obj, rel, sid, sns, sobj, srel = key
        p = plans.get(ns_id)
        named = plans.get(sns) if sid is None and kind == "ins" else None
        if named is not None:
            for a, b in named.computed:
                out.append(("ins", _virtual_row(sns, sobj, a, sns, sobj, b)))
        if p is None:
            out.append((kind, payload))
            continue
        if p.gates and rel.startswith(HIDDEN_MARK):
            continue  # a reserved name: no edge (``RowExpander.reserved``)
        for name in p.this_to.get(rel, ()):
            # a gate's stored row is an edge of its operands
            out.append(("ins", _moved_row(payload, name)) if kind == "ins"
                       else ("del", (ns_id, obj, name) + key[3:]))
        if rel not in p.no_this:
            stays = False
            if kind == "del" and sid is None and (rel, srel) in p.derivers:
                # the row goes, the edge stays while the schema derives it
                stays = derived(ns_id, obj, rel, sns, sobj, srel)
                if stays is None:
                    return None
            if not stays:
                out.append((kind, payload))
        through = p.ttu.get(rel) if sid is None else None
        if kind == "ins":
            for a, b in p.computed:
                out.append(("ins", _virtual_row(ns_id, obj, a, ns_id, obj, b)))
            for a, b in through or ():
                out.append(("ins", _virtual_row(ns_id, obj, a, sns, sobj, b)))
            continue
        for a, b in through or ():
            edge = (ns_id, obj, a, None, sns, sobj, b)
            if edge in stored_too:
                continue  # a stored row states it too
            stays = derived(ns_id, obj, a, sns, sobj, b)
            if stays is None:
                return None
            if not stays:
                out.append(("del", edge))
    if plan.has_gated and not plan.gates_stale and _stales_gates(plan, base, out):
        plan = plan.replace(gates_stale=True)
    return out, plan


def _stales_gates(plan: "RewritePlan", base, ops: list) -> bool:
    """Does this delta move what the base rows' gate tables
    (``keto_tpu/check/gates.py``) were worked out from: an edge into a
    closure that holds a gate comes or goes (the gates a row reaches), or an
    operand gains its first edge (the tables have no row for it). Until the
    next build or fold the oracle then answers the gated checks."""
    flags = plan.flags
    for kind, payload in ops:
        key = payload if kind == "del" else payload.key7()
        ns_id, obj, rel, sid, sns, _sobj, srel = key
        if sid is None and flags.get((sns, srel), 0) & GATED:
            # an insert that states an edge the base rows hold moves nothing
            # (every insert brings its object's computed edges again)
            if kind == "del" or not _base_edge(base, key):
                return True
        if kind == "ins" and rel.startswith(HIDDEN_MARK) and flags.get((ns_id, rel), 0) & HIDDEN:
            dev = base.resolve_set(ns_id, obj, rel)
            if dev is None or dev >= base.n_base_nodes:
                return True
    return False


def _base_edge(base, key: tuple) -> bool:
    """Do ``base``'s own rows (its overlay aside) hold the set -> set edge
    that ``key`` states."""
    ns_id, obj, rel, _sid, sns, sobj, srel = key
    src, dst = base.resolve_set(ns_id, obj, rel), base.resolve_set(sns, sobj, srel)
    nb = base.n_base_nodes
    if src is None or dst is None or src >= nb or dst >= nb:
        return False
    nbrs, _ = base.out_neighbors_bulk(np.asarray([src], np.int64), overlay=False)
    return bool((np.asarray(nbrs) == dst).any())


def _tupleset_row_stays(base, deleted: set, ns_id, obj, t, sns, sobj) -> bool:
    """Does ``base`` hold a row ``ns_id:obj#t@sns:sobj#_`` that this delta
    leaves alone (``t`` a plain tupleset: its rows are its out-edges)."""
    dev = base.resolve_set(ns_id, obj, t)
    if dev is None:
        return False
    nbrs, _ = base.out_neighbors_bulk(np.asarray([dev], np.int64), overlay=False)
    extra = (base.ov_fwd or {}).get(dev, ())
    for d in list(np.asarray(nbrs).tolist()) + list(extra):
        kind, k = base.key_of_dev(int(d))
        if (kind == "set" and (k[0], k[1]) == (sns, sobj)
                and (ns_id, obj, t, None, k[0], k[1], k[2]) not in deleted):
            return True
    return False


class RewritePlan:
    """What a snapshot keeps of the schema it was built under: the schema
    (its fingerprint is a build input like ``wild_ns_ids``), the closure
    bits of every ``(namespace, relation)`` the graph holds, and how many
    edges the expansion derived. ``flags_of`` turns the bits into one byte
    a device row, once a snapshot."""

    def __init__(self, schema: RewriteSchema, flags: dict, n_computed: int, n_ttu: int,
                 targets: Optional[dict] = None, stored_too: Iterable[tuple] = (),
                 gates_stale: bool = False):
        self.schema = schema
        self.fingerprint = schema.fingerprint
        #: ``(ns id, relation) -> REWRITTEN | GATED | IS_GATE | HIDDEN``
        self.flags = flags
        self.n_computed = int(n_computed)
        self.n_ttu = int(n_ttu)
        self.has_gated = any(f & GATED for f in flags.values())
        #: ``(ns, rel) -> {(ns2, rel2)}`` the closure was computed from
        self.targets = targets or {}
        #: key7 of the stored rows that are edges of a shape the schema
        #: derives too (``_NsPlan.derivers``): while such a row stands, the
        #: edge outlives the tupleset row that derived it (``expand_delta``)
        self.stored_too = frozenset(stored_too)
        #: an overlay delta moved what the snapshot's gate tables were
        #: worked out from (``_stales_gates``): the oracle answers the gated
        #: checks until a build or a fold makes tables anew
        self.gates_stale = bool(gates_stale)

    def replace(self, **changes) -> "RewritePlan":
        """This plan with some of its parts exchanged. The closure bits stay
        the same object unless given, so a snapshot's ``flags_of`` may be
        carried over."""
        kw = dict(flags=self.flags, n_computed=self.n_computed, n_ttu=self.n_ttu,
                  targets=self.targets, stored_too=self.stored_too,
                  gates_stale=self.gates_stale)
        kw.update(changes)
        return RewritePlan(self.schema, **kw)

    def settled(self) -> "RewritePlan":
        """The plan of a snapshot whose overlay has been folded into its
        base rows: itself, or itself without ``gates_stale``."""
        return self.replace(gates_stale=False) if self.gates_stale else self

    def to_meta(self) -> dict:
        return {
            "fingerprint": self.fingerprint,
            "flags": [[ns, rel, f] for (ns, rel), f in sorted(self.flags.items())],
            "targets": [[a, b, c, d] for (a, b), ts in sorted(self.targets.items())
                        for c, d in sorted(ts)],
            "n_computed": self.n_computed,
            "n_ttu": self.n_ttu,
            "stored_too": sorted(map(list, self.stored_too)),
        }

    @classmethod
    def from_meta(cls, meta: dict, schema: RewriteSchema) -> "RewritePlan":
        targets: dict = {}
        for a, b, c, d in meta.get("targets", ()):
            targets.setdefault((int(a), b), set()).add((int(c), d))
        return cls(
            schema, {(int(ns), rel): int(f) for ns, rel, f in meta["flags"]},
            meta.get("n_computed", 0), meta.get("n_ttu", 0), targets,
            map(tuple, meta.get("stored_too", ())),
        )

    def relation_flags(self, ns_id: int, rel: str) -> int:
        """The bits of a query's ``(namespace, relation)``, whether or not
        the graph holds a node of it (every relation with a rewrite has an
        entry; a relation without one and without rows has no closure)."""
        return self.flags.get((ns_id, rel), 0)

    def with_pairs(self, pairs: Iterable[tuple]) -> "RewritePlan":
        """This plan after rows have brought the subject-set pairs
        ``(ns, rel, ns2, rel2)``: itself when none is new, else a new plan
        (a new pair can put a relation's closure into another class).
        Raises ``SchemaError`` when the rows close a cycle through a subtract."""
        fresh = [p for p in pairs if (p[2], p[3]) not in self.targets.get((p[0], p[1]), ())]
        if not fresh:
            return self
        targets = {k: set(v) for k, v in self.targets.items()}
        for a, b, c, d in fresh:
            targets.setdefault((a, b), set()).add((c, d))
        self.schema.refuse_cycles_through_subtract(targets)
        return self.replace(flags=self.schema.closure_flags(targets), targets=targets)

    def with_stored_too(self, stored_too: Iterable[tuple]) -> "RewritePlan":
        """This plan with another set of coinciding stored rows."""
        return self.replace(stored_too=stored_too)

    def flags_of(self, snap) -> np.ndarray:
        """``uint8[n_base_nodes]`` by device row: the closure bits of the
        row's ``(namespace, relation)``; 0 for a subject id."""
        cached = snap.__dict__.get("_rewrite_flags")
        if cached is not None and cached[0] is self.flags:
            return cached[1]
        interned = snap.interned
        out = np.zeros(snap.n_base_nodes, np.uint8)
        n_sets = int(snap.num_sets)
        if n_sets:
            key_ns = np.asarray(interned.key_ns, np.int64)[:n_sets]
            key_rel = np.asarray(interned.key_rel, np.int64)[:n_sets]
            n_rel = int(key_rel.max()) + 1
            pair, first, inv = np.unique(
                key_ns * n_rel + key_rel, return_index=True, return_inverse=True
            )
            by_pair = np.zeros(pair.shape[0], np.uint8)
            for i, raw in enumerate(first.tolist()):
                ns_id, _obj, rel = interned.set_key_of(int(raw))
                by_pair[i] = self.flags.get((ns_id, rel), 0)
            out[np.asarray(snap.raw2dev[:n_sets])] = by_pair[inv]
        snap.__dict__["_rewrite_flags"] = (self.flags, out)
        return out


def targets_of_graph(g, dropped: Iterable[tuple] = ()) -> dict:
    """``(ns, rel) -> {(ns2, rel2)}`` over the set -> set edges of an
    interned graph (derived edges included: they are the relation graph's
    own) and the ``dropped`` subject-set rows that are no edge."""
    targets: dict = {}
    n_sets = int(g.num_sets)
    src, dst = np.asarray(g.src), np.asarray(g.dst)
    m = dst < n_sets
    if n_sets and m.any():
        key_ns = np.asarray(g.key_ns, np.int64)
        key_rel = np.asarray(g.key_rel, np.int64)
        n_rel = int(key_rel.max()) + 1
        pair, first, inv = np.unique(
            key_ns * n_rel + key_rel, return_index=True, return_inverse=True
        )
        edge = np.unique(inv[src[m]] * pair.shape[0] + inv[dst[m]])
        names = {}

        def name(i):
            got = names.get(i)
            if got is None:
                ns_id, _obj, rel = g.set_key_of(int(first[i]))
                got = names[i] = (ns_id, rel)
            return got

        for e in edge.tolist():
            a, b = divmod(e, pair.shape[0])
            targets.setdefault(name(a), set()).add(name(b))
    for a, b, c, d in dropped:
        targets.setdefault((a, b), set()).add((c, d))
    return targets


def plan_for(schema: RewriteSchema, g, expander: Optional[RowExpander]) -> Optional[RewritePlan]:
    """The plan of a graph built under ``schema`` (None without rewrites).
    Raises ``SchemaError`` when the rows close a cycle through a subtract."""
    if not schema:
        return None
    targets = targets_of_graph(g, expander.dropped if expander is not None else ())
    schema.refuse_cycles_through_subtract(targets)
    return RewritePlan(
        schema, schema.closure_flags(targets),
        expander.n_computed if expander is not None else 0,
        expander.n_ttu if expander is not None else 0,
        targets,
        expander.stored_too if expander is not None else (),
    )
