"""A plain reference of other semantics than ``benchmarks/reference.py``:
Check under the userset rewrites of Zanzibar (USENIX ATC '19) section 2.3,
over the generated rows and the schema in the configuration's file. It
imports nothing of the program; a configuration takes it as its judge with
``"reference": "rewrites"``.

**Schema.** An entry of the configuration's ``namespaces`` may carry
``"config": {"relations": {"<relation>": <expr>}}``. The expression language
is the paper's, as JSON, each expression an object with exactly one key::

    {"this": {}}
    {"computed_userset": "<relation>"}
    {"tuple_to_userset": {"tupleset": "<relation>", "computed_userset": "<relation>"}}
    {"union": [<expr>, ...]}   {"intersection": [<expr>, ...]}
    {"exclusion": {"base": <expr>, "subtract": <expr>}}

A relation with no entry is ``{"this": {}}``, so on a schema without
rewrites every answer is ``benchmarks/reference.py``'s.

**Semantics.** A node ``n = ns:o#r`` is an object and a relation, ``u`` a
subject id. ``ids(n)`` are the subject ids and ``sets(n)`` the subject sets
``ns2:o2#r2`` of the stored rows on ``n``. ``ALLOW`` is the least solution of::

    ALLOW(ns:o#r, u)                         = EVAL(expr(ns, r), ns:o#r, u)
    EVAL(this, n, u)                         = u in ids(n)  or  ALLOW(s, u) for some s in sets(n)
    EVAL(computed_userset r', ns:o#r, u)     = ALLOW(ns:o#r', u)
    EVAL(tuple_to_userset(t, r'), ns:o#r, u) = ALLOW(ns2:o2#r', u) for some ns2:o2#_ in sets(ns:o#t)
    EVAL(union [e, ...], n, u)               = EVAL(e, n, u) for some e
    EVAL(intersection [e, ...], n, u)        = EVAL(e, n, u) for every e
    EVAL(exclusion(b, s), n, u)              = EVAL(b, n, u) and not EVAL(s, n, u)

``this`` is Keto v0.7's whole rule: a direct grant, or a subject set that
(recursively) allows. The rows of a tupleset are read as stored, never through
the tupleset relation's own rewrite; the subject of such a row is read as an
object and its relation is ignored, as the paper's ``...``.

**Evaluation** is a recursive descent that keeps the nodes of the current
path: a node met again on its own path contributes nothing, so membership
cycles terminate, and on them the answer is the least solution, as a visited
set gives it in ``reference.py``. Within one query a node's answer is kept
once it is final, which a denial is only when no cut beneath it fell on a
node above it; graphs of nested groups are full of diamonds, and a descent
that forgot would walk every path. ``max_depth`` (None = unbounded) is the
control's knob (``benchmarks/control.py``): the queried node is at level 1,
every step to another node (a subject set, a computed userset, the object
behind a tupleset row) is one level more, and a node beyond ``max_depth``
contributes nothing, so a direct grant needs 1, as in ``reference.py``.

**Departures from the paper**, one sentence each. A subject is Keto's opaque
subject id, not ``user:<id>``, and Check asks about a subject id only, never
about a userset. ``computed_userset`` names a relation and nothing else: on
the same object when it stands alone, on the tupleset row's object inside
``tuple_to_userset`` (the paper's ``$TUPLE_USERSET_OBJECT``, the only use it
shows). A tupleset row whose subject is a plain id names no object and
contributes nothing. The paper is silent on cycles in the data; here they
contribute nothing, as above. A schema in which a relation reaches itself
through the ``subtract`` side of an exclusion (by its rewrites and by the
subject sets its rows hold) has no agreed meaning, and the reference refuses
it when it is built, with the cycle in the message. ``max_depth`` is the
control's, not Zanzibar's.
"""

from __future__ import annotations

THIS = {"this": {}}
_FREE = float("inf")  # "leaned on no node of the path"


class SchemaError(ValueError):
    """The configuration's rewrite schema is malformed or has no meaning."""


def _validated(expr, where: str):
    """``expr`` if it is an expression of the language above."""
    if not isinstance(expr, dict) or len(expr) != 1:
        raise SchemaError(f"{where}: an expression is an object with exactly one key, "
                          f"not {expr!r}")
    (op, arg), = expr.items()
    if op == "this" and arg == {}:
        return expr
    if op == "computed_userset" and isinstance(arg, str):
        return expr
    if (op == "tuple_to_userset" and isinstance(arg, dict)
            and set(arg) == {"tupleset", "computed_userset"}
            and all(isinstance(v, str) for v in arg.values())):
        return expr
    if op in ("union", "intersection") and isinstance(arg, list) and arg:
        for child in arg:
            _validated(child, where)
        return expr
    if op == "exclusion" and isinstance(arg, dict) and set(arg) == {"base", "subtract"}:
        _validated(arg["base"], where)
        _validated(arg["subtract"], where)
        return expr
    raise SchemaError(f"{where}: not an expression of the rewrite language: {expr!r}")


class Reference:
    def __init__(self, rows, config: dict):
        ids, sets = {}, {}
        targets = {}  # (ns, rel) -> the (ns2, rel2) of the subject sets its rows hold
        for ns, obj, rel, sid, sns, sobj, srel in rows:
            key = (ns, obj, rel)
            if sid is not None:
                ids.setdefault(key, set()).add(sid)
            else:
                sets.setdefault(key, []).append((sns, sobj, srel))
                targets.setdefault((ns, rel), set()).add((sns, srel))
        self._ids, self._sets = ids, sets
        self._exprs = {}
        for entry in config.get("namespaces", ()):
            relations = (entry.get("config") or {}).get("relations") or {}
            for rel, expr in relations.items():
                self._exprs[(entry["name"], rel)] = _validated(expr, f"{entry['name']}#{rel}")
        self._refuse_cycles_through_subtract(targets)

    # -- the schema's one refusal ------------------------------------------------

    def _edges(self, relation, targets):
        """``[(relation reached, through a subtract)]`` from ``relation = (ns,
        rel)``: by its rewrite, and by the subject sets the rows hold."""
        ns, _ = relation
        out = []

        def walk(expr, negated):
            (op, arg), = expr.items()
            if op == "this":
                out.extend((t, negated) for t in targets.get(relation, ()))
            elif op == "computed_userset":
                out.append(((ns, arg), negated))
            elif op == "tuple_to_userset":
                out.extend(((ns2, arg["computed_userset"]), negated)
                           for ns2 in {t[0] for t in targets.get((ns, arg["tupleset"]), ())})
            elif op == "exclusion":
                walk(arg["base"], negated)
                walk(arg["subtract"], True)
            else:
                for child in arg:
                    walk(child, negated)

        walk(self._exprs.get(relation, THIS), False)
        return out

    def _refuse_cycles_through_subtract(self, targets) -> None:
        edges = {}

        def edges_of(relation):
            if relation not in edges:
                edges[relation] = self._edges(relation, targets)
            return edges[relation]

        for start in self._exprs:
            for first, negated in edges_of(start):
                if not negated:
                    continue
                # does ``first`` lead back to ``start``?
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
                        "a relation reaches itself through the subtract side of an exclusion, "
                        "which has no agreed meaning: "
                        + " -> ".join(f"{ns}#{rel}" for ns, rel in [start] + back[::-1])
                        + f" (the first step is through the subtract of {start[0]}#{start[1]})")

    # -- Check -------------------------------------------------------------------

    def allowed(self, ns, obj, rel, user, max_depth=None) -> bool:
        path = {}  # node -> its level, for the nodes of the current path
        final = {}  # this query's answers that no longer depend on the path

        def node(n, level):
            """``(ALLOW(n, user), the shallowest level of the path that a
            denial leaned on)``; ``_FREE`` when the answer is final."""
            if max_depth is not None and level > max_depth:
                return False, _FREE
            if n in path:
                return False, path[n]
            # within a depth limit an answer holds for the level it was found at
            key = n if max_depth is None else (n, level)
            if key in final:
                return final[key], _FREE
            path[n] = level
            value, leaned = ev(self._exprs.get((n[0], n[2]), THIS), n, level)
            del path[n]
            if value or leaned >= level:
                final[key] = value
                return value, _FREE
            return False, leaned

        def some(results):
            leaned = _FREE
            for value, lean in results:
                if value:
                    return True, _FREE
                leaned = min(leaned, lean)
            return False, leaned

        def ev(expr, n, level):
            (op, arg), = expr.items()
            if op == "this":
                if user in self._ids.get(n, ()):
                    return True, _FREE
                return some(node(s, level + 1) for s in self._sets.get(n, ()))
            if op == "computed_userset":
                return node((n[0], n[1], arg), level + 1)
            if op == "tuple_to_userset":
                return some(node((ns2, obj2, arg["computed_userset"]), level + 1)
                            for ns2, obj2, _ in self._sets.get((n[0], n[1], arg["tupleset"]), ()))
            if op == "union":
                return some(ev(e, n, level) for e in arg)
            if op == "intersection":
                for e in arg:
                    value, leaned = ev(e, n, level)
                    if not value:
                        return False, leaned
                return True, _FREE
            # exclusion. What the subtract side says is final: a cut beneath it
            # on a node above it would be a cycle through a subtract, and the
            # schema that has one was refused when the reference was built.
            value, leaned = ev(arg["base"], n, level)
            if not value:
                return False, leaned
            return not ev(arg["subtract"], n, level)[0], _FREE

        return node((ns, obj, rel), 1)[0]
