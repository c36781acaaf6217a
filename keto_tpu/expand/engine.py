"""Expand engine: materialize the subject-set tree.

Faithful to reference internal/expand/engine.go:30-98: depth-limited
recursion with the shared visited-set cycle guard, page loop per node,
``rest_depth <= 1`` truncates a set node to a leaf, and a SubjectID is always
a leaf. Returns ``None`` for depth ≤ 0, cycles, and empty sets — exactly the
reference's nil-tree cases.

Under userset rewrites (keto_tpu/namespace/rewrites.py) a subject set whose
relation has one expands by its expression: ``this`` to the stored rows as
above, a ``computed_userset`` to a child subject set on the same object, a
``tuple_to_userset`` to one child subject set per tupleset row, and
``union`` / ``intersection`` / ``exclusion`` to a node of that type (the
types ``tree.py`` has reserved since the seed) over its operands; an
exclusion's children are its base and its subtract, in that order. A union
directly under a union is spliced into it. A relation without an entry
expands exactly as before.
"""

from __future__ import annotations

from typing import Optional

from keto_tpu.expand.tree import EXCLUSION, INTERSECTION, LEAF, UNION, Tree
from keto_tpu.namespace.rewrites import THIS, schema_for
from keto_tpu.relationtuple.manager import Manager
from keto_tpu.relationtuple.model import RelationQuery, Subject, SubjectSet
from keto_tpu.x.errors import ErrNamespaceUnknown
from keto_tpu.x.graph import check_and_add_visited
from keto_tpu.x.pagination import with_size, with_token


class ExpandEngine:
    def __init__(self, manager: Manager, page_size: int = 0, namespaces=None):
        """``namespaces``: where the rewrite schema is read, as for
        ``CheckEngine``; without it the store's own."""
        self._manager = manager
        self._page_size = page_size
        self._namespaces = namespaces

    def _expr_of(self, subject: SubjectSet) -> dict:
        nm, schema = schema_for(self._namespaces, self._manager)
        if not schema:
            return THIS
        try:
            ns_id = nm.get_namespace_by_name(subject.namespace).id
        except ErrNamespaceUnknown:
            return THIS
        return schema.expr(ns_id, subject.relation)

    def build_tree(self, subject: Subject, rest_depth: int) -> Optional[Tree]:
        return self._build_tree(subject, rest_depth, visited=set())

    def _rewritten(
        self, expr: dict, subject: SubjectSet, rest_depth: int, visited: set[str],
        operand: bool = False,
    ) -> Optional[Tree]:
        """The tree of ``subject`` under the rewrite ``expr`` (the caller
        has marked ``subject`` visited and ``rest_depth`` is above 1). As an
        ``operand`` of a union, an intersection or an exclusion a computed
        userset is its child subject set itself, not a node around it."""
        (op, arg), = expr.items()
        if op == "this":
            return self._stored(subject, rest_depth, visited)
        if op == "computed_userset":
            child = self._child(
                SubjectSet(subject.namespace, subject.object, arg), rest_depth, visited)
            return child if operand else Tree(type=UNION, subject=subject, children=[child])
        if op == "tuple_to_userset":
            node = Tree(type=UNION, subject=subject)
            tupleset = SubjectSet(subject.namespace, subject.object, arg["tupleset"])
            for rels in self._pages(tupleset):
                for r in rels:
                    s = r.subject
                    if isinstance(s, SubjectSet):
                        child = SubjectSet(s.namespace, s.object, arg["computed_userset"])
                        node.children.append(self._child(child, rest_depth, visited))
            return node if node.children else None
        if op == "exclusion":
            parts = [arg["base"], arg["subtract"]]
        else:
            parts = arg
        node = Tree(type={"union": UNION, "intersection": INTERSECTION}.get(op, EXCLUSION),
                    subject=subject)
        for e in parts:
            sub = self._rewritten(e, subject, rest_depth, visited, operand=True)
            if sub is None:
                if op == "union":
                    continue
                # an operand nobody holds still has its place in the node
                sub = Tree(type=UNION, subject=subject)
            if op == "union" and sub.type == UNION and sub.subject == subject:
                node.children.extend(sub.children)
            else:
                node.children.append(sub)
        return node if node.children else None

    def _child(self, subject: Subject, rest_depth: int, visited: set[str]) -> Tree:
        return self._build_tree(subject, rest_depth - 1, visited) or Tree(
            type=LEAF, subject=subject)

    def _pages(self, subject: SubjectSet):
        next_page = ""
        while True:
            opts = [with_token(next_page)]
            if self._page_size:
                opts.append(with_size(self._page_size))
            rels, next_page = self._manager.get_relation_tuples(
                RelationQuery(
                    namespace=subject.namespace, object=subject.object, relation=subject.relation
                ),
                *opts,
            )
            yield rels
            if next_page == "":
                return

    def _stored(self, subject: SubjectSet, rest_depth: int, visited: set[str]) -> Optional[Tree]:
        """``this``: a union over the stored rows, None when there are none."""
        node = Tree(type=UNION, subject=subject)
        for rels in self._pages(subject):
            for r in rels:
                node.children.append(self._child(r.subject, rest_depth, visited))
        return node if node.children else None

    def _build_tree(self, subject: Subject, rest_depth: int, visited: set[str]) -> Optional[Tree]:
        if rest_depth <= 0:
            return None

        if not isinstance(subject, SubjectSet):
            return Tree(type=LEAF, subject=subject)

        if check_and_add_visited(visited, subject):
            return None

        expr = self._expr_of(subject)
        if expr is not THIS:
            if rest_depth <= 1:
                return Tree(type=LEAF, subject=subject)
            return self._rewritten(expr, subject, rest_depth, visited)

        sub_tree = Tree(type=UNION, subject=subject)
        next_page = ""
        while True:
            opts = [with_token(next_page)]
            if self._page_size:
                opts.append(with_size(self._page_size))
            rels, next_page = self._manager.get_relation_tuples(
                RelationQuery(
                    namespace=subject.namespace, object=subject.object, relation=subject.relation
                ),
                *opts,
            )
            if not rels:
                return None

            if rest_depth <= 1:
                sub_tree.type = LEAF
                return sub_tree

            for r in rels:
                child = self._build_tree(r.subject, rest_depth - 1, visited)
                if child is None:
                    child = Tree(type=LEAF, subject=r.subject)
                sub_tree.children.append(child)

            if next_page == "":
                return sub_tree
