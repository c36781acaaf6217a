"""Oracle check engine: recursive subject-set expansion on the host.

This is a faithful re-implementation of the reference's check engine
(reference internal/check/engine.go:33-95): depth-first search over
subject-set indirections with early exit on match, a shared visited-set cycle
guard, page-at-a-time reads through the Manager contract, and
unknown-namespace → allowed=false (engine.go:76-77).

Its role here is twofold: it is the *differential-testing oracle* the TPU
engine (keto_tpu/check/tpu_engine.py) must agree with bit-for-bit, and the
fallback engine for stores/queries the device snapshot cannot serve.

Where a namespace carries userset rewrites (``config.relations``,
keto_tpu/namespace/rewrites.py) the engine evaluates the equations of
``benchmarks/references/rewrites.py``'s docstring over the same Manager
contract: all six operators, the tupleset's rows read as stored, a node met
again on its own path contributing nothing (the least solution). Without a
rewrite anywhere it runs the reference's code above, line for line.
"""

from __future__ import annotations

from typing import Optional

from keto_tpu.namespace.rewrites import schema_for
from keto_tpu.relationtuple.manager import Manager
from keto_tpu.relationtuple.model import RelationQuery, RelationTuple, SubjectSet
from keto_tpu.x.errors import ErrNamespaceUnknown, ErrNotFound
from keto_tpu.x.graph import check_and_add_visited
from keto_tpu.x.pagination import with_size, with_token

_FREE = float("inf")  # "this denial leaned on no node of the path"


class CheckEngine:
    def __init__(self, manager: Manager, page_size: int = 0, namespaces=None):
        """``namespaces`` (a namespace.Manager, or a zero-arg callable
        returning the current one) is where the rewrite schema is read;
        without it the store's own (``manager.namespaces``) is asked, and a
        store that has none runs without rewrites."""
        self._manager = manager
        # page_size=0 keeps the store default (100); tests inject smaller
        # sizes to assert pagination behavior.
        self._page_size = page_size
        self._namespaces = namespaces


    def set_store(self, manager: Manager) -> None:
        """Fleet promotion handoff: swap the backing store (same tuple
        history at or past the old watermark; the recursive engine reads
        live state, so nothing else needs invalidating)."""
        self._manager = manager

    def subject_is_allowed(self, requested: RelationTuple) -> bool:
        """Can ``requested.subject`` be reached from
        ``requested.object#requested.relation``? Reference engine.go:93-95."""
        nm, schema = schema_for(self._namespaces, self._manager)
        if schema:
            return _RewriteCheck(self, nm, schema, requested).allowed()
        return self._check_one_indirection_further(
            requested,
            RelationQuery(
                namespace=requested.namespace,
                object=requested.object,
                relation=requested.relation,
            ),
            visited=set(),
        )

    def _pages(self, query: RelationQuery):
        """The stored rows matching ``query``, a page at a time; nothing for
        an unknown namespace (engine.go:76-77: denied, not an error)."""
        page = ""
        while True:
            opts = [with_token(page)]
            if self._page_size:
                opts.append(with_size(self._page_size))
            try:
                rows, page = self._manager.get_relation_tuples(query, *opts)
            except ErrNotFound:
                return
            yield rows
            if page == "":
                return

    def _check_one_indirection_further(
        self, requested: RelationTuple, expand_query: RelationQuery, visited: set[str]
    ) -> bool:
        """Page loop over one subject-set expansion. Reference engine.go:69-91."""
        prev_page = ""
        while True:
            opts = [with_token(prev_page)]
            if self._page_size:
                opts.append(with_size(self._page_size))
            try:
                next_rels, next_page = self._manager.get_relation_tuples(expand_query, *opts)
            except ErrNotFound:
                # unknown namespace → denied, not an error (engine.go:76-77)
                return False

            allowed = self._subject_is_allowed(requested, next_rels, visited)
            if allowed or next_page == "":
                return allowed
            prev_page = next_page

    def _subject_is_allowed(
        self, requested: RelationTuple, rels: list[RelationTuple], visited: set[str]
    ) -> bool:
        """Match + recurse over one page of tuples. Reference engine.go:33-67."""
        for sr in rels:
            if check_and_add_visited(visited, sr.subject):
                continue

            if requested.subject == sr.subject:
                return True

            if not isinstance(sr.subject, SubjectSet):
                continue

            if self._check_one_indirection_further(
                requested,
                RelationQuery(
                    namespace=sr.subject.namespace,
                    object=sr.subject.object,
                    relation=sr.subject.relation,
                ),
                visited,
            ):
                return True

        return False


class _RewriteCheck:
    """One Check under a rewrite schema: a recursive descent that keeps the
    nodes of the current path. A node met again on its own path contributes
    nothing, so membership cycles end and the answer is the least solution;
    within the query a node's answer is kept once it is final, which a
    denial is only when no cut beneath it fell on a node above it."""

    def __init__(self, engine: CheckEngine, nm, schema, requested: RelationTuple):
        self._pages = engine._pages
        self._nm = nm
        self._schema = schema
        self._subject = requested.subject
        self._start = (requested.namespace, requested.object, requested.relation)
        self._path: dict = {}  # node -> its level on the current path
        self._final: dict = {}
        self._ns_ids: dict = {}

    def allowed(self) -> bool:
        return self._node(self._start, 1)[0]

    def _expr(self, node):
        name = node[0]
        ns_id = self._ns_ids.get(name, _FREE)
        if ns_id is _FREE:
            try:
                ns_id = self._nm.get_namespace_by_name(name).id
            except ErrNamespaceUnknown:
                ns_id = None
            self._ns_ids[name] = ns_id
        return self._schema.expr(ns_id, node[2])

    def _node(self, node, level):
        """``(allowed, the shallowest level of the path a denial leaned on)``."""
        at = self._path.get(node)
        if at is not None:
            return False, at
        got = self._final.get(node)
        if got is not None:
            return got, _FREE
        self._path[node] = level
        value, leaned = self._eval(self._expr(node), node, level)
        del self._path[node]
        if value or leaned >= level:
            self._final[node] = value
            return value, _FREE
        return False, leaned

    def _reaches(self, node, level):
        """A step to the userset ``node``: the asked subject is that very
        userset, or the userset allows it."""
        if isinstance(self._subject, SubjectSet) and node == (
            self._subject.namespace, self._subject.object, self._subject.relation
        ):
            return True, _FREE
        return self._node(node, level)

    def _some(self, results):
        leaned = _FREE
        for value, lean in results:
            if value:
                return True, _FREE
            leaned = min(leaned, lean)
        return False, leaned

    def _sets(self, node):
        """The subject sets of the stored rows on ``node``, as nodes."""
        for rows in self._pages(RelationQuery(namespace=node[0], object=node[1], relation=node[2])):
            for r in rows:
                s = r.subject
                if isinstance(s, SubjectSet):
                    yield s.namespace, s.object, s.relation

    def _eval(self, expr, node, level):
        (op, arg), = expr.items()
        if op == "this":
            sets = []
            for rows in self._pages(
                RelationQuery(namespace=node[0], object=node[1], relation=node[2])
            ):
                for r in rows:
                    if r.subject == self._subject:
                        return True, _FREE
                    s = r.subject
                    if isinstance(s, SubjectSet):
                        sets.append((s.namespace, s.object, s.relation))
            return self._some(self._node(s, level + 1) for s in sets)
        if op == "computed_userset":
            return self._reaches((node[0], node[1], arg), level + 1)
        if op == "tuple_to_userset":
            return self._some(
                self._reaches((ns2, obj2, arg["computed_userset"]), level + 1)
                for ns2, obj2, _ in list(self._sets((node[0], node[1], arg["tupleset"])))
            )
        if op == "union":
            return self._some(self._eval(e, node, level) for e in arg)
        if op == "intersection":
            for e in arg:
                value, leaned = self._eval(e, node, level)
                if not value:
                    return False, leaned
            return True, _FREE
        # exclusion: what the subtract side says is final (a schema with a
        # cycle through a subtract was refused when it loaded or was built)
        value, leaned = self._eval(arg["base"], node, level)
        if not value:
            return False, leaned
        return not self._eval(arg["subtract"], node, level)[0], _FREE
