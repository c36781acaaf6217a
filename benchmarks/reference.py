"""The plain reference: Check as Keto v0.7 defines it, over the generated
rows, importing nothing of the program.

``allowed(ns, obj, rel, user)`` is true when a row grants ``rel`` on
``ns:obj`` to ``user`` directly, or to a subject set ``ns2:obj2#rel2`` that
(recursively) allows ``user``. A visited set makes cycles terminate.
``max_depth`` (None = unbounded) counts edges from the object, so a direct
grant needs depth 1: the control (control.py) cuts it short to break the
configuration's "answers equal the reference" guarantee.
"""

from __future__ import annotations


class Reference:
    def __init__(self, rows):
        direct, indirect = {}, {}
        for ns, obj, rel, sid, sns, sobj, srel in rows:
            key = (ns, obj, rel)
            if sid is not None:
                direct.setdefault(key, set()).add(sid)
            else:
                indirect.setdefault(key, []).append((sns, sobj, srel))
        self._direct, self._indirect = direct, indirect

    def allowed(self, ns, obj, rel, user, max_depth=None) -> bool:
        frontier, seen, depth = [(ns, obj, rel)], {(ns, obj, rel)}, 0
        while frontier:
            depth += 1
            if max_depth is not None and depth > max_depth:
                return False
            nxt = []
            for key in frontier:
                if user in self._direct.get(key, ()):
                    return True
                for sub in self._indirect.get(key, ()):
                    if sub not in seen:
                        seen.add(sub)
                        nxt.append(sub)
            frontier = nxt
        return False
