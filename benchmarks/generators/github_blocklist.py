"""github-blocklist: OpenFGA's sample store ``github`` (``github_rewrites.py``:
the same rows, the same role lattice) with the two patterns OpenFGA's
modelling guide adds to such a store, as a permission layer over the roles:

    orgs   blocked      = this            rows: user ids, and teams:t#member
    repos  can_read     = reader      but not  blocked from owner
           can_maintain = maintainer  but not  blocked from owner
           can_delete   = admin       and      member from owner
    issues view rows name repos:r#can_read, pulls view rows repos:r#can_maintain

(*Modeling guides -> Blocklists*, ``but not blocked``, the blocklist holding
users and a team's members; *Multiple Restrictions*, ``writer and member from
owner``. ``benchmarks/configs/github-blocklist.json`` holds the schema in the
format of ``benchmarks/references/rewrites.py``.) Rows: ``github_rewrites.build``'s
with the subject set of every issue's and pull's ``view`` row exchanged as
above, plus the blocklists: each organization blocks 40 users, half of them
drawn along the grant paths of its repos' ``reader`` (never one of its own
owners) and half uniformly; one organization in four also blocks the members
of one team nested under one of its root teams (a subject-set row: the forest
beneath that team is blocked with it).

Queries, from the traffic's skew over issues and pulls: of every four
positions three ask ``view`` on the drawn object and one asks a relation of
its repo, uniform over the five roles and the three permissions, at the third
or the fourth position in turn as ``github_rewrites.queries`` has it. Even
positions are engineered holders along one of the node's grant paths (the
node's role for a role, the role under the gate for a permission); one in
eight of them is drawn from the blocklist of the repo's organization instead:
a directly blocked user or a member of the blocked team where the
organization has one, whichever a coin tries first, who holds the role asked,
so that the gate flips an answer every grant path allows. Where a blocklist
holds nobody with that role (a pull's ``maintainer`` mostly) the position
takes a grant path and the blocklists owe the next engineered position one
(never more than one), which keeps the share near one in eight. Odd
positions a uniform user. 75% + 25% x 3/8 = 84% of the checks have a gate in
their closure. The expectation is analytic and follows the gates.
``Graph.gate_flipped_share(queries, expected)`` is the share of positions
whose answer differs from the same rows read by a program blind to the gates
(``UNGATED``: every permission its first operand alone, which is
``github-rewrites``' schema with the three names added).
"""

from __future__ import annotations

from benchmarks.generators import github_rewrites

NAMESPACES = github_rewrites.NAMESPACES
SOURCE_TUPLES = github_rewrites.SOURCE_TUPLES
ROLES = github_rewrites.ROLES
RANK = github_rewrites.RANK
#: permission -> the role under its gate
PERMISSIONS = {"can_read": "reader", "can_maintain": "maintainer", "can_delete": "admin"}
RELATIONS = ROLES + tuple(PERMISSIONS)
#: the repos' relations as a program that ignores the gates reads them
UNGATED = {perm: {"computed_userset": role} for perm, role in PERMISSIONS.items()}
BLOCKED_USERS = 40  # an organization
BLOCKED_TEAM_SHARE = 0.25  # of the organizations
BLOCKLIST_HOLDER_SHARE = 8  # one engineered holder in this many
TEAM_WALKS = 4  # down a blocked team for a member who holds the role


def _index(name: str) -> int:
    return int(name.rsplit("-", 1)[1])


def build(rng, n_tuples: int):
    g = github_rewrites.build(rng, n_tuples)
    rows = g.rows
    team_parent, team_children, team_users, user_teams = {}, {}, {}, {}
    org_roots, org_owners, repo_org, org_repos = {}, {}, {}, {}
    for i, (ns, obj, rel, sid, _sns, sobj, _srel) in enumerate(rows):
        if ns == "teams":
            t = _index(obj)
            if sid is None:
                team_parent[_index(sobj)] = t
                team_children.setdefault(t, []).append(_index(sobj))
            else:
                u = _index(sid)
                team_users.setdefault(t, []).append(u)
                user_teams.setdefault(u, []).append(t)
        elif ns == "orgs":
            o = _index(obj)
            if rel == "owner":
                org_owners.setdefault(o, set()).add(_index(sid))
            elif rel == "member":
                org_roots.setdefault(o, []).append(_index(sobj))
            else:
                org_owners.setdefault(o, set())
        elif ns == "repos":
            if rel == "owner":
                o = repo_org[_index(obj)] = _index(sobj)
                org_repos.setdefault(o, []).append(_index(obj))
        elif ns == "issues":
            rows[i] = (ns, obj, rel, None, "repos", sobj, "can_read")
        else:
            rows[i] = (ns, obj, rel, None, "repos", sobj, "can_maintain")

    anc_cache = {}

    def ancestors(t):
        got = anc_cache.get(t)
        if got is None:
            chain = [t]
            while chain[-1] in team_parent:
                chain.append(team_parent[chain[-1]])
            got = anc_cache[t] = (frozenset(chain), chain[-1])
        return got

    def in_team(u, t):
        return any(t in ancestors(dt)[0] for dt in user_teams.get(u, ()))

    def walk_down(x, rng):
        """A user of team ``x`` or of a team nested under it, or None."""
        for _ in range(8):
            us = team_users.get(x)
            if us and rng.random() < 0.5:
                return rng.choice(us)
            kids = team_children.get(x)
            if not kids:
                return rng.choice(us) if us else None
            x = rng.choice(kids)
        us = team_users.get(x)
        return rng.choice(us) if us else None

    n_users = g.n_users
    nested = sorted(team_parent)
    reader = RANK["reader"]
    blocked_users, blocked_team = {}, {}
    for o in sorted(org_owners):
        owners, repos = org_owners[o], org_repos.get(o, ())
        chosen: dict = {}
        draws = 0
        while len(chosen) < BLOCKED_USERS:
            draws += 1
            u = None
            if len(chosen) < BLOCKED_USERS // 2 and repos and draws < 8 * BLOCKED_USERS:
                u = g.holder(rng.choice(repos), reader, rng)
            if u is None:
                u = rng.randrange(n_users)
            if u not in owners:
                chosen[u] = True
        blocked_users[o] = list(chosen)
        for u in chosen:
            rows.append(("orgs", f"org-{o}", "blocked", f"user-{u}", None, None, None))
        blocked_team[o] = None
        if rng.random() < BLOCKED_TEAM_SHARE:
            t = None
            if org_roots.get(o):
                x = rng.choice(org_roots[o])
                for _ in range(rng.randint(1, 3)):
                    kids = team_children.get(x)
                    if not kids:
                        break
                    x = t = rng.choice(kids)
            if t is None:
                t = rng.choice(nested)
            blocked_team[o] = t
            rows.append(("orgs", f"org-{o}", "blocked", None, "teams", f"team-{t}", "member"))
    blocked_sets = {o: frozenset(us) for o, us in blocked_users.items()}
    roots_of = {o: frozenset(roots) for o, roots in org_roots.items()}

    def blocked(u, o):
        if u in blocked_sets[o]:
            return True
        t = blocked_team[o]
        return t is not None and in_team(u, t)

    def org_member(u, o):
        """``orgs:o#member`` = this | owner."""
        if u in org_owners[o]:
            return True
        roots = roots_of.get(o, ())
        return any(ancestors(dt)[1] in roots for dt in user_teams.get(u, ()))

    def allowed(u, r, rel, gates=True):
        """Does ``user-u`` hold ``rel`` (a role or a permission) on
        ``repo-r``; without ``gates`` as ``UNGATED`` reads it."""
        if rel in RANK:
            return g.holds(u, r, RANK[rel])
        if not g.holds(u, r, RANK[PERMISSIONS[rel]]):
            return False
        if not gates:
            return True
        o = repo_org[r]
        return org_member(u, o) if rel == "can_delete" else not blocked(u, o)

    def blocked_holder(r, rank, rng):
        """A user on the blocklist of ``repo-r``'s organization who holds
        the role of ``rank`` on it: a directly blocked user or a member of
        its blocked team, whichever the draw tries first; None where the
        blocklist holds nobody with that role."""
        o = repo_org[r]
        t = blocked_team[o]
        kinds = ["team", "user"] if t is not None and rng.random() < 0.5 else ["user", "team"]
        for kind in kinds:
            if kind == "user":
                for u in rng.sample(blocked_users[o], len(blocked_users[o])):
                    if g.holds(u, r, rank):
                        return u
            elif t is not None:
                for _ in range(TEAM_WALKS):
                    u = walk_down(t, rng)
                    if u is not None and g.holds(u, r, rank):
                        return u
        return None

    def node_of(ns, obj, rel):
        """``(repo, the repo's relation asked)`` of a query's node."""
        j = _index(obj)
        if ns == "issues":
            return g.issue_repo[j], "can_read"
        if ns == "pulls":
            return g.pull_repo[j], "can_maintain"
        return j, rel

    def gate_flipped_share(queries, expected):
        flipped = 0
        for (ns, obj, rel, user), ok in zip(queries, expected):
            r, asked = node_of(ns, obj, rel)
            flipped += allowed(_index(user), r, asked, gates=False) != ok
        return flipped / len(queries) if queries else 0.0

    def gated_share(queries):
        return sum(node_of(ns, obj, rel)[1] in PERMISSIONS
                   for ns, obj, rel, _ in queries) / len(queries)

    g.repo_org, g.blocked_users, g.blocked_team = repo_org, blocked_users, blocked_team
    g.allowed, g.blocked_holder, g.node_of = allowed, blocked_holder, node_of
    g.gate_flipped_share, g.gated_share = gate_flipped_share, gated_share
    return g


def queries(graph, rng, objects):
    """One query per entry of ``objects`` (see the module's docstring)."""
    out, expected = [], []
    n_issues = len(graph.issue_repo)
    owed = 0.0  # holders the blocklists still owe the engineered positions
    for i, j in enumerate(objects):
        if j < n_issues:
            ns, obj, r, asked = "issues", f"issue-{j}", graph.issue_repo[j], "can_read"
        else:
            ns, obj = "pulls", f"pull-{j - n_issues}"
            r, asked = graph.pull_repo[j - n_issues], "can_maintain"
        rel = "view"
        if i % 4 == 2 + (i // 4) % 2:
            ns, obj = "repos", f"repo-{r}"
            rel = asked = rng.choice(RELATIONS)
        rank = RANK[PERMISSIONS.get(asked, asked)]
        u = None
        if i % 2 == 0:
            owed = min(1.0, owed + 1.0 / BLOCKLIST_HOLDER_SHARE)
            if owed >= 1.0:
                u = graph.blocked_holder(r, rank, rng)
                owed -= u is not None
            if u is None:
                u = graph.holder(r, rank, rng)
        if u is None:
            u = rng.randrange(graph.n_users)
        out.append((ns, obj, rel, f"user-{u}"))
        expected.append(graph.allowed(u, r, asked))
    return out, expected
