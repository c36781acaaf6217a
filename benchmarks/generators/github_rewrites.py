"""github-rewrites: OpenFGA's sample store ``github`` as its model writes it,
unflattened (BASELINE.json config 4: "5 namespaces with userset rewrites").
The store holds what a deployment stores and the schema says the rest:

    orgs   member     = this | owner
    repos  admin      = this | repo_admin from owner
           maintainer = this | admin
           writer     = this | maintainer | repo_writer from owner
           triager    = this | writer
           reader     = this | triager | repo_reader from owner

(``benchmarks/configs/github-rewrites.json`` ``namespaces[].config.relations``;
the format is ``benchmarks/references/rewrites.py``'s). Rows: team nesting in
forests of depth 4 and users' memberships as ``github_org.py`` draws them; an
organization's owners (1-3 users), its root teams as ``member``s, and the
store's own ``repo_admin@orgs:o#owner`` and ``repo_reader@orgs:o#member``,
``repo_writer`` held by a team in half the organizations; a repo's
``owner@orgs:o#...`` and two direct grants, one to a team's members and one
to a user, each at a role uniform over the five; issues grant ``view`` to
their repo's ``reader``, pulls to its ``maintainer``. The deepest chain is
issue -> reader -> triager -> writer -> maintainer -> admin -> a team -> three
nestings -> user: 10 nodes where the flattened store has 7 edges.

Queries, from the traffic's skew over issues and pulls: of every four
positions three ask ``view`` on the drawn object and one asks a role of that
object's repo directly (the role uniform over the five: nodes most of which
hold no stored row); that one sits at the third or the fourth position in
turn, so that half of them fall on each parity. Even positions are
engineered holders: one of the grant paths the queried node has, drawn
uniformly (the user's direct grant, the team's grant and a walk down the
forest, an organization-level role through ``owner`` and then an owner or a
member), where the walk finds nobody a uniform user; odd positions a
uniform user (mostly denials). The expectation is analytic and follows the
schema: role order, organization roles through ``owner``, ``member`` or
``owner``. ``Graph.derived_share(queries, expected)`` is the share of the
granted checks that the same rows without the rewrites would deny.
"""

from __future__ import annotations

NAMESPACES = ("teams", "orgs", "repos", "issues", "pulls")
SOURCE_TUPLES = 10_000_000
LEVELS = 4  # team nesting depth
ROLES = ("admin", "maintainer", "writer", "triager", "reader")  # each implies the next
RANK = {role: i for i, role in enumerate(ROLES)}


class Graph:
    def __init__(self):
        self.rows = []
        self.n_objects = 0


def build(rng, n_tuples: int) -> Graph:
    scale = n_tuples / SOURCE_TUPLES
    n_users = max(1_000, int(800_000 * scale))
    n_teams = max(64, int(120_000 * scale))
    n_orgs = max(8, int(5_000 * scale))
    n_repos = max(64, int(250_000 * scale))

    g = Graph()
    rows = g.rows
    bounds = [i * n_teams // LEVELS for i in range(LEVELS + 1)]

    def level_of(t):
        for k in range(LEVELS):
            if t < bounds[k + 1]:
                return k
        return LEVELS - 1

    team_parent, team_children = {}, {}
    for t in range(bounds[1], n_teams):
        k = level_of(t)
        parent = rng.randrange(bounds[k - 1], bounds[k])
        team_parent[t] = parent
        team_children.setdefault(parent, []).append(t)
        rows.append(("teams", f"team-{parent}", "member", None, "teams", f"team-{t}", "member"))

    anc_cache = {}

    def ancestors(t):
        """(the chain from t to its root, t included; the root)."""
        got = anc_cache.get(t)
        if got is None:
            chain = [t]
            while chain[-1] in team_parent:
                chain.append(team_parent[chain[-1]])
            got = anc_cache[t] = (frozenset(chain), chain[-1])
        return got

    org_roots = {o: [] for o in range(n_orgs)}
    for r in range(bounds[1]):
        o = rng.randrange(n_orgs)
        org_roots[o].append(r)
        rows.append(("orgs", f"org-{o}", "member", None, "teams", f"team-{r}", "member"))
    org_owners, org_writer = [], []
    for o in range(n_orgs):
        owners = [rng.randrange(n_users) for _ in range(rng.randint(1, 3))]
        org_owners.append(owners)
        for u in dict.fromkeys(owners):
            rows.append(("orgs", f"org-{o}", "owner", f"user-{u}", None, None, None))
        rows.append(("orgs", f"org-{o}", "repo_admin", None, "orgs", f"org-{o}", "owner"))
        rows.append(("orgs", f"org-{o}", "repo_reader", None, "orgs", f"org-{o}", "member"))
        tw = rng.randrange(n_teams) if rng.random() < 0.5 else None
        org_writer.append(tw)
        if tw is not None:
            rows.append(("orgs", f"org-{o}", "repo_writer", None, "teams", f"team-{tw}", "member"))

    n_issueish = int(n_tuples * 0.30)
    budget_members = n_tuples - len(rows) - 3 * n_repos - n_issueish
    per_user = max(1, budget_members // n_users)
    team_users, user_teams = {}, {}
    for u in range(n_users):
        for _ in range(per_user):
            t = rng.randrange(n_teams)
            user_teams.setdefault(u, []).append(t)
            team_users.setdefault(t, []).append(u)
            rows.append(("teams", f"team-{t}", "member", f"user-{u}", None, None, None))

    repo_org, repo_team, repo_user = [], [], []
    for r in range(n_repos):
        o = rng.randrange(n_orgs)
        repo_org.append(o)
        rows.append(("repos", f"repo-{r}", "owner", None, "orgs", f"org-{o}", "..."))
        t, t_role = rng.randrange(n_teams), rng.choice(ROLES)
        repo_team.append((t, RANK[t_role]))
        rows.append(("repos", f"repo-{r}", t_role, None, "teams", f"team-{t}", "member"))
        u, u_role = rng.randrange(n_users), rng.choice(ROLES)
        repo_user.append((u, RANK[u_role]))
        rows.append(("repos", f"repo-{r}", u_role, f"user-{u}", None, None, None))

    issue_repo, pull_repo = [], []
    while len(rows) < n_tuples:
        r = rng.randrange(n_repos)
        if len(issue_repo) <= len(pull_repo):
            rows.append(("issues", f"issue-{len(issue_repo)}", "view", None, "repos", f"repo-{r}", "reader"))
            issue_repo.append(r)
        else:
            rows.append(("pulls", f"pull-{len(pull_repo)}", "view", None, "repos", f"repo-{r}", "maintainer"))
            pull_repo.append(r)

    def in_team(u, t):
        return any(t in ancestors(dt)[0] for dt in user_teams.get(u, ()))

    roots_of = [frozenset(roots) for roots in org_roots.values()]

    def org_member(u, o):
        """``orgs:o#member`` = this | owner."""
        if u in org_owners[o]:
            return True
        return any(ancestors(dt)[1] in roots_of[o] for dt in user_teams.get(u, ()))

    def holds(u, r, rank, rewrites=True):
        """Does ``user-u`` hold the role of ``rank`` on ``repo-r``? Without
        ``rewrites``: by the stored rows alone, as Keto v0.7 reads them."""
        gu, gu_rank = repo_user[r]
        if gu == u and (gu_rank <= rank if rewrites else gu_rank == rank):
            return True
        gt, gt_rank = repo_team[r]
        if (gt_rank <= rank if rewrites else gt_rank == rank) and in_team(u, gt):
            return True
        if not rewrites:
            return False
        o = repo_org[r]
        if u in org_owners[o]:
            return True  # repo_admin from owner, and admin implies every role
        tw = org_writer[o]
        if rank >= RANK["writer"] and tw is not None and in_team(u, tw):
            return True
        return rank == RANK["reader"] and org_member(u, o)

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

    def holder(r, rank, rng):
        """A user drawn along one of the grant paths ``repo-r``'s role of
        ``rank`` has, the path uniform among them; None where the walk
        finds nobody."""
        o = repo_org[r]
        paths = [("owner", None)]
        if repo_user[r][1] <= rank:
            paths.append(("user", repo_user[r][0]))
        if repo_team[r][1] <= rank:
            paths.append(("team", repo_team[r][0]))
        if rank >= RANK["writer"] and org_writer[o] is not None:
            paths.append(("team", org_writer[o]))
        if rank == RANK["reader"]:
            paths.append(("member", None))
        kind, x = rng.choice(paths)
        if kind == "user":
            return x
        if kind == "team":
            return walk_down(x, rng)
        if kind == "owner" or not org_roots[o] or rng.random() < 0.25:
            return rng.choice(org_owners[o])
        return walk_down(rng.choice(org_roots[o]), rng)

    def derived_share(queries, expected):
        """Of the granted checks, the share that the stored rows alone
        (every relation read as ``this``) would deny: grants whose every
        witness crosses a derived edge."""
        n_issues = len(issue_repo)
        granted = carried = 0
        for (ns, obj, rel, user), ok in zip(queries, expected):
            if not ok:
                continue
            granted += 1
            j = int(obj.rsplit("-", 1)[1])
            if ns == "issues":
                r, rank = issue_repo[j], RANK["reader"]
            elif ns == "pulls":
                r, rank = pull_repo[j], RANK["maintainer"]
            else:
                r, rank = j, RANK[rel]
            carried += not holds(int(user.rsplit("-", 1)[1]), r, rank, rewrites=False)
        return carried / granted if granted else 0.0

    g.n_objects = len(issue_repo) + len(pull_repo)
    g.n_users = n_users
    g.issue_repo, g.pull_repo = issue_repo, pull_repo
    g.holds, g.holder, g.derived_share = holds, holder, derived_share
    return g


def queries(graph: Graph, rng, objects):
    """One query per entry of ``objects`` (see the module's docstring)."""
    out, expected = [], []
    n_issues = len(graph.issue_repo)
    for i, j in enumerate(objects):
        if j < n_issues:
            ns, obj, r, rank = "issues", f"issue-{j}", graph.issue_repo[j], RANK["reader"]
        else:
            ns, obj = "pulls", f"pull-{j - n_issues}"
            r, rank = graph.pull_repo[j - n_issues], RANK["maintainer"]
        rel = "view"
        if i % 4 == 2 + (i // 4) % 2:
            role = rng.choice(ROLES)
            ns, obj, rel, rank = "repos", f"repo-{r}", role, RANK[role]
        u = graph.holder(r, rank, rng) if i % 2 == 0 else None
        if u is None:
            u = rng.randrange(graph.n_users)
        out.append((ns, obj, rel, f"user-{u}"))
        expected.append(graph.holds(u, r, rank))
    return out, expected
