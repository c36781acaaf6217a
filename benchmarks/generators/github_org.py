"""github-org: GitHub-style org/team/repo/issue graph (BASELINE.json config
4), flattened to plain subject sets as Keto v0.7 models it: users join teams,
teams nest in forests of depth 4, root teams attach to orgs, repos grant
``reader``/``maintainer`` to an org's or a team's members, issues and pulls
grant ``view`` through the repo's reader/maintainer set. The deepest chain is
issue -> reader -> org -> root team -> 3 nested teams -> user = 7 edges.

A copy of ``bench.build_workload_github`` and ``bench.make_queries_github``
that emits plain rows (see rbac_groups.py for the row and query shapes) and
takes each query's object from the traffic's skew: objects ``0..n_issues-1``
are issues, the rest pulls.
"""

from __future__ import annotations

NAMESPACES = ("teams", "orgs", "repos", "issues", "pulls")
SOURCE_TUPLES = 10_000_000
LEVELS = 4  # team nesting depth


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
    # team forest: contiguous level blocks; level-k teams parent into k-1
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

    # direct team memberships: the bulk, sized so that the total lands on
    # n_tuples after repos, issues and pulls
    n_issueish = int(n_tuples * 0.30)
    budget_members = n_tuples - len(rows) - 2 * n_repos - n_issueish
    per_user = max(1, budget_members // n_users)
    team_users, user_teams = {}, {}
    for u in range(n_users):
        for _ in range(per_user):
            t = rng.randrange(n_teams)
            user_teams.setdefault(u, []).append(t)
            team_users.setdefault(t, []).append(u)
            rows.append(("teams", f"team-{t}", "member", f"user-{u}", None, None, None))

    repo_reader, repo_maint = [], []
    for r in range(n_repos):
        if rng.random() < 0.5:
            grant = ("org", rng.randrange(n_orgs))
            rows.append(("repos", f"repo-{r}", "reader", None, "orgs", f"org-{grant[1]}", "member"))
        else:
            grant = ("team", rng.randrange(n_teams))
            rows.append(("repos", f"repo-{r}", "reader", None, "teams", f"team-{grant[1]}", "member"))
        repo_reader.append(grant)
        mt = rng.randrange(n_teams)
        repo_maint.append(("team", mt))
        rows.append(("repos", f"repo-{r}", "maintainer", None, "teams", f"team-{mt}", "member"))

    issue_repo, pull_repo = [], []
    while len(rows) < n_tuples:
        r = rng.randrange(n_repos)
        if len(issue_repo) <= len(pull_repo):
            rows.append(("issues", f"issue-{len(issue_repo)}", "view", None, "repos", f"repo-{r}", "reader"))
            issue_repo.append(r)
        else:
            rows.append(("pulls", f"pull-{len(pull_repo)}", "view", None, "repos", f"repo-{r}", "maintainer"))
            pull_repo.append(r)

    def grant_ok(u, grant):
        kind, x = grant
        if kind == "org":
            roots = set(org_roots[x])
            return any(ancestors(dt)[1] in roots for dt in user_teams.get(u, ()))
        return any(x in ancestors(dt)[0] for dt in user_teams.get(u, ()))

    def member_of_grant(grant, rng):
        """A user holding ``grant``, or None."""
        kind, x = grant
        if kind == "org":
            roots = org_roots[x]
            if not roots:
                return None
            x = rng.choice(roots)
        # random downward walk from team x; direct users at any stop
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

    g.n_objects = len(issue_repo) + len(pull_repo)
    g.n_users = n_users
    g.issue_repo, g.pull_repo = issue_repo, pull_repo
    g.repo_reader, g.repo_maint = repo_reader, repo_maint
    g.grant_ok, g.member_of_grant = grant_ok, member_of_grant
    return g


def queries(graph: Graph, rng, objects):
    """One query per entry of ``objects``: half engineered grants, half
    uniform users (mostly denials), over the deepest objects."""
    out, expected = [], []
    n_issues = len(graph.issue_repo)
    for i, j in enumerate(objects):
        if j < n_issues:
            ns, obj = "issues", f"issue-{j}"
            grant = graph.repo_reader[graph.issue_repo[j]]
        else:
            ns, obj = "pulls", f"pull-{j - n_issues}"
            grant = graph.repo_maint[graph.pull_repo[j - n_issues]]
        u = graph.member_of_grant(grant, rng) if i % 2 == 0 else None
        if u is None:
            u = rng.randrange(graph.n_users)
        out.append((ns, obj, "view", f"user-{u}"))
        expected.append(graph.grant_ok(u, grant))
    return out, expected
