"""``github-blocklist`` beside its cell: the generator is the seed's; its
analytic expectation against the plain reference on every query of a
20,000-tuple graph; how many positions cross a gate and how many a gate
flips, at the cell's own size; the same rows read by a program blind to the
gates (``UNGATED``: every permission its first operand) disagree on exactly
the flipped positions; the manifest declares what it was asked to; the four
readers the cell brought over a recorded scrape pair
(``recorded_gate_scrape_{before,after}.txt``: a CPU rehearsal of this cell at
20,000 tuples, PR 43) and over the recorded scrapes of earlier PRs, which
hold none of their families (a program without the counters reads None,
never 0)."""

import copy
import random
import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

ROOT = Path(__file__).resolve().parents[2]
HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT))

from benchmarks import control, manifest as manifest_mod, run, traffic  # noqa: E402
from benchmarks.daemon import Metrics  # noqa: E402

CELL = "github-blocklist.bulk"
NEW = {
    "gate_check_share.bulk": ("%", "higher", "program_counter", "resolve + pack", "checks_per_s"),
    "gate_positions_mean.bulk": ("count", "lower", "program_counter", "resolve + pack", "checks_per_s"),
    "gate_host_ms_per_kcheck.bulk": ("ms", "lower", "program_counter", "resolve + pack", "checks_per_s"),
    "gate_node_share": ("%", "lower", "program_counter", "snapshot build", "setup_s"),
}
READ = {name: run.load_module("layers", name).read for name in NEW}
#: the lists ISSUE 43 asks the cell to be appended to
APPENDED = (
    "framed_share.bulk", "label_fallback_share.bulk", "inline_compiles.bulk", "overlap_share.bulk",
    "round_tuples_mean.bulk", "door_resolve_share.bulk", "pack_fused_share.bulk",
    "narrow_round_share.bulk", "cut_chunk_share.bulk", "ctrl_narrow_events.bulk",
    "long_stay_ms.bulk", "label_build_s", "rewrite_check_share.bulk",
    "rewrite_oracle_share.bulk", "rewrite_edge_share", "rewrite_expand_s",
)


@pytest.fixture(scope="module")
def small():
    cell = run.Cell(CELL, tuples=20_000)
    graph = cell.generator.build(random.Random(430_001), 20_000)
    objects = traffic.skewed_objects(430_001, graph.n_objects, 16_384, cell.mix["skew"])
    queries, expected = cell.generator.queries(graph, random.Random(430_002), objects)
    return cell, graph, queries, expected


# -- the generator ----------------------------------------------------------------------

def test_the_generator_is_the_seeds(small):
    cell, graph, queries, expected = small
    again = cell.generator.build(random.Random(430_001), 20_000)
    assert again.rows == graph.rows
    objects = traffic.skewed_objects(430_001, again.n_objects, 16_384, cell.mix["skew"])
    assert cell.generator.queries(again, random.Random(430_002), objects) == (queries, expected)
    other = cell.generator.build(random.Random(430_003), 20_000)
    assert other.rows != graph.rows


def test_the_rows_are_github_rewrites_with_the_gates_named_and_the_blocklists(small):
    cell, graph, _, _ = small
    theirs = run.load_module("generators", "github_rewrites").build(random.Random(430_001), 20_000)
    rows = graph.rows
    assert len(theirs.rows) == 20_000
    kept = rows[: len(theirs.rows)]
    for ours, base in zip(kept, theirs.rows):
        if base[0] == "issues":
            assert ours == base[:6] + ("can_read",)
        elif base[0] == "pulls":
            assert ours == base[:6] + ("can_maintain",)
        else:
            assert ours == base
    added = rows[len(theirs.rows):]
    assert {r[:1] + r[2:3] for r in added} == {("orgs", "blocked")}
    orgs = {r[1] for r in rows if r[0] == "orgs"}
    users = [r for r in added if r[3] is not None]
    assert len(users) == cell.generator.BLOCKED_USERS * len(orgs)
    teams = [r for r in added if r[3] is None]
    assert teams and all(r[4:] == ("teams", r[5], "member") for r in teams)
    owners = {(r[1], r[3]) for r in rows if r[0] == "orgs" and r[2] == "owner"}
    assert not owners & {(r[1], r[3]) for r in users}  # nobody blocks an owner


def test_the_configuration_is_github_rewrites_schema_and_the_three_permissions(small):
    cell, _, _, _ = small
    sibling = run.Cell("github-rewrites.bulk", tuples=20_000).config
    ours = {n["name"]: (n.get("config") or {}).get("relations", {}) for n in cell.config["namespaces"]}
    theirs = {n["name"]: (n.get("config") or {}).get("relations", {}) for n in sibling["namespaces"]}
    assert [n["name"] for n in cell.config["namespaces"]] == ["teams", "orgs", "repos", "issues", "pulls"]
    assert [n["id"] for n in cell.config["namespaces"]] == [0, 1, 2, 3, 4]
    assert {k: v for k, v in ours["repos"].items() if k in theirs["repos"]} == theirs["repos"]
    assert ours["orgs"] == {**theirs["orgs"], "blocked": {"this": {}}}
    from_owner = lambda rel: {"tuple_to_userset": {"tupleset": "owner", "computed_userset": rel}}  # noqa: E731
    assert {k: v for k, v in ours["repos"].items() if k not in theirs["repos"]} == {
        "can_read": {"exclusion": {"base": {"computed_userset": "reader"},
                                   "subtract": from_owner("blocked")}},
        "can_maintain": {"exclusion": {"base": {"computed_userset": "maintainer"},
                                       "subtract": from_owner("blocked")}},
        "can_delete": {"intersection": [{"computed_userset": "admin"}, from_owner("member")]},
    }
    assert cell.config["reference"] == "rewrites"
    assert cell.reference.file == "benchmarks/references/rewrites.py"
    assert cell.config["serve"] == {}  # the daemon's defaults
    assert cell.config["reduced"] == ["tuples"] and cell.config["source_tuples"] == 10_000_000
    assert cell.config["tuples"] == 1_000_000 and cell.config["assumed"]


def test_the_expectation_equals_the_reference_on_every_query(small):
    cell, graph, queries, expected = small
    judge = cell.reference.build(graph.rows)
    assert [judge.allowed(*q) for q in queries] == expected
    # three of four ask view, one a relation of the repo, that one on either parity
    repos = [i for i, q in enumerate(queries) if q[0] == "repos"]
    assert len(repos) == len(queries) // 4
    assert {i % 4 for i in repos} == {2, 3}
    assert {q[2] for q in queries if q[0] == "repos"} == set(cell.generator.RELATIONS)
    assert {q[2] for q in queries if q[0] != "repos"} == {"view"}
    assert 0.3 < sum(expected) / len(queries) < 0.8


def test_a_program_blind_to_the_gates_fails_on_exactly_the_flipped_positions(small):
    """The same rows judged with every permission read as its first operand
    (github-rewrites' schema with the three names added): the control that a
    program which ignores a gate does not pass."""
    cell, graph, queries, expected = small
    blind = copy.deepcopy(cell.config)
    repos = next(n for n in blind["namespaces"] if n["name"] == "repos")
    repos["config"]["relations"].update(cell.generator.UNGATED)
    judge = run.load_reference(blind).build(graph.rows)
    flipped = [judge.allowed(*q) != e for q, e in zip(queries, expected)]
    assert sum(flipped) / len(queries) == pytest.approx(graph.gate_flipped_share(queries, expected))
    assert sum(flipped) > 0.04 * len(queries)
    # a gate takes an answer away and never gives one
    assert all(not e for f, e in zip(flipped, expected) if f)
    # both gates do: the blocklist, and the second condition of can_delete
    kinds = {graph.node_of(*q[:3])[1] for q, f in zip(queries, flipped) if f}
    assert kinds == {"can_read", "can_maintain", "can_delete"}


def test_the_shares_at_the_cells_own_size():
    """84% +- 2 of the positions have a gate in their closure; a gate flips
    5-7% of them (ISSUE 43): ~250 of every 4,096 answers a gate-blind program
    gets wrong."""
    cell = run.Cell(CELL, tuples=0)
    assert cell.n_tuples == 1_000_000
    graph = cell.generator.build(random.Random(2_600_430_001), cell.n_tuples)
    assert 1_020_000 <= len(graph.rows) <= 1_020_200  # 500 organizations x 40, and ~125 teams
    objects = traffic.skewed_objects(2_600_430_001, graph.n_objects, 32_768, cell.mix["skew"])
    queries, expected = cell.generator.queries(graph, random.Random(2_600_430_002), objects)
    assert graph.gated_share(queries) == pytest.approx(0.84, abs=0.02)
    assert 0.05 <= graph.gate_flipped_share(queries, expected) <= 0.07
    assert 0.2 <= sum(1 for t in graph.blocked_team.values() if t is not None) / len(graph.blocked_team) <= 0.3


def test_the_control_is_caught_on_both_counts():
    cell = run.Cell(CELL, tuples=20_000)
    out = control.control_run(cell, 11, 10.0, n=8_000)
    assert out["reference"] == "benchmarks/references/rewrites.py"
    assert out["reference_vs_analytic"] == 0
    assert out["control_vs_analytic"] > 0 and out["control_vs_reference_sample"] > 0


# -- the manifest -------------------------------------------------------------------------

def test_the_manifest_declares_the_configuration_the_cell_and_the_four_metrics():
    manifest = manifest_mod.load()
    workload, config = manifest_mod.cell(manifest, CELL)
    assert workload == {"name": CELL, "config": "github-blocklist", "traffic": "bulk", "chips": 1,
                        "why": workload["why"]}
    assert len(workload["why"]) <= 200 and len(config["why"]) <= 200 and len(config["source"]) <= 200
    assert config["file"] == "benchmarks/configs/github-blocklist.json" and config["reduced"] == ["tuples"]
    assert "Blocklists" in config["source"] and "Multiple Restrictions" in config["source"]
    # appended after what was there, not put among it (a later PR appends after these in turn)
    names = [w["name"] for w in manifest["workloads"]]
    assert names.index(CELL) > names.index("github-rewrites.bulk")
    by_name = {m["name"]: m for m in manifest["per_layer"]}
    order = [m["name"] for m in manifest["per_layer"]]
    assert [n for n in order if n in NEW] == list(NEW) and order.index("gate_check_share.bulk") > order.index(
        "long_stay_ms.interactive")
    for name, (unit, better, source, layer, moves) in NEW.items():
        m = by_name[name]
        assert (m["unit"], m["better"], m["source"], m["layer"], m["moves"]) == (
            unit, better, source, layer, moves)
        assert m["workloads"] == [CELL]
    for name in APPENDED:
        assert CELL in by_name[name]["workloads"], name
    e2e = {m["name"]: m for m in manifest["end_to_end"]}
    assert CELL in e2e["checks_per_s"]["workloads"]
    assert manifest_mod.reported_by(e2e["setup_s"], CELL, manifest)


# -- the four readers ---------------------------------------------------------------------

def recorded(stem: str, result=None):
    return SimpleNamespace(
        before=Metrics((HERE / f"{stem}_before.txt").read_text()),
        after=Metrics((HERE / f"{stem}_after.txt").read_text()),
        trace=None, result=result or {"window_s": 3.0, "attempted": 1_359_872, "failed": 0, "wrong": 0})


def test_the_readers_over_the_recorded_scrapes_of_this_cell():
    r = recorded("recorded_gate_scrape")
    assert READ["gate_check_share.bulk"](r) == pytest.approx(84.4, abs=0.5)
    assert READ["gate_positions_mean.bulk"](r) == pytest.approx(2.0)
    assert 0.0 < READ["gate_host_ms_per_kcheck.bulk"](r) < 5.0
    assert READ["gate_node_share"](r) == pytest.approx(100.0 * 4_500 / 15_327)
    # the existing share that has to read 0 there
    assert run.load_module("layers", "rewrite_oracle_share.bulk").read(r) == 0.0


@pytest.mark.parametrize("name", sorted(NEW))
@pytest.mark.parametrize("stem", ["recorded_scrape", "recorded_ctrl_scrape",
                                  "recorded_ctrl_scrape_parent"])
def test_a_program_without_the_families_reads_none(name, stem):
    assert READ[name](recorded(stem)) is None


def test_a_window_without_checks_reads_none():
    same = SimpleNamespace(
        before=Metrics((HERE / "recorded_gate_scrape_after.txt").read_text()),
        after=Metrics((HERE / "recorded_gate_scrape_after.txt").read_text()),
        trace=None, result={"window_s": 3.0, "attempted": 0, "failed": 0, "wrong": 0})
    for name in ("gate_check_share.bulk", "gate_positions_mean.bulk", "gate_host_ms_per_kcheck.bulk"):
        assert READ[name](same) is None
