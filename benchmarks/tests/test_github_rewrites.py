"""``github-rewrites`` beside its cell: the generator's analytic expectation
against the plain reference on every query of a 20,000-tuple graph; the four
readers the cell brought (``rewrite_check_share.bulk``,
``rewrite_oracle_share.bulk``, ``rewrite_edge_share``, ``rewrite_expand_s``)
over canned scrapes and over the recorded scrapes of PR 25 beside this file,
which hold none of their families (a program without the counters reads
None, never 0); the cell builds; the manifest declares what it was asked to."""

import json
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

CELL = "github-rewrites.bulk"
NEW = {
    "rewrite_check_share.bulk": ("%", "higher", "program_counter", "resolve + pack", "checks_per_s"),
    "rewrite_oracle_share.bulk": ("%", "lower", "program_counter", "resolve + pack", "checks_per_s"),
    "rewrite_edge_share": ("%", "lower", "program_counter", "snapshot build", "setup_s"),
    "rewrite_expand_s": ("s", "lower", "program_span", "snapshot build", "setup_s"),
}
READ = {name: run.load_module("layers", name).read for name in NEW}

BEFORE = """\
keto_check_rewrite_checks_total{closure="rewritten"} 196608
keto_check_rewrite_checks_total{closure="plain"} 0
keto_check_rewrite_route_total{route="device"} 196608
keto_check_rewrite_route_total{route="oracle"} 0
keto_check_rewrite_oracle_total{reason="gated_closure"} 0
keto_check_rewrite_oracle_total{reason="gated_unresolved"} 0
keto_snapshot_edges 1305000
keto_snapshot_rewrite_edges{kind="computed_userset"} 100500
keto_snapshot_rewrite_edges{kind="tuple_to_userset"} 75000
keto_build_phase_duration_seconds_sum{phase="scan"} 4.5
keto_build_phase_duration_seconds_count{phase="scan"} 1
keto_build_phase_duration_seconds_sum{phase="rewrites"} 0.875
keto_build_phase_duration_seconds_count{phase="rewrites"} 1
"""
AFTER = BEFORE.replace('closure="rewritten"} 196608', 'closure="rewritten"} 4196608').replace(
    'closure="plain"} 0', 'closure="plain"} 1000000').replace(
    'route="device"} 196608', 'route="device"} 3996608').replace(
    'route="oracle"} 0', 'route="oracle"} 200000')


def run_of(before: str, after: str):
    return SimpleNamespace(before=Metrics(before), after=Metrics(after), trace=None,
                           result={"window_s": 10.0})


def without(text: str, family: str) -> str:
    return "\n".join(ln for ln in text.splitlines() if family not in ln) + "\n"


# -- the generator against the judge ----------------------------------------------

@pytest.fixture(scope="module")
def small():
    cell = run.Cell(CELL, tuples=20_000)
    graph = cell.generator.build(random.Random(380_001), 20_000)
    return cell, graph


def test_the_configuration_carries_the_github_schema_letter_for_letter(small):
    cell, _ = small
    from benchmarks.tests import test_rewrites_reference as hand

    theirs = {n["name"]: n.get("config") for n in hand.GITHUB["namespaces"]}
    ours = {n["name"]: n.get("config") for n in cell.config["namespaces"]}
    assert [n["name"] for n in cell.config["namespaces"]] == [
        "teams", "orgs", "repos", "issues", "pulls"]
    assert [n["id"] for n in cell.config["namespaces"]] == [0, 1, 2, 3, 4]
    for name in ("orgs", "repos"):
        assert ours[name] == theirs[name], name
    assert all(ours[name] is None for name in ("teams", "issues", "pulls"))
    assert cell.config["reference"] == "rewrites"
    # one documented setting, with its reason beside it; the rest are the daemon's defaults
    assert set(cell.config["serve"]) == {"labels_device_min_edges"} and cell.config["serve_why"]
    assert cell.config["reduced"] == ["tuples"] and cell.config["source_tuples"] == 10_000_000
    assert cell.reference.file == "benchmarks/references/rewrites.py"


def test_the_expectation_equals_the_reference_on_every_query(small):
    cell, graph = small
    assert len(graph.rows) == 20_000
    judge = cell.reference.build(graph.rows)
    objects = traffic.skewed_objects(380_001, graph.n_objects, 16_384, cell.mix["skew"])
    queries, expected = cell.generator.queries(graph, random.Random(380_002), objects)
    assert [judge.allowed(*q) for q in queries] == expected
    # three of four ask view, one a role of the repo, that one on either parity
    roles = [i for i, q in enumerate(queries) if q[0] == "repos"]
    assert len(roles) == len(queries) // 4
    assert {i % 4 for i in roles} == {2, 3}
    assert {q[2] for q in queries if q[0] == "repos"} == set(cell.generator.ROLES)
    assert {q[2] for q in queries if q[0] != "repos"} == {"view"}
    granted = sum(expected)
    assert 0.3 < granted / len(queries) < 0.8
    # most grants are the schema's doing: the same rows read as Keto v0.7 deny them
    assert graph.derived_share(queries, expected) >= 0.75
    plain = run.load_reference({})  # no schema: benchmarks/reference.py
    v07 = plain.build(graph.rows)
    denied_by_v07 = sum(1 for q, e in zip(queries, expected) if e and not v07.allowed(*q))
    assert denied_by_v07 / granted == pytest.approx(graph.derived_share(queries, expected))


def test_the_store_is_unflattened(small):
    _, graph = small
    rows = graph.rows
    assert sum(1 for r in rows if r[0] == "repos" and r[2] == "owner" and r[6] == "...") == sum(
        1 for r in rows if r[0] == "repos") // 3
    assert any(r[:3] == ("orgs", "org-0", "repo_admin") and r[4:] == ("orgs", "org-0", "owner")
               for r in rows)
    assert any(r[:3] == ("orgs", "org-0", "repo_reader") and r[4:] == ("orgs", "org-0", "member")
               for r in rows)
    assert not any(r[0] == "repos" and r[4] == "orgs" and r[6] != "..." for r in rows)
    assert {r[6] for r in rows if r[0] == "issues"} == {"reader"}
    assert {r[6] for r in rows if r[0] == "pulls"} == {"maintainer"}


def test_the_control_is_caught_on_both_counts():
    cell = run.Cell(CELL, tuples=20_000)
    out = control.control_run(cell, 11, 10.0, n=8_000)
    assert out["reference"] == "benchmarks/references/rewrites.py"
    assert out["reference_vs_analytic"] == 0
    assert out["control_vs_analytic"] > 0 and out["control_vs_reference_sample"] > 0


# -- the four readers --------------------------------------------------------------

def test_rewritten_checks_over_the_checks_counted_in_the_window():
    assert READ["rewrite_check_share.bulk"](run_of(BEFORE, AFTER)) == pytest.approx(80.0)


def test_oracle_checks_over_the_rewritten_checks_of_the_window():
    assert READ["rewrite_oracle_share.bulk"](run_of(BEFORE, AFTER)) == pytest.approx(5.0)


def test_a_union_only_schema_reads_zero_not_none():
    after = AFTER.replace('route="oracle"} 200000', 'route="oracle"} 0')
    assert READ["rewrite_oracle_share.bulk"](run_of(BEFORE, after)) == 0.0


def test_derived_edges_over_the_snapshots_edges_at_ready():
    assert READ["rewrite_edge_share"](run_of(BEFORE, AFTER)) == pytest.approx(
        100 * 175_500 / 1_305_000)


def test_the_rewrites_phase_at_ready():
    assert READ["rewrite_expand_s"](run_of(BEFORE, AFTER)) == 0.875


@pytest.mark.parametrize("metric", sorted(NEW))
def test_none_on_the_recorded_scrapes_of_a_program_without_the_counters(metric):
    texts = [(HERE / f"recorded_scrape_{name}.txt").read_text() for name in ("before", "after")]
    for family in ("keto_check_rewrite", "keto_snapshot_rewrite_edges", "keto_snapshot_edges",
                   'phase="rewrites"'):
        assert family not in texts[1]
    assert READ[metric](run_of(*texts)) is None


@pytest.mark.parametrize("metric, family", [
    ("rewrite_check_share.bulk", "keto_check_rewrite_checks_total"),
    ("rewrite_oracle_share.bulk", "keto_check_rewrite_route_total"),
    ("rewrite_edge_share", "keto_snapshot_rewrite_edges"),
    ("rewrite_edge_share", "keto_snapshot_edges"),
    ("rewrite_expand_s", 'phase="rewrites"'),
])
def test_none_where_the_program_lacks_the_family(metric, family):
    assert READ[metric](run_of(without(BEFORE, family), without(AFTER, family))) is None


@pytest.mark.parametrize("metric", ["rewrite_check_share.bulk", "rewrite_oracle_share.bulk"])
def test_a_window_in_which_nothing_was_counted_has_nothing_to_read(metric):
    assert READ[metric](run_of(BEFORE, BEFORE)) is None


# -- the cell and the manifest -----------------------------------------------------

def test_the_cell_builds_and_reports_what_the_bulk_cells_report():
    cell = run.Cell(CELL)
    assert cell.n_tuples == 1_000_000 and cell.chips == 1
    assert cell.workload["traffic"] == "bulk" and cell.mix["driver"] == "closed_batch"
    assert [m["name"] for m in cell.e2e] == ["checks_per_s", "setup_s"]
    names = {m["name"] for m in cell.layers}
    assert set(NEW) <= names
    other = {m["name"] for m in run.Cell("github-org.bulk").layers}
    assert other <= names  # every reader of its control reads here too
    # and the label build, the one layer this configuration sets a gate for
    assert names - other == set(NEW) | {"label_build_s"}


@pytest.mark.parametrize("metric", sorted(NEW))
def test_the_manifest_declares_it_for_the_new_cell(metric):
    (entry,) = [m for m in manifest_mod.load()["per_layer"] if m["name"] == metric]
    cells = entry.pop("workloads")  # a later cell may be appended
    assert cells[0] == CELL
    unit, better, source, layer, moves = NEW[metric]
    assert entry == {"name": metric, "unit": unit, "better": better, "source": source,
                     "layer": layer, "moves": moves}


def test_the_manifest_validates_and_names_the_configuration():
    m = manifest_mod.load()
    (entry,) = [c for c in m["configs"] if c["name"] == "github-rewrites"]
    assert entry["file"] == "benchmarks/configs/github-rewrites.json" and entry["reduced"] == ["tuples"]
    assert m["configs"][-1] is entry and m["workloads"][-1]["name"] == CELL
    assert all(len(x["why"]) <= 200 for x in m["configs"][-1:] + m["workloads"][-1:])
    assert len(entry["source"]) <= 200
    config = json.loads((ROOT / entry["file"]).read_text())
    assert config["name"] == "github-rewrites" and config["tuples"] == 1_000_000
