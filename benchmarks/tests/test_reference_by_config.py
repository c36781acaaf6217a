"""A configuration names its own plain reference. Without the key the judge is
``benchmarks/reference.py``, as before; a named one is found in
``benchmarks/references/`` and is the one a run's line and the control use; a
missing or malformed one stops the run before a daemon is started. The
fixture (fixture_rewrites.json, fixture_folders.py: no cell of
``BENCHMARK.json``) shows the hook live: answers that are right by Keto v0.7
and wrong by the schema come out not correct."""

import json
import random
import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from benchmarks import control, reference, run, traffic  # noqa: E402
from benchmarks.daemon import BenchFailure, Metrics  # noqa: E402

FIXTURE = Path(__file__).with_name("fixture_rewrites.json")
SEED = 2_600_000_036

#: ``control_run(Cell(cell, tuples=20_000), SEED, 3.0, n=6_000)`` as the tree
#: before the hook printed it (commit f8e57aa): analytic, sample
RECORDED = {
    "rbac-groups.bulk": (1559, 524),
    "github-org.bulk": (65, 13),
    "drive-docs.bulk": (76, 34),
    "nested-groups.bulk": (8, 3),
}


def cell_from_file(path: Path, generators: str, mix_edits=(), **config_edits):
    """A ``bulk`` cell built from a configuration's file instead of
    ``BENCHMARK.json``: what ``Cell.__init__`` loads by name, loaded the same way."""
    cell = run.Cell.__new__(run.Cell)
    cell.config = dict(json.loads(path.read_text()), **config_edits)
    cell.name, cell.config_entry = f"{cell.config['name']}.bulk", {"name": cell.config["name"]}
    cell.mix = dict(traffic.load_mix("bulk"), **dict(mix_edits))
    cell.generator = run.load_module(generators, cell.config["generator"])
    cell.driver = run.load_module("drivers", cell.mix["driver"])
    cell.reference = run.load_reference(cell.config)
    cell.n_tuples = int(cell.config["tuples"])
    cell.e2e = [{"name": "checks_per_s", "unit": "checks/s"}, {"name": "setup_s", "unit": "s"}]
    return cell


def fixture_cell(**config_edits):
    return cell_from_file(FIXTURE, "tests", mix_edits={"pool_calls": 8, "batch": 256},
                          **config_edits)


def fixture_requests(cell, seed):
    """``(ctx, queries, expected by the schema, answers by Keto v0.7's rule)``."""
    ctx = cell.inputs(seed, 3.0)
    queries, expected = control.requests_of(cell, ctx)
    return ctx, queries, expected, cell.generator.v07_answers(ctx.graph, queries)


@pytest.mark.parametrize("cell_name", sorted(RECORDED))
def test_without_the_key_the_judge_is_the_one_it_was(cell_name):
    cell = run.Cell(cell_name, tuples=20_000)
    cell.config.pop("work_seed", None)  # the work as f8e57aa drew it: from the run's seed
    assert "reference" not in cell.config
    assert cell.reference.build is reference.Reference
    assert cell.reference.file == "benchmarks/reference.py"
    out = control.control_run(cell, SEED, 3.0, n=6_000)
    assert (out["control_vs_analytic"], out["control_vs_reference_sample"]) == RECORDED[cell_name]
    assert out["reference_vs_analytic"] == 0 and out["reference"] == "benchmarks/reference.py"
    ctx = cell.inputs(SEED, 3.0)
    queries, expected = control.requests_of(cell, ctx)
    judge = cell.reference.build(ctx.graph.rows)
    assert type(judge) is reference.Reference
    assert run.check_sample(judge, [(queries, expected)], SEED, run.REFERENCE_SAMPLE) == (2048, 0)


def test_a_named_reference_is_found_by_name():
    cell = fixture_cell()
    assert cell.reference.file == "benchmarks/references/rewrites.py"
    judge = cell.reference.build(cell.inputs(SEED, 3.0).graph.rows)
    assert type(judge).__module__ == "benchmarks.references.rewrites"
    assert judge._exprs.keys() == {("docs", "viewer")}  # built from the configuration's file


@pytest.mark.parametrize("seed", [5, 6, 2_400_000_011])
def test_v07_answers_on_a_rewrite_schema_are_not_correct(seed):
    cell = fixture_cell()
    ctx, queries, expected, v07 = fixture_requests(cell, seed)
    by_schema = cell.reference.build(ctx.graph.rows)
    by_v07 = reference.Reference(ctx.graph.rows)
    # the fixture's two readings are what the two references say
    assert [by_v07.allowed(*q) for q in queries] == v07
    assert [by_schema.allowed(*q) for q in queries] == expected
    compared, bad = run.check_sample(by_schema, [(queries, v07)], seed, run.REFERENCE_SAMPLE)
    assert compared == min(len(queries), run.REFERENCE_SAMPLE) and bad > compared // 4
    assert run.check_sample(by_schema, [(queries, expected)], seed, run.REFERENCE_SAMPLE)[1] == 0


@pytest.mark.parametrize("answers, correct", [("v07", False), ("schema", True)])
def test_the_line_is_judged_by_the_named_reference(answers, correct):
    """``result_line`` on a window's worth of answers, the daemon faked: a
    program that ignores the schema cannot print ``correct: true``."""
    cell = fixture_cell()
    ctx, queries, expected, v07 = fixture_requests(cell, SEED)
    served = v07 if answers == "v07" else expected
    wrong = sum(1 for got, want in zip(served, expected) if got != want)
    quiet = Metrics("")
    fake = SimpleNamespace(
        result={"metrics": {"checks_per_s": 1.0}, "attempted": len(queries), "failed": 0,
                # a program and a generator that agree with each other, against the schema
                "wrong": 0 if answers == "v07" else wrong,
                "answered": [(queries, served)]},
        device={"platform": "cpu", "kind": "cpu", "count": 1, "memory_peak_bytes": 0},
        before=quiet, after=quiet, cache_before=0, cache_after=0, trace=None)
    said = []
    line = run.result_line(cell, ctx, fake, SimpleNamespace(seed=SEED, trace=0), 1.0, said.append)
    assert line["correct"] is correct
    assert list(line)[-1] == "compared"
    assert (line["compared"]["differ_from_reference"]["value"] > 0) is (not correct)
    assert any("benchmarks/references/rewrites.py" in msg for msg in said)


@pytest.mark.parametrize("seed", [5, 6, 2_400_000_011])
def test_the_control_breaks_the_configurations_own_reference(seed):
    out = control.control_run(fixture_cell(), seed, 3.0)
    assert out["reference"] == "benchmarks/references/rewrites.py"
    assert out["reference_vs_analytic"] == 0, out
    assert out["control_vs_analytic"] > 0 and out["control_vs_reference_sample"] > 0, out
    # judged by v0.7's reference the same fixture would not even agree with itself
    v07 = control.control_run(fixture_cell(reference=None), seed, 3.0)
    assert v07["reference_vs_analytic"] > 0, v07


def malformed(tmp_path, body):
    (tmp_path / "references").mkdir(exist_ok=True)
    (tmp_path / "references" / "odd.py").write_text(body)
    return {"reference": "odd"}


@pytest.mark.parametrize("body, names", [
    (None, "nope.py"),
    ("class Reference:\n    def __init__(self, rows, config): pass\n", "odd.py"),
    ("def allowed(ns, obj, rel, user, max_depth=None): return False\n", "odd.py"),
], ids=["missing", "no-allowed", "no-class"])
def test_a_missing_or_malformed_reference_names_its_file(body, names, tmp_path, monkeypatch):
    monkeypatch.setattr(run, "HERE", tmp_path)
    config = {"reference": "nope"} if body is None else malformed(tmp_path, body)
    with pytest.raises(BenchFailure, match=names):
        run.load_reference(config)


def test_a_missing_reference_stops_the_run_before_a_daemon(monkeypatch):
    def no_daemon(*a, **kw):
        raise AssertionError("a daemon was built")

    def with_missing_reference(entry, _load=run.load_config):
        return dict(_load(entry), reference="nope")

    monkeypatch.setattr(run, "Daemon", no_daemon)
    monkeypatch.setattr(run, "load_store", no_daemon)
    monkeypatch.setattr(run, "load_config", with_missing_reference)
    args = SimpleNamespace(workload="rbac-groups.bulk", seed=1, seconds=1.0, trace=0,
                           platform="cpu", tuples=2_000, keep_log="")
    with pytest.raises(BenchFailure, match="benchmarks/references/nope.py"):
        run.execute(args, lambda msg: None)


def test_a_schema_error_is_raised_when_the_reference_is_built():
    subtract_itself = {"exclusion": {"base": {"this": {}},
                                     "subtract": {"computed_userset": "viewer"}}}
    cell = fixture_cell(namespaces=[
        {"id": 0, "name": "docs", "config": {"relations": {"viewer": subtract_itself}}},
        {"id": 1, "name": "folders"}])
    with pytest.raises(ValueError, match="docs#viewer -> docs#viewer"):
        cell.reference.build(cell.generator.build(random.Random(1), 200).rows)
