"""A configuration that names a ``work_seed`` (``nested-groups``) gives every
run's seed the same work: one graph, one pool of calls, in another order. One
that names none (``rbac-groups``) draws both from the run's seed, as before."""

import sys
from collections import Counter
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from benchmarks import run  # noqa: E402

SEEDS = (41, 2_600_000_042)


def pools(cell_name):
    cell = run.Cell(cell_name, tuples=20_000)
    out = []
    for seed in SEEDS:
        ctx = cell.inputs(seed, 3.0)
        out.append((ctx, cell.driver.prepare(ctx)["pool"]))
    return cell, out


def test_a_work_seed_fixes_the_graph_and_the_calls_and_the_seed_orders_them():
    cell, ((ctx_a, a), (ctx_b, b)) = pools("nested-groups.bulk")
    assert ctx_a.work_seed == ctx_b.work_seed == cell.config["work_seed"]
    assert (ctx_a.seed, ctx_b.seed) == SEEDS
    assert ctx_a.graph.rows == ctx_b.graph.rows
    call = lambda entry: frozenset(Counter(zip(entry[1], entry[2])).items())
    assert Counter(map(call, a)) == Counter(map(call, b)), "not the same calls"
    assert [q for _, q, _ in a] != [q for _, q, _ in b], "the seed did not reorder them"
    assert sorted(a[0][1]) != sorted(b[0][1]), "the calls kept their places"
    # the body is the call's own tuples, in the order the expectation follows
    for body, queries, expected in a[:3]:
        assert len(queries) == len(expected) == int(cell.mix["batch"])
        assert body == run.traffic.batch_body(queries)


def test_the_same_seed_gives_the_same_calls_in_the_same_order():
    cell = run.Cell("nested-groups.bulk", tuples=20_000)
    once, again = (cell.driver.prepare(cell.inputs(SEEDS[1], 3.0))["pool"] for _ in range(2))
    assert once == again


@pytest.mark.parametrize("cell_name", ["rbac-groups.bulk", "github-org.bulk", "drive-docs.bulk"])
def test_without_the_key_the_work_follows_the_seed(cell_name):
    cell, ((ctx_a, a), (ctx_b, b)) = pools(cell_name)
    assert "work_seed" not in cell.config
    assert (ctx_a.work_seed, ctx_b.work_seed) == SEEDS
    assert ctx_a.graph.rows != ctx_b.graph.rows
    assert sorted(q for _, qs, _ in a for q in qs) != sorted(q for _, qs, _ in b for q in qs)
