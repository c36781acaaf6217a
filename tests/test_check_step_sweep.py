"""``check_step`` sweeps its buckets in place (keto_tpu/check/kernels.py,
``sweep`` = ``"up"`` / ``"down"``: a bucket's gather reads what the buckets
before it in the same sweep wrote) or all from the same carry (``"whole"``, a
mesh's form). The update is monotone over a finite lattice, so every order
reaches the same least fixpoint and the converged sweep carries the same
"reached via >= 1 edge" term: the packed decisions and the truncation flag are
those of the whole-step pull, bit for bit, in no more pulls. Held here on
hand-laid buckets (layered graphs, cycles, an overlay, relay rows, a cap that
truncates, no active row) against a plain closure, on the engine against the
CPU oracle, and on ``CheckDispatch._settle_block_iters``, which probes the
snapshot under both in-place orders and keeps the one of fewer pulls."""

import random
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from keto_tpu import namespace as namespace_pkg
from keto_tpu.check import CheckEngine, kernels
from keto_tpu.check.tpu_engine import TpuCheckEngine
from keto_tpu.persistence.memory import MemoryPersister
from keto_tpu.relationtuple import RelationTuple, SubjectID, SubjectSet

B = 64  # queries a slice: two words of bitmap
IN_PLACE = kernels.IN_PLACE_SWEEPS


def pad(a, n, fill):
    a = np.asarray(a, np.int32)
    return np.concatenate([a, np.full(n - a.size, fill, np.int32)])


class Laid:
    """A graph over interior rows laid out as a snapshot lays it: the rows
    with in-edges first (``na`` active rows), bucketed by the power of two
    over their in-degree, the rows nothing points at after them; some edges
    held back as the delta overlay's ELL."""

    def __init__(self, rng, n, edges, overlay=0, held=()):
        edges = sorted(set(edges))
        rng.shuffle(edges)
        held = set(held) | set(edges[:overlay])
        # the overlay's destinations are active rows: they keep a base edge
        base_dst = {d for s, d in edges if (s, d) not in held}
        held = {(s, d) for s, d in held if d in base_dst}
        indeg = np.zeros(n, int)
        for s, d in edges:
            if (s, d) not in held:
                indeg[d] += 1
        key = np.where(indeg > 0, np.ceil(np.log2(np.maximum(indeg, 1))) + 1, 99)
        order = sorted(range(n), key=lambda v: (key[v], rng.random()))
        row = np.empty(n, int)
        row[order] = np.arange(n)
        self.ni, self.na = n, int((indeg > 0).sum())
        self.adj = np.zeros((n, n), bool)  # [src row, dst row]
        for s, d in edges:
            self.adj[row[s], row[d]] = True
        buckets, valid = [], []
        for k in sorted(set(key[indeg > 0])):
            rows = [v for v in order if key[v] == k]
            cap = 1 << int(k - 1)
            nb = np.full((len(rows), cap), n, np.int32)
            for i, v in enumerate(rows):
                srcs = [row[s] for s, d in edges if d == v and (s, d) not in held]
                nb[i, : len(srcs)] = srcs
            buckets.append(jnp.asarray(nb))
            valid.append(len(rows))
        self.buckets, self.valid = tuple(buckets), tuple(valid)
        self.ov = None
        if held:
            dsts = sorted({row[d] for _, d in held})
            C = max(sum(1 for _, d in held if row[d] == t) for t in dsts)
            ovn = np.full((len(dsts) + 1, C), n, np.int32)  # one padded row, as the engine pads
            for i, t in enumerate(dsts):
                srcs = [row[s] for s, d in held if row[d] == t]
                ovn[i, : len(srcs)] = srcs
            self.ov = (jnp.asarray(ovn), jnp.asarray(pad(dsts, len(dsts) + 1, self.na)))

    def draw(self, rng, n_seed=400, n_ans=256):
        """Entries of one slice: seed (row, query) pairs, sink answer entries,
        interior targets (``ni``: none)."""
        key = np.unique(rng.integers(0, B, n_seed) * 10_000 + rng.integers(0, self.ni, n_seed))
        self.seed_rows, self.seed_q = key % 10_000, key // 10_000
        self.a_rows, self.a_q = rng.integers(0, self.ni, n_ans), rng.integers(0, B, n_ans)
        self.targets = rng.integers(0, self.ni + 1, B)
        return self

    def entries(self, S=512, SA=256):
        seeds = [pad(self.seed_rows, S, self.ni + 1), pad(self.seed_q, S, 0),
                 pad([], S, self.ni + 1), pad([], S, 0)]
        answers = [pad(self.a_rows, SA, self.ni), pad(self.a_q, SA, 0)]
        return jnp.asarray(np.concatenate(seeds + answers + [self.targets.astype(np.int32)])), (S, S, SA, B)

    def run(self, sweep, it_cap=None, hub=None, entries=None):
        buf, sizes = entries or self.entries()
        out = np.asarray(kernels._check_kernel(
            self.buckets, buf, ov_nbrs=self.ov and self.ov[0], ov_dst=self.ov and self.ov[1],
            hub_nbrs=hub, sizes=sizes, n_active=self.na, n_int=self.ni, valid_rows=self.valid,
            it_cap=it_cap or self.na + 1, sweep=sweep))
        W = B // 32
        return out[:W], int(out[W]), bool(out[W + 1])

    def closure(self):
        """The plain answer: a query's target is reached via one edge or more
        from its seed rows; a sink's answer row counts its own seed bit."""
        reach = np.zeros((B, self.ni), bool)
        reach[self.seed_q, self.seed_rows] = True
        while True:
            grown = reach | (reach.astype(np.uint8) @ self.adj.astype(np.uint8) > 0)
            if (grown == reach).all():
                break
            reach = grown
        via_edge = reach.astype(np.uint8) @ self.adj.astype(np.uint8) > 0
        hit = np.zeros(B, bool)
        has = self.targets < self.ni
        hit[has] = via_edge[np.flatnonzero(has), self.targets[has]]
        np.logical_or.at(hit, self.a_q, reach[self.a_q, self.a_rows])
        words = np.zeros(B // 32, np.uint32)
        for q in np.flatnonzero(hit):
            words[q // 32] |= np.uint32(1) << np.uint32(q % 32)
        return words


def layered(rng, n=160, layers=9, fan=3):
    """Edges from a layer to later layers only: the nesting of a directory."""
    layer = rng.integers(0, layers, n)
    return [(s, d) for s in range(n) for d in rng.integers(0, n, fan) if layer[s] < layer[d]]


def cyclic(rng, n=120, m=260):
    return [(int(s), int(d)) for s, d in rng.integers(0, n, (m, 2)) if s != d]


def chain(n=40, extra=()):
    return [(i, i + 1) for i in range(n - 1)] + list(extra)


GRAPHS = {"layered": layered, "cyclic": cyclic}


@pytest.mark.parametrize("overlay", [0, 12], ids=["base", "overlay"])
@pytest.mark.parametrize("seed", [42, 2_600_420_043])
@pytest.mark.parametrize("kind", sorted(GRAPHS))
def test_every_sweep_gives_the_whole_steps_words_in_no_more_pulls(kind, seed, overlay):
    rng = np.random.default_rng(seed)
    g = Laid(random.Random(seed), 160 if kind == "layered" else 120, GRAPHS[kind](rng), overlay)
    assert len(g.buckets) >= 3 and (g.ov is not None) == bool(overlay)
    for _ in range(3):
        want = g.draw(rng).closure()
        words, pulls, truncated = g.run("whole")
        assert np.array_equal(words, want) and want.any() and not truncated
        assert pulls > 2, "one hop deep: the sweeps have nothing to differ on"
        for sweep in IN_PLACE:
            got, n, cut = g.run(sweep)
            assert np.array_equal(got, want) and not cut
            assert 2 <= n <= pulls, f"{sweep}: {n} pulls where the whole step took {pulls}"


def test_an_overlay_edge_that_closes_a_path_is_walked_inside_the_sweep():
    """A chain of 40 whose edge 20 -> 21 lies in the overlay (row 21 keeps a
    base edge from row 5, so it stays active): the far end is reached through
    the overlay alone, 19 rows beyond it, under every sweep."""
    g = Laid(random.Random(1), 40, chain(40, extra=[(5, 21)]), held=[(20, 21)])
    assert g.ov is not None and g.ov[0].shape == (2, 1)
    g.draw(np.random.default_rng(2))
    head, tail = int(np.flatnonzero(g.adj.sum(0) == 0)[0]), int(np.flatnonzero(g.adj.sum(1) == 0)[0])
    g.seed_rows, g.seed_q = np.array([head]), np.array([0])
    g.a_rows, g.a_q = np.array([], int), np.array([], int)
    g.targets = np.full(B, g.ni)
    g.targets[0] = tail
    want = g.closure()
    assert want[0] & 1, "the chain's end is not reached: vacuous"
    for sweep in kernels.SWEEPS:
        words, pulls, truncated = g.run(sweep)
        assert np.array_equal(words, want) and not truncated and pulls > 2


def relayed(g, rng, C=8):
    """The slice's sink answers regrouped as hub sinks have them: a query's
    rows in relay rows of ``C``, an entry naming relay row k as ``ni + 1 + k``."""
    rows, rows_q, short, short_q, relays = [], [], [], [], []
    for q in range(B):
        mine = rng.choice(g.ni, rng.integers(1, 40), replace=False)
        rows += mine.tolist()
        rows_q += [q] * mine.size
        for c0 in range(0, mine.size, C):
            short.append(g.ni + 1 + len(relays))
            short_q.append(q)
            relays.append(pad(mine[c0:c0 + C], C, g.ni))
    return (np.array(rows), np.array(rows_q)), (np.array(short), np.array(short_q)), jnp.asarray(np.stack(relays))


@pytest.mark.parametrize("sweep", kernels.SWEEPS)
def test_relay_rows_of_hub_sinks_read_the_same_fixpoint(sweep):
    rng = np.random.default_rng(34)
    g = Laid(random.Random(34), 160, layered(rng)).draw(rng)
    plain, short, hub = relayed(g, rng)
    g.a_rows, g.a_q = plain
    want = g.closure()
    assert np.array_equal(g.run(sweep, entries=g.entries(SA=4096))[0], want)
    g.a_rows, g.a_q = short
    words, pulls, truncated = g.run(sweep, hub=hub, entries=g.entries(SA=4096))
    assert np.array_equal(words, want) and want.any() and pulls > 2 and not truncated


@pytest.mark.parametrize("sweep", kernels.SWEEPS)
def test_a_cap_that_cuts_the_loop_sets_the_flag_and_the_exact_cap_agrees(sweep):
    """A chain of 40 rows (edges back along it put them in two buckets and
    shorten nothing) is 39 hops deep from its head; at ``it_cap`` 2 the loop
    stops after its first block of 8 pulls, still growing under every sweep,
    and says so; at the never-truncating cap (active rows + 1) it gives the
    closure."""
    rng = np.random.default_rng(7)
    g = Laid(random.Random(7), 40, chain(40, extra=[(i + 3, i) for i in range(1, 36, 2)])).draw(rng)
    assert len(g.buckets) == 2
    head = int(np.flatnonzero(g.adj.sum(0) == 0)[0])
    g.seed_rows, g.seed_q = np.array([head]), np.array([3])
    _, pulls, truncated = g.run(sweep, it_cap=2)
    assert truncated and pulls == 8
    words, pulls, truncated = g.run(sweep, it_cap=g.na + 1)
    assert np.array_equal(words, g.closure()) and not truncated and 8 < pulls <= g.na + 1


@pytest.mark.parametrize("sweep", kernels.SWEEPS)
def test_without_an_active_row_there_is_no_loop_under_any_sweep(sweep):
    g = Laid(random.Random(3), 30, []).draw(np.random.default_rng(3))
    assert g.na == 0 and g.buckets == ()
    words, pulls, truncated = g.run(sweep, it_cap=8)
    assert np.array_equal(words, g.closure()) and pulls == 0 and not truncated
    assert words.any(), "a sink's answer row holds its own seed bit: some query is granted"


def test_an_unknown_sweep_is_refused():
    g = Laid(random.Random(5), 40, chain(40)).draw(np.random.default_rng(5))
    with pytest.raises(ValueError, match="sweep"):
        g.run("sideways")


# -- the engine: every order against the CPU oracle ---------------------------------


def T(ns, obj, rel, sub):
    return RelationTuple(namespace=ns, object=obj, relation=rel, subject=sub)


NSS = [namespace_pkg.Namespace(id=1, name="g"), namespace_pkg.Namespace(id=2, name="d")]


def nested_rows(seed, groups=60, depth_edges=150, users=40):
    """Groups nested in groups (cycles among them), users in groups, documents
    granted to groups, and checks on both: ``(rows, queries)``."""
    rng = random.Random(seed)
    rows = [T("g", f"g{rng.randrange(groups)}", "m", SubjectSet("g", f"g{rng.randrange(groups)}", "m"))
            for _ in range(depth_edges)]
    rows += [T("g", f"g{rng.randrange(groups)}", "m", SubjectID(f"u{rng.randrange(users)}")) for _ in range(80)]
    rows += [T("d", f"doc{i}", "view", SubjectSet("g", f"g{rng.randrange(groups)}", "m")) for i in range(30)]
    queries = [T("d", f"doc{rng.randrange(30)}", "view", SubjectID(f"u{rng.randrange(users)}")) for _ in range(200)]
    queries += [T("g", f"g{rng.randrange(groups)}", "m", SubjectID(f"u{rng.randrange(users)}")) for _ in range(100)]
    return rows, queries


def nested_store(seed, **kw):
    rows, queries = nested_rows(seed, **kw)
    p = MemoryPersister(namespace_pkg.MemoryManager(NSS))
    p.write_relation_tuples(*rows)
    return p, queries


@pytest.fixture(scope="module")
def nested():
    p, queries = nested_store(42)
    oracle = CheckEngine(p)
    return p, queries, [oracle.subject_is_allowed(q) for q in queries]


@pytest.mark.parametrize("sweep", kernels.SWEEPS)
def test_the_engine_answers_as_the_oracle_under_every_sweep_and_counts_its_slices(nested, sweep):
    p, queries, want = nested
    engine = TpuCheckEngine(p, p.namespaces, labels_enabled=False)
    try:
        engine.dispatch._sweep = sweep
        assert engine.batch_check(queries) == want and any(want) and not all(want)
        counters = engine.maintenance.raw()[0]
        assert counters["bfs_slices"] > 0 and counters["bfs_steps"] > 2 * counters["bfs_slices"]
        assert counters[f"sweep_slices_{sweep}"] == counters["bfs_slices"]
        assert not any(counters.get(f"sweep_slices_{o}") for o in kernels.SWEEPS if o != sweep)
        # a write that lands in the overlay closes new paths: still the oracle's
        p2, _ = nested_store(42)
        fresh = TpuCheckEngine(p2, p2.namespaces, labels_enabled=False)
        try:
            fresh.dispatch._sweep = sweep
            fresh.snapshot()
            p2.write_relation_tuples(*[
                T("g", f"g{i}", "m", SubjectSet("g", f"g{(7 * i + 3) % 60}", "m")) for i in range(0, 60, 5)
            ])
            oracle = CheckEngine(p2)
            assert fresh.batch_check(queries) == [oracle.subject_is_allowed(q) for q in queries]
        finally:
            fresh.close()
    finally:
        engine.close()


@pytest.mark.parametrize("sweep", kernels.SWEEPS)
def test_a_truncated_slice_is_rerun_to_the_exact_fixpoint_under_every_sweep(nested, sweep):
    p, queries, want = nested
    engine = TpuCheckEngine(p, p.namespaces, labels_enabled=False, it_cap=1)
    try:
        engine.dispatch._sweep = sweep
        assert engine.batch_check(queries) == want
    finally:
        engine.close()


# -- the probe settles the order -------------------------------------------------------


def chain_snapshot(direction, k=6):
    """What ``_settle_block_iters`` reads of a snapshot, hand-laid: one
    passive source row and a chain of ``k`` active rows, one row a bucket,
    running up the device ids (``along``) or down them (``against``)."""
    ids = list(range(k)) if direction == "along" else list(range(k))[::-1]
    src = {ids[0]: k}  # the chain's first row hangs off the passive row k
    src.update({ids[i]: ids[i - 1] for i in range(1, k)})
    buckets = tuple(jnp.asarray(np.array([[src[r]]], np.int32)) for r in range(k))
    return SimpleNamespace(
        num_active=k, num_int=k + 1, buckets=[SimpleNamespace(n=1)] * k,
        device_buckets=buckets, device_overlay=None, device_hub=None,
    )


@pytest.fixture(scope="module")
def small_engine():
    p, _ = nested_store(5, groups=12, depth_edges=20, users=6)
    engine = TpuCheckEngine(p, p.namespaces, labels_enabled=False)
    yield engine
    engine.close()


@pytest.mark.parametrize("direction, kept, pulls", [
    ("along", "up", {"up": 2, "down": 7}),
    ("against", "down", {"up": 7, "down": 2}),
])
def test_the_probe_keeps_the_order_of_fewer_pulls_and_keys_the_programs_by_it(
    small_engine, direction, kept, pulls
):
    d = small_engine.dispatch
    was = d._sweep, d._block_iters, d._block_iters_shape
    try:
        d._sweep, d._block_iters = "up", 1
        assert d._check_fixed(8)[-1] == "up"
        d._settle_block_iters(chain_snapshot(direction), 32)
        gauges = small_engine.maintenance.raw()[1]
        assert {o: gauges[f"sweep_probe_pulls_{o}"] for o in IN_PLACE} == pulls
        assert d._sweep == kept and d._check_fixed(8)[-1] == kept
        assert d._check_fixed(8) != (8, d._block_iters, d._donate_entries)  # the order is in the key
        assert d._block_iters == 4  # settled from the kept order's 2 pulls, not the other's 7
    finally:
        d._sweep, d._block_iters, d._block_iters_shape = was


def test_a_tie_keeps_device_id_order(small_engine):
    d = small_engine.dispatch
    was = d._sweep, d._block_iters, d._block_iters_shape
    try:
        d._sweep = "down"
        d._settle_block_iters(chain_snapshot("along", k=1), 32)
        gauges = small_engine.maintenance.raw()[1]
        assert gauges["sweep_probe_pulls_up"] == gauges["sweep_probe_pulls_down"] == 2
        assert d._sweep == "up"
    finally:
        d._sweep, d._block_iters, d._block_iters_shape = was


def test_the_warm_up_probes_the_served_snapshot_and_serves_the_kept_order(nested, monkeypatch):
    p, queries, want = nested
    engine = TpuCheckEngine(p, p.namespaces, labels_enabled=False)
    try:
        d = engine.dispatch
        monkeypatch.setattr(d, "stream_widths", lambda snap: [32])
        assert engine.warm_compile() == 1
        gauges = engine.maintenance.raw()[1]
        up, down = (gauges[f"sweep_probe_pulls_{o}"] for o in IN_PLACE)
        assert min(up, down) >= 2 and d._sweep == ("down" if down < up else "up")
        assert engine.batch_check(queries[:32]) == want[:32]
        counters = engine.maintenance.raw()[0]
        assert counters.get(f"sweep_slices_{d._sweep}", 0) == counters["bfs_slices"] > 0
    finally:
        engine.close()


@pytest.mark.skipif(len(jax.devices()) < 8, reason="needs the 8-device CPU mesh")
def test_a_mesh_keeps_the_whole_step(nested, monkeypatch):
    from keto_tpu.parallel import make_mesh

    p, queries, want = nested
    engine = TpuCheckEngine(p, p.namespaces, mesh=make_mesh(graph=2), shard_rows=True, labels_enabled=False)
    try:
        d = engine.dispatch
        assert d._sweep == "whole" and d._check_fixed(8)[-1] == "whole"
        monkeypatch.setattr(d, "stream_widths", lambda snap: [32])
        engine.warm_compile()
        assert d._sweep == "whole"
        assert "sweep_probe_pulls_up" not in engine.maintenance.raw()[1]
        assert engine.batch_check(queries[:64]) == want[:64]
        counters = engine.maintenance.raw()[0]
        assert counters["sweep_slices_whole"] == counters["bfs_slices"] > 0
    finally:
        engine.close()
