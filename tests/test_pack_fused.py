"""The label route's one native pass (``native_pack.pack_labeled``,
native/pack.cpp ``keto_pack_labeled``) against what it replaces on the chunks
it takes: ``pack_chunk`` and ``label_pairs`` (keto_tpu/check/pack.py).

The contract under test, on the graph shapes the benchmark's cells serve
(their own generators at a small size) and chunk widths 1, 31, 32 and 4,096:

- equal ``host_ans``, equal fallback mask, equal counts by reason, equal
  figures for ``keto_check_pack_rows_total``;
- equal **sets** of pairs ``(pa, pb, pq)`` and of the riders' entries (the
  order is the pass's own), the staging buffer padded as ``label_step``
  expects it (``ni``, ``ni``, 0);
- a chunk the pass declines (``no_library``, ``mesh``, ``overlay``,
  ``multi``) takes the numpy path, gives its answers and is counted once;
- a library of another ABI version loads nothing.
"""

import importlib.util
import json
import random
from pathlib import Path

import numpy as np
import pytest

from keto_tpu.check import native_pack
from keto_tpu.check.engine import CheckEngine
from keto_tpu.check.pack import (
    LABEL_REASONS, _WORD_WIDTHS, _entry_pad, hub_usable, label_pairs, pack_chunk, whole_min,
)
from keto_tpu.check.tpu_engine import TpuCheckEngine
from keto_tpu.relationtuple import RelationTuple, SubjectID, SubjectSet

from rewrites_util import as_tuple, manager_of
from keto_tpu.persistence.memory import MemoryPersister

ROOT = Path(__file__).resolve().parents[1]
PAIR_CAP = 64

needs_native = pytest.mark.skipif(
    not native_pack.available(), reason="native pack library not built"
)

#: shape -> (configuration of benchmarks/configs, engine options)
SHAPES = {
    "rbac-groups": ("rbac-groups", {}),
    "github-org": ("github-org", {}),
    "drive-docs": ("drive-docs", {}),  # folder trees under groups: pair_cap, whole_slice
    "nested-groups": ("nested-groups", {}),  # a Kronecker directory: hub sinks, relay rows
    "github-rewrites": ("github-rewrites", {}),  # a union-only rewritten store
    # the same store under a label index with gaps: uncertifiable pairs
    "github-org-capped-labels": ("github-org", {"labels_max_width": 2, "labels_landmarks": 24}),
}
WIDTHS = (1, 31, 32, 4096)
N_TUPLES = 20_000
N_QUERIES = 3 * 4096


def _generator(name: str):
    path = ROOT / "benchmarks" / "generators" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"_fused_gen_{name}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def _queries(gen, graph, rows, rng):
    """The cell's own queries, then what they never ask: a set as the
    subject (an interior target), a set against itself (``self_hit``), a user
    and an object no row names."""
    objects = [rng.randrange(graph.n_objects) for _ in range(N_QUERIES - 512)]
    qs, _ = gen.queries(graph, random.Random(rng.getrandbits(32)), objects)
    out = [
        RelationTuple(namespace=ns, object=o, relation=r, subject=SubjectID(u))
        for ns, o, r, u in qs
    ]
    sets = [(r[0], r[1], r[2]) for r in rng.sample(rows, 256)]
    sets += [(r[4], r[5], r[6]) for r in rows[:4000] if r[4] is not None][:128]
    for k in range(512):
        ns, obj, rel = rng.choice(sets)
        kind = k % 4
        if kind == 0:  # a set under another set
            sns, sobj, srel = rng.choice(sets)
            subject = SubjectSet(sns, sobj, srel)
        elif kind == 1:  # a set under itself
            subject = SubjectSet(ns, obj, rel)
        elif kind == 2:
            subject = SubjectID(f"nobody-{k}")
        else:
            obj, subject = f"nothing-{k}", SubjectID(qs[k][3])
        out.append(RelationTuple(namespace=ns, object=obj, relation=rel, subject=subject))
    rng.shuffle(out)
    return out


@pytest.fixture(scope="module")
def built():
    """shape -> (engine, snapshot, sd, tg), built when first asked for."""
    cache, engines = {}, []

    def get(shape: str):
        if shape not in cache:
            config_name, options = SHAPES[shape]
            config = json.loads((ROOT / "benchmarks" / "configs" / f"{config_name}.json").read_text())
            gen = _generator(config["generator"])
            rng = random.Random(39_000 + len(cache))
            graph = gen.build(random.Random(rng.getrandbits(32)), N_TUPLES)
            nm = manager_of(config)
            p = MemoryPersister(nm)
            p.write_relation_tuples(*[as_tuple(r) for r in graph.rows])
            engine = TpuCheckEngine(p, nm, **options)
            engines.append(engine)
            queries = _queries(gen, graph, graph.rows, rng)
            # one served batch first: the engine uploads what it serves from
            # (a hub snapshot's relay rows among it)
            engine.batch_check(queries[:64])
            engine.labels_settled()
            snap = engine.snapshot()
            d = engine.dispatch
            sd, tg, multi = d._resolve_bulk(snap, queries)
            if snap.rewrites is not None:
                d._rewrite_split(snap, queries, sd, tg, multi)
            assert not multi and snap.labels is not None
            assert native_pack.walk_eligible(snap)
            cache[shape] = (engine, snap, sd, tg)
        return cache[shape]

    yield get
    for engine in engines:
        engine.close()


def _pair_rows(a, b, c):
    """Three aligned arrays as one sorted [n, 3] table: a multiset."""
    t = np.stack([np.asarray(x, np.int64) for x in (a, b, c)], axis=1)
    return t[np.lexsort(t.T[::-1])]


def _entry_rows(rows, qs, pad_row, keep):
    rows, qs = np.asarray(rows, np.int64), np.asarray(qs, np.int64)
    m = rows != pad_row
    m[m] &= keep[qs[m]]
    t = np.stack([qs[m], rows[m]], axis=1)
    return t[np.lexsort(t.T[::-1])]


def _compare_chunk(snap, sd, tg, i0, i1, W):
    idx, ni, nq, B = snap.labels, snap.num_int, i1 - i0, 32 * W
    view = native_pack.PackView(snap, idx, PAIR_CAP, hub_usable(snap))
    host, fallback, counts = native_pack.pack_labeled(view, sd, tg, i0, i1, whole_min(W))
    packed, host_ref = pack_chunk(snap, sd, tg, {}, i0, i1, W)
    assert (host == host_ref).all()
    assert bool(counts.packed) == (packed is not None)
    if packed is None:
        return None
    fb_ref, reasons_ref, whole_ref, (pa, pb, pq) = label_pairs(
        snap, idx, packed, {}, i0, i1, W, PAIR_CAP
    )
    assert (fallback == fb_ref).all()
    assert counts.fallbacks == int(fb_ref.sum()) and bool(counts.whole) == whole_ref
    reasons = {r: getattr(counts, r) for r in LABEL_REASONS}
    assert reasons == {r: reasons_ref.get(r, 0) for r in LABEL_REASONS}
    assert reasons_ref["multi"] == 0

    # the figures _note_packed reads off the seven arrays
    e1r, e1q, e2r, e2q, ar, aq, targets = packed
    seeds = np.count_nonzero(e1r != ni + 1) + np.count_nonzero(e2r != ni + 1)
    rows = np.count_nonzero(ar < ni) + np.count_nonzero(targets[:nq] < ni)
    if snap.hub_rows is not None:
        rows += int(snap.hub_rows[ar[ar > ni] - (ni + 1)].sum())
    assert (counts.seed_rows, counts.target_rows) == (int(seeds), int(rows))

    # the pairs, as label_step reads them from the staging buffer
    n = counts.pairs
    assert n == pa.size
    P = _entry_pad(B, n)
    buf = np.full(3 * P, -7, np.int32)
    native_pack.labeled_pairs(buf, P)
    assert (_pair_rows(buf[:n], buf[P:P + n], buf[2 * P:2 * P + n]) == _pair_rows(pa, pb, pq)).all()
    assert (buf[n:P] == ni).all() and (buf[P + n:2 * P] == ni).all() and (buf[2 * P + n:] == 0).all()
    with pytest.raises(ValueError):
        native_pack.labeled_pairs(np.zeros(3 * P + 1, np.int32), P)
    if n:
        with pytest.raises(ValueError):
            native_pack.labeled_pairs(np.zeros(3 * (n - 1), np.int32), n - 1)

    # the riders' entries: those of the queries that fell back, and every target
    if fb_ref.any():
        r1r, r1q, r2r, r2q, rar, raq, rt = native_pack.labeled_riders(counts, B)
        always = np.ones(nq, bool)
        assert (_entry_rows(r1r, r1q, ni + 1, always) == _entry_rows(e1r, e1q, ni + 1, fb_ref)).all()
        assert (_entry_rows(r2r, r2q, ni + 1, always) == _entry_rows(e2r, e2q, ni + 1, fb_ref)).all()
        assert (_entry_rows(rar, raq, ni, always) == _entry_rows(ar, aq, ni, fb_ref)).all()
        assert rt.dtype == np.int32 and (rt == targets).all()
        assert fb_ref[r1q].all() and fb_ref[r2q].all() and fb_ref[raq].all()
    return reasons, n, whole_ref


@needs_native
@pytest.mark.parametrize("width", WIDTHS)
@pytest.mark.parametrize("shape", list(SHAPES))
def test_fused_pass_equals_pack_chunk_and_the_pairing(built, shape, width):
    engine, snap, sd, tg = built(shape)
    W = next(w for w in _WORD_WIDTHS if 32 * w >= width)
    rng = random.Random(width)
    starts = [0, sd.shape[0] - width] + [rng.randrange(sd.shape[0] - width) for _ in range(6 if width > 64 else 60)]
    seen, pairs, wholes = {r: 0 for r in LABEL_REASONS}, 0, 0
    for i0 in starts:
        got = _compare_chunk(snap, sd, tg, i0, i0 + width, W)
        if got is not None:
            for r, c in got[0].items():
                seen[r] += c
            pairs += got[1]
            wholes += got[2]
    # the shapes bring what the comparison is for
    if width == 4096:
        assert pairs or wholes
        if shape == "drive-docs":
            assert seen["pair_cap"] and wholes
        if shape == "nested-groups":
            assert snap.hub_ptr is not None and seen["pair_cap"]
        if shape == "github-org-capped-labels":
            assert seen["uncertifiable"]
        assert seen["self_hit"]


def _store(make_persister):
    p = make_persister([("a", 1), ("b", 2)])
    rows = [RelationTuple("a", f"doc{i}", "view", SubjectSet("b", f"g{i % 7}", "member")) for i in range(40)]
    rows += [RelationTuple("b", f"g{i}", "member", SubjectSet("b", f"g{i + 1}", "member")) for i in range(6)]
    rows += [RelationTuple("b", f"g{i % 7}", "member", SubjectID(f"u{i}")) for i in range(30)]
    p.write_relation_tuples(*rows)
    queries = [
        RelationTuple("a", f"doc{i % 40}", "view", SubjectID(f"u{(3 * i) % 34}")) for i in range(96)
    ]
    return p, queries


def _declines(engine) -> dict:
    c = engine.maintenance.snapshot()
    return {k[len("pack_declines_"):]: v for k, v in c.items() if k.startswith("pack_declines_")}


@needs_native
@pytest.mark.parametrize("reason", ["none", "no_library", "mesh", "overlay", "multi"])
def test_a_declined_chunk_takes_the_numpy_path_and_is_counted_once(make_persister, monkeypatch, reason):
    p, queries = _store(make_persister)
    options = {}
    if reason == "mesh":
        from keto_tpu.parallel.mesh import make_mesh

        options["mesh"] = make_mesh(graph=1)
    if reason == "no_library":
        monkeypatch.setattr(native_pack, "available", lambda: False)
    if reason == "multi":
        queries = queries + [RelationTuple("a", "", "view", SubjectID("u3"))]
    engine = TpuCheckEngine(p, p.namespaces, **options)
    oracle = CheckEngine(p)
    try:
        engine.batch_check(queries[:8])
        assert engine.labels_settled()  # the index may be built behind the first answers
        if reason == "overlay":
            # a membership goes: a tombstone on a sink's in-edge, which the host
            # masks and the label index never held
            p.delete_relation_tuples(RelationTuple("b", "g2", "member", SubjectID("u2")))
            snap = engine.snapshot()
            assert snap.ov_removed is not None and snap.ov_removed.size and snap.labels_usable
        fused, before = native_pack.COUNTERS["fused"], _declines(engine)
        routes = dict(engine.dispatch.route_slices)
        got = engine.batch_check(queries)
        assert got == [oracle.subject_is_allowed(q) for q in queries]
        chunks = sum(engine.dispatch.route_slices.values()) - sum(routes.values())
        assert chunks == 1
        after = _declines(engine)
        moved = {k: after[k] - before.get(k, 0) for k in after if after[k] != before.get(k, 0)}
        if reason == "none":
            assert moved == {} and native_pack.COUNTERS["fused"] == fused + 1
        else:
            assert moved == {reason: 1} and native_pack.COUNTERS["fused"] == fused
    finally:
        engine.close()


@needs_native
def test_a_library_of_another_abi_version_loads_nothing(monkeypatch):
    """``load_library`` takes a library only at the binding's own ABI number:
    an older build left beside newer Python (the parent's was 3) is passed
    over and every chunk packs with numpy."""
    assert native_pack.load_library().keto_pack_version() == native_pack._ABI_VERSION == 4
    monkeypatch.setattr(native_pack, "_ABI_VERSION", 3)
    monkeypatch.setattr(native_pack, "_lib", None)
    monkeypatch.setattr(native_pack, "_lib_checked", False)
    assert native_pack.load_library() is None and not native_pack.available()
