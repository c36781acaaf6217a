"""The dispatch thread's `resolve` state by itself, uncontended (ISSUE 44,
ROADMAP S1 (a)): a cell's own graph at its size and its own framed bodies of
4,096, resolved at the door, through `CheckDispatch._dispatch_slices` in a
process that does nothing else. Reads the state off a `DispatchClock` bound to
this thread (what `/metrics` reads while serving), then the same chunks with
the pack + launch stubbed out under cProfile, to split the state into numpy /
native calls and the Python around them.

usage, from the root of the checkout to be read (this one, or an unpacked
`git archive` of another commit: the script uses only names PR 43 already had):
    python3 <path to>/scripts/resolve_split.py <config> <tuples> <seed> <chunks>"""

import cProfile
import importlib.util
import json
import os
import pstats
import random
import sys
import time
from pathlib import Path

tree = Path(os.getcwd())
config_name, n_tuples, seed, n_chunks = sys.argv[1], int(sys.argv[2]), int(sys.argv[3]), int(sys.argv[4])
sys.path.insert(0, str(tree))

import numpy as np  # noqa: E402

from keto_tpu import namespace as namespace_pkg  # noqa: E402
from keto_tpu.check.frame import QueryBatch, QueryFrame  # noqa: E402
from keto_tpu.check.tpu_engine import TpuCheckEngine  # noqa: E402
from keto_tpu.graph.native import FrameTable  # noqa: E402
from keto_tpu.persistence.memory import MemoryPersister  # noqa: E402
from keto_tpu.relationtuple import RelationTuple, SubjectID, SubjectSet  # noqa: E402
from keto_tpu.x import timeline  # noqa: E402
from benchmarks import traffic  # noqa: E402


def load(kind, name):
    spec = importlib.util.spec_from_file_location(f"_split_{kind}_{name}", tree / "benchmarks" / kind / f"{name}.py")
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def as_tuple(r):
    ns, obj, rel, sid, sns, sobj, srel = r
    subject = SubjectID(sid) if sid is not None else SubjectSet(sns, sobj, srel)
    return RelationTuple(namespace=ns, object=obj, relation=rel, subject=subject)


t0 = time.perf_counter()
config = json.loads((tree / "benchmarks" / "configs" / f"{config_name}.json").read_text())
gen = load("generators", config["generator"])
work_seed = int(config.get("work_seed", seed))
graph = gen.build(random.Random(work_seed), n_tuples)
nm = namespace_pkg.MemoryManager(namespace_pkg.namespace_from_json(n) for n in config["namespaces"])
p = MemoryPersister(nm)
p.write_relation_tuples(*[as_tuple(r) for r in graph.rows])
engine = TpuCheckEngine(p, nm, **{k: v for k, v in config.get("serve", {}).items() if k == "labels_device_min_edges"})
mix = traffic.load_mix("bulk")
B = int(mix["batch"])
objects = traffic.skewed_objects(work_seed, graph.n_objects, n_chunks * B, mix["skew"])
queries, expected = gen.queries(graph, random.Random(work_seed + 1), objects)
first = [as_tuple((ns, o, r, u, None, None, None)) for ns, o, r, u in queries[:64]]
engine.batch_check(first)
engine.labels_settled()
snap = engine.snapshot()
d = engine.dispatch
table = FrameTable.build(nm)
frames = []
for i in range(0, len(queries), B):
    body = traffic.batch_body(queries[i:i + B])
    got = table.frame(body, 65536)
    assert not isinstance(got, str), got
    frame = QueryFrame(*got, body, nm)
    frame.resolve_at_door(snap)
    assert frame.door is not None
    frames.append(frame)
print(f"{config_name}: built in {time.perf_counter() - t0:.1f}s, {len(frames)} chunks of {B}, "
      f"rewrites={snap.rewrites is not None} gated={getattr(snap.rewrites, 'has_gated', False)} "
      f"labels={snap.labels is not None} hub={snap.hub_ptr is not None}", flush=True)

STATES = timeline.DISPATCH_STATES


def one_pass(frames, land=True):
    clock = timeline.DispatchClock()
    timeline.bind_dispatch_clock(clock)
    per_chunk = []
    slices = 0
    for frame in frames:
        before = list(clock.seconds)
        clock.enter(timeline.WAIT_WORK)
        recs = list(d._dispatch_slices(snap, QueryBatch([(frame, 0, frame.n)])))
        clock.enter(timeline.WAIT_WORK)
        per_chunk.append([a - b for a, b in zip(clock.seconds, before)])
        slices += len(recs)
        for rec in recs:
            if land and rec[0] is not None:
                d._unpack_slice(rec[0], rec[1], rec[2])
            d._stage_release(rec[4])
    timeline.bind_dispatch_clock(None)
    return np.asarray(per_chunk) * 1e3, slices


one_pass(frames[:8])  # compile what the chunks need
for rep in range(2):
    ms, slices = one_pass(frames)
    med = np.median(ms, axis=0)
    mean = ms.mean(axis=0)
    print(f"{config_name} pass {rep}: {slices} slices of {len(frames)} chunks; ms a chunk, median (mean): "
          + ", ".join(f"{s} {med[i]:.3f} ({mean[i]:.3f})" for i, s in enumerate(STATES) if mean[i] > 0.0005),
          flush=True)

# the resolve state alone under cProfile: pack + launch stubbed out
zeros = np.zeros(65536, bool)


def stub(snap, sd, tg, multi, i0, i1, W, it_cap=None):
    return None, zeros[: i1 - i0].copy(), []


d._device_batch_labeled = stub
d._device_batch = stub
t1 = time.perf_counter()
for frame in frames:
    list(d._dispatch_slices(snap, QueryBatch([(frame, 0, frame.n)])))
plain = (time.perf_counter() - t1) * 1e3 / len(frames)
prof = cProfile.Profile()
prof.enable()
for frame in frames:
    list(d._dispatch_slices(snap, QueryBatch([(frame, 0, frame.n)])))
prof.disable()
st = pstats.Stats(prof)
c_ms = py_ms = 0.0
c_calls = 0
rows = []
for (fname, line, name), (cc, nc, tt, ct, callers) in st.stats.items():
    is_c = fname == "~" or "numpy" in fname
    if is_c:
        c_ms += tt * 1e3
        c_calls += nc
    else:
        py_ms += tt * 1e3
    rows.append((tt * 1e3 / len(frames), nc / len(frames), f"{Path(fname).name}:{line}:{name}"))
rows.sort(reverse=True)
n = len(frames)
print(f"{config_name} resolve alone (pack and launch stubbed): {plain:.3f} ms a chunk unprofiled; under cProfile "
      f"C/numpy calls {c_ms / n:.3f} ms in {c_calls / n:.1f} calls a chunk, Python around them {py_ms / n:.3f} ms",
      flush=True)
for tt, nc, name in rows[:14]:
    print(f"    {tt:.4f} ms  {nc:6.1f} calls  {name}")
from keto_tpu.check import native_pack  # noqa: E402
print(f"{config_name} counters:", {k: v for k, v in engine.maintenance.snapshot().items()
                                  if k.startswith(("resolve_", "stream_chunk", "rewrite_", "gate_checks", "gate_positions"))},
      "pack paths", dict(native_pack.COUNTERS), flush=True)
engine.close()
