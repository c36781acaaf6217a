"""Stage-level profile of TpuCheckEngine.batch_check at bench scale.

Breaks the batch into its host/device stages and times each: bulk resolve,
chunk packing, kernel dispatch, result fetch — plus a pure-device re-run of
an already-packed chunk to isolate kernel time from host overhead.

Usage: python scripts/profile_check.py [n_tuples] [n_checks]
"""
from __future__ import annotations

import random
import sys
import time

import jax
import numpy as np

sys.path.insert(0, ".")
from bench import build_workload, make_queries  # noqa: E402

from keto_tpu import namespace as namespace_pkg  # noqa: E402
from keto_tpu.check.kernels import _check_kernel  # noqa: E402
from keto_tpu.check.pack import _WORD_WIDTHS, pack_chunk, pack_entries  # noqa: E402
from keto_tpu.check.tpu_engine import TpuCheckEngine  # noqa: E402
from keto_tpu.persistence.memory import MemoryPersister  # noqa: E402


def log(*a):
    print(*a, file=sys.stderr, flush=True)


def main():
    import os

    n_tuples = int(sys.argv[1]) if len(sys.argv) > 1 else 1_000_000
    n_checks = int(sys.argv[2]) if len(sys.argv) > 2 else 100_000
    rng = random.Random(42)
    log(f"devices: {jax.devices()}")

    t0 = time.perf_counter()
    if os.environ.get("PROF_WORKLOAD") == "github":
        from bench import build_workload_github, make_queries_github

        tuples, ctx = build_workload_github(rng, n_tuples)
        nm = namespace_pkg.MemoryManager(
            [
                namespace_pkg.Namespace(id=i + 1, name=n)
                for i, n in enumerate(("orgs", "teams", "repos", "issues", "pulls"))
            ]
        )
        queries_fn = lambda: make_queries_github(rng, n_checks, ctx)  # noqa: E731
    else:
        tuples, doc_grant, membership, user_reaches, member_of, n_users, T = build_workload(rng, n_tuples)
        nm = namespace_pkg.MemoryManager(
            [namespace_pkg.Namespace(id=1, name="groups"), namespace_pkg.Namespace(id=2, name="docs")]
        )
        queries_fn = lambda: make_queries(rng, n_checks, doc_grant, n_users, user_reaches, member_of, T)  # noqa: E731
    store = MemoryPersister(nm)
    store.write_relation_tuples(*tuples)
    mb = int(os.environ.get("PROF_MAX_BATCH", 32 * _WORD_WIDTHS[-1]))
    budget = int(float(os.environ.get("PROF_MEM_GB", "6")) * (1 << 30))
    engine = TpuCheckEngine(store, store.namespaces, max_batch=mb, mem_budget_bytes=budget)
    snap = engine.snapshot()
    log(f"setup {time.perf_counter()-t0:.1f}s; nodes={snap.n_nodes} "
        f"active={snap.num_active} int={snap.num_int} live={snap.num_live} "
        f"buckets={[(b.n, b.nbrs.shape) for b in snap.buckets]}")

    queries, expected = queries_fn()

    # warmup / compile
    t0 = time.perf_counter()
    engine.batch_check(queries[: engine.dispatch._max_batch])
    log(f"warmup {time.perf_counter()-t0:.1f}s  block_iters={engine.dispatch._block_iters}")

    # --- stage 1: resolve ---
    t0 = time.perf_counter()
    sd, tg, multi = engine.dispatch._resolve_bulk(snap, queries)
    t_resolve = time.perf_counter() - t0
    log(f"resolve_bulk: {t_resolve*1e3:.0f} ms ({n_checks/t_resolve:,.0f} q/s), multi={len(multi)}")

    # --- stage 2: pack all chunks (host only) ---
    cap = engine.dispatch._slice_cap(snap)
    log(f"slice cap {cap} queries (W={cap // 32})")
    bounds = [(i, min(i + cap, n_checks)) for i in range(0, n_checks, cap)]
    W = next(w for w in _WORD_WIDTHS if 32 * w >= min(cap, n_checks))
    t0 = time.perf_counter()
    packs = [pack_chunk(snap, sd, tg, multi, a, b, W) for a, b in bounds]
    t_pack = time.perf_counter() - t0
    log(f"pack_chunk x{len(bounds)}: {t_pack*1e3:.0f} ms total, {t_pack/len(bounds)*1e3:.1f} ms/chunk")

    # --- stage 3: device transfer + dispatch + fetch, fully serial ---
    import jax.numpy as jnp
    t_xfer = t_disp = t_fetch = 0.0
    iters_seen = []
    packs = [(p, h) for p, h in packs if p is not None]
    for (packed, host_ans) in packs:
        t0 = time.perf_counter()
        buf, sizes = pack_entries(packed)
        entries = jnp.asarray(buf)
        t_xfer += time.perf_counter() - t0
        t0 = time.perf_counter()
        out = _check_kernel(
            snap.device_buckets, entries, sizes=sizes,
            n_active=snap.num_active, n_int=snap.num_int,
            valid_rows=tuple(b.n for b in snap.buckets),
            it_cap=engine.dispatch._it_cap, block_iters=engine.dispatch._block_iters,
            bitmap_sharding=None,
        )
        t_disp += time.perf_counter() - t0
        t0 = time.perf_counter()
        got = jax.device_get(out)
        t_fetch += time.perf_counter() - t0
        iters_seen.append(int(got[-2]))
    log(f"serial: xfer={t_xfer*1e3:.0f} ms  dispatch={t_disp*1e3:.0f} ms  "
        f"fetch(blocking)={t_fetch*1e3:.0f} ms  iters={iters_seen[:5]}...")

    # --- stage 4: device-only throughput: re-dispatch the same chunk args N times ---
    if not packs:
        log("no device chunks; skipping device-only stage")
        return
    packed, _ = packs[0]
    buf, sizes = pack_entries(packed)
    dev_entries = jax.device_put(jnp.asarray(buf))
    jax.block_until_ready(dev_entries)
    reps = max(4, len(packs))
    t0 = time.perf_counter()
    outs = []
    for _ in range(reps):
        outs.append(_check_kernel(
            snap.device_buckets, dev_entries, sizes=sizes,
            n_active=snap.num_active, n_int=snap.num_int,
            valid_rows=tuple(b.n for b in snap.buckets),
            it_cap=engine.dispatch._it_cap, block_iters=engine.dispatch._block_iters,
            bitmap_sharding=None,
        ))
    jax.block_until_ready(outs)
    t_dev = time.perf_counter() - t0
    nq = bounds[0][1] - bounds[0][0]
    log(f"device-only: {t_dev/reps*1e3:.1f} ms/chunk -> {nq*reps/t_dev:,.0f} checks/s ceiling")

    # --- end-to-end current implementation (3 reps: single passes are noisy) ---
    for rep in range(3):
        t0 = time.perf_counter()
        got = engine.batch_check(queries)
        t_e2e = time.perf_counter() - t0
        n_wrong = sum(g != e for g, e in zip(got, expected))
        log(f"e2e batch_check[{rep}]: {t_e2e*1e3:.0f} ms -> {n_checks/t_e2e:,.0f} checks/s, wrong={n_wrong}")


if __name__ == "__main__":
    main()
