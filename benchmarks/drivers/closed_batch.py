"""Closed loop of batch callers: ``callers`` threads, each with one kept-alive
connection, each sending ``POST /check/batch`` of ``batch`` tuples and the next
call when the last returns; a caller that is refused with 429 waits the
``Retry-After`` the server advises, as the SDK does. The bodies are a pool of
``pool_calls`` calls built from the seed at set-up and sent round-robin, so
every seed offers the same amount of work per call; where the configuration
names a ``work_seed`` the calls are drawn from that one and the run's seed
puts them, and the tuples of each, in another order. Reports ``checks_per_s``: correct decisions
delivered, over the time from the window's start to its last reply.
"""

from __future__ import annotations

import itertools
import json
import random
import threading
import time

from benchmarks import traffic
from benchmarks.client import Conn, server_total_ms
from benchmarks.stats import median, tally

WARM_PASSES_MOST = 10


def prepare(ctx) -> dict:
    mix = ctx.mix
    n_calls, batch = int(mix["pool_calls"]), int(mix["batch"])
    objects = traffic.skewed_objects(ctx.work_seed, ctx.graph.n_objects, n_calls * batch, mix["skew"])
    queries, expected = ctx.generator.queries(ctx.graph, random.Random(ctx.work_seed + 1), objects)
    calls = [list(zip(queries[i:i + batch], expected[i:i + batch]))
             for i in range(0, len(queries), batch)]
    if ctx.work_seed != ctx.seed:
        # the configuration fixed the work: the same calls for every seed,
        # sent in another order, each with its tuples in another order
        order = random.Random(ctx.seed)
        order.shuffle(calls)
        for call in calls:
            order.shuffle(call)
    pool = []
    for call in calls:
        qs, es = [q for q, _ in call], [e for _, e in call]
        pool.append((traffic.batch_body(qs), qs, es))
    return {"pool": pool}


def _drive(ctx, state, seconds: float, max_calls: int | None = None):
    """Returns ``([(pool index, t_start, t_end, status, raw body, Server-Timing
    total ms)], reconnects)`` of every call, times relative to the window's
    start."""
    pool = state["pool"]
    counter = itertools.count()
    records, lock = [], threading.Lock()
    errors, reconnects = [], []
    t0 = time.perf_counter()
    t_stop = t0 + seconds

    def caller():
        conn = Conn("127.0.0.1", ctx.read_port, timeout=120.0)
        local = []
        try:
            while time.perf_counter() < t_stop:
                i = next(counter)
                if max_calls is not None and i >= max_calls:
                    break
                idx = i % len(pool)
                ts = time.perf_counter()
                status, raw, headers = conn.request("POST", "/check/batch", pool[idx][0])
                te = time.perf_counter()
                local.append((idx, ts - t0, te - t0, status, raw,
                              server_total_ms(headers.get("Server-Timing"))))
                if status == 429:
                    # hammering a daemon that sheds keeps it shedding: every
                    # refused body is still read and parsed before the door
                    time.sleep(min(float(headers.get("Retry-After") or 1.0),
                                   max(0.0, t_stop - time.perf_counter())))
        except Exception as e:  # a dead caller is a failed run, not a slow one
            errors.append(repr(e))
        finally:
            conn.close()
            with lock:
                records.extend(local)
                reconnects.append(max(0, conn.reconnects))

    threads = [threading.Thread(target=caller, daemon=True) for _ in range(int(ctx.mix["callers"]))]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=seconds + 300)
    if errors or any(t.is_alive() for t in threads):
        raise RuntimeError(f"batch callers failed: {errors[:3]}")
    return records, sum(reconnects)


def warm(ctx, state) -> None:
    """Untimed passes of the cell's own request shape, all callers at once,
    until one passes with nothing refused: the first slices of a new graph
    stall on compiles, and the admission controller answers a stall by
    shedding for some seconds."""
    for _ in range(WARM_PASSES_MOST):
        records, _ = _drive(ctx, state, seconds=60.0, max_calls=int(ctx.mix["warm_calls"]))
        if all(status == 200 for _, _, _, status, _, _ in records):
            return
    raise RuntimeError(f"the daemon still refuses calls after {WARM_PASSES_MOST} warm passes")


def run(ctx, state, seconds: float) -> dict:
    records, reconnects = _drive(ctx, state, seconds)
    window_s = max(te for _, _, te, _, _, _ in records)
    pool = state["pool"]
    attempted = failed = correct = wrong = 0
    answered = []  # (query, served) for the reference's sample
    for idx, _, _, status, raw, _ in records:
        _, queries, expected = pool[idx]
        attempted += len(queries)
        results = json.loads(raw).get("results") if status == 200 else None
        if results is None or len(results) != len(queries):
            failed += len(queries)
            continue
        for q, got, want in zip(queries, results, expected):
            if bool(got) == want:
                correct += 1
            else:
                wrong += 1
        answered.append((queries, results))
    client_ms = [(te - ts) * 1e3 for _, ts, te, _, _, _ in records]
    server_ms = [s for *_, s in records if s is not None]
    return {
        "metrics": {"checks_per_s": correct / window_s},
        "attempted": attempted, "failed": failed, "wrong": wrong,
        "window_s": window_s, "calls": len(records),
        "answered": answered,
        "client_ms_median": median(client_ms),
        "server_ms_median": median(server_ms) if server_ms else None,
        "reconnects": reconnects,
        "statuses": tally(status for _, _, _, status, _, _ in records),
    }

