"""Open loop of single checks: ``GET /check`` on a Poisson schedule at the
rate the mix fixes for the configuration, from ``clients`` threads with one
kept-alive connection each, no retries and no hedging. Latency runs from the
scheduled arrival, so a stall is charged to every request it delays; how late
the generator itself sent is reported beside it. A failed, shed or wrong
answer counts as slower than any limit.
"""

from __future__ import annotations

import itertools
import random
import threading
import time

from benchmarks import traffic
from benchmarks.client import Conn, server_total_ms
from benchmarks.stats import median, percentile, supported, tally

MISS_MS = 1e9  # what a failed, shed or wrong answer is charged


def rate_of(ctx) -> float:
    rate = ctx.mix["rate"]
    if isinstance(rate, dict):
        if ctx.config_name not in rate:
            raise ValueError(f"traffic mix has no rate for configuration {ctx.config_name!r}")
        rate = rate[ctx.config_name]
    return float(rate)


def prepare(ctx, rate: float | None = None, seconds: float | None = None) -> dict:
    rate = rate_of(ctx) if rate is None else rate
    seconds = ctx.seconds if seconds is None else seconds
    offsets = traffic.arrival_offsets(ctx.seed, rate, seconds)
    warm_offsets = traffic.arrival_offsets(ctx.seed + 3, rate, float(ctx.mix["warm_seconds"]))
    # then bursts of 1 to `clients` simultaneous checks: the batcher packs a
    # burst into one slice, and a slice geometry first met inside the window
    # would stall it for a compile or a load from the cache
    t = warm_offsets[-1]
    for _ in range(int(ctx.mix["warm_burst_rounds"])):
        for k in _burst_sizes(int(ctx.mix["clients"])):
            t += 0.02
            warm_offsets += [t] * k
    objects = traffic.skewed_objects(ctx.seed, ctx.graph.n_objects,
                                     len(offsets) + len(warm_offsets), ctx.mix["skew"])
    queries, expected = ctx.generator.queries(ctx.graph, random.Random(ctx.seed + 1), objects)
    paths = [traffic.check_path(q) for q in queries]
    n = len(offsets)
    return {"offsets": offsets, "queries": queries[:n], "expected": expected[:n],
            "paths": paths[:n], "warm_paths": paths[n:], "warm_offsets": warm_offsets}


def _burst_sizes(most: int) -> list[int]:
    """1, 2, 3, 4, 6, 8, 12, ... up to ``most``: every power of two and the
    step between."""
    sizes, k = [], 1
    while k < most:
        sizes += [k, k + k // 2] if k > 1 else [k]
        k *= 2
    return sorted({min(x, most) for x in sizes} | {most})


def _drive(ctx, offsets, paths) -> list:
    """``[(index, latency from scheduled arrival s, lateness of the send s,
    status, Server-Timing total ms)]``."""
    counter = itertools.count()
    records, lock = [], threading.Lock()
    errors = []
    t0 = time.perf_counter() + 0.05

    def worker():
        conn = Conn("127.0.0.1", ctx.read_port, timeout=60.0)
        local = []
        try:
            while True:
                i = next(counter)
                if i >= len(offsets):
                    break
                due = t0 + offsets[i]
                delay = due - time.perf_counter()
                if delay > 0:
                    time.sleep(delay)
                sent = time.perf_counter()
                try:
                    status, _, headers = conn.request("GET", paths[i])
                    total = server_total_ms(headers.get("Server-Timing"))
                except OSError:
                    status, total = -1, None
                local.append((i, time.perf_counter() - due, sent - due, status, total))
        except Exception as e:
            errors.append(repr(e))
        finally:
            conn.close()
            with lock:
                records.extend(local)

    threads = [threading.Thread(target=worker, daemon=True) for _ in range(int(ctx.mix["clients"]))]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=offsets[-1] + 300)
    if errors or any(t.is_alive() for t in threads):
        raise RuntimeError(f"check clients failed: {errors[:3]}")
    return records


def warm(ctx, state) -> None:
    """An untimed stretch of the cell's own traffic (the same arrival process
    at the same rate), then bursts of every size up to the number of clients,
    so that the batcher forms the slice shapes the window will form and the
    daemon compiles them now."""
    _drive(ctx, state["warm_offsets"], state["warm_paths"])


def run(ctx, state, seconds: float) -> dict:
    """``seconds`` is fixed by the schedule ``prepare`` drew."""
    records = _drive(ctx, state["offsets"], state["paths"])
    return reduce(state, records)


def reduce(state, records) -> dict:
    queries, expected = state["queries"], state["expected"]
    lat_ms, late_ms, service_ms, server_ms, answered_q, answered_r = [], [], [], [], [], []
    failed = wrong = 0
    for i, lat, late, status, total in records:
        late_ms.append(late * 1e3)
        if status not in (200, 403):
            failed += 1
            lat_ms.append(MISS_MS)
            continue
        got = status == 200
        answered_q.append(queries[i])
        answered_r.append(got)
        if got != expected[i]:
            wrong += 1
            lat_ms.append(MISS_MS)
            continue
        lat_ms.append(lat * 1e3)
        service_ms.append((lat - late) * 1e3)
        if total is not None:
            server_ms.append(total)
    n = len(lat_ms)
    if not supported(n, 0.95):
        raise RuntimeError(f"{n} requests cannot carry a 95th percentile")
    return {
        "metrics": {"check_p50_ms": percentile(lat_ms, 0.5),
                    "check_p95_ms": percentile(lat_ms, 0.95)},
        "attempted": n, "failed": failed, "wrong": wrong,
        "window_s": state["offsets"][-1], "calls": n,
        "answered": [(answered_q, answered_r)],
        "client_ms_median": median(service_ms) if service_ms else None,
        "server_ms_median": median(server_ms) if server_ms else None,
        "late_p95_ms": percentile(late_ms, 0.95),
        "statuses": tally(status for _, _, _, status, _ in records),
    }

