"""From a ``jax.profiler`` trace (``.xplane.pb``) to the few numbers the
benchmark reports: the traced window, the seconds in which an operation ran
on the device (the union of its op intervals, averaged over the device
planes), the time per XLA module, the device operations that took most time,
and the longest idle gaps named by what the host was doing in them.

Run as ``python trace_reduce.py <trace dir or .xplane.pb> <out.json>`` in a
process of its own, pinned to the CPU backend: it reads the file with
``jax.profiler.ProfileData`` and the benchmark's parent stays off jax.
"""

from __future__ import annotations

import bisect
import json
import sys
from pathlib import Path

OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
TOP = 10
GAPS_NAMED = 200  # the longest gaps that get a name


def union(intervals):
    """Sorted, merged ``[(start, end)]``."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def _is_cpu_exec_line(name: str) -> bool:
    return name.startswith(("tf_XLAPjRtCpuClient", "tf_XLAEigen", "tf_XLATfrtCpuClient"))


def load(path: Path):
    """``{plane: {line: [(name, start_ns, end_ns)]}}`` of one xplane file."""
    import jax

    if path.is_dir():
        found = sorted(path.glob("plugins/profile/*/*.xplane.pb"))
        if not found:
            raise FileNotFoundError(f"no .xplane.pb under {path}")
        path = found[-1]
    data = jax.profiler.ProfileData.from_file(str(path))
    planes = {}
    for plane in data.planes:
        lines = planes.setdefault(plane.name, {})
        for line in plane.lines:
            events = lines.setdefault(line.name, [])
            for ev in line.events:
                events.append((ev.name, ev.start_ns, ev.start_ns + ev.duration_ns))
    return planes


def reduce_planes(planes: dict) -> dict:
    device = {p: lines for p, lines in planes.items() if p.startswith("/device:")
              and (OPS_LINE in lines or MODULES_LINE in lines)}
    host_lines = dict(planes.get("/host:CPU", {}))
    if not device:
        # the CPU backend has no device plane: its executor threads stand in,
        # so that the rehearsal drives the same reduction
        ops = [ev for name, evs in host_lines.items() if _is_cpu_exec_line(name)
               for ev in evs if not ev[0].startswith("ThreadpoolListener")]
        device = {"/host:CPU(executor threads)": {OPS_LINE: ops}} if ops else {}
        host_lines = {n: e for n, e in host_lines.items() if not _is_cpu_exec_line(n)}
    every = [ev for lines in planes.values() for evs in lines.values() for ev in evs]
    if not every:
        raise ValueError("the trace holds no event")
    t_lo, t_hi = min(e[1] for e in every), max(e[2] for e in every)
    out = {
        "window_s": (t_hi - t_lo) / 1e9,
        "device_planes": sorted(device),
        "lines": {p: {n: len(e) for n, e in lines.items()} for p, lines in planes.items()},
        "busy_s": 0.0, "modules": {}, "device_ops": [], "idle_gaps": [],
    }
    if not device:
        return out
    host = sorted(
        (s, e, f"{name} [{line.split('/')[0]}]")
        for line, evs in host_lines.items() for name, s, e in evs if e > s
    )
    host_starts = [h[0] for h in host]
    longest_host = max((e - s for s, e, _ in host), default=0)
    busy_total, modules, ops, gap_by_name = 0.0, {}, {}, {}
    for lines in device.values():
        op_events = lines.get(OPS_LINE) or lines.get(MODULES_LINE) or []
        busy = union((s, e) for _, s, e in op_events)
        busy_total += sum(e - s for s, e in busy) / 1e9
        for name, s, e in lines.get(MODULES_LINE, ()):
            modules[name] = modules.get(name, 0.0) + (e - s) / 1e9
        for name, s, e in op_events:
            # a TPU op is named by its whole HLO line: keep the instruction
            # and what it calls
            short = name.split(" = ")[0].lstrip("%")
            if ", calls=" in name:
                short += " (" + name.rsplit(", calls=", 1)[1].lstrip("%") + ")"
            ops[short] = ops.get(short, 0.0) + (e - s) / 1e9
        edges = [t_lo] + [t for iv in busy for t in iv] + [t_hi]
        gaps = sorted(((edges[i + 1] - edges[i], edges[i], edges[i + 1])
                       for i in range(0, len(edges), 2) if edges[i + 1] > edges[i]),
                      reverse=True)[:GAPS_NAMED]
        for length, gs, ge in gaps:
            best, best_overlap = "no host span", 0
            lo = bisect.bisect_left(host_starts, gs - longest_host)
            hi = bisect.bisect_right(host_starts, ge)
            for s, e, label in host[lo:hi]:
                overlap = min(e, ge) - max(s, gs)
                if overlap > best_overlap:
                    best, best_overlap = label, overlap
            gap_by_name[best] = gap_by_name.get(best, 0.0) + length / 1e9
    n = len(device)
    out["busy_s"] = busy_total / n
    out["modules"] = {k: v / n for k, v in modules.items()}
    top = lambda d: [[k, v / n] for k, v in sorted(d.items(), key=lambda kv: -kv[1])[:TOP]]
    out["device_ops"] = top(ops)
    out["idle_gaps"] = top(gap_by_name)
    return out


def main(argv) -> int:
    src, dst = Path(argv[1]), Path(argv[2])
    dst.write_text(json.dumps(reduce_planes(load(src))))
    return 0


if __name__ == "__main__":
    raise SystemExit(main(sys.argv))
