"""The dispatch thread by state from a run's two scrapes (`scripts/bench_probe.py`: `<name>.before.txt`, `<name>.after.txt`, `<name>.out`) and its
result line: ms a round, ms per 1,000 correct checks, wait_work's share.
usage: thread_states.py <out dir> [name ...]"""
import json
import re
import sys
from pathlib import Path

out = Path(sys.argv[1])
names = sys.argv[2:] or sorted(p.name[:-len(".after.txt")] for p in out.glob("*.after.txt"))


def parse(path):
    vals = {}
    for line in path.read_text().splitlines():
        m = re.match(r"(\w+)(\{[^}]*\})? (\S+)$", line)
        if m:
            vals[(m.group(1), m.group(2) or "")] = float(m.group(3))
    return vals


for name in names:
    try:
        b, a = parse(out / f"{name}.before.txt"), parse(out / f"{name}.after.txt")
        line = json.loads((out / f"{name}.out").read_text().strip().splitlines()[-1])
    except Exception as e:  # a run that printed no result
        print(f"{name}: {type(e).__name__} {e}")
        continue
    d = lambda fam, lab="": a.get((fam, lab), 0.0) - b.get((fam, lab), 0.0)
    states = {}
    for (fam, lab), _ in a.items():
        if fam == "keto_dispatch_thread_seconds_total":
            states[re.search(r'state="(\w+)"', lab).group(1)] = d(fam, lab)
    rounds = sum(d(f, l) for (f, l) in a if f == "keto_dispatch_rounds_total")
    tuples = d("keto_dispatch_round_tuples_total")
    m = line["metrics"]
    cps = m.get("checks_per_s", {}).get("value")
    correct = line["attempted"] - line["failed"] if "attempted" in line else None
    total = sum(states.values())
    work = total - states.get("wait_work", 0.0)
    res = {l: d("keto_check_resolve_chunks_total", l) for (f, l) in a if f == "keto_check_resolve_chunks_total"}
    decl = sum(d(f, l) for (f, l) in a if f == "keto_check_resolve_declines_total")
    chunks = {re.search(r'cut="(\w+)"', l).group(1): d(f, l) for (f, l) in a if f == "keto_stream_chunks_total"}
    pw = lambda stage: (d("keto_timeline_stage_duration_seconds_sum", '{stage="%s"}' % stage),
                        d("keto_timeline_stage_duration_seconds_count", '{stage="%s"}' % stage))
    stage_ms = {}
    for st in ("pool_wait", "pack", "dispatch", "decode"):
        s_, c_ = pw(st)
        stage_ms[st] = round(1e3 * s_ / c_, 2) if c_ else None
    print(f"{name}: checks_per_s {cps} p50 {m.get('check_p50_ms', {}).get('value')} p95 {m.get('check_p95_ms', {}).get('value')} "
          f"setup_s {m.get('setup_s', {}).get('value')} correct {line.get('correct')} failed {line.get('failed')}")
    if rounds:
        per = {k: round(1e3 * v / rounds, 3) for k, v in sorted(states.items())}
        print(f"    rounds {int(rounds)} of {tuples / rounds:.1f}; ms a round {per}; all but wait_work {1e3 * work / rounds:.3f} "
              f"({1e3 * work / (line['attempted'] - line['failed']) * 1000:.4f} ms per 1,000 checks attempted-failed); "
              f"wait_work {100 * states.get('wait_work', 0) / total:.1f}% of the window")
        print(f"    resolve chunks {res} declines {decl} cuts {chunks} pieces {d('keto_stream_chunk_pieces_total')} stages ms {stage_ms}")
