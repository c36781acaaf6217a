"""One benchmark run (benchmarks/run.py's own main, the tree in the working
directory) that also keeps the window's two /metrics scrapes: the last two
`Metrics` the harness builds are `run.before` and `run.after`.
usage, from the root of the checkout to be run:
    PROBE_OUT=<prefix> python3 <path to>/scripts/bench_probe.py <run.py's arguments>
`scripts/thread_states.py` reads what this leaves."""
import os
import sys

sys.path.insert(0, os.getcwd())
from benchmarks import daemon, run  # noqa: E402

KEEP = ("keto_dispatch_", "keto_check_resolve", "keto_native_pack", "keto_stream_chunk",
        "keto_check_pack", "keto_check_gate", "keto_check_rewrite", "keto_check_packed",
        "keto_timeline_stage_duration_seconds_sum", "keto_timeline_stage_duration_seconds_count",
        "keto_stream_route", "keto_label_", "keto_compiles_total", "keto_stream_take")
texts = []
orig = daemon.Metrics.__init__


def init(self, text):
    texts.append(text)
    orig(self, text)


daemon.Metrics.__init__ = init
rc = run.main(sys.argv[1:])
for name, text in zip(("before", "after"), texts[-2:]):
    with open(f"{os.environ['PROBE_OUT']}.{name}.txt", "w") as f:
        f.write("\n".join(l for l in text.splitlines() if l.startswith(KEEP)) + "\n")
sys.exit(rc)
