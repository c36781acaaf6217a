from benchmarks.layer_util import delta


def read(run):
    """What the gates cost the one dispatch thread per 1,000 correct checks:
    its seconds expanding resolved batches into positions and combining
    landed slices' bits, inside the window. None where the program has no
    such family."""
    if not run.after.series("keto_check_gate_seconds_total"):
        return None
    r = run.result
    correct = r["attempted"] - r["failed"] - r["wrong"]
    if correct <= 0:
        return None
    return delta(run, "keto_check_gate_seconds_total") * 1e3 / (correct / 1e3)
