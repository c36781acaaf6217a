from benchmarks.layer_util import delta


def read(run):
    """Tuples of ``POST /check/batch`` calls that reached the batcher as one
    framed buffer, of all such tuples inside the window. None where the
    program has no such counter."""
    total = delta(run, "keto_check_batch_tuples_total")
    if total <= 0:
        return None
    return 100.0 * delta(run, "keto_check_batch_tuples_total", path="framed") / total
