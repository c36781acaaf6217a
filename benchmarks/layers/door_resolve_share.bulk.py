from benchmarks.layer_util import delta


def read(run):
    """Check queries whose records were resolved to raw node ids at the door
    (on the REST pool thread that framed the body), of all the queries the
    dispatch rounds inside the window resolved, there or on the dispatch
    thread. None where the program does not count where."""
    total = delta(run, "keto_check_resolve_tuples_total")
    if total <= 0:
        return None
    return 100.0 * delta(run, "keto_check_resolve_tuples_total", where="door") / total
