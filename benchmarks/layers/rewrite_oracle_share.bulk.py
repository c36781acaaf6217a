from benchmarks.layer_util import delta


def read(run):
    """Checks with a rewritten closure that the CPU oracle answered (their
    closure reaches an intersection or an exclusion), of all such checks in
    the window; 0 under a union-only schema. None where the program does
    not count the route."""
    total = delta(run, "keto_check_rewrite_route_total")
    if total <= 0:
        return None
    return 100.0 * delta(run, "keto_check_rewrite_route_total", route="oracle") / total
