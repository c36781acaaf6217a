from benchmarks.layer_util import delta

CHECKS = "keto_label_checks_total"


def read(run):
    """Checks routed to the BFS kernel while labels were live (pair cap,
    coverage gaps, self-queries, multi-start), of all checks the label route
    saw inside the window. None where nothing was routed."""
    total = delta(run, CHECKS)
    if total <= 0:
        return None
    return 100.0 * delta(run, CHECKS, path="fallback") / total
