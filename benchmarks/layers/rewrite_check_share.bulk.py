from benchmarks.layer_util import delta


def read(run):
    """Checks whose (namespace, relation) has a userset rewrite in its
    closure, of all the checks the window's rounds resolved under a rewrite
    schema: how much of the traffic the schema touches. None where the
    program does not count by closure, or serves no schema."""
    total = delta(run, "keto_check_rewrite_checks_total")
    if total <= 0:
        return None
    return 100.0 * delta(run, "keto_check_rewrite_checks_total", closure="rewritten") / total
