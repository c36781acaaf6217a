from benchmarks.layer_util import delta


def read(run):
    """Checks whose closure reaches a gate (an intersection or an exclusion
    of the schema), served by the device's expansion or by the oracle, of all
    the checks a rewrite schema counted in the window. None where the program
    has no such family."""
    if not run.after.series("keto_check_gate_checks_total"):
        return None
    total = delta(run, "keto_check_rewrite_checks_total")
    if total <= 0:
        return None
    return 100.0 * delta(run, "keto_check_gate_checks_total") / total
