from benchmarks.layer_util import delta


def read(run):
    """Device positions the expansion made a gated check in the window (its
    own row where that reaches anything besides gates, and one an operand of
    every gate): what a gate costs pack and the kernels. None where the
    program has no such family or no gated check was seen."""
    if not run.after.series("keto_check_gate_positions_total"):
        return None
    gated = delta(run, "keto_check_gate_checks_total")
    if gated <= 0:
        return None
    return delta(run, "keto_check_gate_positions_total") / gated
