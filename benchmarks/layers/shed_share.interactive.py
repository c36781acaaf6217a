from benchmarks.layer_util import delta


def read(run):
    """Requests refused at the lanes or the admission door, of those attempted."""
    shed = delta(run, "keto_lane_shed_total") + delta(run, "keto_admission_shed_total")
    return 100.0 * shed / run.result["attempted"]
