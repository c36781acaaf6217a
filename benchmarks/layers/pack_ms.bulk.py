from benchmarks.layer_util import hist_mean_ms


def read(run):
    """Timeline segment pack -> dispatch: resolve and pack on the host."""
    return hist_mean_ms(run, "keto_timeline_stage_duration_seconds", stage="dispatch")
