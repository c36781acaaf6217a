from benchmarks.layer_util import hist_mean_ms


def read(run):
    """How near the edge the controller's estimate runs: the mean queue
    delay it estimated at its ticks inside the window, over the latency
    budget it sheds against."""
    mean_ms = hist_mean_ms(run, "keto_admission_queue_delay_seconds")
    budget_s = run.after.get("keto_admission_latency_budget_seconds")
    if mean_ms is None or budget_s <= 0:
        return None
    return 100.0 * mean_ms / (budget_s * 1e3)
