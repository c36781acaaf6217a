from benchmarks.layer_util import hist_mean_ms


def read(run):
    """Mean wait of a writer in the group-commit queue inside the window
    (enqueue -> its group's commit starts). None without the family or
    without a write."""
    return hist_mean_ms(run, "keto_group_commit_wait_seconds")
