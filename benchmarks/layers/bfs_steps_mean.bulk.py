from benchmarks.layer_util import delta


def read(run):
    """Pulls of ``check_step`` per BFS or hybrid slice landed inside the
    window, mean. None on a program without the counters, or where no such
    slice landed."""
    slices = delta(run, "keto_check_bfs_slices_total")
    return delta(run, "keto_check_bfs_steps_total") / slices if slices > 0 else None
