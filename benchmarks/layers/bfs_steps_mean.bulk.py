from benchmarks.layer_util import delta

STEPS = "keto_check_bfs_steps"


def read(run):
    """Pulls per BFS or hybrid slice landed inside the window, mean. None on
    a program without the family, or where no such slice landed."""
    n = delta(run, STEPS + "_count")
    return delta(run, STEPS + "_sum") / n if n > 0 else None
