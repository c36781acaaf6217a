def read(run):
    """Device trace: the device time of XLA modules whose name contains
    ``check_step`` (the BFS fixpoint: whole slices and hybrid sub-batches),
    over the traced window. ``kernel_busy_share.bulk`` sums it with
    ``label_step``."""
    if run.trace is None:
        return None
    busy = sum(s for name, s in run.trace["modules"].items() if "check_step" in name)
    return 100.0 * busy / run.trace["window_s"]
