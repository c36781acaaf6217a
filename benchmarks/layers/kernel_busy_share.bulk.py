KERNELS = ("check_step", "label_step")


def read(run):
    """Device trace: the union of the device intervals of XLA modules whose
    name contains ``check_step`` or ``label_step``, over the traced window."""
    if run.trace is None:
        return None
    busy = sum(s for name, s in run.trace["modules"].items() if any(k in name for k in KERNELS))
    return 100.0 * busy / run.trace["window_s"]
