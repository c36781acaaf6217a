def read(run):
    """``/health/ready`` = ok to the daemon's "width-ladder warmup" log line:
    the boot label build and the kernel ladder."""
    return run.stamps["warmed"] - run.stamps["ready"]
