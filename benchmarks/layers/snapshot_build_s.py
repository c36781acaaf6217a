def read(run):
    """Sum of the snapshot build's phases (scan, intern, device_build, labels,
    ...) as the daemon timed them, at ready."""
    total = run.before.get("keto_build_phase_duration_seconds_sum")
    return total if total > 0 else None
