def read(run):
    """The ``labels`` phase of the snapshot build as the daemon timed it, at
    ready. None where no label index was built (or the program has no such
    phase)."""
    total = run.before.get("keto_build_phase_duration_seconds_sum", phase="labels")
    return total if total > 0 else None
