def read(run):
    """The ``rewrites`` phase of the snapshot build as the daemon timed it,
    at ready: the expansion of userset rewrites into edges (inside
    ``snapshot_build_s``). None where the build had no such phase."""
    if run.before.get("keto_build_phase_duration_seconds_count", phase="rewrites") <= 0:
        return None
    return run.before.get("keto_build_phase_duration_seconds_sum", phase="rewrites")
