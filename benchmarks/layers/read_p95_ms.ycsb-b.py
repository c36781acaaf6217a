def read(run):
    """The window's unpinned reads from scheduled arrival to reply, 95th
    percentile: the tail ``check_p50_ms`` does not show (a read that arrives
    while a fold holds the engine's lock waits for it)."""
    return run.result.get("extras", {}).get("read_p95_ms")
