def read(run):
    """A follow-up's send -> its reply on the client, median: the wait for a
    snapshot at the write's snaptoken plus one check."""
    return run.result.get("pinned_wait_ms_median")
