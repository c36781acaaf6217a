def read(run):
    """A write's scheduled arrival -> its acknowledgement on the client,
    median over the window's acknowledged writes: the lateness of the send,
    the write port, the group commit's wait and the SQLite commit."""
    return run.result.get("write_ack_ms_median")
