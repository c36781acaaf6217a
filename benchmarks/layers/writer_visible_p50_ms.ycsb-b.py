def read(run):
    """A write's scheduled arrival -> the reply of its follow-up check, pinned
    to the snaptoken the write returned, that gave the answer the write makes
    true; median over the window's writes (a failed, shed, timed-out or wrongly
    answered write or follow-up is charged ``MISS_MS``): what a writer feels,
    as ``drivers/readwrite.py`` computes ``write_visible_p50_ms``."""
    return run.result.get("write_visible_p50_ms")
