def read(run):
    """As ``writer_visible_p50_ms.ycsb-b``, the 95th percentile: the driver's
    ``write_visible_p95_ms`` (263 writes a window, 13 beyond it)."""
    return run.result.get("write_visible_p95_ms")
