from benchmarks.layer_util import serve_overhead_ms as read  # noqa: F401
