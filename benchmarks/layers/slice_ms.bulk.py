from benchmarks.layer_util import slice_ms as read  # noqa: F401
