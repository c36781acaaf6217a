from benchmarks.layer_util import queue_ms as read  # noqa: F401
