from benchmarks.layer_util import window_compiles as read  # noqa: F401
