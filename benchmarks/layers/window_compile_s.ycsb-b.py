from benchmarks.layer_clock import window_compile_s as read  # noqa: F401
