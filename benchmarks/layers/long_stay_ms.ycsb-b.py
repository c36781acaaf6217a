from benchmarks.layer_ctrl import long_stay_ms as read  # noqa: F401
