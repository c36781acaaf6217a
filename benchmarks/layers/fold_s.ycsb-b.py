from benchmarks.layer_clock import window_delta

#: seconds the window's folds took, all of them under the engine's lock
read = window_delta("keto_fold_duration_seconds_total")
