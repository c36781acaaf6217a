from benchmarks.layer_clock import window_delta

#: folds of overlay segments into the base snapshot inside the window, every outcome
read = window_delta("keto_fold_runs_total")
