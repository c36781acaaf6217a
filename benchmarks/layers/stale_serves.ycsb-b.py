from benchmarks.layer_clock import window_delta

#: rounds answered from a snapshot older than the store's watermark inside the
#: window, every holder: should be 0 (the configuration's guarantee forbids one)
read = window_delta("keto_snapshot_stale_serves_total")
