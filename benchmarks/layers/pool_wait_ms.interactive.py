from benchmarks.layer_clock import stage_ms

#: head and body read on the event loop -> the handler entered on a pool thread
read = stage_ms("pool_wait")
