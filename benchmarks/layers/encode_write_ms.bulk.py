from benchmarks.layer_clock import stage_ms

#: the handler returned -> the response encoded and flushed
read = stage_ms("encode_write")
