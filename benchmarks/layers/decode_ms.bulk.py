from benchmarks.layer_clock import stage_ms

#: json.loads + RelationTuple.from_json of a call's body
read = stage_ms("decode")
