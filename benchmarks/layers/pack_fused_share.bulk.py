from benchmarks.layer_util import delta


def read(run):
    """Chunks of check slices that the label route's one native pass packed
    (``path="fused"``: the host walk, the routing and the pairing in one
    GIL-released call), of all the chunks packed inside the window, whatever
    the path. None where the program has no such path (every program before
    PR 39); 0 where it has the path and took it for no chunk: a stale native
    library reads so, or a daemon whose every chunk is declined."""
    if not any(labels.get("path") == "fused"
               for labels, _ in run.after.series("keto_native_pack_chunks_total")):
        return None
    total = delta(run, "keto_native_pack_chunks_total")
    if total <= 0:
        return None
    return 100.0 * delta(run, "keto_native_pack_chunks_total", path="fused") / total
