from benchmarks.layer_util import delta


def read(run):
    """Chunks of check slices that the dispatch thread resolved in its one
    native pass (``path="native"``: raw node ids to device rows, closure bytes
    and entry counts in one GIL-released call), of all the chunks it resolved
    inside the window. None where the program has no such family (every
    program before PR 44); 0 where it has the family and took the pass for no
    chunk: a stale native library reads so, or a daemon whose every chunk is
    declined (``keto_check_resolve_declines_total`` says why)."""
    if not any(labels.get("path") == "native"
               for labels, _ in run.after.series("keto_check_resolve_chunks_total")):
        return None
    total = delta(run, "keto_check_resolve_chunks_total")
    if total <= 0:
        return None
    return 100.0 * delta(run, "keto_check_resolve_chunks_total", path="native") / total
