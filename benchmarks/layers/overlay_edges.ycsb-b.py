from benchmarks.layer_clock import has

EDGES = "keto_overlay_edges"


def read(run):
    """Edges the serving snapshot's overlay holds at the window's end: what
    the writes since the last fold or rebuild left on the read path."""
    return run.after.get(EDGES) if has(run, EDGES) else None
