def read(run):
    """Edges no stored row states (derived from a computed_userset or a
    tuple_to_userset), of all the serving snapshot's edges, at ready: the
    share of the graph the schema wrote. None where the program has no such
    gauges."""
    edges = run.before.get("keto_snapshot_edges")
    if edges <= 0 or not run.before.series("keto_snapshot_rewrite_edges"):
        return None
    return 100.0 * run.before.get("keto_snapshot_rewrite_edges") / edges
