def read(run):
    """Gate and operand nodes, of all the serving snapshot's object#relation
    nodes, at ready: the share of the node set the gates added. None where
    the program has no such gauges."""
    nodes = run.before.get("keto_snapshot_set_nodes")
    if nodes <= 0 or not run.before.series("keto_snapshot_gate_nodes"):
        return None
    return 100.0 * run.before.get("keto_snapshot_gate_nodes") / nodes
