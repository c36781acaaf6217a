from benchmarks.layer_util import delta


def read(run):
    """Tuples a dispatch round took off the lanes, on average over the rounds
    taken inside the window: what the dispatch thread's per-round cost is
    spread over. None where the program does not count a round's tuples."""
    tuples = delta(run, "keto_dispatch_round_tuples_total")
    rounds = delta(run, "keto_dispatch_rounds_total")
    if tuples <= 0 or rounds <= 0:
        return None
    return tuples / rounds
