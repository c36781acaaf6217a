from benchmarks.layer_ctrl import ROUND_CAP, share


def read(run):
    """Dispatch rounds whose room for batch-lane work was set by the slice
    controller's cap (``by="controller"``: with ``engine.batch_size`` 4,096
    that is rung 2,048, a narrowed round), of all the rounds taken inside the
    window. None where the program does not count what set a round's room."""
    return share(run, ROUND_CAP, by="controller")
