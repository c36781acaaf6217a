from benchmarks.layer_clock import dispatch_busy_s


def read(run):
    """What 1,000 correct checks cost the one dispatch thread: its seconds
    in every state but ``wait_work`` inside the window, over the correct
    decisions delivered. Times ``checks_per_s`` / 1,000 it is the share of a
    second the thread works."""
    busy = dispatch_busy_s(run)
    r = run.result
    correct = r["attempted"] - r["failed"] - r["wrong"]
    if busy is None or correct <= 0:
        return None
    return busy * 1e3 / (correct / 1e3)
