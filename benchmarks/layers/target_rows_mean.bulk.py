from benchmarks.layer_util import delta


def read(run):
    """Target-side rows (the interior target, or the rows a sink's answer is
    gathered from) per check that reached ``pack_chunk`` inside the window.
    None on a program without the counters, or where nothing was packed."""
    packed = delta(run, "keto_check_packed_total")
    if packed <= 0:
        return None
    return delta(run, "keto_check_pack_rows_total", side="target") / packed
