from benchmarks.layer_ctrl import share


def read(run):
    """Slices the label kernel answered, of all slices landed in the window.
    While an overlay holds an edge between interior rows (``lab_dirty``) the
    engine switches the label route off for the whole snapshot and every
    slice is ``bfs`` until a fold has patched the index: this share is where
    that shows."""
    return share(run, "keto_stream_route_slices_total", route="label")
