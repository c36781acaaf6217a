from benchmarks.layer_ctrl import CHUNKS, share


def read(run):
    """Resolved chunks of check slices that were whole under the geometric
    bound and cut only because the slice controller's entry budget lowered it
    (``cut="budget"``), of all the chunks dispatched inside the window. None
    where the program does not count what cut a chunk."""
    return share(run, CHUNKS, cut="budget")
