from benchmarks.layer_clock import has
from benchmarks.layer_util import delta

#: (declines by reason, chunks by path) of the two native passes
PASSES = (("keto_check_resolve_declines_total", "keto_check_resolve_chunks_total"),
          ("keto_check_pack_declines_total", "keto_native_pack_chunks_total"))


def read(run):
    """Chunks a native pass (resolve, pack) declined inside the window, every
    reason (``overlay`` and ``overlay_start`` are a written store's), over
    the chunks the two passes were offered: each counts once a chunk. None on
    a program without the families or in a window without a chunk."""
    if not all(has(run, family) for pair in PASSES for family in pair):
        return None
    chunks = sum(delta(run, counted) for _, counted in PASSES)
    if chunks <= 0:
        return None
    return 100.0 * sum(delta(run, declined) for declined, _ in PASSES) / chunks
