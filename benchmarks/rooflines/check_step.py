"""The bytes ``check_step`` moves, counted from what the program reports.

A pull gathers, for every slot of the bucketed ELL (an in-neighbour of a valid
row, or the padding of that row up to its bucket's degree), one row of the
bitmap: ``words`` 32-bit words. The program counts pulls times words over the
slices it landed (``keto_check_pull_words_total``) and says how many slots one
pull gathers (``keto_snapshot_ell_slots``, both kinds)."""

from __future__ import annotations


def pull_bytes(pull_words: float, ell_slots: float) -> float:
    """HBM bytes the pulls read, at the least: the gathers alone. What a pull
    writes (one row an active row), the entry scatters, the relay rows of hub
    sinks (one more gather of a pull's size a slice at most) and the answer
    gather are left out, so a share computed from this is a floor."""
    return pull_words * ell_slots * 4.0
