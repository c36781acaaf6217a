"""A daemon entry with the timed path broken underneath: every 997th decision
is inverted where the batcher hands it to its request
(``CheckBatcher._fill``). test_broken_path.py drives a whole run on it and
has to see ``correct`` come out false."""

import itertools
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from benchmarks import daemon_entry  # noqa: E402
from keto_tpu.driver.batch import CheckBatcher  # noqa: E402

_count = itertools.count(1)
_fill = CheckBatcher._fill


def _broken_fill(self, item, idx, allowed, token):
    if next(_count) % 997 == 0:
        allowed = not allowed
    return _fill(self, item, idx, allowed, token)


CheckBatcher._fill = _broken_fill

if __name__ == "__main__":
    daemon_entry.main()
