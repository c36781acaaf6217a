"""The table of peaks, keyed by the device kind JAX reports. A device that is
not in the table is an error, never a default."""

from __future__ import annotations

import json
from pathlib import Path


def peaks_for(device_kind: str) -> dict:
    with open(Path(__file__).with_name("peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"no peaks recorded for device kind {device_kind!r} "
                       f"(known: {sorted(table)})")
    return table[device_kind]
