"""The table of peaks, keyed by ``device_kind``.  A kind that is not in the
table is an error, not a default."""

from __future__ import annotations

import json
import os

_PATH = os.path.join(os.path.dirname(os.path.abspath(__file__)), "peaks.json")


def peaks_for(device_kind: str) -> dict:
    with open(_PATH) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(
            f"no peaks for device kind {device_kind!r} in {_PATH}; "
            f"known: {sorted(table)}"
        )
    return table[device_kind]
