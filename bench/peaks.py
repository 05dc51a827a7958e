"""Published peaks of one chip, keyed by JAX's ``device_kind``."""
from __future__ import annotations

import json
import os

PEAKS_FILE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "peaks.json")


def peak(device_kind: str, path: str = PEAKS_FILE) -> dict:
    """The peak table's row for ``device_kind``; an unknown kind is an
    error, never a default."""
    with open(path) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r} in {path}; known: {sorted(table)}")
    return table[device_kind]
