"""The one table of device peaks, keyed by the exact ``device_kind`` JAX
reports.  A kind that is not in the table is an error, not a default."""
from __future__ import annotations

import json
import os

_TABLE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                      "peaks.json")


def peaks(device_kind: str) -> dict:
    with open(_TABLE, encoding="utf-8") as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"no peaks for device_kind {device_kind!r}; the "
                       f"table has {sorted(table)}. Add a row with its "
                       "source; do not guess.")
    return table[device_kind]
