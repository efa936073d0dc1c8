"""Published peaks of one chip, keyed by JAX's ``device_kind``."""
from __future__ import annotations

import json
import os
from typing import Any, Dict

_TABLE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                      "peaks.json")


def peaks_for(device_kind: str, table: str = _TABLE) -> Dict[str, Any]:
    """The peaks of ``device_kind``; a kind not in the table is an error,
    never a default."""
    with open(table) as f:
        peaks = json.load(f)
    if device_kind not in peaks:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{table} (have {sorted(peaks)})")
    return peaks[device_kind]
