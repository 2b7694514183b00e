"""Published peaks per chip, keyed by JAX's ``device_kind``, for roofline
shares; ``bench/peaks.json`` names the source of each row."""
from __future__ import annotations

import json
import pathlib
from typing import Dict

PEAKS = pathlib.Path(__file__).resolve().parents[1] / "peaks.json"


def peaks(device_kind: str) -> Dict[str, float]:
    """The row for ``device_kind``; a device that is not in the table is
    an error, never a default."""
    table = json.loads(PEAKS.read_text())
    if device_kind not in table:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r}; add a row to {PEAKS}")
    return table[device_kind]
