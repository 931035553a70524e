"""The chip's published peaks, from ``peaks.json``, keyed by the
``device_kind`` JAX reports.  A device that is not in the table is an
error, never a default."""
from __future__ import annotations

import json
import pathlib
from typing import Dict

PEAKS_FILE = pathlib.Path(__file__).resolve().parents[1] / "peaks.json"


def peaks_for(device_kind: str, path: pathlib.Path = PEAKS_FILE
              ) -> Dict[str, float]:
    """Peaks of one chip of ``device_kind``; KeyError when unknown."""
    table = json.loads(path.read_text())["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{path.name}; known: {sorted(table)}")
    return {k: float(v) for k, v in table[device_kind].items()}
