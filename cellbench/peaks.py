"""The table of peaks, keyed by `device_kind`. A device that is not in it is
an error, never a default."""

from __future__ import annotations

import json
from pathlib import Path

HERE = Path(__file__).resolve().parent


def peaks_for(device_kind: str) -> dict:
    table = json.loads((HERE / "peaks.json").read_text())
    if device_kind not in table:
        raise KeyError(
            f"device kind {device_kind!r} is not in cellbench/peaks.json "
            f"(known: {sorted(table)}); add its published peaks with their source"
        )
    return table[device_kind]
