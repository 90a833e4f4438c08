"""The table of published peaks (``peaks.json``), keyed by ``device_kind``. A
device that is not in the table is an error, never a default."""

from __future__ import annotations

import json
import os


def peaks_for(device_kind: str) -> dict:
    with open(os.path.join(os.path.dirname(os.path.abspath(__file__)), "peaks.json")) as f:
        table = json.load(f)["peaks"]
    kind = (device_kind or "").lower()
    for key, peaks in table.items():
        if key in kind:
            return peaks
    raise KeyError(
        f"device_kind {device_kind!r} is not in chipbench/peaks.json ({sorted(table)}); "
        "add its published peaks with their source"
    )
