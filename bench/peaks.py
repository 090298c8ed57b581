"""Published peaks of each chip, keyed by JAX's ``device_kind``.

The table is ``peaks.json`` beside this file, each row with its source.
A kind that is not in the table is an error, never a default.
"""
from __future__ import annotations

import json
from pathlib import Path

TABLE = Path(__file__).with_name("peaks.json")


def peaks(device_kind: str) -> dict:
    table = json.loads(TABLE.read_text())
    if device_kind not in table:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r}; known: {sorted(table)}")
    return table[device_kind]
