"""Published peaks of each chip the benchmark runs on (``peaks.json``).

A device kind that is not in the table is an error, never a default."""
from __future__ import annotations

import json
from pathlib import Path

TABLE = Path(__file__).resolve().parent / "peaks.json"


def peaks(device_kind: str, table: Path = None) -> dict:
    table = TABLE if table is None else table
    with open(table) as f:
        devices = json.load(f)["devices"]
    if device_kind not in devices:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{table.name} (have {sorted(devices)})")
    return devices[device_kind]
