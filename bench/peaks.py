"""The chip's published peaks, keyed by ``device_kind`` (``peaks.json``).

The program runs in float32 with every matmul at HIGHEST precision, which
the MXU executes as several bf16 passes; a share of the bf16 peak therefore
understates the share of what float32 can reach. A device kind that is not
in the table is an error, never a default.
"""
from __future__ import annotations

import json
from pathlib import Path

TABLE = Path(__file__).resolve().parent / "peaks.json"


class UnknownDevice(KeyError):
    pass


def for_kind(kind: str) -> dict:
    devices = json.loads(TABLE.read_text())["devices"]
    if kind not in devices:
        raise UnknownDevice(f"no peaks for device kind {kind!r} in {TABLE}; "
                            f"known: {sorted(devices)}")
    return devices[kind]
