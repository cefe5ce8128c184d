"""Per-chip peaks keyed by JAX's ``device_kind`` (``peaks.json``).  A
device that is not in the table is an error, never a default."""

from __future__ import annotations

import json
from pathlib import Path

TABLE = json.loads((Path(__file__).resolve().parent / "peaks.json")
                   .read_text())


class UnknownDevice(KeyError):
    pass


def lookup(device_kind: str) -> dict:
    if device_kind not in TABLE:
        raise UnknownDevice(f"no peaks for device kind {device_kind!r} in "
                            f"peaks.json; known: {sorted(TABLE)}")
    return TABLE[device_kind]
