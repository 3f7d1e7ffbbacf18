"""The chip's published peaks, keyed by ``jax.Device.device_kind``."""
from __future__ import annotations

import json
import pathlib

TABLE = pathlib.Path(__file__).with_name("peaks.json")


def peaks(device_kind: str) -> dict:
    """Peaks of one chip of this kind; an unknown kind is an error, never a default."""
    table = json.loads(TABLE.read_text())
    if device_kind == "source" or device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in {TABLE.name}")
    return table[device_kind]
