"""The chip's published peaks (``peaks.json``), keyed by ``device_kind``.

A device that is not in the table is an error, never a default: a share
of a peak that was not published for the device measured would be a
number about another chip.
"""
from __future__ import annotations

import json
from pathlib import Path

PEAKS_FILE = Path(__file__).resolve().parent / "peaks.json"


class UnknownDevice(KeyError):
    """No published peaks for this ``device_kind``."""


def peaks(device_kind: str, path: Path = PEAKS_FILE) -> dict:
    table = json.loads(path.read_text())["devices"]
    try:
        return dict(table[device_kind])
    except KeyError:
        raise UnknownDevice(f"no peaks for device_kind {device_kind!r} in "
                            f"{path.name}; known: {sorted(table)}") from None
