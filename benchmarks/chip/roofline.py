"""Peaks of the chip, for the share of a roofline or of a peak that a
metric reader reports.

``peaks.json`` holds each chip's published peaks keyed by JAX's
``device_kind``; a chip that is not in it is an error, never a default.
The harness looks its chip up before any work, so that a run on a chip
without published peaks stops there; no metric reader reads them yet.
"""
from __future__ import annotations

import json
from pathlib import Path

PEAKS_FILE = Path(__file__).resolve().parent / "peaks.json"


def peaks(device_kind: str) -> dict:
    table = json.loads(PEAKS_FILE.read_text())
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{PEAKS_FILE.name}; add them with their source")
    return table[device_kind]

