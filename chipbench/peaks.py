"""The table of peaks, keyed by ``device_kind``.  An unknown kind raises."""

from __future__ import annotations

import json
import os

_PATH = os.path.join(os.path.dirname(os.path.abspath(__file__)), "peaks.json")


def peaks_for(device_kind: str) -> dict:
    with open(_PATH) as f:
        table = json.load(f)
    if device_kind not in table or device_kind.startswith("_"):
        known = sorted(k for k in table if not k.startswith("_"))
        raise KeyError(f"no peaks for device kind {device_kind!r} in {_PATH} (known: {known})")
    return table[device_kind]
