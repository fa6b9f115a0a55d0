"""The table of peaks, keyed by ``device_kind``; an unknown kind raises."""

from __future__ import annotations

import json
import os

_PATH = os.path.join(os.path.dirname(os.path.abspath(__file__)), "peaks.json")


def peaks_for(device_kind: str) -> dict:
    with open(_PATH) as fh:
        table = json.load(fh)
    if device_kind.startswith("_") or device_kind not in table:
        raise KeyError(
            f"device_kind {device_kind!r} is not in {_PATH}: add its "
            "published peaks with their source; there is no default")
    return table[device_kind]
