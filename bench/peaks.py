"""Published peaks of the chips the benchmark runs on, keyed by the
``device_kind`` JAX reports (``peaks.json`` holds the numbers and their
source).  A device that is not in the table is an error, never a default."""

from __future__ import annotations

import json
from pathlib import Path
from typing import Dict

TABLE = Path(__file__).resolve().parent / "peaks.json"


def lookup(device_kind: str, allow_cpu: bool = False) -> Dict[str, float]:
    devices = json.loads(TABLE.read_text())["devices"]
    if device_kind in devices:
        return devices[device_kind]
    if allow_cpu:
        # the CPU rehearsal reads no device metric; any positive peak will do
        return {k: 1.0 for k in next(iter(devices.values()))}
    raise KeyError(f"no published peaks for device kind {device_kind!r}; "
                   f"add them to {TABLE.name} with their source")
