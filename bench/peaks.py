"""Published peaks of the chips the benchmark runs on, keyed by JAX's
``device_kind``. A kind that is not in ``peaks.json`` is an error: a
roofline or utilization against a guessed peak means nothing."""
from __future__ import annotations

import json
import os
from typing import Dict

PATH = os.path.join(os.path.dirname(os.path.abspath(__file__)), "peaks.json")


class UnknownDevice(KeyError):
    """The device kind has no row in the peaks table."""


def peaks_for(device_kind: str, path: str = PATH) -> Dict[str, float]:
    with open(path) as f:
        table = json.load(f)
    if device_kind not in table:
        raise UnknownDevice(
            f"no published peaks for device kind {device_kind!r}; "
            f"known: {sorted(table)}")
    return table[device_kind]
