"""Published peaks of the chips the benchmark runs on (peaks.json),
keyed by JAX's ``device_kind``.  A kind that is not in the table is an
error, never a default: a roofline against the wrong peak is a wrong
number that looks right."""

from __future__ import annotations

import json
import os

_PATH = os.path.join(os.path.dirname(os.path.abspath(__file__)), "peaks.json")


class UnknownDevice(KeyError):
    pass


def peaks(device_kind: str) -> dict:
    with open(_PATH) as f:
        table = json.load(f)
    if device_kind not in table:
        raise UnknownDevice(
            f"no peaks for device_kind {device_kind!r}; add it to peaks.json"
        )
    return table[device_kind]
