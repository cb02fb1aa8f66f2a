"""Published peaks of each accelerator, keyed by JAX's ``device_kind``.

A device that is not in ``peaks.json`` is an error, never a default: a
share of an unknown peak is not a measurement."""
from __future__ import annotations

import json
import os

_PATH = os.path.join(os.path.dirname(os.path.abspath(__file__)), "peaks.json")


class UnknownDevice(KeyError):
    pass


def table() -> dict:
    with open(_PATH) as f:
        return json.load(f)


def peaks(device_kind: str) -> dict:
    """The peaks row of one device kind: ``bf16_flops_per_s``,
    ``hbm_bytes_per_s``, ``hbm_bytes`` and their ``source``."""
    rows = table()
    if device_kind not in rows:
        raise UnknownDevice(f"no published peaks for device kind "
                            f"{device_kind!r}; known: {sorted(rows)}")
    return rows[device_kind]
