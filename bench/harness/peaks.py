"""Published per-chip peaks, keyed by JAX's ``device_kind``.

A device that is not in ``peaks.json`` is an error, never a default.
"""

from __future__ import annotations

import json
import pathlib

TABLE = json.loads((pathlib.Path(__file__).with_name("peaks.json"))
                   .read_text())


class UnknownDevice(KeyError):
    pass


def peaks(device_kind: str) -> dict:
    try:
        return TABLE[device_kind]
    except KeyError:
        raise UnknownDevice(
            f"no published peaks for device kind {device_kind!r}; known: "
            f"{sorted(TABLE)}") from None
