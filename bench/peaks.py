"""Published peaks of each device kind (``peaks.json``).  A device that
is not in the table is an error, never a default."""
from __future__ import annotations

import json
import pathlib

PATH = pathlib.Path(__file__).resolve().parent / "peaks.json"


class UnknownDevice(KeyError):
    pass


def lookup(device_kind: str, path=PATH) -> dict:
    table = json.loads(pathlib.Path(path).read_text())
    if device_kind not in table:
        raise UnknownDevice(f"no peaks for device kind {device_kind!r}; "
                            f"known: {sorted(table)}")
    return table[device_kind]
