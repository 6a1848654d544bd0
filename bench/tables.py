"""The dictionary a configuration is packed with, built once per checkout.

The program builds one model-wide table of frequent 4-grams over the
quantized weights.  Every seed's weights are drawn from the same Laplace
law, so one table serves them all: it is built by the program's own
``build_serve_params`` from the configuration's ``table_seed`` draw the
first time a checkout runs the configuration, kept under
``bench/.cache/tables/``, and handed to the packer on every later run.
The key covers the configuration file, the weight law and every source
file of the program, so a change to any of them builds a new table.
"""
from __future__ import annotations

import hashlib
import json
import pathlib

import numpy as np

from bench import spec

CACHE = spec.BENCH / ".cache" / "tables"


def key(config: dict, src=spec.CHECKOUT / "src" / "repro") -> str:
    h = hashlib.sha256(json.dumps(config, sort_keys=True).encode())
    h.update((spec.BENCH / "weights.py").read_bytes())
    for p in sorted(pathlib.Path(src).rglob("*.py")):
        h.update(str(p.relative_to(src)).encode())
        h.update(p.read_bytes())
    return h.hexdigest()[:16]


def path_for(config: dict, cache=None) -> pathlib.Path:
    return pathlib.Path(cache or CACHE) / f"{config['name']}-{key(config)}.npz"


def save(table: dict, path: pathlib.Path) -> None:
    path.parent.mkdir(parents=True, exist_ok=True)
    grams = np.array(list(table.keys()), np.uint8).reshape(len(table), 4)
    codes = np.fromiter(table.values(), np.int64, count=len(table))
    tmp = path.with_suffix(".tmp.npz")
    np.savez(tmp, grams=grams, codes=codes)
    tmp.replace(path)


def load(path: pathlib.Path):
    if not path.is_file():
        return None
    with np.load(path) as z:
        return {tuple(int(b) for b in g): int(c)
                for g, c in zip(z["grams"], z["codes"])}
