"""Finds every part of the benchmark by name: configurations, workloads
(cells), traffic mixes, per-layer metric readers and reference models.
Adding one adds a file; nothing here changes."""
from __future__ import annotations

import importlib
import importlib.util
import json
import pathlib

BENCH = pathlib.Path(__file__).resolve().parent
CHECKOUT = BENCH.parent


def _json(path: pathlib.Path) -> dict:
    if not path.is_file():
        raise FileNotFoundError(f"no such benchmark file: {path}")
    return json.loads(path.read_text())


def workload(name: str, root=BENCH) -> dict:
    return _json(pathlib.Path(root) / "workloads" / f"{name}.json")


def config(name: str, root=BENCH) -> dict:
    return _json(pathlib.Path(root) / "configs" / f"{name}.json")


def traffic(name: str, root=BENCH) -> dict:
    return _json(pathlib.Path(root) / "traffic" / f"{name}.json")


def model(model_type: str):
    """The reference model and layout of one architecture family."""
    return importlib.import_module(f"bench.models.{model_type}")


def metric_reader(name: str, root=BENCH):
    """``bench/metrics/<name>.py``; its ``read(ctx)`` returns the metric or
    None where the run holds nothing to read."""
    path = pathlib.Path(root) / "metrics" / f"{name}.py"
    mod_name = "bench_metric_" + name.replace(".", "_").replace("-", "_")
    s = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(s)
    s.loader.exec_module(mod)
    return mod


def benchmark(path=CHECKOUT / "BENCHMARK.json") -> dict:
    return _json(pathlib.Path(path))


def cell_metrics(bench: dict, cell: str, kind: str) -> list:
    """Metric entries of ``kind`` ('end_to_end' | 'per_layer') that the
    cell reports: those naming it, or naming no cells at all."""
    return [m for m in bench[kind]
            if "workloads" not in m or cell in m["workloads"]]
