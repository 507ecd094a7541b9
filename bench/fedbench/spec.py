"""Find a cell's files by the names in ``BENCHMARK.json``.

A cell names a configuration (``bench/configs/<config>.json``) and a
traffic mix (``bench/traffic/<traffic>.json``); a per-layer metric is read
by ``bench/metrics/<metric>.py``; the model's plain reference is
``bench/reference/<model>.py``.  Nothing here is specific to one of them,
so a later cell, mix or metric is new files and a new entry.
"""

from __future__ import annotations

import importlib.util
import json
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parents[1]
ROOT = BENCH_DIR.parent


def load_benchmark(root: Path = ROOT) -> dict:
    return json.loads((Path(root) / "BENCHMARK.json").read_text())


def load_json(kind: str, name: str, bench_dir: Path = BENCH_DIR) -> dict:
    path = Path(bench_dir) / kind / f"{name}.json"
    if not path.is_file():
        raise FileNotFoundError(f"no {kind} file {path}")
    return json.loads(path.read_text())


def cell(bench: dict, name: str) -> dict:
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload {name!r} in BENCHMARK.json; have "
                   f"{[w['name'] for w in bench['workloads']]}")


def _reports(metric: dict, cell_name: str) -> bool:
    return "workloads" not in metric or cell_name in metric["workloads"]


def end_to_end(bench: dict, cell_name: str) -> list[dict]:
    return [m for m in bench["end_to_end"] if _reports(m, cell_name)]


def per_layer(bench: dict, cell_name: str) -> list[dict]:
    moved = {m["name"] for m in end_to_end(bench, cell_name)}
    return [m for m in bench["per_layer"]
            if _reports(m, cell_name) and m["moves"] in moved]


def load_module(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or spec.loader is None:
        raise ImportError(f"cannot load {path}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def reader(metric: str):
    """The ``read(window)`` function of ``bench/metrics/<metric>.py``."""
    path = BENCH_DIR / "metrics" / f"{metric}.py"
    return load_module(path, f"bench_metric_{metric.replace('.', '_')}").read


def reference(model: str):
    """The ``Reference`` class of ``bench/reference/<model>.py``."""
    path = BENCH_DIR / "reference" / f"{model}.py"
    return load_module(path, f"bench_reference_{model}").Reference


def peaks(device_kind: str) -> dict:
    """The peak rates of ``bench/peaks.json`` for a device kind; a device
    missing from the table is an error, never a default."""
    table = json.loads((BENCH_DIR / "peaks.json").read_text())
    if device_kind not in table["devices"]:
        raise KeyError(f"device {device_kind!r} is not in bench/peaks.json "
                       f"(have {sorted(table['devices'])})")
    return table["devices"][device_kind]
