"""Finds what a cell of ``BENCHMARK.json`` names, by name, in files:

* a configuration: the ``file`` of its ``configs`` entry;
* a traffic mix: ``bench/traffic/<traffic>.json``, whose ``kind`` names its
  generator ``bench/traffic/<kind>.py``;
* the limits that decide ``correct``: ``bench/limits/<cell>.json``;
* a configuration's architecture module: ``bench/arch/<arch>.py``;
* a configuration's plain reference: ``bench/references/<reference>.py``;
* a per-layer metric: ``bench/metrics/<metric>.py``.

So a cell, a configuration, a mix or a metric is added with files and
entries alone.
"""
from __future__ import annotations

import importlib.util
import json
from pathlib import Path
from types import ModuleType
from typing import List


def load(root: Path) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def _by_name(entries: List[dict], name: str, what: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise KeyError(f"no {what} named {name!r} (known: {[e['name'] for e in entries]})")


def cell(bench: dict, name: str) -> dict:
    return _by_name(bench["workloads"], name, "workload")


def config(root: Path, bench: dict, name: str) -> dict:
    return json.loads((root / _by_name(bench["configs"], name, "config")["file"]).read_text())


def traffic(root: Path, name: str) -> dict:
    return json.loads((root / "bench" / "traffic" / f"{name}.json").read_text())


def limits(root: Path, cell_name: str) -> dict:
    return json.loads((root / "bench" / "limits" / f"{cell_name}.json").read_text())


def _module(path: Path) -> ModuleType:
    if not path.is_file():
        raise FileNotFoundError(path)
    spec = importlib.util.spec_from_file_location(f"bench_{path.parent.name}_{path.stem}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def generator(root: Path, kind: str) -> ModuleType:
    return _module(root / "bench" / "traffic" / f"{kind}.py")


def arch(root: Path, name: str) -> ModuleType:
    return _module(root / "bench" / "arch" / f"{name}.py")


def reference(root: Path, name: str) -> ModuleType:
    return _module(root / "bench" / "references" / f"{name}.py")


def metric_reader(root: Path, name: str) -> ModuleType:
    return _module(root / "bench" / "metrics" / f"{name}.py")


def metrics_of(bench: dict, section: str, cell_name: str) -> List[dict]:
    """The ``end_to_end`` or ``per_layer`` metrics a cell reports: those
    without a ``workloads`` key, and those that list it."""
    return [m for m in bench[section] if cell_name in m.get("workloads", [cell_name])]
