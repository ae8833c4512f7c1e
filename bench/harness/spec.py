"""Finds a cell's files by the names that `BENCHMARK.json` gives them.

A cell is one entry of ``workloads``: a configuration under a traffic mix.
Every file that belongs to one configuration, one traffic mix or one metric
is found by its name, so a later change adds files and entries and edits
none:

* ``bench/configs/<config>.json`` (the path the ``configs`` entry names)
* ``bench/traffic/<traffic>.json``
* ``bench/e2e_metrics/<metric>.py`` and ``bench/layer_metrics/<metric>.py``,
  each defining ``read(run) -> float | None``
* ``bench/references/<reference>.py``, named by the configuration file
"""

from __future__ import annotations

import importlib.util
import json
from dataclasses import dataclass
from pathlib import Path
from types import ModuleType
from typing import Callable

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent


class SpecError(ValueError):
    """The cell, or a file it needs, is not where its name says."""


@dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: dict            # the configuration file's contents
    traffic: dict           # the traffic mix file's contents
    end_to_end: tuple       # BENCHMARK.json metric entries this cell reports
    per_layer: tuple
    bench: Path             # the directory the lookups ran against


def load_spec(root: Path = ROOT) -> dict:
    path = root / "BENCHMARK.json"
    if not path.is_file():
        raise SpecError(f"no BENCHMARK.json at {root}")
    return json.loads(path.read_text())


def _one(entries, name: str, what: str) -> dict:
    found = [e for e in entries if e["name"] == name]
    if len(found) != 1:
        raise SpecError(f"{what} {name!r}: {len(found)} entries in BENCHMARK.json")
    return found[0]


def _read_json(path: Path, what: str) -> dict:
    if not path.is_file():
        raise SpecError(f"{what}: no file {path}")
    return json.loads(path.read_text())


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def find_cell(spec: dict, workload: str, root: Path = ROOT) -> Cell:
    """The cell named ``workload`` with its configuration and traffic files
    read, and the metrics BENCHMARK.json says it reports."""
    bench = root / "bench"
    w = _one(spec["workloads"], workload, "workload")
    c = _one(spec["configs"], w["config"], "config")
    config = _read_json(root / c["file"], f"config {c['name']!r}")
    traffic = _read_json(bench / "traffic" / f"{w['traffic']}.json",
                         f"traffic {w['traffic']!r}")
    return Cell(
        name=workload, chips=int(w["chips"]), config=config, traffic=traffic,
        end_to_end=tuple(m for m in spec["end_to_end"] if _applies(m, workload)),
        per_layer=tuple(m for m in spec["per_layer"] if _applies(m, workload)),
        bench=bench)


def _load_module(path: Path, what: str) -> ModuleType:
    if not path.is_file():
        raise SpecError(f"{what}: no file {path}")
    mod_spec = importlib.util.spec_from_file_location(
        f"bench_{path.parent.name}_{path.stem}".replace(".", "_").replace("-", "_"),
        path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod


def metric_reader(cell: Cell, metric: dict, kind: str) -> Callable:
    """``read(run)`` of an end-to-end (``kind="e2e"``) or per-layer
    (``kind="layer"``) metric, from the file named after it."""
    folder = {"e2e": "e2e_metrics", "layer": "layer_metrics"}[kind]
    return _load_module(cell.bench / folder / f"{metric['name']}.py",
                        f"metric {metric['name']!r}").read


def reference_module(cell: Cell) -> ModuleType:
    """The plain reference the configuration file names."""
    name = cell.config["reference"]
    return _load_module(cell.bench / "references" / f"{name}.py",
                        f"reference {name!r}")
