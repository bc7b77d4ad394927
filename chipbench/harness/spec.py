"""Find a cell's parts by the names in ``BENCHMARK.json``.

A cell names a configuration and a traffic mix; each is a file of its own
(``chipbench/configs/<config>.json``, ``chipbench/traffic/<mix>.json``),
and each per-layer metric is a reader ``chipbench/metrics/<metric>.py``.
A configuration's ``reference`` key names its architecture: the module
``chipbench/arch/<reference>.py`` (the program's config, the weights'
layout, the counts of useful work; see ``chipbench/arch/__init__.py``)
and the plain reference ``chipbench/reference/<reference>.py``.

Adding a cell, a configuration, a mix, a metric or an architecture adds
files and entries and edits none.  A configuration of a new kind of model
brings its architecture as those two new modules, and names them in its
``reference`` key; a reader of a new kernel asks the architecture's
``kernel_calls`` for the kernel by name and finds its events in the trace
by that name (``xtrace.kernel_ops``).
"""
from __future__ import annotations

import dataclasses
import importlib
import json
from pathlib import Path
from typing import Dict, List

BENCH_DIR = Path(__file__).resolve().parents[1]
ROOT = BENCH_DIR.parent


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: Dict            # the configuration file, as run
    mix: Dict               # the traffic mix file
    end_to_end: List[Dict]  # BENCHMARK.json metrics that this cell reports
    per_layer: List[Dict]

    @property
    def arch(self) -> Dict:
        return self.config["arch"]

    @property
    def architecture(self):
        return architecture(self.config)


def _load_json(path: Path) -> Dict:
    with open(path) as f:
        return json.load(f)


def _reports(metric: Dict, cell: str) -> bool:
    return cell in metric.get("workloads", [cell])


def load_cell(name: str, bench_file: Path = ROOT / "BENCHMARK.json") -> Cell:
    bench = _load_json(bench_file)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; known: {sorted(cells)}")
    w = cells[name]
    return Cell(
        name=name, chips=int(w["chips"]),
        config=_load_json(BENCH_DIR / "configs" / f"{w['config']}.json"),
        mix=_load_json(BENCH_DIR / "traffic" / f"{w['traffic']}.json"),
        end_to_end=[m for m in bench["end_to_end"] if _reports(m, name)],
        per_layer=[m for m in bench["per_layer"] if _reports(m, name)])


def reader(metric: str):
    """The per-layer metric's reader: ``read(run) -> float | None``."""
    mod = importlib.import_module(f"chipbench.metrics.{metric}")
    return mod.read


def architecture(config: Dict):
    """The configuration's architecture module."""
    return importlib.import_module(f"chipbench.arch.{config['reference']}")


def reference(config: Dict):
    """The configuration's plain reference module."""
    return importlib.import_module(f"chipbench.reference.{config['reference']}")
