"""Find a cell's pieces by name.

``BENCHMARK.json`` (at the checkout's root) lists the cells; everything
that belongs to one configuration, traffic mix, cell or per-layer metric
sits in a file of its own under ``bench/``:

* ``configs/<config>.json`` — the configuration's sizes as published, what
  was cut, and the program's settings for it; its ``reference`` key names
  the plain reference beside it (``configs/<config>.ref.py``);
* ``traffic/<traffic>.json`` — the federated workload's parameters;
* ``cells/<workload>.json`` — the limits of the numbers compared, with the
  readings they were set from;
* ``metrics/<metric>.py`` — a reader ``read(facts)`` returning one number
  or None.

A later cell, configuration, mix or metric is added by adding files and
entries; no file here needs an edit."""
from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    limits: dict
    ref_path: Path
    per_layer: list
    root: Path


def _for(entries: list, workload: str) -> list:
    return [m for m in entries if workload in m.get("workloads", [workload])]


def load(workload: str, bench: Path = BENCH) -> Cell:
    root = bench.parent
    spec = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r}; known: {sorted(cells)}")
    w = cells[workload]
    conf = {c["name"]: c for c in spec["configs"]}[w["config"]]
    cfg_path = root / conf["file"]
    config = json.loads(cfg_path.read_text())
    traffic = json.loads((bench / "traffic" / f"{w['traffic']}.json")
                         .read_text())
    cell_file = bench / "cells" / f"{workload}.json"
    limits = (json.loads(cell_file.read_text())["limits"]
              if cell_file.exists() else {})
    return Cell(name=workload, chips=w["chips"], config=config,
                traffic=traffic, limits=limits,
                ref_path=cfg_path.parent / config["reference"],
                per_layer=_for(spec["per_layer"], workload),
                root=root)


def metric_reader(name: str, bench: Path = BENCH):
    """The ``read`` function of ``metrics/<name>.py``."""
    path = bench / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        "metric_" + name.replace("-", "_").replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read
