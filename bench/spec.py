"""What one cell is, read from ``BENCHMARK.json`` and the files it names.

A cell (an entry of ``workloads``) names a configuration and a traffic mix.
Each lives in files of its own, found by name:

- ``bench/configs/<config>.json``: the configuration as it is run, with
  its source, what was reduced and assumed, and the deployment it stands
  for; ``bench/configs/<config>.py`` beside it holds the plain reference
  and the model-FLOPs function.
- ``bench/traffic/<traffic>.json``: the traffic mix (kind, batch, lengths,
  token distribution, optimizer settings of the job).
- ``bench/workloads/<cell>.json``: the limits of the numbers that decide
  ``correct`` in that cell, with the readings they were set from.
- ``bench/metrics/<metric>.py``: one reader per per-layer metric.

Adding a configuration, a mix, a cell or a metric adds files and entries;
no file that is already here needs an edit.
"""

from __future__ import annotations

import importlib.util
import json
from dataclasses import dataclass
from pathlib import Path
from types import ModuleType

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


def load_module(path: Path) -> ModuleType:
    """Import a file by path (configuration and metric files are named
    after benchmark names, which need not be Python identifiers)."""
    name = "bench_" + "".join(c if c.isalnum() else "_" for c in
                              str(path.relative_to(BENCH).with_suffix("")))
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclass
class Cell:
    name: str
    chips: int
    config: dict            # bench/configs/<config>.json
    model: ModuleType       # bench/configs/<config>.py
    traffic: dict           # bench/traffic/<traffic>.json
    limits: dict            # bench/workloads/<cell>.json "limits"
    end_to_end: list        # BENCHMARK.json metrics this cell reports
    per_layer: list


def _reports(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str) -> Cell:
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; BENCHMARK.json has "
                         f"{sorted(cells)}")
    w = cells[name]
    return make_cell(
        name, chips=w["chips"],
        config_path=BENCH / "configs" / f"{w['config']}.json",
        traffic_path=BENCH / "traffic" / f"{w['traffic']}.json",
        workload_path=BENCH / "workloads" / f"{name}.json",
        end_to_end=[m for m in spec["end_to_end"] if _reports(m, name)],
        per_layer=[m for m in spec["per_layer"] if _reports(m, name)])


def make_cell(name: str, *, chips: int, config_path: Path,
              traffic_path: Path, workload_path: Path,
              end_to_end: list, per_layer: list,
              model_path: Path | None = None) -> Cell:
    return Cell(
        name=name, chips=chips,
        config=json.loads(config_path.read_text()),
        model=load_module(model_path or config_path.with_suffix(".py")),
        traffic=json.loads(traffic_path.read_text()),
        limits=json.loads(workload_path.read_text())["limits"],
        end_to_end=end_to_end, per_layer=per_layer)


def metric_reader(name: str):
    """The ``read(ctx)`` function of ``bench/metrics/<name>.py``."""
    return load_module(BENCH / "metrics" / f"{name}.py").read
