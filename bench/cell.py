"""Find a cell and everything it is made of, by name.

A cell is one entry of ``workloads`` in ``BENCHMARK.json``.  It names a
configuration (an entry of ``configs``, whose ``file`` holds the sizes),
a traffic mix (``bench/traffic/<mix>.json``) and, through the metric
entries that list it or list no cells at all, the readers of its metrics
(``bench/metrics/<metric>.py``).  Adding a cell, a configuration, a mix or
a metric is adding files and entries; nothing here names one.
"""

from __future__ import annotations

import importlib.util
import json
import pathlib
from dataclasses import dataclass, field
from types import ModuleType

from bench import traffic_gen

BENCH_DIR = "bench"


class UnknownWorkload(LookupError):
    """The workload named on the command line is not in BENCHMARK.json."""


@dataclass
class Metric:
    name: str
    unit: str
    reader: ModuleType


@dataclass
class Cell:
    name: str
    chips: int
    config_name: str
    config: dict
    traffic_name: str
    traffic: dict
    end_to_end: list[Metric] = field(default_factory=list)
    per_layer: list[Metric] = field(default_factory=list)

    def metrics(self, trace: bool) -> list[Metric]:
        return self.per_layer if trace else self.end_to_end


def _reader(root: pathlib.Path, name: str) -> ModuleType:
    path = root / BENCH_DIR / "metrics" / f"{name}.py"
    if not path.is_file():
        raise FileNotFoundError(f"metric {name!r} has no reader at {path}")
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{name.replace('.', '_').replace('-', '_')}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    if not callable(getattr(module, "read", None)):
        raise TypeError(f"{path} defines no read(run)")
    return module


def _metrics(root: pathlib.Path, entries: list[dict], cell: str
             ) -> list[Metric]:
    return [Metric(m["name"], m["unit"], _reader(root, m["name"]))
            for m in entries
            if "workloads" not in m or cell in m["workloads"]]


def load_cell(root: pathlib.Path, name: str) -> Cell:
    """The cell ``name`` of ``root/BENCHMARK.json``, with its files read."""
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise UnknownWorkload(
            f"unknown workload {name!r}; BENCHMARK.json has "
            f"{sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    config = json.loads((root / configs[w["config"]]["file"]).read_text())
    traffic = json.loads(
        (root / BENCH_DIR / "traffic" / f"{w['traffic']}.json").read_text())
    traffic_gen.validate(traffic)
    return Cell(
        name=name, chips=int(w["chips"]),
        config_name=w["config"], config=config,
        traffic_name=w["traffic"], traffic=traffic,
        end_to_end=_metrics(root, bench["end_to_end"], name),
        per_layer=_metrics(root, bench["per_layer"], name),
    )
