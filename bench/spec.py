"""`BENCHMARK.json` and the files it names, found by name.

A cell (an entry of ``workloads``) names a configuration and a traffic mix;
each per-layer metric names itself. Their files:

* ``bench/configs/<config>.json``  the configuration as it is run;
* ``bench/references/<reference>.py``  its plain reference (named in the
  configuration file);
* ``bench/traffic/<traffic>.json``  the traffic mix's parameters;
* ``bench/metrics/<metric>.py``  the metric's reader, ``read(ctx)``.

Nothing here lists a cell, configuration, mix or metric: adding one is
adding its file and its entry in `BENCHMARK.json`.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import re
import sys
from pathlib import Path
from types import ModuleType
from typing import Dict, List, Optional

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


def load_module(path: Path) -> ModuleType:
    """Import a file by path, once (metric file names hold dots)."""
    if not path.is_file():
        raise FileNotFoundError(f"no file {path}")
    name = "bench_file_" + re.sub(r"\W", "_", str(path.resolve()))
    if name not in sys.modules:
        spec = importlib.util.spec_from_file_location(name, path)
        module = importlib.util.module_from_spec(spec)
        sys.modules[name] = module
        spec.loader.exec_module(module)
    return sys.modules[name]


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: Dict           # the configuration file's content
    traffic: Dict          # the traffic file's content, with its "name"
    end_to_end: List[Dict]
    per_layer: List[Dict]
    reference: ModuleType

    @property
    def model(self) -> Dict:
        return self.config["model"]


def _for_cell(metrics: List[Dict], cell: str) -> List[Dict]:
    return [m for m in metrics if cell in m.get("workloads", [cell])]


def load_benchmark(root: Path = ROOT) -> Dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def config_file(name: str, bench: Dict, root: Path = ROOT) -> Path:
    entry = next((c for c in bench["configs"] if c["name"] == name), None)
    if entry is None:
        raise KeyError(f"no configuration {name!r} in BENCHMARK.json")
    return root / entry["file"]


def traffic_file(name: str, bench_dir: Path = BENCH) -> Path:
    return bench_dir / "traffic" / f"{name}.json"


def metric_file(name: str, bench_dir: Path = BENCH) -> Path:
    return bench_dir / "metrics" / f"{name}.py"


def reference_file(name: str, bench_dir: Path = BENCH) -> Path:
    return bench_dir / "references" / f"{name}.py"


def load_cell(name: str, root: Path = ROOT,
              bench: Optional[Dict] = None) -> Cell:
    """The cell ``name`` of `BENCHMARK.json` with its files read."""
    bench = bench if bench is not None else load_benchmark(root)
    entry = next((w for w in bench["workloads"] if w["name"] == name), None)
    if entry is None:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; cells: "
                       f"{[w['name'] for w in bench['workloads']]}")
    config = json.loads(config_file(entry["config"], bench, root).read_text())
    traffic = json.loads(traffic_file(entry["traffic"], root / "bench").read_text())
    traffic["name"] = entry["traffic"]
    reference = load_module(reference_file(config["reference"], root / "bench"))
    return Cell(name=name, chips=int(entry["chips"]), config=config,
                traffic=traffic,
                end_to_end=_for_cell(bench["end_to_end"], name),
                per_layer=_for_cell(bench["per_layer"], name),
                reference=reference)
