"""Find a cell's files by the names ``BENCHMARK.json`` gives them.

A configuration ``<name>`` is ``bench/configs/<name>.json`` (its sizes)
with its plain reference ``bench/configs/<name>.py`` beside it; a traffic
mix ``<name>`` is ``bench/traffic/<name>.json``; a per-layer metric
``<name>`` is read by ``bench/metrics/<name>.py``.  Adding a cell means
adding such files and an entry in ``BENCHMARK.json``; nothing here names
one.
"""
from __future__ import annotations

import importlib.util
import json
from dataclasses import dataclass
from pathlib import Path
from types import ModuleType
from typing import Any, Dict, List

BENCH = Path(__file__).resolve().parents[1]


def load_module(path: Path, name: str) -> ModuleType:
    """Import the Python file at ``path`` (its name may hold dots and
    dashes, so it is loaded by path, not by package)."""
    if not path.is_file():
        raise FileNotFoundError(path)
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclass
class Cell:
    name: str
    chips: int
    config_name: str
    config: Dict[str, Any]          # the configuration file
    traffic_name: str
    traffic: Dict[str, Any]         # the traffic file
    end_to_end: List[Dict[str, Any]]
    per_layer: List[Dict[str, Any]]
    bench_dir: Path

    def reference(self) -> ModuleType:
        """The configuration's plain reference module."""
        return load_module(self.bench_dir / "configs" / f"{self.config_name}.py",
                           f"bench_ref_{self.config_name}")

    def metric_reader(self, name: str) -> ModuleType:
        return load_module(self.bench_dir / "metrics" / f"{name}.py",
                           f"bench_metric_{name}")


def _applies(metric: Dict[str, Any], cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(root: Path, workload: str, bench_dir: Path = BENCH) -> Cell:
    """The cell ``workload`` of ``root/BENCHMARK.json`` with its files."""
    spec = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json "
                       f"(have {sorted(cells)})")
    w = cells[workload]
    configs = {c["name"]: c for c in spec["configs"]}
    config = json.loads((root / configs[w["config"]]["file"]).read_text())
    traffic = json.loads(
        (bench_dir / "traffic" / f"{w['traffic']}.json").read_text())
    return Cell(
        name=workload, chips=int(w["chips"]),
        config_name=w["config"], config=config,
        traffic_name=w["traffic"], traffic=traffic,
        end_to_end=[m for m in spec["end_to_end"] if _applies(m, workload)],
        per_layer=[m for m in spec["per_layer"] if _applies(m, workload)],
        bench_dir=bench_dir)
