"""Find a cell's configuration, traffic mix and metric readers by name.

A later change adds a configuration, a mix, a per-layer metric or a model
family (families/) by adding a file; nothing here names one.
"""
from __future__ import annotations

import importlib.util
import json
from dataclasses import dataclass
from pathlib import Path

from . import families

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent


class SpecError(RuntimeError):
    pass


@dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: list       # metric entries of BENCHMARK.json this cell reports
    per_layer: list


def load_json(path: Path) -> dict:
    try:
        return json.loads(Path(path).read_text())
    except FileNotFoundError:
        raise SpecError(f"missing file: {path}") from None


def load_traffic(name: str, base_dir: Path = HERE / "traffic") -> dict:
    mix = load_json(base_dir / f"{name}.json")
    mix["name"] = name
    return mix


def _reports(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, bench_path: Path = ROOT / "BENCHMARK.json") -> Cell:
    bench = load_json(bench_path)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SpecError(f"no workload {name!r} in {bench_path}; "
                        f"have {sorted(cells)}")
    w = cells[name]
    cfg_entry = {c["name"]: c for c in bench["configs"]}[w["config"]]
    config = load_json(ROOT / cfg_entry["file"])
    families.load(config)
    return Cell(
        name=name, chips=int(w["chips"]), config=config,
        traffic=load_traffic(w["traffic"]),
        end_to_end=[m for m in bench["end_to_end"] if _reports(m, name)],
        per_layer=[m for m in bench["per_layer"] if _reports(m, name)])


def load_reader(metric: str, base_dir: Path = HERE / "metrics"):
    """The per-layer metric's reader module, ``metrics/<metric>.py``."""
    path = base_dir / f"{metric}.py"
    if not path.exists():
        raise SpecError(f"no reader for per-layer metric {metric!r}: {path}")
    spec = importlib.util.spec_from_file_location(
        f"chipbench_metric_{metric.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod
