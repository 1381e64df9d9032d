"""Finds a cell and everything it names, by name, from files.

``BENCHMARK.json`` lists the cells (``workloads``), the configurations
and the metrics.  Each configuration is ``configs/<name>.json``, each
traffic mix ``traffic/<name>.json``, each per-layer metric
``metrics/<name>.py`` and each cell's correctness limits
``limits/<cell>.json``, all beside this file.  A new cell is new files
plus new entries in ``BENCHMARK.json``; nothing here changes.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import re
import sys
from pathlib import Path
from typing import Dict, List, Optional

HERE = Path(__file__).resolve().parent
CHECKOUT = HERE.parents[1]

NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT_RE = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def check_name(name: str, what: str = "name") -> str:
    if not isinstance(name, str) or not NAME_RE.match(name):
        raise ValueError(f"{what} {name!r}: a name is 1-64 of A-Z a-z 0-9 "
                         f"_ . - and starts with a letter, digit or _")
    return name


def check_unit(unit: str) -> str:
    if not isinstance(unit, str) or not UNIT_RE.match(unit):
        raise ValueError(f"unit {unit!r}: 1-16 of A-Z a-z 0-9 _ / % . -")
    return unit


@dataclasses.dataclass
class Metric:
    name: str
    unit: str
    better: str
    source: str
    layer: Optional[str] = None
    moves: Optional[str] = None
    workloads: Optional[List[str]] = None
    bound: Optional[float] = None

    def applies_to(self, cell: str) -> bool:
        return self.workloads is None or cell in self.workloads


@dataclasses.dataclass
class Cell:
    name: str
    config_name: str
    traffic_name: str
    chips: int
    config: dict
    traffic: dict
    limits: dict
    end_to_end: List[Metric]
    per_layer: List[Metric]


def _metric(entry: dict) -> Metric:
    m = Metric(**entry)
    check_name(m.name, "metric")
    check_unit(m.unit)
    if m.better not in ("lower", "higher"):
        raise ValueError(f"metric {m.name}: better is lower or higher")
    return m


def load_benchmark(root: Path = CHECKOUT) -> dict:
    with open(root / "BENCHMARK.json") as f:
        return json.load(f)


def _json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def find_cell(name: str, bench: Optional[dict] = None,
              here: Path = HERE) -> Cell:
    """The cell ``name`` with its configuration, traffic, limits and
    metrics, each read from the file its name points to."""
    bench = bench if bench is not None else load_benchmark()
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r}; known: {sorted(cells)}")
    w = cells[name]
    for k in ("name", "config", "traffic"):
        check_name(w[k], k)
    e2e = [_metric(m) for m in bench["end_to_end"]]
    per = [_metric(m) for m in bench["per_layer"]]
    return Cell(
        name=name, config_name=w["config"], traffic_name=w["traffic"],
        chips=int(w["chips"]),
        config=_json(here / "configs" / f"{w['config']}.json"),
        traffic=_json(here / "traffic" / f"{w['traffic']}.json"),
        limits=_json(here / "limits" / f"{name}.json"),
        end_to_end=[m for m in e2e if m.applies_to(name)],
        per_layer=[m for m in per if m.applies_to(name)])


def _load(path: Path, modname: str):
    modname = modname.replace(".", "_").replace("-", "_")
    if modname not in sys.modules:
        spec = importlib.util.spec_from_file_location(modname, path)
        mod = importlib.util.module_from_spec(spec)
        sys.modules[modname] = mod
        spec.loader.exec_module(mod)
    return sys.modules[modname]


def metric_reader(name: str, here: Path = HERE):
    """The module ``metrics/<name>.py``: ``LAYER``, ``UNIT``, ``MOVES``
    and ``read(run) -> float | None``."""
    return _load(here / "metrics" / f"{check_name(name, 'metric')}.py",
                 "chip_metric_" + name)


def graph_family(name: str, here: Path = HERE):
    """The generator module ``graphs/<name>.py``."""
    return _load(here / "graphs" / f"{check_name(name, 'graph family')}.py",
                 "chip_graph_" + name)


def peaks(device_kind: str, here: Path = HERE) -> Dict[str, float]:
    table = _json(here / "peaks.json")
    if device_kind not in table["devices"]:
        raise KeyError(f"device kind {device_kind!r} has no peaks in "
                       f"peaks.json (known: {sorted(table['devices'])})")
    return table["devices"][device_kind]
