"""Find a cell, its configuration, its model code, its traffic and its
metrics by name.

A cell is an entry of ``workloads`` in ``BENCHMARK.json``.  Its
configuration file is the one ``configs`` names; the model code for it is
``<family dir>/<family>.py``, where ``family`` is the configuration
file's key of that name; its traffic mix is
``<traffic dir>/<traffic>.json``; each per-layer metric's reader is
``<metric dir>/<metric>.py``.  Adding a cell, a configuration, a family, a
traffic mix or a metric is adding files and entries: nothing here names
one.
"""
from __future__ import annotations

import dataclasses
import hashlib
import importlib.util
import json
import pathlib
import re
import sys
import types
from typing import Callable, Dict, List, Optional, Sequence

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent
BENCHMARK = ROOT / "BENCHMARK.json"
TRAFFIC_DIRS = (BENCH / "traffic",)
METRIC_DIRS = (BENCH / "metrics",)
FAMILY_DIRS = (BENCH / "families",)
_NAME = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}")


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict              # the configuration file's contents
    family: types.ModuleType  # the model code the configuration names
    traffic: dict             # the traffic file's contents
    end_to_end: List[dict]    # BENCHMARK.json metrics this cell reports
    per_layer: List[dict]
    metric_dirs: Sequence[pathlib.Path]

    def reader(self, metric: str) -> Callable:
        """The ``read`` function of a per-layer metric's own file."""
        for d in self.metric_dirs:
            path = pathlib.Path(d) / f"{metric}.py"
            if path.is_file():
                spec = importlib.util.spec_from_file_location(
                    f"bench_metric_{metric.replace('.', '_')}", path)
                mod = importlib.util.module_from_spec(spec)
                spec.loader.exec_module(mod)
                return mod.read
        raise FileNotFoundError(f"no reader for metric {metric!r} in "
                                f"{[str(d) for d in self.metric_dirs]}")


def load_family(name: str, dirs: Sequence[pathlib.Path] = FAMILY_DIRS
                ) -> types.ModuleType:
    """The model code of family ``name``: ``<family dir>/<name>.py``, the
    first of ``dirs`` that has it, loaded once per file so that its jitted
    functions compile once per process."""
    if not isinstance(name, str) or not _NAME.fullmatch(name):
        raise ValueError(f"family {name!r} is not a name")
    for d in dirs:
        path = (pathlib.Path(d) / f"{name}.py").resolve()
        if not path.is_file():
            continue
        key = "bench_family_" + hashlib.sha1(
            str(path).encode()).hexdigest()[:12]
        if key not in sys.modules:
            spec = importlib.util.spec_from_file_location(key, path)
            mod = importlib.util.module_from_spec(spec)
            sys.modules[key] = mod
            try:
                spec.loader.exec_module(mod)
            except BaseException:
                del sys.modules[key]
                raise
        return sys.modules[key]
    raise FileNotFoundError(f"no family {name!r} in {[str(d) for d in dirs]}")


def _find(name: str, dirs: Sequence[pathlib.Path], what: str) -> dict:
    for d in dirs:
        path = pathlib.Path(d) / f"{name}.json"
        if path.is_file():
            return json.loads(path.read_text())
    raise FileNotFoundError(f"no {what} {name!r} in {[str(d) for d in dirs]}")


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, *, benchmark: pathlib.Path = BENCHMARK,
              traffic_dirs: Sequence[pathlib.Path] = TRAFFIC_DIRS,
              metric_dirs: Sequence[pathlib.Path] = METRIC_DIRS,
              family_dirs: Sequence[pathlib.Path] = FAMILY_DIRS) -> Cell:
    """The cell ``name`` of ``benchmark``, with its files read and its
    family's model code loaded.  A configuration without a ``family`` is
    an error: nothing falls back to a default."""
    benchmark = pathlib.Path(benchmark)
    spec = json.loads(benchmark.read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in {benchmark}; have "
                       f"{sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in spec["configs"]}
    cfg_path = benchmark.parent / configs[w["config"]]["file"]
    config = json.loads(cfg_path.read_text())
    if "family" not in config:
        raise KeyError(f"configuration {cfg_path} names no family")
    return Cell(
        name=name, chips=int(w["chips"]), config=config,
        family=load_family(config["family"], family_dirs),
        traffic=_find(w["traffic"], traffic_dirs, "traffic mix"),
        end_to_end=[m for m in spec["end_to_end"] if _applies(m, name)],
        per_layer=[m for m in spec["per_layer"] if _applies(m, name)],
        metric_dirs=list(metric_dirs))


def peaks(device_kind: str, path: Optional[pathlib.Path] = None) -> Dict:
    """The chip's published peaks; a device kind not in the table is an
    error, never a default."""
    table = json.loads((path or BENCH / "peaks.json").read_text())
    if device_kind not in table["devices"]:
        raise KeyError(f"no peaks for device kind {device_kind!r} in the "
                       f"table (have {sorted(table['devices'])})")
    return table["devices"][device_kind]
