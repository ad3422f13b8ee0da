"""Find a cell's pieces by name.

``BENCHMARK.json`` (at the checkout's root) names each cell's
configuration, traffic mix and chips, and lists the metrics.  The files
behind those names:

* ``chipbench/workloads/<cell>.json`` — the driver kind and the engine's
  sizes for this cell;
* ``chipbench/configs/<config>.json`` — the model or program sizes;
* ``chipbench/traffic/<traffic>.json`` — the traffic parameters that
  :mod:`chipbench.traffic` reads;
* ``chipbench/drivers/<driver>.py`` — the code that drives one kind of
  cell (``run(ctx) -> dict``);
* ``chipbench/metrics/<metric>.py`` — one reader per metric
  (``read(run, trace) -> float | None``).

A later cell, configuration, traffic mix or metric is added by adding
such files and entries; no file here needs an edit.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path
from types import ModuleType
from typing import Any, Dict, List

PACKAGE = "chipbench"


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    config_name: str
    traffic_name: str
    chips: int
    spec: Dict[str, Any]  # workloads/<cell>.json
    config: Dict[str, Any]  # configs/<config>.json
    traffic: Dict[str, Any]  # traffic/<traffic>.json
    end_to_end: List[Dict[str, Any]]  # BENCHMARK.json entries this cell reports
    per_layer: List[Dict[str, Any]]


def _load_json(path: Path) -> Dict[str, Any]:
    with open(path) as f:
        return json.load(f)


def load_module(path: Path, name: str) -> ModuleType:
    """Import a file by path (metric files carry dots in their names)."""
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or spec.loader is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Bench:
    """The benchmark rooted at a checkout directory."""

    def __init__(self, root: Path):
        self.root = Path(root)
        self.dir = self.root / PACKAGE
        self.spec = _load_json(self.root / "BENCHMARK.json")

    def _entry(self, key: str, name: str) -> Dict[str, Any]:
        for e in self.spec[key]:
            if e["name"] == name:
                return e
        raise KeyError(f"no {key[:-1]} named {name!r} in BENCHMARK.json")

    @staticmethod
    def _reports(metric: Dict[str, Any], cell: str) -> bool:
        return "workloads" not in metric or cell in metric["workloads"]

    def cell(self, name: str) -> Cell:
        w = self._entry("workloads", name)
        e2e = [m for m in self.spec["end_to_end"] if self._reports(m, name)]
        e2e_names = {m["name"] for m in e2e}
        per_layer = [
            m
            for m in self.spec["per_layer"]
            if (name in m["workloads"] if "workloads" in m else m["moves"] in e2e_names)
        ]
        return Cell(
            name=name,
            config_name=w["config"],
            traffic_name=w["traffic"],
            chips=int(w["chips"]),
            spec=_load_json(self.dir / "workloads" / f"{name}.json"),
            config=_load_json(self.dir / "configs" / f"{w['config']}.json"),
            traffic=_load_json(self.dir / "traffic" / f"{w['traffic']}.json"),
            end_to_end=e2e,
            per_layer=per_layer,
        )

    def driver(self, kind: str) -> ModuleType:
        return load_module(self.dir / "drivers" / f"{kind}.py", f"{PACKAGE}_driver_{kind}")

    def metric(self, name: str) -> ModuleType:
        mod = load_module(
            self.dir / "metrics" / f"{name}.py",
            f"{PACKAGE}_metric_" + name.replace(".", "_"),
        )
        if getattr(mod, "NAME", None) != name:
            raise ValueError(f"metrics/{name}.py declares NAME={getattr(mod, 'NAME', None)!r}")
        return mod

    def peaks(self, device_kind: str) -> Dict[str, Any]:
        table = _load_json(self.dir / "peaks.json")
        if device_kind not in table["devices"]:
            raise KeyError(
                f"no peaks for device kind {device_kind!r} in chipbench/peaks.json"
            )
        return table["devices"][device_kind]
