"""Finds everything of a cell by name: BENCHMARK.json names the cells,
metrics and configurations; each configuration, traffic mix, cell
geometry, metric reader, model adapter and reference is a file of its own
under ``bench/``, so a new cell is new files and new entries only:

    bench/configs/<config>.json       sizes, spiking settings, source
    bench/traffic/<mix>.json          arrival process, lengths
    bench/cells/<workload>.json       rate, engine geometry, check limits
    bench/metrics/<metric>.py         reader of one metric
    bench/adapters/<model>.py         program config + seeded weights
    bench/reference/<model>.py        plain float32 reference
"""
from __future__ import annotations

import importlib.util
import json
import sys
from dataclasses import dataclass
from pathlib import Path

BENCH = Path(__file__).resolve().parent


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: Path, name: str):
    if name in sys.modules:
        return sys.modules[name]
    if not path.is_file():
        raise SystemExit(f"bench: no file {path}")
    for p in (str(BENCH), str(BENCH / "metrics")):
        if p not in sys.path:
            sys.path.insert(0, p)
    s = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(s)
    sys.modules[name] = mod
    s.loader.exec_module(mod)
    return mod


@dataclass
class Cell:
    name: str
    chips: int
    conf: dict          # the configuration file
    mix: dict           # the traffic file
    geometry: dict      # the cell file
    end_to_end: list    # BENCHMARK.json metric entries this cell reports
    per_layer: list
    bench: Path = BENCH

    def adapter(self):
        m = self.conf["model"]
        return load_module(self.bench / "adapters" / f"{m}.py", f"adapter_{m}")

    def reference(self):
        m = self.conf["model"]
        return load_module(self.bench / "reference" / f"{m}.py",
                           f"reference_{m}")


def metric_module(name: str):
    return load_module(BENCH / "metrics" / f"{name}.py", f"metric_{name}")


class Spec:
    def __init__(self, root: Path):
        self.root = root
        self.bench = root / "bench"
        path = root / "BENCHMARK.json"
        if not path.is_file():
            raise SystemExit(f"bench: no {path}")
        self.doc = load_json(path)

    def _applies(self, metric: dict, workload: str) -> bool:
        return workload in metric.get("workloads", [workload])

    def cell(self, workload: str) -> Cell:
        by_name = {w["name"]: w for w in self.doc["workloads"]}
        if workload not in by_name:
            raise SystemExit(f"bench: no workload {workload!r} in BENCHMARK.json "
                             f"(have {sorted(by_name)})")
        w = by_name[workload]
        confs = {c["name"]: c for c in self.doc["configs"]}
        conf = load_json(self.root / confs[w["config"]]["file"])
        return Cell(
            name=workload, chips=w["chips"], conf=conf,
            mix=load_json(self.bench / "traffic" / f"{w['traffic']}.json"),
            geometry=load_json(self.bench / "cells" / f"{workload}.json"),
            end_to_end=[m for m in self.doc["end_to_end"]
                        if self._applies(m, workload)],
            per_layer=[m for m in self.doc["per_layer"]
                       if self._applies(m, workload)],
        )
