"""Find a cell's files by the names in ``BENCHMARK.json``.

Everything that belongs to one configuration, one traffic mix or one
per-layer metric sits in files of its own, found by name:

    bench/configs/<config>.json     sizes, precision, optimizer, source
    bench/configs/<config>.py       plain reference of that configuration
    bench/flops/<config>.py         model FLOPs per token
    bench/traffic/<traffic>.json    batch, lengths, strategy, engine, interval
    bench/cells/<workload>.json     the limits that decide ``correct``, at
                                    the cell's size and at the small size
                                    the tests run
    bench/metrics/<metric>.py       reader of one per-layer metric

A later cell, configuration, mix or metric is added by adding such files and
entries to ``BENCHMARK.json``; no file here changes.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
from typing import Any, Dict, List

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class SpecError(Exception):
    """A cell, or a file it needs, is missing or malformed."""


def _read_json(path: str) -> Dict[str, Any]:
    try:
        with open(path) as f:
            return json.load(f)
    except FileNotFoundError:
        raise SpecError(f"missing file {path}") from None


def load_module(path: str, name: str):
    """Import one file of the benchmark by path (names may hold '-')."""
    if not os.path.isfile(path):
        raise SpecError(f"missing file {path}")
    mod_spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Metric:
    name: str
    unit: str

    def reader(self, bench_dir: str):
        return load_module(os.path.join(bench_dir, "metrics",
                                        f"{self.name}.py"),
                           f"bench_metric_{self.name}")


@dataclasses.dataclass
class Cell:
    name: str
    config_name: str
    chips: int
    config: Dict[str, Any]
    traffic: Dict[str, Any]
    limits: Dict[str, float]
    smoke_limits: Dict[str, float]
    end_to_end: List[Metric]
    per_layer: List[Metric]
    bench_dir: str

    def reference(self):
        return load_module(os.path.join(self.bench_dir, "configs",
                                        f"{self.config_name}.py"),
                           f"bench_ref_{self.config_name}")

    def flops(self):
        return load_module(os.path.join(self.bench_dir, "flops",
                                        f"{self.config_name}.py"),
                           f"bench_flops_{self.config_name}")


def _metrics_for(entries, kind: str, cell: str, reported: set) -> List[Metric]:
    out = []
    for e in entries:
        listed = e.get("workloads")
        if listed is not None:
            if cell not in listed:
                continue
        elif kind == "per_layer" and e["moves"] not in reported:
            continue
        out.append(Metric(e["name"], e["unit"]))
    return out


def load_cell(root: str, workload: str, bench_dir: str = BENCH_DIR) -> Cell:
    """The cell ``workload`` of ``<root>/BENCHMARK.json`` with its files."""
    bench = _read_json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SpecError(f"no workload {workload!r} in BENCHMARK.json "
                        f"(have {sorted(cells)})")
    w = cells[workload]
    config = _read_json(os.path.join(bench_dir, "configs",
                                     f"{w['config']}.json"))
    traffic = _read_json(os.path.join(bench_dir, "traffic",
                                      f"{w['traffic']}.json"))
    limits = _read_json(os.path.join(bench_dir, "cells", f"{workload}.json"))
    e2e = _metrics_for(bench["end_to_end"], "end_to_end", workload, set())
    reported = {m.name for m in e2e}
    per_layer = _metrics_for(bench["per_layer"], "per_layer", workload,
                             reported)
    return Cell(workload, w["config"], int(w["chips"]), config, traffic,
                limits["limits"], limits.get("smoke_limits", {}), e2e,
                per_layer, bench_dir)
