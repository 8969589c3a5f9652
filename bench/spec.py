"""Reading the benchmark's data files.

A cell is ``bench/workloads/<cell>.json``: the configuration and the traffic
mix it runs, its chips, its ``why``, the rounds the correctness check
follows and the limit of every number that check compares. A configuration
is ``bench/configs/<config>.json``; a traffic mix is
``bench/traffic/<traffic>.json``; a metric is ``bench/metrics/<metric>.py``.
``BENCHMARK.json`` at the root says which metrics each cell reports. Every
file is found by its name, so a new cell, configuration, mix or metric is a
new file and an entry in ``BENCHMARK.json``, never an edit of a file that
is there.
"""
from __future__ import annotations

import importlib.util
import json
import sys
from dataclasses import dataclass
from pathlib import Path
from typing import Any, Callable, Dict, List

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent


class SpecError(Exception):
    """A data file is missing or does not say what the harness needs."""


def _load_json(path: Path) -> Dict[str, Any]:
    try:
        with open(path) as f:
            return json.load(f)
    except FileNotFoundError as e:
        raise SpecError(f"no such file: {path}") from e


@dataclass(frozen=True)
class Metric:
    name: str
    unit: str
    better: str
    workloads: tuple          # () = every cell that reports ``moves``
    moves: str = ""


@dataclass(frozen=True)
class Cell:
    name: str
    workload: Dict[str, Any]
    config: Dict[str, Any]
    traffic: Dict[str, Any]
    end_to_end: List[Metric]
    per_layer: List[Metric]

    @property
    def chips(self) -> int:
        return int(self.workload["chips"])


def _metrics(bench: Dict[str, Any], kind: str) -> List[Metric]:
    out = []
    for m in bench.get(kind, []):
        out.append(Metric(name=m["name"], unit=m["unit"], better=m["better"],
                          workloads=tuple(m.get("workloads", ())),
                          moves=m.get("moves", "")))
    return out


def load_cell(name: str, root: Path = ROOT) -> Cell:
    """The cell ``name`` with its configuration, traffic and metrics."""
    bench = _load_json(root / "BENCHMARK.json")
    names = [w["name"] for w in bench.get("workloads", [])]
    if name not in names:
        raise SpecError(f"unknown workload {name!r}; BENCHMARK.json has "
                        f"{names}")
    bdir = root / "bench"
    workload = _load_json(bdir / "workloads" / f"{name}.json")
    config = _load_json(bdir / "configs" / f"{workload['config']}.json")
    traffic = _load_json(bdir / "traffic" / f"{workload['traffic']}.json")
    e2e = [m for m in _metrics(bench, "end_to_end")
           if not m.workloads or name in m.workloads]
    reported = {m.name for m in e2e}
    per_layer = [m for m in _metrics(bench, "per_layer")
                 if (name in m.workloads if m.workloads
                     else m.moves in reported)]
    return Cell(name, workload, config, traffic, e2e, per_layer)


def load_reader(metric: str, root: Path = ROOT) -> Callable[[Dict], Any]:
    """``read(ctx)`` of ``bench/metrics/<metric>.py``: the metric's value
    from the run's context, or None where the run holds nothing to read.
    A reader may import the benchmark's modules and the shared helpers of
    its directory (``_spans``, ``_ops``)."""
    path = root / "bench" / "metrics" / f"{metric}.py"
    for shared in (str(BENCH_DIR), str(path.parent)):
        if shared not in sys.path:
            sys.path.insert(0, shared)
    if not path.is_file():
        raise SpecError(f"no reader for metric {metric!r}: {path}")
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{metric.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def load_peaks(device_kind: str, root: Path = ROOT) -> Dict[str, float]:
    """The chip's published peaks; an unknown device kind is an error."""
    table = _load_json(root / "bench" / "peaks.json")
    if device_kind not in table["devices"]:
        raise SpecError(f"no peaks for device kind {device_kind!r} in "
                        f"bench/peaks.json")
    return table["devices"][device_kind]
