"""Reading the benchmark's data files.

A cell is ``bench/workloads/<cell>.json``: the configuration and the traffic
mix it runs, its chips, its ``why``, the rounds the correctness check
follows and the limit of every number that check compares. A configuration
is ``bench/configs/<config>.json``; it names its model ``family``, whose
code is ``bench/families/<family>.py``. A traffic mix is
``bench/traffic/<traffic>.json``; a metric is ``bench/metrics/<metric>.py``.
``BENCHMARK.json`` at the root says which metrics each cell reports. Every
file is found by its name, so a new cell, configuration, family, mix or
metric is a new file and an entry in ``BENCHMARK.json``, never an edit of
a file that is there.

A family module gives the harness everything that depends on the model;
``bench/run.py`` knows nothing of any model. It defines (``FAMILY_API``):

- ``arch(config, precision="")``: the model's shape for the functions
  below, at the configuration's matmul precision or at ``precision``;
- ``make_traffic(traffic, arch, seed)``: the clients' data, from the mix
  and the seed;
- ``make_weights(arch, seed)``: the initial global weights, on the device;
- ``to_host(weights)``: their host copy, which the reference starts from;
- ``make_simulation(config, traffic, clients, weights, seed,
  observability, capture)``: ``(sim, fl_config)``, the program's
  ``FLSimulation`` holding those weights and recording its first rounds in
  ``capture`` (``build.Captured``);
- ``fl_extra(traffic)``: the keys the family adds to the readers'
  ``ctx["fl"]``;
- ``shapes(config, traffic, fl)``: ``n``, ``d``, ``k``, ``p`` of a client's
  selection (rows, features, cluster slots, PCA components), for the
  kernels' readers;
- ``round_flops(config, traffic, fl)``: the model operations of one round,
  by part;
- ``captured_rounds(capture)``: the recorded rounds as host records;
- ``follow(arch, fl, clients, weights_host, seed, rounds)``: the plain
  reference's run of those rounds, and the numbers compared, by name, with
  ``rounds_checked`` among them;
- ``control(config, traffic, seed, rounds, fault="")``: those numbers for
  the control, or for the reference with one of ``FAULTS`` planted
  (``bench/control.py``).

It may also define ``ANNOTATIONS``, entries in the form of
``bench/annotations.json`` that the traced run adds to that table.
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


FAMILY_API = ("arch", "make_traffic", "make_weights", "to_host",
              "make_simulation", "fl_extra", "shapes", "round_flops",
              "captured_rounds", "follow", "control", "FAULTS")


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

    @property
    def family(self) -> str:
        return self.config["family"]


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
    if "family" not in config:
        raise SpecError(f"configuration {workload['config']!r} names no "
                        f"family")
    _family_path(config["family"], root)
    traffic = _load_json(bdir / "traffic" / f"{workload['traffic']}.json")
    e2e = [m for m in _metrics(bench, "end_to_end")
           if not m.workloads or name in m.workloads]
    reported = {m.name for m in e2e}
    per_layer = [m for m in _metrics(bench, "per_layer")
                 if (name in m.workloads if m.workloads
                     else m.moves in reported)]
    return Cell(name, workload, config, traffic, e2e, per_layer)


def _ident(name: str) -> str:
    return name.replace(".", "_").replace("-", "_")


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
        f"bench_metric_{_ident(metric)}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def _family_path(family: str, root: Path) -> Path:
    path = root / "bench" / "families" / f"{family}.py"
    if not path.is_file():
        raise SpecError(f"no module for family {family!r}: {path}")
    return path


def load_family(family: str, root: Path = ROOT):
    """The module ``bench/families/<family>.py``, with every name of
    ``FAMILY_API``. It may import the benchmark's modules."""
    path = _family_path(family, root)
    if str(BENCH_DIR) not in sys.path:
        sys.path.insert(0, str(BENCH_DIR))
    spec = importlib.util.spec_from_file_location(
        f"bench_family_{_ident(family)}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    missing = [n for n in FAMILY_API if not hasattr(mod, n)]
    if missing:
        raise SpecError(f"family module {path} lacks {missing}")
    return mod


def load_peaks(device_kind: str, root: Path = ROOT) -> Dict[str, float]:
    """The chip's published peaks; an unknown device kind is an error."""
    table = _load_json(root / "bench" / "peaks.json")
    if device_kind not in table["devices"]:
        raise SpecError(f"no peaks for device kind {device_kind!r} in "
                        f"bench/peaks.json")
    return table["devices"][device_kind]
