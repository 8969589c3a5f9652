"""The WRN family: pre-activation wide residual networks split after a
group, on synthetic CIFAR images, in the program's ``repro.models.wrn``.

A thin adapter: the builders are ``bench/build.py``, the plain reference
and the comparison ``bench/reference.py``, the operation counts
``bench/flops.py``. ``bench/spec.py`` says what a family provides.
"""
from __future__ import annotations

from typing import Any, Dict

import build
import flops
import reference as R

FAULTS = R.FAULTS

make_traffic = build.make_traffic
make_weights = build.make_weights
make_simulation = build.make_simulation
captured_rounds = build.captured_rounds
to_host = R.to_host


def arch(config: Dict[str, Any], precision: str = "") -> R.Arch:
    return R.arch_from(config, precision)


def fl_extra(traffic: Dict[str, Any]) -> Dict[str, Any]:
    return {"classes_per_client": traffic["classes_per_client"]}


def shapes(config: Dict[str, Any], traffic: Dict[str, Any],
           fl: Dict[str, Any]) -> Dict[str, int]:
    """A client's selection: its rows, the split map's features, a slot
    per cluster of every class, and the PCA components kept."""
    n = int(traffic["samples_per_client"])
    d = flops.split_features(config["model"])
    return {"n": n, "d": d,
            "k": config["model"]["num_classes"] * fl["clusters_per_class"],
            "p": min(fl["pca_components"], n - 1, d)}


def round_flops(config: Dict[str, Any], traffic: Dict[str, Any],
                fl: Dict[str, Any]) -> Dict[str, int]:
    return flops.round_model_flops(config["model"], fl,
                                   int(traffic["samples_per_client"]))


def follow(arch: R.Arch, fl: Dict[str, Any], clients, w0_host, seed: int,
           rounds) -> Dict[str, float]:
    """The reference follows the program's recorded ``rounds`` from the
    same weights and keys; the numbers of ``reference.compare``."""
    ref = R.run_rounds(arch, fl, clients, w0_host, build.sim_key(seed),
                       len(rounds), follow=rounds)
    return R.compare(rounds, ref, w0_host)


def control(config: Dict[str, Any], traffic: Dict[str, Any], seed: int,
            rounds: int, fault: str = "") -> Dict[str, float]:
    """The reference at ``high`` precision (three bfloat16 passes, the
    nearest below the configuration's ``highest``) judged against the one
    at the configuration's; with ``fault``, the reference at the
    configuration's precision with that fault planted takes its place."""
    arch32 = arch(config)
    fl = build.fl_settings(config, traffic)
    clients = make_traffic(traffic, arch32, seed)
    w0 = to_host(make_weights(arch32, seed))
    key = build.sim_key(seed)
    low = R.run_rounds(arch32 if fault else arch(config, "high"), fl,
                       clients, w0, key, rounds, fault=fault)
    ref = R.run_rounds(arch32, fl, clients, w0, key, rounds, follow=low)
    return R.compare(low, ref, w0)
