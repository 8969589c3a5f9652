"""Building a cell's run from its data files: the traffic from the seed, the
program's model, clients and ``FLConfig``, and the ``FLSimulation`` the
window drives, with the weights the benchmark made.

The simulation is the program's own, with two additions that change no
work: ``_cohort_round`` and the server's ``aggregate`` keep a reference to
what the first rounds produced, for the comparison after the window.
"""
from __future__ import annotations

from typing import Any, Dict, List, Tuple

import jax
import numpy as np

from reference import Arch, Round, arch_from, init_params, split
from traffic.synthetic import k_shards, synthetic_images

PROGRAM_FL_KEYS = (
    "local_epochs", "local_batch_size", "local_lr", "pca_components",
    "clusters_per_class", "kmeans_iters", "select_per_cluster",
    "meta_epochs", "meta_batch_size", "meta_lr", "meta_l2",
    "use_selection", "batched_selection", "pca_solver",
    "use_pallas_selection", "distributed_selection", "selection_chunk_size",
    "transport_codec", "transport_checksum")


def fl_settings(config: Dict[str, Any], traffic: Dict[str, Any]
                ) -> Dict[str, Any]:
    """The configuration's FL settings with the fleet of the traffic mix.
    A mix that names its own cohort size (its source's) sets it."""
    fl = dict(config["fl"])
    fl["num_clients"] = int(traffic["num_clients"])
    if "clients_per_round" in traffic:
        fl["clients_per_round"] = int(traffic["clients_per_round"])
    return fl


def make_traffic(traffic: Dict[str, Any], arch: Arch, seed: int
                 ) -> List[Tuple[np.ndarray, np.ndarray, np.ndarray]]:
    """Per client (x, y, uid): the mix's images drawn from ``seed``, split
    into shards; ``uid`` is each row's index in the drawn set."""
    x, y = synthetic_images(
        int(traffic["train_samples"]), image_size=arch.image_size,
        channels=arch.channels, num_classes=arch.num_classes,
        modes_per_class=int(traffic["modes_per_class"]),
        noise=float(traffic["noise"]), seed=seed,
        structure_seed=int(traffic["structure_seed"]))
    parts = k_shards(y, arch.num_classes, int(traffic["num_clients"]),
                     int(traffic["classes_per_client"]),
                     int(traffic["samples_per_client"]), seed)
    return [(x[idx], y[idx], idx) for idx, _ in parts]


def make_weights(arch: Arch, seed: int):
    """The initial global weights, in one jitted call on the device."""
    key = jax.random.fold_in(jax.random.PRNGKey(seed), 0x5EED)
    return jax.jit(lambda k: init_params(arch, k))(key)


def sim_key(seed: int):
    """The simulation's round key stream, drawn from the seed."""
    return jax.random.fold_in(jax.random.PRNGKey(seed), 0xF1)


class Captured:
    """What the first ``rounds`` rounds produced, kept by reference."""

    def __init__(self, rounds: int):
        self.rounds = rounds
        self.items: List[Dict[str, Any]] = []

    def want(self) -> bool:
        return len(self.items) < self.rounds


def make_simulation(config: Dict[str, Any], traffic: Dict[str, Any],
                    clients_np, weights, seed: int, observability: bool,
                    capture: Captured):
    """The program's FLSimulation over the cell's clients, holding the
    benchmark's weights and key."""
    from repro.configs import FLConfig
    from repro.configs.wrn_cifar import WRNConfig
    from repro.data import ClientData, Dataset
    from repro.fl.simulation import FLSimulation
    from repro.models.wrn import make_split_wrn

    m = config["model"]
    wrn = WRNConfig(name=config["name"], depth=m["depth"], widen=m["widen"],
                    num_classes=m["num_classes"], image_size=m["image_size"],
                    channels=m["channels"], split_group=m["split_group"])
    fl = fl_settings(config, traffic)
    cfg = FLConfig(num_clients=fl["num_clients"],
                   clients_per_round=fl["clients_per_round"],
                   observability=observability,
                   **{k: fl[k] for k in PROGRAM_FL_KEYS if k in fl})
    model = make_split_wrn(wrn)
    nc = m["num_classes"]
    clients = [ClientData(i, Dataset(x, y, nc), np.unique(y))
               for i, (x, y, _) in enumerate(clients_np)]
    # evaluation stays outside the window; the test set only names classes
    test = Dataset(clients_np[0][0][:1], clients_np[0][1][:1], nc)

    class BenchSimulation(FLSimulation):
        def _cohort_round(self, cohort, keys, client_ids=None):
            out = super()._cohort_round(cohort, keys, client_ids=client_ids)
            if capture.want():
                cparams, metas, losses = out
                capture.items.append({
                    "cohort": [int(i) for i in client_ids],
                    "losses": [float(v) for v in losses],
                    "metas": list(metas)})
            return out

    sim = BenchSimulation(model, clients, test, cfg, seed=seed)
    arch = arch_from(config)
    prog_tree = jax.tree.structure(sim.server.global_params)
    if jax.tree.structure(weights) != prog_tree:
        raise RuntimeError("the benchmark's weights do not have the "
                           "program's parameter layout")
    sim.server.global_params = weights
    sim.server.upper_init = split(arch, weights)[1]
    sim.key = sim_key(seed)

    server = sim.server

    def keep_aggregate(*args, **kwargs):
        res = type(server).aggregate(server, *args, **kwargs)
        if capture.items and "upper" not in capture.items[-1]:
            capture.items[-1]["upper"] = res.upper_trained
            capture.items[-1]["global"] = res.global_params
        return res

    sim.server.aggregate = keep_aggregate
    return sim, cfg


def captured_rounds(capture: Captured) -> List[Round]:
    """The program's first rounds as reference records, on the host."""
    out = []
    for it in capture.items:
        if "global" not in it:
            break
        know, know_y = [], []
        for meta in it["metas"]:
            if meta is None:
                know.append(np.zeros((0,), np.float32))
                know_y.append(np.zeros((0,), np.int32))
                continue
            know.append(np.asarray(meta[0], np.float32))
            know_y.append(np.asarray(meta[1]))
        out.append(Round(it["cohort"], it["losses"], know, know_y,
                         jax.tree.map(np.asarray, it["global"]),
                         jax.tree.map(np.asarray, it["upper"])))
    return out
