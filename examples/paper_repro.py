"""End-to-end driver: the paper's full experiment grid (Tables 2-8) at
configurable scale — trains the split-FL WRN for a few hundred rounds when
given the budget. This is deliverable (b)'s 'train for a few hundred steps'
driver: every round is a full federated train step over all clients.

  # ~10 min CPU run (reduced scale):
  PYTHONPATH=src python examples/paper_repro.py --rounds 30 --clients 5

  # the paper's full setting (needs real CIFAR-10 + GPUs/TPUs):
  PYTHONPATH=src python examples/paper_repro.py --rounds 100 --clients 20 \
      --samples-per-client 2500 --clusters 20 --full-wrn
"""
import argparse
import json
import os

import jax
import numpy as np

from repro.checkpoint import CheckpointManager
from repro.compile_cache import enable_compile_cache
from repro.configs import FLConfig, get_wrn_config
from repro.data import SyntheticImageDataset, partition_k_shards
from repro.fl.simulation import FLSimulation
from repro.models.wrn import make_split_wrn
from repro.obs.timing import monotonic


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--rounds", type=int, default=30)
    ap.add_argument("--clients", type=int, default=5)
    ap.add_argument("--samples-per-client", type=int, default=400)
    ap.add_argument("--clusters", type=int, default=4)
    ap.add_argument("--meta-epochs", type=int, default=10)
    ap.add_argument("--l2", type=float, default=5e-4)
    ap.add_argument("--full-wrn", action="store_true",
                    help="WRN-40-1 at 32x32 (the paper's exact model)")
    ap.add_argument("--no-selection", action="store_true",
                    help="Table 2 baseline: upload ALL activation maps")
    ap.add_argument("--out", default="experiments/paper_repro.json")
    ap.add_argument("--ckpt-dir", default=None)
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_wrn_config() if args.full_wrn else get_wrn_config().reduced()
    model = make_split_wrn(cfg)

    n_train = max(args.clients * args.samples_per_client, 3000)
    train = SyntheticImageDataset(n_train, image_size=cfg.image_size,
                                  modes_per_class=3, seed=0)
    test = SyntheticImageDataset(800, image_size=cfg.image_size,
                                 modes_per_class=3, seed=1)
    clients = partition_k_shards(train, args.clients, k_classes=2,
                                 samples_per_client=args.samples_per_client)

    flcfg = FLConfig(num_clients=args.clients,
                     clients_per_round=args.clients,
                     local_epochs=1, local_batch_size=50, local_lr=0.05,
                     pca_components=24, clusters_per_class=args.clusters,
                     meta_epochs=args.meta_epochs, meta_batch_size=20,
                     meta_lr=0.05, meta_l2=args.l2,
                     use_selection=not args.no_selection)

    sim = FLSimulation(model, clients, test, flcfg, seed=0)
    t0 = monotonic()
    res = sim.run(rounds=args.rounds, eval_every=max(args.rounds // 10, 1),
                  verbose=True)
    if args.ckpt_dir:
        CheckpointManager(args.ckpt_dir).save(
            args.rounds, sim.server.global_params, {"cfg": str(flcfg)})

    out = {
        "config": vars(args),
        "test_acc": res.test_acc,
        "fedavg_acc": res.fedavg_acc,
        "metadata_counts": res.metadata_counts,
        "selected_fraction": res.selected_fraction,
        "comm": {k: v for k, v in res.comm.items()},
        "wall_time_s": monotonic() - t0,
    }
    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(out, f, indent=1, default=str)
    print(f"\nwrote {args.out}; final acc {res.test_acc[-1]:.2%} "
          f"({'no-selection baseline' if args.no_selection else 'with selection'})")


if __name__ == "__main__":
    main()
