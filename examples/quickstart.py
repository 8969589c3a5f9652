"""Quickstart: the paper's split-FL with clustered data selection, end to end
on CPU in ~2 minutes.

  PYTHONPATH=src python examples/quickstart.py [--trace TRACE.jsonl]

``--trace`` turns on observability (FLConfig.observability) and writes the
run's span/metrics trace as JSONL — inspect it with
``python -m repro.obs summarize TRACE.jsonl``.
"""
import argparse

import jax

from repro.compile_cache import enable_compile_cache
from repro.configs import FLConfig, get_wrn_config
from repro.data import SyntheticImageDataset, partition_k_shards
from repro.fl.simulation import FLSimulation
from repro.models.wrn import make_split_wrn


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--trace", default=None, metavar="PATH",
                    help="enable observability and write the trace JSONL")
    args = ap.parse_args(argv)
    enable_compile_cache()
    # 1. the paper's model (reduced WRN for CPU) split after group 1
    cfg = get_wrn_config().reduced()
    model = make_split_wrn(cfg)
    print(f"model: {cfg.name}, split after group {cfg.split_group}")

    # 2. non-IID clients — the paper's §4.1 setting, scaled down:
    #    each client holds samples from just 2 of 10 classes
    train = SyntheticImageDataset(2000, image_size=cfg.image_size,
                                  modes_per_class=3, seed=0)
    test = SyntheticImageDataset(400, image_size=cfg.image_size,
                                 modes_per_class=3, seed=1)
    clients = partition_k_shards(train, num_clients=4, k_classes=2,
                                 samples_per_client=250)
    print(f"clients: {len(clients)}, classes per client: "
          f"{[c.classes.tolist() for c in clients]}")

    # 3. FL config: PCA -> K-means -> 1 representative per cluster (§3.1)
    #    meta_epochs/meta_batch_size are sized for the transport-layer
    #    semantics: the server meta-trains on exactly the |D_M| rows that
    #    crossed the wire (32 here — empty-cluster slots never arrive)
    flcfg = FLConfig(num_clients=4, clients_per_round=4, local_epochs=1,
                     local_batch_size=50, local_lr=0.05,
                     pca_components=24, clusters_per_class=4,
                     meta_epochs=40, meta_batch_size=8, meta_lr=0.05,
                     observability=args.trace is not None)

    # 4. run Algorithm 1 for a few rounds
    sim = FLSimulation(model, clients, test, flcfg, seed=0)
    res = sim.run(rounds=3, eval_every=1, verbose=True)
    if args.trace:
        sim.tracer.write_jsonl(args.trace)
        print(f"trace: {len(sim.tracer.spans)} spans, "
              f"{len(sim.tracer.events)} events -> {args.trace}")

    frac = res.metadata_counts[-1] / res.comm["total_samples"]
    print(f"\nselected metadata fraction: {frac:.2%}  (paper: ~0.8%)")
    print(f"metadata upload: {res.comm['up']['metadata']/1e6:.2f} MB; "
          f"weight upload: {res.comm['up']['weights']/1e6:.2f} MB")
    print(f"final composed-model accuracy: {res.test_acc[-1]:.2%}; "
          f"FedAvg global model: {res.fedavg_acc[-1]:.2%}")


if __name__ == "__main__":
    main()
