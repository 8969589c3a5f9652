"""The paper's technique generalized to a language model: split a tiny
llama-style LM at layer j, run FedAvg on the lower part, select
representative hidden states by PCA+K-means, and meta-train the upper part
on them — all with the same core library the WRN path uses.

  PYTHONPATH=src python examples/federated_lm.py
"""
import jax
import jax.numpy as jnp
import numpy as np

from repro.compile_cache import enable_compile_cache
from repro.configs import FLConfig, get_config
from repro.core import fedavg as fa
from repro.core.meta_training import meta_train
from repro.core.selection import select_metadata
from repro.data import SyntheticTokenDataset, partition_k_shards
from repro.models.transformer import make_split_lm
from repro.optim import sgd


def main():
    enable_compile_cache()
    cfg = get_config("llama3.2-1b").reduced()
    model, lm = make_split_lm(cfg)
    print(f"LM: {cfg.name} (reduced), split at layer {model.split_layer} "
          f"of {cfg.num_layers}")

    # non-IID clients: per-class bigram token processes
    ds = SyntheticTokenDataset(512, seq_len=32, vocab_size=cfg.vocab_size,
                               num_classes=6)
    clients = partition_k_shards(ds, 4, k_classes=2, samples_per_client=96)

    key = jax.random.PRNGKey(0)
    params = model.init(key)
    _, upper0 = model.split(params)
    opt = sgd(0.05)

    for rnd in range(3):
        client_params, metadatas = [], []
        k_round = jax.random.fold_in(key, rnd)
        for ci, c in enumerate(clients):
            toks = jnp.asarray(c.data.x)
            # LocalUpdate (§3.2)
            bs = 16
            steps = len(toks) // bs
            batches = toks[:steps * bs].reshape(steps, bs, -1)
            p, _, losses = fa.local_update(
                params, opt, opt.init(params), (batches,),
                lambda p_, b: model.loss(p_, (b[0],)))
            client_params.append(p)
            # Extract&Selection (§3.1) on mean-pooled split-layer hiddens
            acts = model.apply_lower(params, toks)          # (N, T, d)
            # per-client key: one fold per (round, client) — a shared
            # round key would give every client the same kmeans init
            # stream and correlate their selections (flcheck RNG004)
            sel = select_metadata(acts.mean(1), None,
                                  jax.random.fold_in(k_round, ci),
                                  per_class=False, clusters_per_class=6,
                                  pca_components=16, kmeans_iters=10)
            metadatas.append((jnp.take(acts, sel.indices, 0),
                              jnp.take(toks, sel.indices, 0), sel.valid))
        # server: aggregate metadata, MetaTraining (§3.3)
        acts = jnp.concatenate([m[0] for m in metadatas])
        toks = jnp.concatenate([m[1] for m in metadatas])
        valid = jnp.concatenate([m[2] for m in metadatas])
        upper, meta_losses = meta_train(
            upper0, model.upper_loss, acts, toks, epochs=5, batch_size=8,
            lr=0.05, key=jax.random.fold_in(key, 100 + rnd), valid=valid)
        # compose + FedAvg
        new_global = fa.weight_average(client_params)
        composed = model.merge(model.split(new_global)[0], upper)
        # next-token accuracy of the composed model on held-out data
        test = jnp.asarray(ds.x[:64])
        logits = model.apply(composed, test)
        acc = float((jnp.argmax(logits[:, :-1], -1) == test[:, 1:]).mean())
        frac = float(valid.sum()) / sum(len(c.data) for c in clients)
        print(f"round {rnd}: selected {int(valid.sum())} seqs "
              f"({frac:.1%} of client data), meta loss "
              f"{float(meta_losses[-1]):.3f}, composed next-token acc {acc:.3f}")
        params = new_global
    print("done — the same §3 pipeline, attention-free of the backbone type")


if __name__ == "__main__":
    main()
