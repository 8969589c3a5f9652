#!/usr/bin/env python3
"""Chip smoke: the split-FL round with data selection, on a TPU, at the
paper's model width.

  python3 chip_smoke.py [--seed S]        # one chip: two FLSimulation rounds
  python3 chip_smoke.py --four-chips      # 4 chips: the sharded round only

The model is WRN-40-1 (``get_wrn_config()``, never ``.reduced()``): 32x32x3
inputs, split after group 1, so every split map has 16x32x32 = 16,384
features. The weights are random, initialised from ``--seed``. The data are
synthetic: ``SyntheticImageDataset`` at 32x32 stands in for CIFAR-10, with
50,000 training images split by ``partition_k_shards`` into 20 clients x
2,500 images of 2 classes each (the paper's section 4.1 non-IID layout), and
2,000 test images. Both are drawn from ``--seed``.

``FLConfig`` keeps the paper's defaults (200 PCA components, 10 clusters per
class, 25 Lloyd sweeps, local batch 50, 2 meta-training epochs) and turns on
the Pallas selection kernels and the int8 knowledge codec, so the fused
Lloyd kernel and the quantize kernel are on the path: 2,500 rows per client
and 100 knowledge slots per frame.

One chip: ``FLSimulation(...).run(rounds=2)``; then one client's selection
through the Pallas path against ``select_metadata_reference`` on the same
activations (the reference at ``highest`` matmul precision). The times it
prints are smoke timings of this run, compilation included, not benchmark
numbers.

Four chips: ``run_round`` with ``distributed_selection=True`` over a 4-device
``selection_mesh``, and the same round on one device with
``distributed_selection=False``, compared (contract 1 of
``docs/architecture.md``).

It fails (non-zero exit, no final line) when JAX finds no TPU, when a loss
or weight is non-finite, when the selection or int8 encode program holds no
Mosaic kernel (``tpu_custom_call``), when the ledger shows no knowledge
bytes, or when the selection agreement falls below ``MIN_AGREEMENT``. The
last line of a passing run is ``{"ok": true, "device": {...}}``.

The persistent compile cache is ``$JAX_COMPILATION_CACHE_DIR`` when set,
else ``<repo>/.jax_cache``.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

from repro.compile_cache import enable_compile_cache  # noqa: E402
from repro.obs.timing import monotonic  # noqa: E402

TRAIN_SAMPLES, TEST_SAMPLES = 50_000, 2_000
NUM_CLIENTS, SAMPLES_PER_CLIENT, CLASSES_PER_CLIENT = 20, 2_500, 2
ROUNDS = 2
MESH_DEVICES = 4
# least fraction of occupied slots on which the Pallas path and the
# reference pick the same sample (see CHANGES.md for what the chip showed)
MIN_AGREEMENT = 0.9


class SmokeFailure(Exception):
    """A check of the smoke run failed."""


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def device_info() -> dict:
    import jax
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def workload(seed: int):
    """(model, clients, test set, FLConfig) of the smoke run."""
    from repro.configs import FLConfig, get_wrn_config
    from repro.data import SyntheticImageDataset, partition_k_shards
    from repro.models.wrn import make_split_wrn
    wrn = get_wrn_config()
    model = make_split_wrn(wrn)
    train = SyntheticImageDataset(TRAIN_SAMPLES, image_size=wrn.image_size,
                                  seed=seed)
    test = SyntheticImageDataset(TEST_SAMPLES, image_size=wrn.image_size,
                                 seed=seed + 1)
    clients = partition_k_shards(train, NUM_CLIENTS,
                                 k_classes=CLASSES_PER_CLIENT,
                                 samples_per_client=SAMPLES_PER_CLIENT,
                                 seed=seed)
    cfg = FLConfig(num_clients=NUM_CLIENTS, clients_per_round=NUM_CLIENTS,
                   use_pallas_selection=True, transport_codec="int8")
    print(f"model: {wrn.name}, {wrn.image_size}x{wrn.image_size}x"
          f"{wrn.channels} inputs, split after group {wrn.split_group}")
    print(f"data: {NUM_CLIENTS} clients x {SAMPLES_PER_CLIENT} samples, "
          f"{CLASSES_PER_CLIENT} classes each; {TEST_SAMPLES} test samples "
          f"(synthetic, seed {seed})")
    print(f"FLConfig: pca_components={cfg.pca_components} "
          f"clusters_per_class={cfg.clusters_per_class} "
          f"kmeans_iters={cfg.kmeans_iters} "
          f"local_batch_size={cfg.local_batch_size} "
          f"meta_epochs={cfg.meta_epochs} "
          f"use_pallas_selection={cfg.use_pallas_selection} "
          f"transport_codec={cfg.transport_codec}")
    return model, clients, test, cfg


def all_finite(tree) -> bool:
    import jax
    import numpy as np
    return all(bool(np.isfinite(np.asarray(a)).all())
               for a in jax.tree.leaves(tree))


def max_abs_diff(a, b) -> float:
    import jax
    import numpy as np
    return max(float(np.max(np.abs(np.asarray(x, np.float64)
                                   - np.asarray(y, np.float64))))
               for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)))


def kernel_programs(model, params, client, cfg, num_classes: int):
    """The selection and int8 encode programs, lowered for this device:
    each must hold a Mosaic kernel, not an interpret-mode or jnp stand-in."""
    import jax
    import jax.numpy as jnp
    from repro.core.selection import select_metadata
    from repro.kernels import ops
    x = jax.ShapeDtypeStruct(client.data.x.shape, jnp.float32)
    acts = jax.eval_shape(lambda v: model.apply_lower(params, v), x)
    sel_hlo = select_metadata.lower(
        acts, jax.ShapeDtypeStruct((x.shape[0],), jnp.int32),
        jax.random.PRNGKey(0), num_classes=num_classes,
        clusters_per_class=cfg.clusters_per_class,
        pca_components=cfg.pca_components, kmeans_iters=cfg.kmeans_iters,
        use_pallas=True, pca_solver=cfg.pca_solver).as_text()
    slots = num_classes * cfg.clusters_per_class
    feat = 1
    for s in acts.shape[1:]:
        feat *= s
    enc_hlo = jax.jit(ops.quantize_affine).lower(
        jax.ShapeDtypeStruct((slots, feat), jnp.float32),
        jax.ShapeDtypeStruct((slots,), jnp.bool_)).as_text()
    for name, hlo in (("selection", sel_hlo), ("int8 encode", enc_hlo)):
        n = hlo.count("tpu_custom_call")
        print(f"{name} program: {n} tpu_custom_call site(s)")
        check(n > 0, f"the {name} program holds no Mosaic kernel")


def selection_agreement(model, params, client, cfg, key,
                        num_classes: int) -> float:
    """One client's selection through the Pallas path against the seed
    reference on the same activations. Returns the fraction of slots
    occupied in either selection on which both pick the same sample."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.core.selection import (select_metadata,
                                      select_metadata_reference)
    acts = model.apply_lower(params, jnp.asarray(client.data.x))
    y = jnp.asarray(client.data.y)
    kw = dict(num_classes=num_classes,
              clusters_per_class=cfg.clusters_per_class,
              pca_components=cfg.pca_components,
              kmeans_iters=cfg.kmeans_iters)
    with jax.default_matmul_precision("highest"):
        ref = select_metadata_reference(acts, y, key, **kw)
    ri, rv = np.asarray(ref.indices), np.asarray(ref.valid)

    def agreement(sel, label):
        si, sv = np.asarray(sel.indices), np.asarray(sel.valid)
        occupied = sv | rv
        same = (sv == rv) & (si == ri) & occupied
        frac = float(same.sum() / max(occupied.sum(), 1))
        print(f"selection ({label}) vs reference (client "
              f"{client.client_id}): {int(same.sum())}/{int(occupied.sum())}"
              f" occupied slots pick the same sample ({frac!r}); occupied "
              f"slots {int(sv.sum())} vs {int(rv.sum())}")
        return frac

    frac = agreement(select_metadata(acts, y, key, use_pallas=True,
                                     pca_solver=cfg.pca_solver, **kw),
                     "pallas, default precision")
    # diagnostic: the same path with every XLA matmul at full f32 tells
    # kernel faults apart from the default reduced-precision passes
    with jax.default_matmul_precision("highest"):
        agreement(select_metadata(acts, y, key, use_pallas=True,
                                  pca_solver=cfg.pca_solver, **kw),
                  "pallas, highest precision")
    return frac


def one_chip(seed: int) -> None:
    import jax
    import numpy as np
    from repro.fl.simulation import FLSimulation

    round_s = []

    class TimedSimulation(FLSimulation):
        """FLSimulation that keeps each round's host wall time (a round
        ends in host-side evaluation, so the device work is done)."""

        def _run_round(self, t, *args):
            t0 = monotonic()
            super()._run_round(t, *args)
            round_s.append(monotonic() - t0)

    t0 = monotonic()
    model, clients, test, cfg = workload(seed)
    sim = TimedSimulation(model, clients, test, cfg, seed=seed)
    nparams = sum(int(np.prod(a.shape))
                  for a in jax.tree.leaves(sim.server.global_params))
    print(f"parameters: {nparams}; set-up {monotonic() - t0!r} s")

    res = sim.run(rounds=ROUNDS)
    print(f"smoke timing (not a benchmark): round 0 incl. compile "
          f"{round_s[0]!r} s, round 1 {round_s[1]!r} s")
    print(f"metadata count per round: {res.metadata_counts}; selected "
          f"fraction {res.selected_fraction!r}")
    comm = res.comm
    print(f"ledger up bytes: {comm['up']} (total {comm['total_up']})")
    print(f"ledger down bytes: {comm['down']} (total {comm['total_down']})")
    print(f"client loss (cohort mean per round): {res.client_loss}")
    print(f"test accuracy: M_COM {res.test_acc}, FedAvg {res.fedavg_acc}")

    check(all(np.isfinite(res.client_loss)), "non-finite client loss")
    check(all(np.isfinite(res.test_acc + res.fedavg_acc)),
          "non-finite accuracy")
    check(all_finite(sim.server.global_params), "non-finite global weights")
    check(comm["up"].get("metadata", 0) > 0,
          "no knowledge bytes on the ledger")

    params = sim.server.global_params
    kernel_programs(model, params, clients[0], cfg, test.num_classes)
    frac = selection_agreement(model, params, clients[0], cfg,
                               jax.random.PRNGKey(seed), test.num_classes)
    check(frac >= MIN_AGREEMENT,
          f"selection agreement {frac!r} < {MIN_AGREEMENT}")


def four_chips(seed: int) -> None:
    import jax
    import numpy as np
    from repro.core.distributed import selection_mesh
    from repro.core.rounds import run_round

    model, clients, test, cfg = workload(seed)
    k_init, k_round = jax.random.split(jax.random.PRNGKey(seed))
    params = model.init(k_init)
    _, upper0 = model.split(params)
    sharded_cfg = dataclasses.replace(cfg, distributed_selection=True)
    mesh = selection_mesh(MESH_DEVICES)

    rounds = {}
    for name, c, m in (("sharded", sharded_cfg, mesh), ("one-device", cfg,
                                                        None)):
        t0 = monotonic()
        r = run_round(model, params, upper0, clients, c, k_round,
                      num_classes=test.num_classes, mesh=m)
        jax.block_until_ready(r.global_params)
        print(f"{name} round: smoke timing (not a benchmark), compile "
              f"included, {monotonic() - t0!r} s; metadata_count "
              f"{r.metadata_count}; client_losses {r.client_losses}",
              flush=True)
        check(all(np.isfinite(r.client_losses)), f"{name}: non-finite loss")
        check(all_finite(r.global_params), f"{name}: non-finite weights")
        rounds[name] = r
    sharded, single = rounds["sharded"], rounds["one-device"]
    same_count = sharded.metadata_count == single.metadata_count
    print(f"metadata_count: {'agree' if same_count else 'DIFFER'}")
    for name, a, b in (
            ("client_losses", sharded.client_losses, single.client_losses),
            ("global params", sharded.global_params, single.global_params),
            ("composed params", sharded.composed_params,
             single.composed_params)):
        d = max_abs_diff(a, b)
        print(f"{name}: {'bit-identical' if d == 0 else 'DIFFER'}"
              f" (largest absolute difference {d!r})")
    check(same_count, "sharded and one-device metadata counts differ")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the synthetic data and the random weights")
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded round on 4 devices and the "
                         "one-device round it is compared with")
    args = ap.parse_args(argv)

    dev = device_info()
    print(f"device: platform={dev['platform']} kind={dev['kind']} "
          f"count={dev['count']}")
    if dev["platform"] != "tpu":
        print(f"chip_smoke: no TPU found (JAX reports platform "
              f"{dev['platform']!r})", file=sys.stderr)
        return 1
    need = MESH_DEVICES if args.four_chips else 1
    if dev["count"] < need:
        print(f"chip_smoke: needs {need} TPU devices, found {dev['count']}",
              file=sys.stderr)
        return 1
    print(f"compile cache: {enable_compile_cache()}")
    try:
        if args.four_chips:
            four_chips(args.seed)
        else:
            one_chip(args.seed)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": dev}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
